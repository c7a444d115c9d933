"""The committed Llama-3-style BPE fixture, end to end (VERDICT r5 weak #5).

Until this PR the HF tokenizer adapter, the server's chat-template
rendering, and ``/tokenize``/``/detokenize`` were only exercised against
the byte tokenizer — the HF branch needed hub egress. The fixture
(tests/fixtures/llama3_tokenizer, regenerated by
tests/fixtures/make_llama3_tokenizer.py) is a real byte-level-BPE
``tokenizer.json`` with the Llama-3 special tokens and chat template,
loadable fully offline, so those paths now run in tier-1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import types
import urllib.request

import jax
import pytest

from ditl_tpu.config import ModelConfig
from ditl_tpu.data import tokenizer as tokenizer_mod
from ditl_tpu.data.tokenizer import HFTokenizer, check_vocab, get_tokenizer
from ditl_tpu.infer import grammar
from ditl_tpu.infer.engine import Generator
from ditl_tpu.infer.server import _chat_prompt, make_server
from ditl_tpu.models import llama

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "llama3_tokenizer")


@pytest.fixture(scope="module")
def hf_tok():
    return HFTokenizer(FIXTURE)


def test_fixture_loads_and_roundtrips(hf_tok):
    assert hf_tok.vocab_size == 384
    assert hf_tok.bos_id != hf_tok.eos_id
    text = "hello world! the quick brown fox: 42"
    ids = hf_tok.encode(text)
    assert ids and all(0 <= i < hf_tok.vocab_size for i in ids)
    assert hf_tok.decode(ids) == text
    # BPE actually merged something: fewer tokens than bytes.
    assert len(ids) < len(text.encode("utf-8"))


def test_get_tokenizer_resolves_local_path(hf_tok):
    tok = get_tokenizer(FIXTURE)
    assert isinstance(tok, HFTokenizer)
    assert tok.encode("hello world") == hf_tok.encode("hello world")


def test_decode_skips_out_of_table_ids(hf_tok):
    # A model head padded wider than the tokenizer can emit ids >= vocab;
    # decode must skip them instead of raising (data/tokenizer.py:decode).
    ids = hf_tok.encode("hello") + [hf_tok.vocab_size + 7]
    assert hf_tok.decode(ids) == "hello"


def test_chat_template_renders_llama3_turns(hf_tok):
    msgs = [
        {"role": "system", "content": "You are a helpful assistant."},
        {"role": "user", "content": "What is 2+2?"},
    ]
    prompt = _chat_prompt(msgs, hf_tok)
    assert prompt.startswith("<|begin_of_text|>")
    assert "<|start_header_id|>system<|end_header_id|>" in prompt
    assert "<|start_header_id|>user<|end_header_id|>" in prompt
    assert prompt.endswith("<|start_header_id|>assistant<|end_header_id|>\n\n")
    assert prompt.count("<|eot_id|>") == 2
    # The template's specials are single tokens, not byte spaghetti.
    ids = hf_tok.encode(prompt)
    header = hf_tok.token_to_id("<|start_header_id|>")
    assert ids.count(header) == 3


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.loads(resp.read())


def test_server_tokenize_chat_end_to_end(hf_tok):
    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        dtype="float32", param_dtype="float32",
    )
    # Model head (512) wider than the fixture (384): the legitimate
    # padded-vocab direction — must log, not raise.
    check_vocab(hf_tok, cfg.vocab_size, "test")
    params = llama.init_params(jax.random.key(0), cfg)
    server = make_server(
        Generator(params, cfg, hf_tok), host="127.0.0.1", port=0,
        default_max_tokens=8,
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    try:
        text = "hello world, how are you?"
        status, out = _post(port, "/tokenize",
                            {"prompt": text, "add_special_tokens": False})
        assert status == 200
        assert out["tokens"] == hf_tok.encode(text)
        status, with_bos = _post(port, "/tokenize", {"prompt": text})
        assert with_bos["tokens"] == [hf_tok.bos_id] + out["tokens"]
        status, out = _post(port, "/detokenize", {"tokens": out["tokens"]})
        assert status == 200
        assert out["prompt"] == text
        # Chat completion renders through the fixture's Llama-3 template and
        # decodes through the fixture's vocab — the full serving loop.
        status, out = _post(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "temperature": 0.0,
        })
        assert status == 200
        assert out["object"] == "chat.completion"
        assert isinstance(out["choices"][0]["message"]["content"], str)
        assert out["usage"]["completion_tokens"] <= 4
    finally:
        server.shutdown()


# -- the two loaders of one object (ISSUE 61) --------------------------------
#
# A local directory with a tokenizer.json is read through `tokenizers` alone;
# the cases below hold that path to what AutoTokenizer gives for the same
# directory. `test_tokenizers_loader_matches_autotokenizer` is the one test
# that may import `transformers`.


def _write_json(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


def _wordlevel_dir(tmp_path) -> str:
    """What the benchmark's serving cells load: a word-level table in which
    every id is its own piece, Qwen2's specials at the top, no bos, no
    decoder, `clean_up_tokenization_spaces` false."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    n, specials = 640, {637: "<|endoftext|>", 638: "<|im_start|>", 639: "<|im_end|>"}
    vocab = {specials.get(i, f"t{i}"): i for i in range(n)}
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="t0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.add_special_tokens(list(specials.values()))
    path = tmp_path / "wordlevel"
    path.mkdir()
    tok.save(str(path / "tokenizer.json"))
    eos = specials[637]
    _write_json(path / "special_tokens_map.json", {"eos_token": eos, "pad_token": eos})
    _write_json(path / "tokenizer_config.json", {
        "tokenizer_class": "PreTrainedTokenizerFast", "eos_token": eos,
        "pad_token": eos, "clean_up_tokenization_spaces": False,
        "model_max_length": 1 << 20})
    return str(path)


_ODD_TEMPLATE = """{{- bos_token }}
{%- for m in messages %}
    {%- if m['role'] == 'tool' %}{{ raise_exception('no tools here') }}{% endif %}
    {%- if m['role'] == 'skip' %}{% continue %}{% endif %}
    {%- if m['role'] == 'assistant' %}
        {% generation %}{{ m['content'] | tojson }}{% endgeneration %}
    {%- else %}
        {{- '[' + m['role'] + '] ' + m['content'] + ' <&> ' + eos_token }}
    {%- endif %}
{%- endfor %}
{%- if tools is none and add_generation_prompt %}{{ additional_special_tokens[0] }}{% endif %}
"""


def _odd_bytelevel_dir(tmp_path) -> str:
    """The corners of a published directory at once: the file truncates, pads
    and prefixes a space and the config does not; the config names tokens the
    file lacks (a plain string, an AddedToken dict, a list, one that only
    special_tokens_map.json knows); clean-up is on; the template is a
    chat_template.jinja that uses what `transformers` adds to jinja."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=True)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(
        ["the quick brown fox , is n't it ? hello world !"] * 8,
        trainers.BpeTrainer(vocab_size=300, special_tokens=["<unk>", "<s>"],
                            initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
                            show_progress=False))
    tok.enable_truncation(max_length=4)
    tok.enable_padding(length=8, pad_token="<unk>")
    path = tmp_path / "odd"
    path.mkdir()
    tok.save(str(path / "tokenizer.json"))
    _write_json(path / "tokenizer_config.json", {
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": {"__type": "AddedToken", "content": "<s>", "lstrip": False,
                      "normalized": False, "rstrip": False, "single_word": False},
        "eos_token": "</s>", "unk_token": "<unk>",
        "additional_special_tokens": ["<|tool|>"],
        "clean_up_tokenization_spaces": True,
        "chat_template": "{{ 'the config template loses to the file' }}"})
    _write_json(path / "special_tokens_map.json", {
        "pad_token": {"content": "<pad>", "lstrip": False, "normalized": False,
                      "rstrip": False, "single_word": False},
        "additional_special_tokens": ["<|tool|>", "<|extra|>"]})
    (path / "chat_template.jinja").write_text(_ODD_TEMPLATE, encoding="utf-8")
    return str(path)


_TEXTS = [
    "hello world! the quick brown fox: 42",
    "  two  spaces ,  and   three . is n't it ? we 're here !",
    "<|begin_of_text|>specials<|eot_id|> <s> in </s> the <pad> text <|tool|><|endoftext|>",
    "t5 t17 t636 <|im_start|> t9 unknown-piece",
    "caf\u00e9 \u4f60\u597d\n\n\ttabs",
    "",
]
_CHATS = [
    [{"role": "system", "content": "You are a helpful assistant. "},
     {"role": "user", "content": "What is 2+2?"}],
    [{"role": "user", "content": "a"}, {"role": "skip", "content": "b"},
     {"role": "assistant", "content": "c <&> \u00e9"}],
    [{"content": "a turn with no role"}],  # both templates raise
    [{"role": "tool", "content": "x"}],  # the odd template raises
]


@pytest.mark.parametrize("which", ["llama3_fixture", "wordlevel", "odd_bytelevel"])
def test_tokenizers_loader_matches_autotokenizer(which, tmp_path, monkeypatch):
    from transformers import AutoTokenizer

    path = {"llama3_fixture": lambda _: FIXTURE, "wordlevel": _wordlevel_dir,
            "odd_bytelevel": _odd_bytelevel_dir}[which](tmp_path)
    new = HFTokenizer(path)
    assert new.loader == "tokenizers"
    # The same adapter round the object AutoTokenizer builds for the directory.
    monkeypatch.setattr(tokenizer_mod, "_TokenizerDir", AutoTokenizer.from_pretrained)
    ref = HFTokenizer(path)
    monkeypatch.undo()
    assert type(ref._tok).__module__.startswith("transformers")

    for attr in ("vocab_size", "bos_id", "eos_id", "pad_id", "all_special_ids"):
        assert getattr(new, attr) == getattr(ref, attr), attr
    for text in _TEXTS:
        ids = ref.encode(text)
        assert new.encode(text) == ids, text
        assert new.decode(ids) == ref.decode(ids), text
    everything = list(range(ref.vocab_size + 3))
    assert new.decode(everything) == ref.decode(everything)
    assert [new.id_to_token(i) for i in everything] == [ref.id_to_token(i) for i in everything]
    for piece in ("<|start_header_id|>", "</s>", "<pad>", "t17", "no such piece"):
        assert new.token_to_id(piece) == ref.token_to_id(piece), piece
    assert grammar.token_strings(new) == grammar.token_strings(ref)

    assert bool(new.chat_template) == bool(ref.chat_template) == (which != "wordlevel")
    plain = "\n".join(f"{m.get('role', 'user')}: {m.get('content', '')}"
                      for m in _CHATS[0]) + "\nassistant:"
    fell_back = 0
    for messages in _CHATS:
        prompt = _chat_prompt(messages, new)
        assert prompt == _chat_prompt(messages, ref), messages
        fell_back += prompt.endswith("\nassistant:")
    assert (_chat_prompt(_CHATS[0], new) == plain) == (which == "wordlevel")
    # no template: every chat falls back; else those the template refuses
    assert fell_back == {"wordlevel": 4, "llama3_fixture": 1, "odd_bytelevel": 2}[which]


def test_tokenizers_loader_imports_neither_transformers_nor_torch():
    """The point of the loader: a start on a directory with a tokenizer.json,
    a chat prompt and guided decoding's byte table, and the 10-20 s import
    never happens."""
    script = (
        "import sys\n"
        "from ditl_tpu.data.tokenizer import get_tokenizer\n"
        "from ditl_tpu.infer import grammar\n"
        "from ditl_tpu.infer.server import _chat_prompt\n"
        f"tok = get_tokenizer({FIXTURE!r})\n"
        "assert tok.loader == 'tokenizers'\n"
        "prompt = _chat_prompt([{'role': 'user', 'content': 'hi'}], tok)\n"
        "assert prompt.startswith('<|begin_of_text|><|start_header_id|>user'), prompt\n"
        "assert len(grammar.token_strings(tok)) == tok.vocab_size\n"
        "print(sorted({m.split('.')[0] for m in sys.modules} & {'transformers', 'torch'}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


@pytest.mark.parametrize("name", ["meta-llama/Llama-3.1-8B", "sentencepiece_dir"])
def test_other_names_still_go_to_autotokenizer(name, tmp_path, monkeypatch):
    """A hub name, and a directory with only a SentencePiece tokenizer.model,
    keep AutoTokenizer (a stand-in here: nothing is imported or downloaded)."""
    if name == "sentencepiece_dir":
        (tmp_path / "tokenizer.model").write_bytes(b"\x00")
        _write_json(tmp_path / "tokenizer_config.json", {"eos_token": "</s>"})
        name = str(tmp_path)
    asked = []

    class FakeAuto:
        bos_token_id, eos_token_id, pad_token_id = None, 7, None
        all_special_ids, chat_template = [7], None

        def __len__(self):
            return 32

        def encode(self, text, add_special_tokens):
            assert add_special_tokens is False
            return [3, 4]

        @classmethod
        def from_pretrained(cls, name):
            asked.append(name)
            return cls()

    monkeypatch.setitem(sys.modules, "transformers",
                        types.SimpleNamespace(AutoTokenizer=FakeAuto))
    tok = get_tokenizer(name)
    assert asked == [name] and tok.loader == "transformers"
    assert (tok.vocab_size, tok.bos_id, tok.eos_id, tok.pad_id) == (32, 1, 7, 7)
    assert tok.encode("x") == [3, 4] and tok.all_special_ids == [7]
