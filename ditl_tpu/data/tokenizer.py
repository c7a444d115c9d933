"""Tokenizers (L3).

The reference has no tokenizer — raw review text goes to the remote API and
the 'device op' encodes characters as ``float(ord(c))`` (ref
``src/utils.py:25-28``). A real on-TPU fine-tune needs token ids, so:

- ``ByteTokenizer``: dependency-free UTF-8 byte-level tokenizer (vocab 256 +
  specials) — the default for tests/benchmarks; deterministic and hub-free.
- ``get_tokenizer``: resolves ``DataConfig.tokenizer`` to either the byte
  tokenizer or ``HFTokenizer`` (for Llama-3.1 runs with the real vocab): a
  local directory with a ``tokenizer.json`` is read through ``tokenizers``
  alone, a hub name goes to ``transformers.AutoTokenizer``.

Both expose the same tiny surface: ``vocab_size``, ``encode``, ``decode``,
``pad_id``, ``bos_id``, ``eos_id``; ``HFTokenizer`` adds what the server's chat
endpoint and guided decoding read (the chat template, the special ids, an
id's vocabulary string).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Protocol, Sequence

__all__ = ["Tokenizer", "ByteTokenizer", "HFTokenizer", "check_vocab",
           "get_tokenizer"]


class Tokenizer(Protocol):
    vocab_size: int
    pad_id: int
    bos_id: int
    eos_id: int

    def encode(self, text: str) -> list[int]: ...

    def decode(self, ids: Sequence[int]) -> str: ...


class ByteTokenizer:
    """UTF-8 bytes shifted by the number of special tokens."""

    def __init__(self):
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = 2
        self.byte_offset = 3  # id of byte b is b + byte_offset (public:
        # the native packer, loader, and tests key off it)
        self.vocab_size = 256 + self.byte_offset

    def encode(self, text: str) -> list[int]:
        return [b + self.byte_offset for b in text.encode("utf-8")]

    def decode(self, ids: Sequence[int]) -> str:
        # Skip specials and out-of-vocab ids (a model head can be wider than
        # the tokenizer — e.g. vocab padded up for MXU tiling).
        data = bytes(
            i - self.byte_offset
            for i in ids
            if self.byte_offset <= i < self.byte_offset + 256
        )
        return data.decode("utf-8", errors="replace")


_NAMED_SPECIALS = ("bos_token", "eos_token", "unk_token", "sep_token",
                   "pad_token", "cls_token", "mask_token")
# PreTrainedTokenizerBase.clean_up_tokenization, for a directory whose config
# sets clean_up_tokenization_spaces
_CLEAN_UP = ((" .", "."), (" ?", "?"), (" !", "!"), (" ,", ","), (" ' ", "'"),
             (" n't", "n't"), (" 'm", "'m"), (" 's", "'s"), (" 've", "'ve"),
             (" 're", "'re"))


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _content(token) -> str | None:
    """A special token as the JSON files spell it: a string, or an
    ``AddedToken`` dict with ``content``."""
    return token.get("content") if isinstance(token, dict) else token


def _compile_chat_template(template: str):
    """The jinja environment ``transformers`` renders chat templates in
    (``utils/chat_template_utils._compile_jinja_template``): sandboxed,
    ``trim_blocks`` / ``lstrip_blocks``, loop controls, ``raise_exception``,
    ``strftime_now``, a ``tojson`` that does not escape HTML, and
    ``{% generation %}`` blocks rendered as their body."""
    import datetime

    import jinja2
    import jinja2.ext
    import jinja2.sandbox

    class Generation(jinja2.ext.Extension):
        tags = {"generation"}

        def parse(self, parser):
            next(parser.stream)
            return parser.parse_statements(["name:endgeneration"], drop_needle=True)

    def raise_exception(message):
        raise jinja2.exceptions.TemplateError(message)

    def tojson(x, ensure_ascii=False, indent=None, separators=None, sort_keys=False):
        return json.dumps(x, ensure_ascii=ensure_ascii, indent=indent,
                          separators=separators, sort_keys=sort_keys)

    env = jinja2.sandbox.ImmutableSandboxedEnvironment(
        trim_blocks=True, lstrip_blocks=True,
        extensions=[Generation, jinja2.ext.loopcontrols],
    )
    env.filters["tojson"] = tojson
    env.globals["raise_exception"] = raise_exception
    env.globals["strftime_now"] = lambda fmt: datetime.datetime.now().strftime(fmt)
    return env.from_string(template)


def _tokenizer_config(path: str) -> dict:
    """``tokenizer_config.json``, with ``special_tokens_map.json`` laid over it
    where the config is from before it carried the added tokens itself (the
    map then has the last word, as in ``_from_pretrained``)."""
    cfg = _read_json(os.path.join(path, "tokenizer_config.json"))
    if "added_tokens_decoder" not in cfg:
        legacy = _read_json(os.path.join(path, "special_tokens_map.json"))
        extra = legacy.pop("additional_special_tokens", None) or []
        cfg.update(legacy)
        cfg["additional_special_tokens"] = list(
            cfg.get("additional_special_tokens") or []) + extra
    template = cfg.get("chat_template")
    if isinstance(template, list):  # named templates: [{name, template}]
        template = {t["name"]: t["template"] for t in template}
    if isinstance(template, dict):
        template = template.get("default")
    jinja_file = os.path.join(path, "chat_template.jinja")
    if os.path.isfile(jinja_file):  # beside the config, it wins
        with open(jinja_file, encoding="utf-8") as f:
            template = f.read()
    cfg["chat_template"] = template
    return cfg


def _load_backend(path: str, cfg: dict, special_tokens: list[str]):
    """The directory's ``tokenizers.Tokenizer`` as AutoTokenizer would leave
    it: a call of its ``encode`` turns the file's truncation and padding off;
    its constructor adds every token of the config that the file lacks, and
    carries the config's ``add_prefix_space`` into the pre-tokenizer."""
    from tokenizers import AddedToken, Tokenizer, pre_tokenizers

    tok = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
    tok.no_truncation()
    tok.no_padding()
    tok.encode_special_tokens = bool(cfg.get("split_special_tokens", False))
    have = tok.get_added_tokens_decoder().values()
    reprs = {repr(t) for t in have}
    names = {t.content for t in have}
    add = []
    for _, t in sorted((int(i), t) for i, t in
                       (cfg.get("added_tokens_decoder") or {}).items()):
        t = AddedToken(**{k: v for k, v in t.items() if k != "__type"})
        if repr(t) not in reprs:
            add.append(t)
            names.add(t.content)
    add += [AddedToken(s, special=True) for s in special_tokens if s not in names]
    for t in add:
        t.special = t.special or t.content in special_tokens
    if add:
        tok.add_tokens(add)
    prefix_space = bool(cfg.get("add_prefix_space", False))
    state = json.loads(tok.pre_tokenizer.__getstate__()) if tok.pre_tokenizer else {}
    if state.get("add_prefix_space", prefix_space) != prefix_space:
        state["add_prefix_space"] = prefix_space
        tok.pre_tokenizer = getattr(pre_tokenizers, state.pop("type"))(**state)
    return tok


class _TokenizerDir:
    """A local directory with a ``tokenizer.json``, read through
    ``tokenizers`` and ``json`` alone, offering what ``HFTokenizer`` asks of a
    fast ``AutoTokenizer`` with the same answers: that class is a wrapper
    round this very ``tokenizers.Tokenizer``, and importing it (with
    ``torch`` behind it) was 18-20 s of every serving start."""

    def __init__(self, path: str):
        cfg = _tokenizer_config(path)
        named = {k: _content(cfg.get(k)) for k in _NAMED_SPECIALS}
        named.update({k: _content(v) for k, v in
                      (cfg.get("extra_special_tokens") or {}).items()})
        additional = list(dict.fromkeys(
            _content(t) for t in cfg.get("additional_special_tokens") or []))
        # what a chat template sees as variables, and what all_special_ids lists
        self.special_tokens_map = {k: v for k, v in named.items() if v}
        if additional:
            self.special_tokens_map["additional_special_tokens"] = additional
        self.all_special_tokens = list(dict.fromkeys(
            [v for v in named.values() if v] + additional))
        self._backend = _load_backend(path, cfg, self.all_special_tokens)
        self._clean_up = bool(cfg.get("clean_up_tokenization_spaces", False))
        self.chat_template = cfg["chat_template"]
        unk = named["unk_token"]
        self.unk_token_id = None if unk is None else self._backend.token_to_id(unk)
        self.bos_token_id = self.convert_tokens_to_ids(named["bos_token"])
        self.eos_token_id = self.convert_tokens_to_ids(named["eos_token"])
        self.pad_token_id = self.convert_tokens_to_ids(named["pad_token"])

    def convert_tokens_to_ids(self, token: str | None) -> int | None:
        if token is None:
            return None
        i = self._backend.token_to_id(token)
        return self.unk_token_id if i is None else i

    def __len__(self) -> int:
        return self._backend.get_vocab_size(with_added_tokens=True)

    @property
    def all_special_ids(self) -> list[int]:
        return [self.convert_tokens_to_ids(t) for t in self.all_special_tokens]

    def convert_ids_to_tokens(self, i: int) -> str | None:
        return self._backend.id_to_token(i)

    def encode(self, text: str, add_special_tokens: bool = False) -> list[int]:
        # A batch of one, as AutoTokenizer encodes: `encode_batch` releases the
        # interpreter's lock while it tokenizes and `encode` holds it, which
        # for a 33,000-token prompt is ~0.1 s of the engine thread's ticks.
        return self._backend.encode_batch(
            [text], add_special_tokens=add_special_tokens)[0].ids

    def decode(self, ids: Sequence[int]) -> str:
        text = self._backend.decode(ids, skip_special_tokens=False)
        if self._clean_up:
            for spaced, tight in _CLEAN_UP:
                text = text.replace(spaced, tight)
        return text

    @functools.cached_property
    def _template(self):  # compiled at the first chat request, kept with the tokenizer
        return _compile_chat_template(self.chat_template)

    def apply_chat_template(self, messages: list[dict], tokenize: bool = False,
                            add_generation_prompt: bool = True) -> str:
        if tokenize:
            raise NotImplementedError("the rendered text only")
        return self._template.render(
            messages=messages, tools=None, documents=None,
            add_generation_prompt=add_generation_prompt, **self.special_tokens_map)


class HFTokenizer:
    """Adapter over a Hugging Face tokenizer. A local directory that holds a
    ``tokenizer.json`` is read by ``_TokenizerDir`` (``loader`` says
    ``tokenizers``); anything else, a hub name or a directory with only a
    SentencePiece ``tokenizer.model``, goes to ``transformers.AutoTokenizer``
    (``loader`` says ``transformers``), imported here because that import
    takes 10-20 s. The rest of the program asks this adapter and never the
    object inside."""

    def __init__(self, name: str):
        if os.path.isfile(os.path.join(name, "tokenizer.json")):
            self.loader = "tokenizers"
            self._tok = _TokenizerDir(name)
        else:
            from transformers import AutoTokenizer

            self.loader = "transformers"
            self._tok = AutoTokenizer.from_pretrained(name)
        self.vocab_size = len(self._tok)
        # `is not None`, not `or`: token id 0 is a legitimate special token.
        self.bos_id = self._tok.bos_token_id if self._tok.bos_token_id is not None else 1
        self.eos_id = self._tok.eos_token_id if self._tok.eos_token_id is not None else 2
        self.pad_id = (
            self._tok.pad_token_id if self._tok.pad_token_id is not None else self.eos_id
        )
        self.all_special_ids = list(self._tok.all_special_ids)
        self.chat_template = self._tok.chat_template

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def decode(self, ids: Sequence[int]) -> str:
        # Drop ids outside the tokenizer's table: a model head can be wider
        # than the tokenizer (vocab padded for MXU tiling, or Llama-3.1's
        # reserved rows), and an undertrained model can emit those ids —
        # HF decode would raise/garble instead of skipping.
        return self._tok.decode([i for i in ids if 0 <= i < self.vocab_size])

    def id_to_token(self, token_id: int) -> str | None:
        """The vocabulary string of an id (guided decoding's byte table)."""
        return self._tok.convert_ids_to_tokens(token_id)

    def token_to_id(self, token: str) -> int | None:
        return self._tok.convert_tokens_to_ids(token)

    def apply_chat_template(self, messages: list[dict]) -> str:
        """The directory's chat template over ``messages``, ending in the
        generation prompt; raises what the template raises."""
        return self._tok.apply_chat_template(
            messages, tokenize=False, add_generation_prompt=True)


def check_vocab(tokenizer: Tokenizer, model_vocab: int, where: str) -> None:
    """Padded-vocab seam validation (one rule everywhere): a tokenizer
    WIDER than the model head means ids the model cannot embed — hard
    error; a model head wider than the tokenizer is legitimate (padding /
    reserved rows) — the decode paths skip those ids and grammar tables
    mask them, so it only logs."""
    tv = tokenizer.vocab_size
    if tv > model_vocab:
        raise ValueError(
            f"{where}: tokenizer vocab {tv} exceeds the model's "
            f"{model_vocab} — prompts could contain ids the embedding "
            f"table does not have"
        )
    if tv < model_vocab:
        from ditl_tpu.utils.logging import get_logger

        get_logger(__name__).info(
            "%s: model head (%d) wider than tokenizer (%d): padded/"
            "reserved rows; out-of-table ids are skipped on decode and "
            "masked in grammar tables", where, model_vocab, tv,
        )


def get_tokenizer(name: str = "byte") -> Tokenizer:
    if name == "byte":
        return ByteTokenizer()
    return HFTokenizer(name)
