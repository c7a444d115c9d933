"""A synthetic tokenizer as wide as the model's vocabulary.

The server's byte tokenizer decodes only ids 3..258, and random weights over
a 152k-wide head emit almost nothing else, so a byte-tokenized stream is
silent until its last event and a client cannot time a first token. This
builds, once a checkout, a word-level tokenizer in which EVERY id decodes to
its own non-empty piece (``t<id>``), pieces are separated by one space, and
Qwen2's three special tokens keep their published ids. The server loads it
by path (``--tokenizer DIR``; ``HFTokenizer`` -> ``AutoTokenizer``), which is
also the tokenizer path a Qwen2 deployment runs.

A prompt built from pieces has exactly as many tokens as pieces, and a
client counts the tokens of a chunk by splitting its text on spaces.
"""

from __future__ import annotations

import json
import os

# Qwen2's tokenizer_config.json: eos and pad are <|endoftext|>, there is no
# bos (the program then prepends its fallback id 1).
SPECIALS = {151643: "<|endoftext|>", 151644: "<|im_start|>", 151645: "<|im_end|>"}


def piece(token_id: int) -> str:
    return SPECIALS.get(token_id, f"t{token_id}")


def text_of(ids) -> str:
    """The text that encodes to exactly ``ids`` (none of them special)."""
    return " ".join(f"t{int(i)}" for i in ids)


def count_tokens(text: str) -> int:
    return len(text.split())


def ensure(out_dir: str, vocab_size: int) -> str:
    """Directory of the tokenizer for ``vocab_size``, built if absent."""
    path = os.path.join(out_dir, f"tokenizer-{vocab_size}")
    if os.path.exists(os.path.join(path, "tokenizer_config.json")):
        return path
    from tokenizers import Tokenizer, models, pre_tokenizers

    specials = {i: s for i, s in SPECIALS.items() if i < vocab_size}
    vocab = {piece(i): i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="t0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.add_special_tokens(list(specials.values()))
    tmp = f"{path}.tmp{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    tok.save(os.path.join(tmp, "tokenizer.json"))
    eos = specials.get(151643, "t2")
    with open(os.path.join(tmp, "special_tokens_map.json"), "w") as f:
        json.dump({"eos_token": eos, "pad_token": eos}, f)
    with open(os.path.join(tmp, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "eos_token": eos, "pad_token": eos,
                   "clean_up_tokenization_spaces": False,
                   "model_max_length": 1 << 20}, f)
    try:
        os.rename(tmp, path)
    except OSError:  # another run built it meanwhile
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
    return path
