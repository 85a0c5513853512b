"""The tokenizer's ASCII path against the walk it replaces (ISSUE 33).

`text.basic_tokenize` splits a text that `str.isascii()` with one compiled
pattern and `WordPieceTokenizer.ids` looks each word up whole; every other
text takes `text._walk`, the per-character split, which is kept as that path
and as the oracle here. For every string the two must agree letter for
letter, and the ids through `ids` must be the ids of the walk's pieces.
"""

import importlib.util
import os
import random

import numpy as np
import pytest

from tpuserve import text as text_mod
from tpuserve.config import ModelConfig
from tpuserve.models import build
from tpuserve.obs import Metrics
from tpuserve.text import (
    SPECIALS, UNK, WordPieceTokenizer, _walk, basic_tokenize, synthetic_vocab,
)

# Every printable ASCII character is a piece of this vocabulary (cased too),
# so no ASCII text hits [UNK] here: a wrong split shows as other ids.
FULL = synthetic_vocab(2048)
# A vocabulary with holes: whole words, words that split, words with no piece.
SPARSE = {t: i for i, t in enumerate(
    list(SPECIALS) + ["un", "##aff", "##able", "##a", "##ff", "aff", "hello",
                      "##s", "world", ",", "!", "Hello", "a", "b", "##b", "1",
                      "##1", "cafe", "i", "\u4e2d", "x" * 101, "y" * 100])}


def oracle_pieces(tok: WordPieceTokenizer, text: str) -> list[str]:
    """`tokenize` as it was before the pattern: the walk, then wordpiece."""
    out: list[str] = []
    for word in _walk(text, tok.lower):
        out.extend(tok.wordpiece(word))
    return out


def oracle_ids(tok: WordPieceTokenizer, text: str) -> list[int]:
    """The ids as `encode` and `_encode` made them: every piece looked up."""
    return [tok.vocab.get(p, tok.unk_id) for p in oracle_pieces(tok, text)]


def check(text: str, vocab: dict[str, int] = FULL) -> None:
    for lower in (True, False):
        tok = WordPieceTokenizer(vocab, lower=lower)
        assert basic_tokenize(text, lower) == _walk(text, lower), (text, lower)
        assert tok.tokenize(text) == oracle_pieces(tok, text), (text, lower)
        ids = tok.ids(text)
        assert ids == oracle_ids(tok, text), (text, lower)
        assert all(type(i) is int for i in ids)
        assert tok.n_tokens(text) == len(ids) + 2


# -- (a) every ASCII code point ------------------------------------------------

@pytest.mark.parametrize("cp", range(128))
def test_each_ascii_code_point_alone_and_between_letters(cp):
    ch = chr(cp)
    for text in (ch, "a" + ch + "b", "A" + ch + "B", ch + "ab" + ch,
                 "ab" + ch + ch + "cd"):
        check(text)
        check(text, SPARSE)


# -- (b) seeded random ASCII ---------------------------------------------------

_SEPARATORS = " \t\n\r\x0b\x0c\x00\x1c\x1d\x1e\x1f\x7f"
_PUNCT = "!\"#$%&'()*+,-./:;<=>?@[\\]^_`{|}~"
_ALNUM = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"


def random_ascii(seed: int) -> str:
    rng = random.Random(seed)
    parts = []
    for _ in range(rng.randint(1, 60)):
        kind = rng.random()
        if kind < 0.45:
            parts.append("".join(rng.choices(_ALNUM, k=rng.randint(1, 12))))
        elif kind < 0.75:
            parts.append("".join(rng.choices(_SEPARATORS, k=rng.randint(1, 4))))
        elif kind < 0.9:
            parts.append("".join(rng.choices(_PUNCT, k=rng.randint(1, 3))))
        else:
            parts.append(chr(rng.randrange(128)))
    return "".join(parts)


@pytest.mark.parametrize("seed", range(32))
def test_random_ascii_strings(seed):
    text = random_ascii(seed)
    assert text.isascii()
    check(text)
    check(text, SPARSE)


# -- (c) case ------------------------------------------------------------------

@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("text", [
    "Hello, World!", "HELLO hello HeLLo", "MiXeD123Case_and-Punct.",
    "ALLCAPS\tTabbed\nLines", "a B c D"])
def test_mixed_case(text, lower):
    words = basic_tokenize(text, lower)
    assert any(c.isupper() for w in words for c in w) == (not lower)
    assert "".join(words).lower() == "".join(_walk(text)).lower()
    check(text)
    check(text, SPARSE)


def test_cased_vocabulary_keeps_the_capital():
    assert WordPieceTokenizer(SPARSE, lower=False).ids("Hello") == [SPARSE["Hello"]]
    assert WordPieceTokenizer(SPARSE, lower=True).ids("Hello") == [SPARSE["hello"]]


# -- (d) the edges of wordpiece ------------------------------------------------

@pytest.mark.parametrize("text,pieces", [
    ("x" * 101, [UNK]),                      # in the vocabulary, but too long
    ("y" * 100, ["y" * 100]),                # as long as a word may be
    ("z" * 150, [UNK]),
    ("unaffable", ["un", "##aff", "##able"]),
    ("hellos", ["hello", "##s"]),
    ("zzz", [UNK]),                          # no piece at all
    ("helloz", [UNK]),                       # a first piece, then none
    ("hello zzz world", ["hello", UNK, "world"]),
    ("hello, world!", ["hello", ",", "world", "!"]),
    ("ab1", ["a", "##b", "##1"]),
    ("", []),
    (" ", []),
    (" \t\n\r\x00\x1f\x7f  ", []),
    ("[UNK]", [UNK, UNK, UNK]),              # brackets are punctuation, not in SPARSE
])
def test_wordpiece_edges(text, pieces):
    tok = WordPieceTokenizer(SPARSE)
    assert tok.tokenize(text) == pieces
    assert tok.ids(text) == [SPARSE[p] for p in pieces]
    check(text, SPARSE)


# -- (e) one character that is not ASCII: the walk, whole ----------------------

NON_ASCII = [
    "Caf\u00e9 au lait",       # an accent: stripped by NFD + Mn
    "cafe\u0301",              # the same, already decomposed
    "a\u4e2db",                # CJK: its own token
    "\u0130stanbul",           # lowers to i + a combining dot
    "na\u00efve \u2014 dash",  # an em dash: Unicode punctuation
    "hello\u00a0world",        # a no-break space: isspace()
    "hello\u200bworld",        # a zero-width space: Cf, kept inside the word
    "x\u0085y",                # NEL: a Cc outside ASCII
    "\u00df Stra\u00dfe",
]


@pytest.mark.parametrize("text", NON_ASCII)
def test_non_ascii_text_takes_the_walk(text, monkeypatch):
    calls = []
    walk = text_mod._walk

    def spy(t, lower=True):
        calls.append(t)
        return walk(t, lower)

    check(text)
    check(text, SPARSE)
    monkeypatch.setattr(text_mod, "_walk", spy)
    assert not text.isascii()
    assert basic_tokenize(text) == walk(text)
    assert calls == [text]
    basic_tokenize("plain ascii, no walk")
    assert calls == [text]


def test_the_walk_still_does_what_it_did():
    assert _walk("Hello, World!") == ["hello", ",", "world", "!"]
    assert basic_tokenize("Caf\u00e9") == ["cafe"]
    assert basic_tokenize("a\u4e2db") == ["a", "\u4e2d", "b"]
    assert basic_tokenize("\u0130") == ["i"]


# -- encode / n_tokens / BertServing._encode against the benchmark's table -----

def _benchmark_vocab():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "vocab.py")
    spec = importlib.util.spec_from_file_location("benchmark_vocab_for_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """The benchmark's vocabulary at a small size, its file, a text of its
    words and that text's ids by the benchmark's own `encode`."""
    bv = _benchmark_vocab()
    toks = bv.make_vocab(512)
    path = tmp_path_factory.mktemp("vocab") / "vocab.txt"
    bv.write_vocab(str(path), toks)
    word_id = {t: i for i, t in enumerate(toks)}
    rng = random.Random(7)
    text = " ".join(rng.choice(toks[bv.FIRST_WORD:]) for _ in range(40))
    return str(path), text, bv.encode(text, word_id)


def test_encode_and_n_tokens_frame_as_the_benchmark_does(table):
    path, text, want = table
    tok = WordPieceTokenizer.from_vocab_file(path)
    assert tok.ids(text) == want[1:-1].tolist()
    assert tok.n_tokens(text) == len(want)
    ids, mask = tok.encode(text, 64)
    n = len(want)
    assert ids.dtype == np.int32 and ids.shape == mask.shape == (64,)
    np.testing.assert_array_equal(ids[:n], want)
    assert np.all(ids[n:] == tok.pad_id)
    assert mask.tolist() == [1] * n + [0] * (64 - n)


@pytest.mark.parametrize("max_len", [2, 3, 8, 41, 42, 43])
def test_encode_truncates_as_before(table, max_len):
    path, text, want = table
    tok = WordPieceTokenizer.from_vocab_file(path)
    ids, mask = tok.encode(text, max_len)
    n = min(len(want), max_len)
    assert ids[0] == tok.cls_id and ids[n - 1] == tok.sep_id
    np.testing.assert_array_equal(ids[1:n - 1], want[1:n - 1])
    assert int(mask.sum()) == n


def _bert(path: str, seq: int):
    return build(ModelConfig(
        name="bert", family="bert", batch_buckets=[1], seq_buckets=[seq],
        dtype="float32", num_classes=3, parallelism="single",
        options=dict(layers=1, d_model=16, heads=2, d_ff=32, vocab_file=path)))


def test_bert_serving_encode_is_the_benchmarks_ids(table):
    path, text, want = table
    model = _bert(path, 64)
    got = model._encode(text)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    short = _bert(path, 16)._encode(text)
    np.testing.assert_array_equal(short, np.append(want[:15], want[-1]))
    np.testing.assert_array_equal(
        model._encode(""), [model.tokenizer.cls_id, model.tokenizer.sep_id])


def test_bert_serving_encode_equals_the_walk_on_other_text(table):
    path, _, _ = table
    model = _bert(path, 64)
    tok = model.tokenizer
    for text in NON_ASCII + ["Hello, World!", random_ascii(3)]:
        want = [tok.cls_id] + oracle_ids(tok, text)
        want = want[:63] + [tok.sep_id]
        np.testing.assert_array_equal(model._encode(text), want)


# -- the counter that says which split ran -------------------------------------

def test_path_counter_counts_one_a_document(table):
    path, text, _ = table
    model = _bert(path, 64)
    metrics = Metrics()
    model.bind_metrics(metrics)

    def read() -> tuple[float, float]:
        v = metrics.counter_values()
        return (v["ingest_tokenize_path_total{model=bert,path=ascii}"],
                v["ingest_tokenize_path_total{model=bert,path=unicode}"])

    assert read() == (0, 0)
    model._encode_all([text, "two words", ""])
    assert read() == (3, 0)
    model._encode_all(["Caf\u00e9", text, "a\u4e2db"])
    assert read() == (4, 2)
    items, many = model.host_decode_items(
        b'{"texts": ["plain", "na\\u00efve"]}', "application/json")
    assert many and len(items) == 2
    assert read() == (5, 3)
    assert metrics.counter_values()["ingest_tokens_total{model=bert}"] > 0
