"""Traffic is a pure function of the seed, and every seed gets the same set
of sizes and arrivals in another order."""

import json

import numpy as np
import pytest

from benchmark import spec, vocab

text = spec.load_module("traffic", "text")
TABLE = vocab.make_vocab(2048)


@pytest.mark.parametrize("mix_name", ["docs-closed", "rehearsal-open"])
def test_same_seed_same_bytes_other_seed_same_sizes(mix_name):
    mix = spec.load_mix(mix_name)
    a = text.make_requests(mix, 3_000_000_007, TABLE, 200)
    b = text.make_requests(mix, 3_000_000_007, TABLE, 200)
    c = text.make_requests(mix, 5, TABLE, 200)
    assert [r.body for r in a] == [r.body for r in b]
    assert [r.body for r in a] != [r.body for r in c]
    sizes = lambda rs: sorted(t for r in rs for t in r.tokens)  # noqa: E731
    assert sizes(a) == sizes(c)
    assert sorted(r.cls for r in a) == sorted(r.cls for r in c)


def test_lengths_follow_the_mix_and_the_words_are_one_token_each():
    mix = spec.load_mix("docs-closed")
    reqs = text.make_requests(mix, 1, TABLE, 64)
    lens = np.asarray([t for r in reqs for t in r.tokens])
    assert lens.min() >= 16 and lens.max() <= 510
    assert 270 <= np.median(lens) <= 330
    word_id = {w: i for i, w in enumerate(TABLE)}
    body = json.loads(reqs[0].body)
    for t, n in zip(body["texts"], reqs[0].tokens):
        ids = vocab.encode(t, word_id)
        assert len(ids) == n + 2 and ids[0] == vocab.CLS and ids[-1] == vocab.SEP
        assert (ids[1:-1] >= vocab.FIRST_WORD).all()


def test_open_mix_shares_and_single_text_bodies():
    mix = spec.load_mix("rehearsal-open")
    reqs = text.make_requests(mix, 9, TABLE, 100)
    short = [r for r in reqs if r.cls == "short"]
    assert len(short) == 80 and all(r.items == 1 for r in short)
    assert "text" in json.loads(short[0].body)
    assert all(r.items == 4 for r in reqs if r.cls == "frame4")


def test_due_times_fill_the_window_whatever_the_seed():
    mix = spec.load_mix("rehearsal-open")
    a = text.due_times(mix, 1, 300, 30.0)
    b = text.due_times(mix, 2**31 + 7, 300, 30.0)
    assert a[0] == 0 and (np.diff(a) > 0).all() and a[-1] < 30.0
    assert not np.allclose(a, b)
    gaps = lambda d: np.sort(np.diff(np.append(d, 30.0)))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b))


def test_the_program_tokenizer_agrees_with_the_benchmark_one(tmp_path):
    """One token per word under the served WordPiece tokenizer too."""
    from tpuserve.text import WordPieceTokenizer

    path = tmp_path / "vocab.txt"
    vocab.write_vocab(str(path), TABLE)
    tok = WordPieceTokenizer.from_vocab_file(str(path))
    word_id = {w: i for i, w in enumerate(TABLE)}
    rng = np.random.default_rng(0)
    t = text.text_of(rng, np.asarray(TABLE), 40)
    mine = vocab.encode(t, word_id)
    theirs = [tok.cls_id] + [tok.vocab[p] for p in tok.tokenize(t)] + [tok.sep_id]
    assert list(mine) == theirs
