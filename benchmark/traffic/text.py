"""Traffic kind `text`: JSON text requests for a classifier, from a mix file
and a seed. One general generator; a mix is data (benchmark/mixes/*.json).

A mix names request classes, each with a share, the number of texts per POST
and a distribution of text lengths in tokens. Every seed gets the SAME set of
lengths, classes and arrival gaps, in another order: the sets are quantile
grids of the stated distributions, and the seed only permutes them and draws
the words. So runs differ in order and content, never in the amount of work.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

from benchmark import vocab as vocab_mod


@dataclass
class Request:
    body: bytes
    items: int
    cls: str
    tokens: list[int]  # words per text (the program adds [CLS] and [SEP])


def length_grid(dist: dict, n: int) -> np.ndarray:
    """`n` lengths at the mid-quantiles of `dist`, clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "fixed":
        v = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(int)


def gap_grid(n: int, total_s: float) -> np.ndarray:
    """`n` exponential gaps at mid-quantiles, scaled to sum to `total_s`."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (total_s / g.sum())


def text_of(rng: np.random.Generator, table: np.ndarray, n: int) -> str:
    """`n` words of the vocabulary table (entries from FIRST_WORD on)."""
    return " ".join(table[rng.integers(vocab_mod.FIRST_WORD, len(table), n)])


def make_body(texts: list[str], single: bool) -> bytes:
    return json.dumps({"text": texts[0]} if single
                      else {"texts": texts}).encode()


def make_requests(mix: dict, seed: int, vocab_words: list[str],
                  n: int) -> list[Request]:
    """`n` requests of the mix, class counts by share, in seed order."""
    rng = np.random.default_rng([seed, 1])
    words = np.asarray(vocab_words)
    classes = mix["classes"]
    counts = [int(math.floor(c["share"] * n)) for c in classes]
    counts[0] += n - sum(counts)
    order = np.repeat(np.arange(len(classes)), counts)
    rng.shuffle(order)
    lengths = []
    for c, k in zip(classes, counts):
        grid = length_grid(c["tokens"], max(1, k * c["items"]))
        rng.shuffle(grid)
        lengths.append(list(grid))
    out = []
    for ci in order:
        c = classes[ci]
        toks = [int(lengths[ci].pop()) for _ in range(c["items"])]
        texts = [text_of(rng, words, t) for t in toks]
        out.append(Request(make_body(texts, c["items"] == 1),
                           c["items"], c["name"], toks))
    return out


def make_check(mix: dict, seed: int, vocab_words: list[str]) -> list[tuple[Request, list[str]]]:
    """The correctness sample: the mix's `check` list, one request per entry,
    lengths as stated there, words from the seed. Returns each request with
    its texts (the reference tokenizes them itself)."""
    rng = np.random.default_rng([seed, 2])
    words = np.asarray(vocab_words)
    out = []
    for entry in mix["check"]:
        toks = [int(t) for t in entry["tokens"]]
        texts = [text_of(rng, words, t) for t in toks]
        req = Request(make_body(texts, len(texts) == 1), len(texts), "check", toks)
        out.append((req, texts))
    return out


def due_times(mix: dict, seed: int, n: int, seconds: float) -> np.ndarray:
    """Open loop: `n` due times in [0, seconds), exponential gaps from a
    fixed grid in seed order; the last gap runs to the window's end."""
    rng = np.random.default_rng([seed, 3])
    gaps = gap_grid(n, seconds)
    rng.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def prepare(work: str, cfg: dict) -> tuple[list[str], dict]:
    """Write the vocabulary file the served model reads; returns the words the
    generator may use and the model options that point at the file."""
    table = vocab_mod.make_vocab(int(cfg["vocab_size"]))
    path = os.path.join(work, "vocab.txt")
    vocab_mod.write_vocab(path, table)
    return table, {"vocab_file": path}


def check_inputs(sample: list, table: list[str]) -> list[np.ndarray]:
    """Token ids of every text of the sample, by the benchmark's own
    whole-word tokenization (never the program's)."""
    word_id = {w: i for i, w in enumerate(table)}
    return [vocab_mod.encode(t, word_id) for _req, texts in sample for t in texts]
