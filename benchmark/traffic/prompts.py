"""Traffic kind `prompts`: JSON generation requests, `{"prompt", "seed",
"max_new_tokens", "temperature"}` to `:generate`, from a mix file and a seed.
One general generator; a mix is data (benchmark/mixes/*.json).

A mix names request classes, each with a share, a distribution of prompt
lengths in tokens, a distribution of tokens asked for (`max_new_tokens`) and a
temperature (0, greedy, unless it says otherwise). As in traffic kind `text`,
every seed gets the SAME set of lengths, in another order, and the same
arrival gaps: the seed permutes quantile grids and draws the words. One
request is one item, and its answer is the response itself:
`{"text", "tokens", "n_tokens"}`.

The correctness sample (`check` in the mix: prompt length and tokens asked
for, one request an entry) is always greedy, whatever the classes sample
with: the check's comparison holds for greedy tokens only.

The generators of lengths, gaps and words are traffic kind `text`'s.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass

import numpy as np

from benchmark import spec
from benchmark import vocab as vocab_mod

_text = spec.load_module("traffic", "text")
prepare = _text.prepare      # the vocabulary file the served model reads
due_times = _text.due_times  # open loop: seeded exponential gaps from a fixed grid


@dataclass
class Request:
    body: bytes
    items: int           # one request, one answer
    cls: str
    tokens: list[int]    # the prompt's length, in a list as `text` has it
    max_new: int


def answers_of(obj: dict) -> list:
    """One response holds one answer, itself, where it has tokens."""
    return [obj] if obj.get("tokens") else []


def _request(rng, words, n_prompt: int, max_new: int, temperature: float,
             cls: str) -> tuple[Request, str]:
    prompt = _text.text_of(rng, words, n_prompt)
    body = {"prompt": prompt, "seed": int(rng.integers(0, 2**31 - 1)),
            "max_new_tokens": max_new, "temperature": temperature}
    return Request(json.dumps(body).encode(), 1, cls, [n_prompt], max_new), prompt


def make_requests(mix: dict, seed: int, vocab_words: list[str], n: int) -> list[Request]:
    """`n` requests of the mix, class counts by share, in seed order."""
    rng = np.random.default_rng([seed, 1])
    words = np.asarray(vocab_words)
    classes = mix["classes"]
    counts = [int(math.floor(c["share"] * n)) for c in classes]
    counts[0] += n - sum(counts)
    order = np.repeat(np.arange(len(classes)), counts)
    rng.shuffle(order)
    grids = []
    for c, k in zip(classes, counts):
        pair = [_text.length_grid(c[key], max(1, k)) for key in ("prompt_tokens", "max_new_tokens")]
        for grid in pair:  # each on its own, so long prompts do not always ask for many tokens
            rng.shuffle(grid)
        grids.append([list(g) for g in pair])
    out = []
    for ci in order:
        c = classes[ci]
        out.append(_request(rng, words, int(grids[ci][0].pop()), int(grids[ci][1].pop()),
                            float(c.get("temperature", 0.0)), c["name"])[0])
    return out


def make_check(mix: dict, seed: int, vocab_words: list[str]) -> list[tuple[Request, list[str]]]:
    """The correctness sample: one greedy request per entry of the mix's
    `check`, words from the seed, each with its prompt (the reference finds
    the ids itself)."""
    rng = np.random.default_rng([seed, 2])
    words = np.asarray(vocab_words)
    out = []
    for entry in mix["check"]:
        req, prompt = _request(rng, words, int(entry["prompt_tokens"]),
                               int(entry["max_new_tokens"]), 0.0, "check")
        out.append((req, [prompt]))
    return out


def check_inputs(sample: list, table: list[str]) -> list[dict]:
    """Per request of the sample: the prompt's ids by the benchmark's own
    whole-word tokenization (one id a word, nothing added: the program puts
    neither [CLS] nor [SEP] round a prompt), the tokens asked for, and the id
    that ends a text."""
    word_id = {w: i for i, w in enumerate(table)}
    return [{"ids": np.asarray([word_id[w] for w in prompts[0].split(" ")], np.int32),
             "max_new": req.max_new, "eos_id": vocab_mod.SEP} for req, prompts in sample]
