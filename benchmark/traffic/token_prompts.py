"""Traffic kind `token_prompts`: JSON generation requests that carry token ids,
`{"prompt_ids", "seed", "max_new_tokens", "temperature"}` to `:generate`, from
a mix file and a seed. For a model whose published config names no tokenizer
and whose vocabulary may be sliced: ids are uniform over the rows the
configuration holds (`vocab_size` rows from `deployment_share.vocab_first`,
else from 0). One general generator; a mix is data (benchmark/mixes/*.json).

A mix names request classes as traffic kind `prompts` does: a share, a
distribution of prompt lengths in tokens, a distribution of tokens asked for
(`max_new_tokens`) and a temperature (0, greedy, unless it says otherwise).
Every seed gets the SAME set of lengths in another order, and other ids: the
seed orders quantile grids (traffic kind `text`'s) and draws the ids. No
request is repeated. One request is one item, and its answer is the response
itself: `{"tokens", "n_tokens"}`.

Every seed also gets the same WORK in any stretch of the pool, because a run
consumes only the pool's head (a 45 s window of the generating cell a quarter
of 2,048) and a plain shuffle hands each seed another sample of the lengths:
the median latency then spread 4.5 to 4.8% over six seeds, all of it the draw
(PERF.md section 6, PR 28). The order is `balanced_order`'s: any aligned run
of 2**k requests holds each class by its share and, of each class's prompt
lengths and of its tokens asked for, one from each of the evenly cut runs of
the sorted grid. The seed decides which one, at every level.

The correctness sample (`check` in the mix: prompt length and tokens asked
for, one request an entry) is always greedy and asks for `logprobs` (the
mix's `check_logprobs`, default 8): the family's comparison reads them.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass

import numpy as np

from benchmark import spec

_text = spec.load_module("traffic", "text")
due_times = _text.due_times  # open loop: seeded exponential gaps from a fixed grid


@dataclass
class Request:
    body: bytes
    items: int           # one request, one answer
    cls: str
    tokens: list[int]    # the prompt's length, in a list as `text` has it
    max_new: int


def prepare(work: str, cfg: dict) -> tuple[list[int], dict]:
    """No vocabulary file: the "vocabulary" the generator draws from is the
    range of held rows, `[first, first + count)`."""
    first = int(cfg.get("deployment_share", {}).get("vocab_first", 0))
    return [first, int(cfg["vocab_size"])], {}


def answers_of(obj: dict) -> list:
    """One response holds one answer, itself, where it has tokens."""
    return [obj] if obj.get("tokens") else []


def _request(rng, rows: list[int], n_prompt: int, max_new: int, temperature: float,
             cls: str, logprobs: int = 0) -> tuple[Request, np.ndarray]:
    ids = rng.integers(rows[0], rows[0] + rows[1], n_prompt)
    body = {"prompt_ids": ids.tolist(), "seed": int(rng.integers(0, 2**31 - 1)),
            "max_new_tokens": max_new, "temperature": temperature}
    if logprobs:
        body["logprobs"] = logprobs
    return Request(json.dumps(body).encode(), 1, cls, [n_prompt], max_new), ids


def balanced_order(rng, n: int) -> list[int]:
    """A seeded order of `range(n)` in which every aligned run of 2**k places
    holds one index from each of 2**k evenly cut runs of `range(n)`: the
    radical-inverse order with a coin tossed at every node of its tree (the
    lower and the upper half of a run of indices take its places in turn,
    each half ordered so within itself; the coin says which half goes first,
    and a larger lower half always does)."""
    def order(lo: int, hi: int) -> list[int]:
        if hi - lo == 1:
            return [lo]
        mid = lo + (hi - lo + 1) // 2
        a, b = order(lo, mid), order(mid, hi)
        if len(a) == len(b) and rng.integers(2):
            a, b = b, a
        out = [0] * (hi - lo)
        out[0::2], out[1::2] = a, b
        return out
    return order(0, n) if n else []


def make_requests(mix: dict, seed: int, rows: list[int], n: int) -> list[Request]:
    """`n` requests of the mix, class counts by share, in the seed's balanced
    order (the module's docstring says why not a plain shuffle)."""
    rng = np.random.default_rng([seed, 1])
    classes = mix["classes"]
    counts = [int(math.floor(c["share"] * n)) for c in classes]
    counts[0] += n - sum(counts)
    by_place = np.repeat(np.arange(len(classes)), counts)[balanced_order(rng, n)]
    lengths = []
    for c, k in zip(classes, counts):
        # each grid in an order of its own, so long prompts do not always ask for many tokens
        lengths.append([iter(np.sort(_text.length_grid(c[key], max(1, k)))[balanced_order(rng, k)])
                        for key in ("prompt_tokens", "max_new_tokens")])
    out = []
    for ci in by_place:
        c = classes[ci]
        out.append(_request(rng, rows, int(next(lengths[ci][0])), int(next(lengths[ci][1])),
                            float(c.get("temperature", 0.0)), c["name"])[0])
    return out


def make_check(mix: dict, seed: int, rows: list[int]) -> list[tuple[Request, list]]:
    """The correctness sample: one greedy request per entry of the mix's
    `check`, ids from the seed, each with its ids."""
    rng = np.random.default_rng([seed, 2])
    out = []
    for entry in mix["check"]:
        req, ids = _request(rng, rows, int(entry["prompt_tokens"]),
                            int(entry["max_new_tokens"]), 0.0, "check",
                            int(mix.get("check_logprobs", 8)))
        out.append((req, [ids]))
    return out


def check_inputs(sample: list, rows: list[int]) -> list[dict]:
    """Per request of the sample: the prompt's ids as sent and the tokens
    asked for."""
    return [{"ids": np.asarray(ids[0], np.int64), "max_new": req.max_new}
            for req, ids in sample]
