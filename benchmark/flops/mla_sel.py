"""Operations and bytes of the `mla_sel` family's two device programs, of their
latent-attention parts and of the indexer's two scopes, from the
configuration's sizes and what a window's counters say the programs worked on.
Every count is the LEAST the work needs WHATEVER WALKS IT, so a share of the
roofline computed from it cannot pass 100% (`flops/mla.py` says what that leaves
out), and a walk that reads or scores more than its picks reads a low share:

- the matrices are `flops/mla.py`'s five an attention, the indexer's three
  (`WI_qb`, `WI_k`, `WI_w`), the dense or shared SwiGLU, the router over every
  output, the held experts' products for the picks that landed on them (of the
  held experts only those that were HIT read once), the head over the held
  vocabulary rows for the rows that are sampled.
- THE INDEXER scores a query past `index_topk` against every key it may see: 2 x
  `index_heads` x `index_dim` operations a causal pair (the ReLU, the weights
  and the sum over heads are left out), and its keys are read once a tile or a
  lane: `index_dim` values a cached row. A query at or under `index_topk` keeps
  every key: nothing is scored for it.
- ATTENTION runs over `min(index_topk, t + 1)` keys a query, in the form with the
  fewer operations (`flops/mla.py` `attention`: at 2,048 picks a query each with
  its own, the absorbed form, 2 H (2 r + rope) a pair: an expanded key serves one
  query). Bytes: a step reads each lane's PICKED latent rows, `row` values
  each; a prefill launch's rows pick among the same cached rows, so each cached
  row is read once a launch at most, as `flops/mla.py` counts it.

The generic readers hand a launch's live tokens and the sum of their contexts
alone (`prefill_chunk`, `decode_step`); the pairs are reckoned from those as ONE
run of consecutive positions a prefill launch and every lane at the mean
context a step, which the cell's traffic makes exact but for launches of two
pieces. The `sel_*` readers hand the program's own counts (`index`, `attend`,
`step`).

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call.
"""

from __future__ import annotations

from benchmark import spec

_mla = spec.load_module("flops", "mla")
earlier_rows = _mla.earlier_rows


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind; `index`: the indexer's three."""
    d = sz["d_model"]
    return {**_mla._matrices(sz),
            "index": sz["q_rank"] * sz["index_heads"] * sz["index_dim"] + d * sz["index_dim"]
            + d * sz["index_heads"]}


def index_ops(sz: dict, pairs: float) -> float:
    """The index scores of `pairs` (query, key) pairs, one layer."""
    return pairs * 2.0 * sz["index_heads"] * sz["index_dim"]


def attend_ops(sz: dict, pairs: float) -> float:
    """Attention over `pairs` PICKED pairs, one layer: the absorbed form (a
    query's picks are its own, so an expanded key would serve one query)."""
    return pairs * 2.0 * sz["heads"] * (2 * sz["kv_rank"] + sz["rope"])


def run_pairs(sz: dict, tokens: float, context_sum: float) -> tuple[float, float]:
    """(picked pairs, scored pairs) of `tokens` consecutive positions whose
    contexts sum to `context_sum`: position t keeps min(index_topk, t + 1) keys
    and, past `index_topk`, is scored against its t + 1."""
    k = float(sz["index_topk"])
    first = earlier_rows(tokens, context_sum)          # the run's first position
    dense = min(max(k - first, 0.0), tokens)           # its positions under index_topk
    kept = dense * (first + (dense + 1) / 2.0) + (tokens - dense) * k
    return kept, max(0.0, context_sum - dense * (first + (dense + 1) / 2.0))


def lane_pairs(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """(picked pairs, scored pairs) of `lanes` lanes at the mean context."""
    k, mean = float(sz["index_topk"]), context_sum / lanes if lanes else 0.0
    return lanes * min(k, mean), context_sum if mean > k else 0.0


def launch(sz: dict, tokens: float, sampled: float, kept: float, scored: float,
           latent_rows: float, index_rows: float, held_picks: float,
           experts_hit: float) -> tuple[float, float]:
    """One launch that took `tokens` live tokens through every layer, sampled
    `sampled` of them, whose attention ran over `kept` picked pairs and whose
    indexer scored `scored` pairs (both ONE layer's), reading `latent_rows`
    latent rows and `index_rows` index keys a layer, `held_picks` of whose
    expert picks landed on `experts_hit` experts (both summed over the sparse
    layers) -> (operations, bytes)."""
    m = _matrices(sz)
    wb, d, n = sz["weight_bytes"], sz["d_model"], sz["layers"]
    always = n * (m["mla"] + m["index"]) + sz["n_dense"] * m["dense"] \
        + sz["n_sparse"] * m["sparse_always"]
    ops = 2.0 * tokens * always + 2.0 * held_picks * m["expert"] + 2.0 * sampled * d * sz["vocab"] \
        + n * (attend_ops(sz, kept) + index_ops(sz, scored))
    nbytes = wb * (always + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d \
        + n * wb * (sz["row"] * latent_rows + sz["index_dim"] * index_rows
                    + sz["cache_row"] * tokens)
    return ops, nbytes


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    """Every lane reads its picked latent rows and, past `index_topk`, every
    index key of its context."""
    kept, scored = lane_pairs(sz, lanes, context_sum)
    return launch(sz, lanes, lanes, kept, scored, kept, scored, held_picks, experts_hit)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """The rows earlier launches cached are read once, the latent and (where
    anything is scored) the index key; the first token is sampled by at most
    one launch a prompt."""
    kept, scored = run_pairs(sz, tokens, context_sum)
    cached = earlier_rows(tokens, context_sum)
    return launch(sz, tokens, 1.0, kept, scored, cached, cached if scored else 0.0,
                  held_picks, experts_hit)


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The `mla_decode` scope of one step, every layer: the attention's and
    the indexer's matrices once, every lane's index keys and picked rows."""
    m, n, wb = _matrices(sz), sz["layers"], sz["weight_bytes"]
    kept, scored = lane_pairs(sz, lanes, context_sum)
    return n * (2.0 * lanes * (m["mla"] + m["index"]) + attend_ops(sz, kept)
                + index_ops(sz, scored)), \
        n * wb * (m["mla"] + m["index"] + sz["row"] * kept + sz["index_dim"] * scored
                  + sz["cache_row"] * lanes)


def attend_prefill(sz: dict, tokens: float, pairs: float, rows_attended: float
                   ) -> tuple[float, float]:
    """The `mla_prefill` scope of one launch, every layer (`pairs`: the sum of
    the tokens' contexts; `rows_attended`: each piece's whole context once)."""
    m, n, wb = _matrices(sz), sz["layers"], sz["weight_bytes"]
    kept, scored = run_pairs(sz, tokens, pairs)
    cached = max(0.0, rows_attended - tokens)
    return n * (2.0 * tokens * (m["mla"] + m["index"]) + attend_ops(sz, kept)
                + index_ops(sz, scored)), \
        n * wb * (m["mla"] + m["index"] + sz["row"] * cached
                  + (sz["index_dim"] * cached if scored else 0.0) + sz["cache_row"] * tokens)


# -- the indexer's own scopes, from the program's own counts (one launch, every layer) ----

def index(sz: dict, scored: float, rows: float) -> tuple[float, float]:
    """`sel_index`: `scored` pairs a layer, `rows` index keys a layer read once."""
    return sz["layers"] * index_ops(sz, scored), \
        sz["layers"] * sz["weight_bytes"] * sz["index_dim"] * rows


def attend(sz: dict, kept: float, rows: float) -> tuple[float, float]:
    """`sel_attend`: `kept` picked pairs a layer, `rows` latent rows a layer
    read at least (a step: the picks themselves; a launch: its pieces' cached
    rows, each once)."""
    return sz["layers"] * attend_ops(sz, kept), \
        sz["layers"] * sz["weight_bytes"] * sz["row"] * rows


def step(sz: dict, scored: float, kept: float) -> tuple[float, float]:
    """Both scopes of a step: each picked lane's index keys and picked rows."""
    a, b = index(sz, scored, scored), attend(sz, kept, kept)
    return a[0] + b[0], a[1] + b[1]


def experts_step(sz: dict, lanes: float, held_picks: float, experts_hit: float
                 ) -> tuple[float, float]:
    """The `moe_experts` scope of one step (`flops/hybrid_conv.py`'s count): the
    picks' three products, the HIT experts' matrices read once, a pick's row in
    and out. `lanes` is not read: the picks are."""
    m, wb = _matrices(sz), sz["weight_bytes"]
    return 2.0 * held_picks * m["expert"], \
        wb * (experts_hit * m["expert"] + held_picks * 2.0 * sz["d_model"])


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"] * sz["experts_held"] / sz["num_experts"]
    hit = sz["experts_held"] * (1.0 - (1.0 - 1.0 / sz["experts_held"]) ** picks)
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_sparse"],
                       hit * sz["n_sparse"])
