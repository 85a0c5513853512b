"""Operations and bytes of the `mla` family's two device programs and of their
latent-attention parts, from the configuration's sizes and what a window's
counters say the programs worked on. Every count is the LEAST the work needs,
so a share of the roofline computed from it cannot pass 100%:

- operations are the multiply-adds alone (2 each) over LIVE tokens: the five
  MLA matrices a layer (`W_qa`, `W_qb`, `W_kva`, `W_kvb`, `W_o`: `W_kvb` once a
  token in either form, on the token's own latent or, absorbed, on its query
  and its output), attention in THE FORM WITH THE FEWER OPERATIONS at the
  launch's sizes (`attention`, which names it), the dense or shared SwiGLU,
  the router, the experts' products for the picks, the head for the rows that
  are sampled. Norms, rotary, softmax, top-k, sorting and gathers are left out.
- bytes are the least traffic to device memory: every matrix that is used read
  ONCE a launch (of the routed experts only those that were HIT), each cached
  latent row a launch attends over read ONCE at `row` values (1,152 B at the
  published sizes: keys and values are the same row), the new rows written,
  the embedding rows gathered. Activations, padding, lanes that are free, a
  row gathered twice and a block read past a prompt's end count for nothing.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call
(a decode step of `batch` lanes at context `seq`, the expected experts hit).
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind."""
    d, h = sz["d_model"], sz["heads"]
    return {
        "mla": d * sz["q_rank"] + sz["q_rank"] * h * (sz["nope"] + sz["rope"]) + d * sz["row"]
        + sz["kv_rank"] * h * (sz["nope"] + sz["v_dim"]) + h * sz["v_dim"] * d,
        "dense": 3 * d * sz["dense_width"],
        # the router and the shared expert
        "sparse_always": d * sz["num_experts"] + 3 * d * sz["shared_width"],
        "expert": 3 * d * sz["expert_width"],
    }


def attention(sz: dict, pairs: float, cached_rows: float) -> tuple[float, str]:
    """One layer's scores and context over `pairs` (query, key) pairs, with
    `cached_rows` latent rows that earlier launches left (a launch's own
    rows pass through `W_kvb` with the projections) -> (operations, the form
    that needs them). Absorbed: 2 H (2 r + rope) a pair. Expanded: 2 H (nope +
    rope + v) a pair and 2 r H (nope + v) a cached row, once a launch."""
    h, r = sz["heads"], sz["kv_rank"]
    absorbed = pairs * 2.0 * h * (2 * r + sz["rope"])
    expanded = pairs * 2.0 * h * (sz["nope"] + sz["rope"] + sz["v_dim"]) \
        + cached_rows * 2.0 * r * h * (sz["nope"] + sz["v_dim"])
    return (absorbed, "absorbed") if absorbed <= expanded else (expanded, "expanded")


def row_bytes(sz: dict) -> float:
    """What one token keeps in ONE layer's pages."""
    return float(sz["row"] * sz["weight_bytes"])


def launch(sz: dict, tokens: float, sampled: float, pairs: float, cached_rows: float,
           held_picks: float, experts_hit: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend over `pairs` (query, key) pairs (a token at position
    p counts p + 1) of which `cached_rows` distinct rows a layer were cached
    by earlier launches, `held_picks` of whose expert picks landed on
    `experts_hit` experts (both summed over the sparse layers) ->
    (operations, bytes)."""
    m = _matrices(sz)
    wb, d, n = sz["weight_bytes"], sz["d_model"], sz["layers"]
    always = n * m["mla"] + sz["n_dense"] * m["dense"] + sz["n_sparse"] * m["sparse_always"]
    ops = 2.0 * tokens * always + 2.0 * held_picks * m["expert"] + 2.0 * sampled * d * sz["vocab"] \
        + n * attention(sz, pairs, cached_rows)[0]
    nbytes = wb * (always + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d \
        + n * row_bytes(sz) * (tokens + cached_rows)
    return ops, nbytes


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    """Every lane's whole context is rows an earlier launch cached (its own
    row it has at hand)."""
    return launch(sz, lanes, lanes, context_sum, max(0.0, context_sum - lanes),
                  held_picks, experts_hit)


def earlier_rows(tokens: float, context_sum: float) -> float:
    """The cached rows a prefill launch reads AT LEAST, from the generic
    counters alone: a launch of one piece whose tokens' contexts sum to
    `context_sum` began at this position (several pieces began at positions
    that sum to more)."""
    return max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """The first token is sampled by at most one launch a prompt."""
    return launch(sz, tokens, 1.0, context_sum, earlier_rows(tokens, context_sum),
                  held_picks, experts_hit)


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The `mla_decode` scope of one step, every layer (the mixer from its two
    down-projections to `W_o`'s product): the five matrices once, each live
    lane's cached rows read ONCE, its new row written."""
    m, n = _matrices(sz), sz["layers"]
    cached = max(0.0, context_sum - lanes)
    return n * (2.0 * lanes * m["mla"] + attention(sz, context_sum, cached)[0]), \
        n * (sz["weight_bytes"] * m["mla"] + row_bytes(sz) * (cached + lanes))


def attend_prefill(sz: dict, tokens: float, pairs: float, rows_attended: float
                   ) -> tuple[float, float]:
    """The `mla_prefill` scope of one launch, every layer: `rows_attended` is
    the launch's `mla_rows_attended_total` (each piece's whole context once),
    so the rows earlier launches cached are that less the launch's own."""
    m, n = _matrices(sz), sz["layers"]
    cached = max(0.0, rows_attended - tokens)
    return n * (2.0 * tokens * m["mla"] + attention(sz, pairs, cached)[0]), \
        n * (sz["weight_bytes"] * m["mla"] + row_bytes(sz) * (cached + tokens))


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"]
    hit = sz["num_experts"] * (1.0 - (1.0 - 1.0 / sz["num_experts"]) ** picks)
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_sparse"],
                       hit * sz["n_sparse"])
