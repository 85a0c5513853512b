"""Operations and bytes of the `mla_sc` family's two device programs, of their
latent-attention parts and of their routed layer, from the configuration's
sizes and what a window's counters say the programs worked on. Every count is
the LEAST the work needs, so a share of the roofline computed from it cannot
pass 100% (`flops/mla.py` says what that leaves out):

- a layer is DOUBLE: two latent attentions (`flops/mla.py`'s count of one, its
  form with the fewer operations and its one cached row a token, taken
  `n_attn` = 2 x layers times), two dense SwiGLUs of `dense_width`, and one
  routed layer: the router over every output, the held experts' three
  products for the picks that landed on them, of the held experts only those
  that were HIT read once. A zero-compute pick costs NOTHING: no operation and
  no byte is counted for it (its weight times a row the launch has at hand).
- the head over the held vocabulary rows, for the rows that are sampled.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call.
"""

from __future__ import annotations

from benchmark import spec

_mla = spec.load_module("flops", "mla")
attention, row_bytes, earlier_rows = _mla.attention, _mla.row_bytes, _mla.earlier_rows


def _matrices(sz: dict) -> dict:
    """Parameters by role: one attention, one dense SwiGLU, one router, one expert."""
    d = sz["d_model"]
    return {"mla": _mla._matrices({**sz, "dense_width": 0, "shared_width": 0})["mla"],
            "dense": 3 * d * sz["dense_width"],
            "router": d * (sz["num_experts"] + sz["zero_experts"]),
            "expert": 3 * d * sz["expert_width"]}


def launch(sz: dict, tokens: float, sampled: float, pairs: float, cached_rows: float,
           held_picks: float, experts_hit: float) -> tuple[float, float]:
    """`flops/mla.py` `launch` for this family's layer: `held_picks` and
    `experts_hit` are summed over the routed layers; `pairs` and
    `cached_rows` are ONE attention's."""
    m = _matrices(sz)
    wb, d, n, na = sz["weight_bytes"], sz["d_model"], sz["layers"], sz["n_attn"]
    always = na * m["mla"] + n * (2 * m["dense"] + m["router"])
    ops = 2.0 * tokens * always + 2.0 * held_picks * m["expert"] + 2.0 * sampled * d * sz["vocab"] \
        + na * attention(sz, pairs, cached_rows)[0]
    nbytes = wb * (always + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d \
        + na * row_bytes(sz) * (tokens + cached_rows)
    return ops, nbytes


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    return launch(sz, lanes, lanes, context_sum, max(0.0, context_sum - lanes),
                  held_picks, experts_hit)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    return launch(sz, tokens, 1.0, context_sum, earlier_rows(tokens, context_sum),
                  held_picks, experts_hit)


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The `mla_decode` scopes of one step, all `n_attn` of them."""
    m, na = _matrices(sz), sz["n_attn"]
    cached = max(0.0, context_sum - lanes)
    return na * (2.0 * lanes * m["mla"] + attention(sz, context_sum, cached)[0]), \
        na * (sz["weight_bytes"] * m["mla"] + row_bytes(sz) * (cached + lanes))


def attend_prefill(sz: dict, tokens: float, pairs: float, rows_attended: float
                   ) -> tuple[float, float]:
    """The `mla_prefill` scopes of one launch. `rows_attended` is the launch's
    `mla_rows_attended_total`, which this family sums over its `n_attn`
    attentions: one attention's is that over `n_attn`."""
    m, na = _matrices(sz), sz["n_attn"]
    cached = max(0.0, rows_attended / na - tokens)
    return na * (2.0 * tokens * m["mla"] + attention(sz, pairs, cached)[0]), \
        na * (sz["weight_bytes"] * m["mla"] + row_bytes(sz) * (cached + tokens))


def routed_layer(sz: dict, tokens: float, held_picks: float, experts_hit: float
                 ) -> tuple[float, float]:
    """The `moe_layer` scopes of one launch, every layer: the router for
    every live token, the held picks' three products, the router and the HIT
    experts read once, the tokens' rows read and the layer's result written
    (float32). `held_picks` and `experts_hit` are summed over the layers."""
    m, wb, d, n = _matrices(sz), sz["weight_bytes"], sz["d_model"], sz["layers"]
    return 2.0 * tokens * n * m["router"] + 2.0 * held_picks * m["expert"], \
        wb * (n * m["router"] + experts_hit * m["expert"]) + n * tokens * d * (wb + 4)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"] * sz["experts_held"] / (sz["num_experts"] + sz["zero_experts"])
    hit = sz["experts_held"] * (1.0 - (1.0 - 1.0 / sz["experts_held"]) ** picks)
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_sparse"],
                       hit * sz["n_sparse"])
