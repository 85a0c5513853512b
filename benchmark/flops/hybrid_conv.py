"""Operations and bytes of the `hybrid_conv` family's two device programs, of
their short-convolution mixers, of decode attention and of a step's routed
experts, from the configuration's sizes and what a window's counters say the
programs worked on. Every count is the LEAST the work needs (flops/hybrid.py's
header says what that means), so a share of the roofline computed from it
cannot pass 100%:

- operations are the multiply-adds alone (2 each) over LIVE tokens: a
  convolution layer's two projections (`W_in` to three thirds, `W_out`) and, a
  channel, its k taps and its two gates (`B * z`, `C * c`); an attention layer's
  four projections, scores and context over the positions a token may attend to
  (the norms and the rotary are left out); a dense layer's three products; in
  every routed layer the router and the experts' three products for the picks
  (every expert is held); the head for the rows that are sampled.
- bytes: every matrix that is used read ONCE a launch (of the routed experts
  only those that were HIT; the head is the embedding), a live lane's (a step)
  or piece's (a launch) stored rows once read and once written a convolution
  layer, the K and V a live token attends over read once, the new rows written,
  the embedding rows gathered. Free lanes and padding count for nothing.
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind."""
    d, hd = sz["d_model"], sz["head_dim"]
    return {
        "conv": 3 * d * d + d * d + sz["conv_kernel"] * d,
        "attn": 2 * d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd,
        "dense": 3 * d * sz["ffn_width"],
        "router": d * sz["num_experts"],
        "expert": 3 * d * sz["expert_width"],
    }


def _always(sz: dict) -> float:
    """The parameters every token passes through, whatever it picks."""
    m = _matrices(sz)
    return sz["n_mamba"] * m["conv"] + sz["n_attn"] * m["attn"] + sz["n_dense"] * m["dense"] \
        + sz["n_expert"] * m["router"]


def state_bytes(sz: dict) -> float:
    """One slot's stored rows in ONE convolution layer: all it keeps there."""
    return (sz["conv_kernel"] - 1) * sz["d_model"] * sz["weight_bytes"]


def _conv_ops(sz: dict, tokens: float) -> float:
    """One layer's taps and its two gates: 2 k + 2 operations a channel a token."""
    return tokens * (2.0 * sz["conv_kernel"] + 2.0) * sz["d_model"]


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float, states: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend from positions that sum to `context_sum`, whose expert
    picks number `held_picks` and hit `experts_hit` experts (both summed over
    the routed layers), and read and wrote `states` slots' rows in every
    convolution layer -> (operations, bytes)."""
    m = _matrices(sz)
    wb, d, hd = sz["weight_bytes"], sz["d_model"], sz["head_dim"]
    n_m, n_a = sz["n_mamba"], sz["n_attn"]
    ops = 2.0 * tokens * _always(sz) + 2.0 * held_picks * m["expert"] \
        + 2.0 * sampled * d * sz["vocab"] + n_m * _conv_ops(sz, tokens) \
        + n_a * 2.0 * 2.0 * context_sum * sz["heads"] * hd
    nbytes = wb * (_always(sz) + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d \
        + n_m * 2.0 * states * state_bytes(sz) + n_a * wb * 2.0 * sz["kv_heads"] * hd * tokens
    return ops, nbytes


def kv_read_bytes(sz: dict, context_sum: float) -> float:
    """K and V a launch reads at least: each token's own context, once."""
    return sz["n_attn"] * 2.0 * sz["kv_heads"] * sz["head_dim"] * sz["weight_bytes"] * context_sum


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum, held_picks, experts_hit, lanes)
    return ops, nbytes + kv_read_bytes(sz, context_sum)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """A launch reads the K and V of earlier launches at least once; its own it
    has at hand. It carries at least one piece: one slot's rows."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, held_picks, experts_hit,
                         1.0 if tokens else 0.0)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, earlier)


def update(sz: dict, lanes: float) -> tuple[float, float]:
    """The short-convolution mixers of one decode step, every such layer, from
    `W_in` to `W_out`'s product: the layer's matrices and taps once, `lanes`
    live lanes' two rows once read and once written."""
    m, n = _matrices(sz), sz["n_mamba"]
    ops = n * (2.0 * lanes * (m["conv"] - sz["conv_kernel"] * sz["d_model"])
               + _conv_ops(sz, lanes))
    return ops, n * (sz["weight_bytes"] * m["conv"] + 2.0 * lanes * state_bytes(sz))


def scan(sz: dict, tokens: float, pieces: float) -> tuple[float, float]:
    """The convolutions and gates of one prefill launch, every such layer, from
    `b` to `C * c` (the projections are outside): a live token's `b` and `C`
    read and its gated row written, in the served type; `pieces` slots' rows
    once read and once written. There is no recurrence to count."""
    n, wb = sz["n_mamba"], sz["weight_bytes"]
    return n * _conv_ops(sz, tokens), \
        n * (2.0 * pieces * state_bytes(sz) + tokens * 3.0 * sz["d_model"] * wb)


def experts_step(sz: dict, lanes: float, held_picks: float,
                 experts_hit: float) -> tuple[float, float]:
    """The routed experts' own work in one decode step, every routed layer (the
    `moe_experts` scope: the grouped products and the body between them; the
    router and the dispatch are outside): the HIT experts' three matrices once,
    a pick's row in and out, 6 x d x width operations a pick. `lanes` is not
    read: the picks are."""
    m, wb = _matrices(sz), sz["weight_bytes"]
    return 2.0 * held_picks * m["expert"], \
        wb * (experts_hit * m["expert"] + held_picks * 2.0 * sz["d_model"])


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The attention mixers of one decode step (from the projections to `W_o`'s
    product): the matrices once a layer; scores and context over each live
    lane's own positions; each live lane's own K and V rows read once, the new
    rows written."""
    hd, wb, n = sz["head_dim"], sz["weight_bytes"], sz["n_attn"]
    matrices = _matrices(sz)["attn"]
    ops = n * (2.0 * lanes * matrices + 2.0 * 2.0 * context_sum * sz["heads"] * hd)
    row = 2.0 * sz["kv_heads"] * hd * wb          # K and V of one position, one layer
    return ops, n * (wb * matrices + row * lanes) + kv_read_bytes(sz, context_sum)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"]
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_expert"],
                       min(sz["num_experts"], picks) * sz["n_expert"])
