"""Operations and bytes of the `hybrid_delta` family's two device programs, of
their delta-rule parts and of decode attention, from the configuration's sizes
and what a window's counters say the programs worked on. Every count is the
LEAST the work needs (flops/hybrid.py's header says what that means), so a
share of the roofline computed from it cannot pass 100%:

- operations are the multiply-adds alone (2 each) over LIVE tokens: a
  delta-rule layer's projections (q, k, v, the decay's and the gate's two low
  ranks, beta, `W_o`) and its recurrence, 3 x 2 x H x D x D a token a layer: the
  multiply-adds of the correction (`S'^T k`), of the write (`k (x) (v - ...)`) and
  of the read (`S^T q`), however the program groups them (the decay's
  multiplications, the chunked form's tables and its triangular solve are left
  out); a softmax layer's projections with its gate's, scores and context over
  the positions a token may attend to; in every layer the router, the shared
  expert and the held experts' three products for the picks that landed on
  them; the head for the rows that are sampled.
- bytes: every matrix that is used read ONCE a launch (of the routed experts
  only those that were HIT), a live lane's (a step) or piece's (a launch)
  state and convolution rows once read and once written a delta-rule layer,
  the K and V a live token attends over read once, the new rows written, the
  embedding rows gathered. Free lanes, padding and absent picks count for
  nothing.
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind."""
    d, hd = sz["d_model"], sz["head_dim"]
    inner, r = sz["delta_heads"] * sz["delta_head_dim"], sz["delta_rank"]
    q_side = (2 if sz["attn_gate"] else 1) * d * sz["heads"] * hd      # W_q and the gate's W_g
    return {
        "delta": 4 * d * inner + 2 * (d * r + r * inner) + d * sz["delta_heads"],
        "attn": q_side + 2 * d * sz["kv_heads"] * hd + sz["heads"] * hd * d,
        "expert_always": d * sz["num_experts"] + 3 * d * sz["shared_width"],
        "expert": 3 * d * sz["expert_width"],
    }


def _block(sz: dict) -> float:
    """Values of one slot's state in ONE delta-rule layer (float32)."""
    return sz["delta_heads"] * sz["delta_head_dim"] ** 2


def state_bytes(sz: dict) -> float:
    """One slot's state and convolution rows in ONE delta-rule layer."""
    return 4.0 * _block(sz) + (sz["conv_kernel"] - 1) * sz["conv_channels"] * sz["weight_bytes"]


def _recurrence_ops(sz: dict, tokens: float) -> float:
    """One layer: the correction, the write and the read, 2 x 3 an element."""
    return 2.0 * 3.0 * tokens * _block(sz)


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float, states: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend from positions that sum to `context_sum`, `held_picks`
    of whose expert picks landed on held experts and hit `experts_hit` of them
    (both summed over the layers), and read and wrote `states` slots' state in
    every delta-rule layer -> (operations, bytes)."""
    m = _matrices(sz)
    wb, d, hd = sz["weight_bytes"], sz["d_model"], sz["head_dim"]
    n_m, n_a = sz["n_mamba"], sz["n_attn"]
    always = n_m * m["delta"] + n_a * m["attn"] + sz["layers"] * m["expert_always"]
    ops = 2.0 * tokens * always + 2.0 * held_picks * m["expert"] + 2.0 * sampled * d * sz["vocab"] \
        + n_m * _recurrence_ops(sz, tokens) + n_a * 2.0 * 2.0 * context_sum * sz["heads"] * hd
    nbytes = wb * (always + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d \
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
    has at hand. It carries at least one piece: one slot's state."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, held_picks, experts_hit,
                         1.0 if tokens else 0.0)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, earlier)


def update(sz: dict, lanes: float) -> tuple[float, float]:
    """The delta-rule mixers of one decode step, every such layer, from the
    projections to `W_o`'s product: `lanes` live lanes' states and convolution
    rows once read and once written, the layer's matrices once."""
    m, n = _matrices(sz), sz["n_mamba"]
    ops = n * (2.0 * lanes * m["delta"] + _recurrence_ops(sz, lanes))
    return ops, n * (sz["weight_bytes"] * m["delta"] + 2.0 * lanes * state_bytes(sz))


def delta_update(sz: dict, lanes: float) -> tuple[float, float]:
    """The state's update and read ALONE in one decode step, every delta-rule
    layer (`tpuserve/ops/delta_update.py`): a live lane's state once in and
    once out; what it is handed (q, k, v, the decay, float32 by head and
    channel, and beta) and what it hands on (o)."""
    n, inner = sz["n_mamba"], sz["delta_heads"] * sz["delta_head_dim"]
    vectors = 4.0 * (5 * inner + sz["delta_heads"])
    return n * _recurrence_ops(sz, lanes), n * lanes * (2.0 * 4.0 * _block(sz) + vectors)


def scan(sz: dict, tokens: float, pieces: float) -> tuple[float, float]:
    """The chunked delta rule of one prefill launch, every such layer, from
    the convolution to the gated norm (the projections are outside): the
    recurrence of `tokens` live tokens; `pieces` slots' states and convolution
    rows once read and once written; what the scan is handed and hands on a
    live token: q, k, v before the convolution (the served type), the decay
    (float32 by head and channel) and beta read, the gated rows written."""
    inner, n = sz["delta_heads"] * sz["delta_head_dim"], sz["n_mamba"]
    per_token = sz["weight_bytes"] * 4 * inner + 4.0 * (inner + sz["delta_heads"])
    return n * _recurrence_ops(sz, tokens), \
        n * (2.0 * pieces * state_bytes(sz) + tokens * per_token)


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The softmax mixers of one decode step (from the projections to `W_o`'s
    product): the matrices once a layer (the gate's with them); scores and
    context over each live lane's own positions; each live lane's own K and V
    rows read once, the new rows written."""
    hd, wb, n = sz["head_dim"], sz["weight_bytes"], sz["n_attn"]
    matrices = _matrices(sz)["attn"]
    ops = n * (2.0 * lanes * matrices + 2.0 * 2.0 * context_sum * sz["heads"] * hd)
    row = 2.0 * sz["kv_heads"] * hd * wb          # K and V of one position, one layer
    return ops, n * (wb * matrices + row * lanes) + kv_read_bytes(sz, context_sum)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    share = sz["experts_held"] / max(1, sz["num_experts"])
    picks = batch * sz["top_k"] * share
    return decode_step(sz, batch, float(batch * seq), picks * sz["layers"],
                       min(sz["experts_held"], picks) * sz["layers"])
