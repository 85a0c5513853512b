"""Operations and bytes of the `hybrid` family's two device programs and of
their state-space parts, from the configuration's sizes and what a window's
counters say the programs worked on. Every count is the LEAST the work needs,
so a share of the roofline computed from it cannot pass 100%:

- operations are the multiply-adds alone (2 each) over LIVE tokens: a Mamba-2
  layer's in- and out-projection and its recurrence (the state's update and
  its read, 2 x H x P x N multiply-adds a token, however the program groups
  them), attention's projections, scores and context over the positions a
  token may attend to, the router, both latent projections, the shared
  expert, the held experts' two products for the picks that landed on them,
  the head for the rows that are sampled. Norms, the convolution, softplus,
  sigmoid, top-k, sorting and gathers are left out.
- bytes are the least traffic to device memory: every matrix that is used
  read ONCE a launch (of the routed experts only those that were HIT), each
  live lane's (a step) or piece's (a prefill launch) recurrent state and
  convolution rows once read and once written, the K and V a live token
  attends over read once, the new K and V rows written, the embedding rows
  gathered. Activations, padding, lanes that are free (the program reads and
  writes their state too) and the absent picks' rows count for nothing.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call
(a decode step of `batch` lanes at context `seq`): in a generating cell the
"bucket" they read from the trace is no bucket, and the number means nothing
(PERF.md section 5); it is still a least count of a step of that many lanes.
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind."""
    d, hd = sz["d_model"], sz["head_dim"]
    inner = sz["mamba_heads"] * sz["mamba_head_dim"]
    bc = 2 * sz["mamba_groups"] * sz["state_size"]
    return {
        "mamba_in": d * (2 * inner + bc + sz["mamba_heads"]), "mamba_out": inner * d,
        "attn": d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd + sz["heads"] * hd * d,
        # the router, both latent projections, the shared expert
        "expert_always": d * sz["num_experts"] + 2 * d * sz["latent"] + 2 * d * sz["shared_width"],
        "expert": 2 * sz["latent"] * sz["expert_width"],
    }


def state_bytes(sz: dict) -> float:
    """One slot's recurrent state and convolution rows in ONE Mamba-2 layer."""
    return sz["mamba_heads"] * sz["mamba_head_dim"] * sz["state_size"] * 4.0 \
        + (sz["conv_kernel"] - 1) * sz["conv_channels"] * sz["weight_bytes"]


def _recurrence_ops(sz: dict, tokens: float) -> float:
    """One layer: the update and the read of the state, 2 multiply-adds an element."""
    return 2.0 * 2.0 * tokens * sz["mamba_heads"] * sz["mamba_head_dim"] * sz["state_size"]


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float, states: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend from positions that sum to `context_sum`, `held_picks`
    of whose expert picks landed on held experts and hit `experts_hit` of them
    (both summed over the expert layers), and read and wrote `states` slots'
    recurrent state in every Mamba-2 layer -> (operations, bytes)."""
    m = _matrices(sz)
    wb, d, hd = sz["weight_bytes"], sz["d_model"], sz["head_dim"]
    n_m, n_a, n_e = sz["n_mamba"], sz["n_attn"], sz["n_expert"]
    always = n_m * (m["mamba_in"] + m["mamba_out"]) + n_a * m["attn"] + n_e * m["expert_always"]
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
    """The state updates of one decode step, every Mamba-2 layer (the mixer
    from its in-projection to its out-projection): `lanes` live lanes' states
    once read and once written, `W_in` and `W_out` once."""
    m = _matrices(sz)
    n = sz["n_mamba"]
    ops = n * (2.0 * lanes * (m["mamba_in"] + m["mamba_out"]) + _recurrence_ops(sz, lanes))
    return ops, n * (sz["weight_bytes"] * (m["mamba_in"] + m["mamba_out"])
                     + 2.0 * lanes * state_bytes(sz))


def scan(sz: dict, tokens: float, pieces: float) -> tuple[float, float]:
    """The chunked scans of one prefill launch, every Mamba-2 layer, from the
    convolution to the gated norm (the projections are outside): the
    recurrence of `tokens` live tokens; `pieces` slots' states once read and
    once written; what the scan is handed and hands on, in the served type, a
    live token: z, x, B, C read, dt (float32) read, y written."""
    inner = sz["mamba_heads"] * sz["mamba_head_dim"]
    per_token = sz["weight_bytes"] * (2 * inner + sz["conv_channels"]) + 4.0 * sz["mamba_heads"]
    n = sz["n_mamba"]
    return n * _recurrence_ops(sz, tokens), \
        n * (2.0 * pieces * state_bytes(sz) + tokens * per_token)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    share = sz["experts_held"] / max(1, sz["num_experts"])
    picks = batch * sz["top_k"] * share
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_expert"],
                       min(sz["experts_held"], picks) * sz["n_expert"])
