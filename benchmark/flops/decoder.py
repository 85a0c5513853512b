"""Operations and bytes of the `decoder` family's two device programs, from
the configuration's sizes and what a window's counters say the programs
worked on. Both counts are the LEAST the work needs, so a share of the
roofline computed from them cannot pass 100%:

- operations are the matrix multiplications alone (2 per multiply-add) over
  LIVE tokens: projections, the gate, scores and context over the positions a
  token may attend to (its own context in a full layer, at most the window in
  a window layer), the dense or shared SwiGLU, the router, the held experts'
  products for the picks that landed on them, the head for the rows that are
  sampled. Norms, rotary, softmax, top-k, sorting and gathers are left out.
- bytes are the least traffic to device memory: every matrix that is used
  read ONCE a launch (of the routed experts only those that were hit), the
  K and V a live token attends over read once a launch, the new K and V rows
  written, the embedding rows gathered. Activations, padding (a chunk padded
  to its static width, lanes that are free, the padded block table) and
  everything re-read count for nothing.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call
(a decode step of `batch` lanes at context `seq`, every held expert hit).
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role: what every token passes through, per layer."""
    d, hd, kv = sz["d_model"], sz["head_dim"], sz["kv_heads"]
    out = {"attn": [], "dense": [], "shared": [], "router": [], "expert": 0}
    for i, h in enumerate(sz["heads"]):
        out["attn"].append(d * h * hd + 2 * d * kv * hd + h * hd * d + (d * h if sz["gated"] else 0))
        sparse = sz["mlp_types"][i] == "sparse"
        out["dense"].append(0 if sparse else 3 * d * sz["dense_width"])
        out["shared"].append(3 * d * sz["shared_width"] if sparse else 0)
        out["router"].append(d * sz["num_experts"] if sparse else 0)
    out["expert"] = 3 * d * sz["expert_width"]
    return out


def launch(sz: dict, tokens: float, sampled: float, context_sum: float, held_picks: float,
           experts_hit: float) -> tuple[float, float]:
    """One launch (a prefill chunk or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend from positions that sum to `context_sum` (a token at
    position p counts p + 1), `held_picks` of whose expert picks landed on
    held experts (summed over the sparse layers), hitting `experts_hit` held
    experts (summed over the sparse layers) -> (operations, bytes)."""
    m = _matrices(sz)
    wb, d, hd, kv = sz["weight_bytes"], sz["d_model"], sz["head_dim"], sz["kv_heads"]
    always = sum(m["attn"]) + sum(m["dense"]) + sum(m["shared"]) + sum(m["router"])
    mean_ctx = context_sum / tokens if tokens else 0.0
    ops = 2.0 * tokens * always + 2.0 * held_picks * m["expert"] + 2.0 * sampled * d * sz["vocab"]
    nbytes = wb * (always + experts_hit * m["expert"] + d * sz["vocab"]) + wb * tokens * d
    for kind, h in zip(sz["layer_types"], sz["heads"]):
        # Positions a token attends over: its context, at most the window in a window layer.
        seen = context_sum if kind == "full_attention" else tokens * min(mean_ctx, sz["window"])
        ops += 2.0 * 2.0 * seen * h * hd                 # scores and context
        nbytes += wb * 2.0 * kv * hd * tokens            # the new K and V rows, written
    return ops, nbytes


def kv_read_bytes(sz: dict, lanes: float, context_sum: float) -> float:
    """K and V a decode step reads at least: each lane its own context in a
    full layer and at most the window in a window layer."""
    mean_ctx = context_sum / lanes if lanes else 0.0
    per_pos = 2.0 * sz["kv_heads"] * sz["head_dim"] * sz["weight_bytes"]
    return sum(per_pos * (context_sum if kind == "full_attention"
                          else lanes * min(mean_ctx, sz["window"]))
               for kind in sz["layer_types"])


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum, held_picks, experts_hit)
    return ops, nbytes + kv_read_bytes(sz, lanes, context_sum)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    """A chunk reads the K and V of earlier chunks at least once; its own it
    has at hand. The first token is sampled by at most one chunk a prompt."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, held_picks, experts_hit)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, 1.0, earlier)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    sparse = sz["mlp_types"].count("sparse")
    share = sz["experts_held"] / max(1, sz["num_experts"])
    return decode_step(sz, batch, float(batch * seq), batch * sz["top_k"] * sparse * share,
                       min(sz["experts_held"], batch * sz["top_k"] * share) * sparse)
