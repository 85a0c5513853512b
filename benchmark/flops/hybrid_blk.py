"""Operations and bytes of the `hybrid_blk` family's two device programs, of
their linear-attention mixers and of attention over picked blocks, from the
configuration's sizes and what a window's counters say the programs worked on.
Every count is the LEAST the work needs (flops/hybrid.py's header says what
that means) whatever implements it, so a share of the roofline computed from it
cannot pass 100%:

- operations are the multiply-adds alone (2 each) over LIVE tokens: a mixer's
  projections (q, k, v, o and the gate where there is one), a dense layer's three
  products, the head for the rows that are sampled; a linear-attention layer's
  recurrence as the recurrence itself (a head: `k^T v` into the state and `q S`
  out of it, 4 D^2 a token; the chunked form does more and is not what the
  mathematics needs); attention's scores and context over the keys a query
  ATTENDS (its picked blocks' where it is past `dense_len`) and, a picked query,
  its scores against the pooled keys; norms, the rotary, softmaxes, the block
  maxima and the picks themselves count for nothing.
- bytes: every matrix that is used read ONCE a launch, a live lane's (a step) or
  piece's (a launch) state once read and once written a linear-attention layer,
  the K and V rows a query attends read once, the pooled keys of a picked query's
  context read once, the new rows written, the embedding rows gathered. Free
  lanes and padding count for nothing.

THE TWO GENERIC READERS (`gen_step_roofline_share`, `gen_prefill_roofline_share`)
hand `decode_step` and `prefill_chunk` the launch's context sum alone (positions
attended FROM), not what was attended: a token at position x attends at least
`min(x, (topk - 1) block + 1)` keys, and that is at least `x ((topk - 1) block +
1) / max_ctx` (a concave function over its chord), so the attention's part there
is the context sum times that ratio: a floor that is sure, and low. The `blk_*`
shares below read the program's own counts of what was scored and attended.
"""

from __future__ import annotations


def _matrices(sz: dict) -> dict:
    """Parameters by role, one layer of each kind."""
    d, hd, lw = sz["d_model"], sz["head_dim"], sz["lin_heads"] * sz["lin_head_dim"]
    return {
        "lin": (4 + int(sz["lin_gate"])) * d * lw,
        "attn": (2 + int(sz["attn_gate"])) * d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd,
        "dense": 3 * d * sz["ffn_width"],
    }


def _always(sz: dict) -> float:
    """The parameters every token passes through."""
    m = _matrices(sz)
    return sz["n_mamba"] * m["lin"] + sz["n_attn"] * m["attn"] + sz["layers"] * m["dense"]


def state_bytes(sz: dict) -> float:
    """One slot's state in ONE linear-attention layer: (heads, D, D) float32."""
    return sz["lin_heads"] * sz["lin_head_dim"] ** 2 * 4.0


def _recurrence_ops(sz: dict, tokens: float) -> float:
    """One layer's recurrence: `k^T v` into the state and `q S` out of it."""
    return tokens * 4.0 * sz["lin_heads"] * sz["lin_head_dim"] ** 2


def attended_floor(sz: dict, context_sum: float) -> float:
    """Keys attended, at least, by tokens whose positions sum to `context_sum`
    (module docstring)."""
    sp = sz["sparse"]
    return context_sum * min(1.0, ((sp["topk"] - 1) * sp["block_size"] + 1) / sz["max_ctx"])


def _kv_row(sz: dict) -> float:
    """K and V of one position, one attention layer."""
    return 2.0 * sz["kv_heads"] * sz["head_dim"] * sz["weight_bytes"]


def launch(sz: dict, tokens: float, sampled: float, attended: float,
           states: float) -> tuple[float, float]:
    """One launch (a prefill launch or a decode step) that took `tokens` live
    tokens through every layer, sampled `sampled` of them through the head,
    whose tokens attend `attended` keys in all, and read and wrote `states`
    slots' states in every linear-attention layer -> (operations, bytes)."""
    wb, d = sz["weight_bytes"], sz["d_model"]
    n_m, n_a = sz["n_mamba"], sz["n_attn"]
    head = d * sz["vocab"]
    ops = 2.0 * tokens * _always(sz) + 2.0 * sampled * head + n_m * _recurrence_ops(sz, tokens) \
        + n_a * 4.0 * attended * sz["heads"] * sz["head_dim"]
    nbytes = wb * (_always(sz) + head) + wb * tokens * d + n_m * 2.0 * states * state_bytes(sz) \
        + n_a * _kv_row(sz) * tokens
    return ops, nbytes


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float = 0.0,
                experts_hit: float = 0.0) -> tuple[float, float]:
    attended = attended_floor(sz, context_sum)
    ops, nbytes = launch(sz, lanes, lanes, attended, lanes)
    return ops, nbytes + sz["n_attn"] * _kv_row(sz) * attended


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float = 0.0,
                  experts_hit: float = 0.0) -> tuple[float, float]:
    """A launch has its own K and V at hand; of earlier launches' it reads at
    least what ONE of its rows attends. It carries at least one piece."""
    attended = attended_floor(sz, context_sum)
    ops, nbytes = launch(sz, tokens, 1.0, attended, 1.0 if tokens else 0.0)
    one = attended / tokens if tokens else 0.0
    return ops, nbytes + sz["n_attn"] * _kv_row(sz) * max(0.0, one - tokens)


def update(sz: dict, lanes: float) -> tuple[float, float]:
    """The linear-attention mixers of one decode step, every such layer, from
    the projections to `W_o`'s product: the layer's matrices once, `lanes` live
    lanes' states once read and once written."""
    m, n = _matrices(sz), sz["n_mamba"]
    return n * (2.0 * lanes * m["lin"] + _recurrence_ops(sz, lanes)), \
        n * (sz["weight_bytes"] * m["lin"] + 2.0 * lanes * state_bytes(sz))


def scan(sz: dict, tokens: float, pieces: float) -> tuple[float, float]:
    """The recurrences of one prefill launch, every such layer (the projections,
    the norms and the gate are outside the scope): a live token's q, k and v read
    and its read written, in the served type; `pieces` slots' states once read
    and once written."""
    n, wb = sz["n_mamba"], sz["weight_bytes"]
    row = sz["lin_heads"] * sz["lin_head_dim"] * wb
    return n * _recurrence_ops(sz, tokens), \
        n * (2.0 * pieces * state_bytes(sz) + tokens * 4.0 * row)


def blk_attend(sz: dict, attended: float, queries: float) -> tuple[float, float]:
    """Attention over the picked blocks in one prefill launch, every attention
    layer: scores and context over the `attended` (query, key) pairs of its
    `queries` picked queries; the K and V rows ONE of them attends read once (the
    tiles' rows share their blocks' rows)."""
    n = sz["n_attn"]
    one = attended / queries if queries else 0.0
    return n * 4.0 * attended * sz["heads"] * sz["head_dim"], n * _kv_row(sz) * one


def blk_step(sz: dict, scored: float, attended: float) -> tuple[float, float]:
    """A step's pooling, selection and walk together, every attention layer:
    `scored` blocks (a KV group each) whose `block / stride` pooled keys are read
    once and scored by the group's heads; `attended` (query, key) pairs whose K
    and V rows are read once and taken by every head."""
    n, sp, hd, wb = sz["n_attn"], sz["sparse"], sz["head_dim"], sz["weight_bytes"]
    windows = scored * sp["block_size"] / sp["kernel_stride"]
    group = sz["heads"] / sz["kv_heads"]
    ops = n * (2.0 * windows * group * hd + 4.0 * attended * sz["heads"] * hd)
    return ops, n * (windows * hd * wb + attended * _kv_row(sz))


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    return decode_step(sz, batch, float(batch * seq))
