"""Operations and bytes of the `hybrid_ffn` family's two device programs, of
their state-space parts and of decode attention, from the configuration's
sizes and what a window's counters say the programs worked on. Every count is
the LEAST the work needs (flops/hybrid.py's header says what that means), so a
share of the roofline computed from it cannot pass 100%.

The Mamba-2 mixers, the attention mixers, the head and the embedding rows are
counted by flops/hybrid.py's own `launch`, `update` and `scan` (the sizes carry
no expert layer: `n_expert` 0); what this family adds is the dense SwiGLU
feed-forward of EVERY layer: its three matrices read once a launch and 2 x 3 x
d x f operations a live token. A tied head is read once, as an untied one is,
and the embedding's gathered rows besides.
"""

from __future__ import annotations

from benchmark import spec

_hy = spec.load_module("flops", "hybrid")
state_bytes, update, scan, kv_read_bytes = _hy.state_bytes, _hy.update, _hy.scan, _hy.kv_read_bytes


def _ffn(sz: dict) -> float:
    """Parameters of the feed-forwards, all layers."""
    return sz["layers"] * 3.0 * sz["d_model"] * sz["ffn_width"]


def launch(sz: dict, tokens: float, sampled: float, context_sum: float,
           states: float) -> tuple[float, float]:
    ops, nbytes = _hy.launch(sz, tokens, sampled, context_sum, 0.0, 0.0, states)
    return ops + 2.0 * tokens * _ffn(sz), nbytes + sz["weight_bytes"] * _ffn(sz)


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float = 0.0,
                experts_hit: float = 0.0) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum, lanes)
    return ops, nbytes + kv_read_bytes(sz, context_sum)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float = 0.0,
                  experts_hit: float = 0.0) -> tuple[float, float]:
    """A launch reads the K and V of earlier launches at least once; its own it
    has at hand. It carries at least one piece: one slot's state."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum, 1.0 if tokens else 0.0)
    earlier = max(0.0, context_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0
    return ops, nbytes + kv_read_bytes(sz, earlier)


def attend_decode(sz: dict, lanes: float, context_sum: float) -> tuple[float, float]:
    """The attention mixers of one decode step (from the projections to
    `W_o`'s product): the four matrices once a layer; scores and context over
    each live lane's own positions (2 x 2 multiply-adds a position, a query
    head and a lane of its width); each live lane's own K and V rows read once
    (`kv_heads x head_dim` values a token, a layer and a side: 1,024 B in the
    cell), the new rows written."""
    d, hd, wb, n = sz["d_model"], sz["head_dim"], sz["weight_bytes"], sz["n_attn"]
    matrices = 2.0 * d * sz["heads"] * hd + 2.0 * d * sz["kv_heads"] * hd
    ops = n * (2.0 * lanes * matrices + 2.0 * 2.0 * context_sum * sz["heads"] * hd)
    row = 2.0 * sz["kv_heads"] * hd * wb          # K and V of one position, one layer
    return ops, n * (wb * matrices + row * lanes) + kv_read_bytes(sz, context_sum)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    return decode_step(sz, batch, float(batch * seq))
