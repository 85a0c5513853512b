"""Operations and bytes of the `eva` family's two device programs, from the
configuration's sizes and what a window's counters say the programs worked on.
Both counts are the LEAST the work needs, so a share of the roofline computed
from them cannot pass 100%.

FOR THIS FAMILY `gen_context_tokens_total` COUNTS THE ROWS A TOKEN ATTENDS, not
its position: the exact rows of its own window so far (`i % W + 1`) and the
summary rows of every earlier window (`(i // W) x W / c`). That sum is what the
generic readers hand `decode_step` and `prefill_chunk` as `context_sum`, and
what the cache's bytes go by.

- operations are the matrix multiplications (2 per multiply-add) over LIVE
  tokens: the four projections and the SwiGLU, 4 x d a row attended a layer
  (scores and context), the pooling's 2 x 2 x d a position a layer (its weights
  and its two sums), the head's served block for the rows that are sampled.
  Norms, rotary and softmaxes are left out.
- bytes are the least traffic to device memory: every matrix read ONCE a
  launch (the head's served block alone), an attended row's K and V once a
  layer, a new row and a `c`-th of a summary row written a token a layer, the
  float32 stream counted at 4 B a value once a token. Activations, padding and
  everything re-read count for nothing.
"""

from __future__ import annotations


def _layer_matrices(sz: dict) -> int:
    d, hd = sz["d_model"], sz["head_dim"]
    return 2 * d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd + 3 * d * sz["dense_width"]


def _row_bytes(sz: dict) -> float:
    """One cache row's K and V, a layer."""
    return 2.0 * sz["kv_heads"] * sz["head_dim"] * sz["weight_bytes"]


def rows_at(sz: dict, position: int) -> int:
    """Rows the token at `position` attends: exact and summary."""
    w = sz["win_tokens"]
    return position % w + 1 + (position // w) * (w // sz["chunk"])


def launch(sz: dict, tokens: float, sampled: float, rows_sum: float) -> tuple[float, float]:
    """One launch that took `tokens` live tokens through every layer, sampled
    `sampled` of them, whose tokens attend `rows_sum` rows in all (a layer)
    -> (operations, bytes), the cache's reads apart."""
    n, d, wb = sz["layers"], sz["d_model"], sz["weight_bytes"]
    ops = 2.0 * tokens * n * _layer_matrices(sz) + 4.0 * d * rows_sum * n \
        + 4.0 * d * tokens * n + 2.0 * sampled * d * sz["vocab"]
    nbytes = wb * (n * _layer_matrices(sz) + d * sz["vocab"]) + 4.0 * tokens * d \
        + n * _row_bytes(sz) * tokens * (1.0 + 1.0 / sz["chunk"])
    return ops, nbytes


def kv_read_bytes(sz: dict, lanes: float, rows_sum: float) -> float:
    """K and V a launch reads at least: every attended row once a layer."""
    return sz["layers"] * _row_bytes(sz) * rows_sum


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float = 0.0,
                experts_hit: float = 0.0) -> tuple[float, float]:
    ops, nbytes = launch(sz, lanes, lanes, context_sum)
    return ops, nbytes + kv_read_bytes(sz, lanes, context_sum)


def _earlier(tokens: float, rows_sum: float) -> float:
    """Rows a launch's tokens attend that are not the launch's own, at least:
    a token's mean rows less the launch's own rows before it."""
    return max(0.0, rows_sum / tokens - (tokens + 1) / 2.0) if tokens else 0.0


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float = 0.0,
                  experts_hit: float = 0.0) -> tuple[float, float]:
    """A launch reads the rows of earlier launches at least once; its own it
    has at hand. The first token is sampled by at most one launch a prompt."""
    ops, nbytes = launch(sz, tokens, 1.0, context_sum)
    return ops, nbytes + kv_read_bytes(sz, 1.0, _earlier(tokens, context_sum))


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    return decode_step(sz, batch, float(batch * rows_at(sz, seq)))


def attend_decode(sz: dict, lanes: float, rows: float) -> tuple[float, float]:
    """The walk alone (scope `eva_decode`), every layer of a step of `lanes`
    live lanes that attend `rows` rows in all: scores and context, the rows'
    K and V once, the queries in and the contexts out."""
    n, d, wb = sz["layers"], sz["d_model"], sz["weight_bytes"]
    return 4.0 * d * rows * n, n * (_row_bytes(sz) * rows + lanes * d * (wb + 4.0))


def attend_prefill(sz: dict, tokens: float, rows: float) -> tuple[float, float]:
    """A launch's attention and pooling alone (scopes `eva_prefill` and
    `eva_summarise`), every layer: scores and context over `rows` attended rows
    in all, the pooling; the launch's own K and V and the earlier rows once,
    the queries in, the contexts out in float32, the summaries written."""
    n, d, wb = sz["layers"], sz["d_model"], sz["weight_bytes"]
    row = _row_bytes(sz)
    return (4.0 * d * rows + 4.0 * d * tokens) * n, \
        n * (row * (tokens * (1.0 + 1.0 / sz["chunk"]) + _earlier(tokens, rows))
             + tokens * d * (wb + 4.0))
