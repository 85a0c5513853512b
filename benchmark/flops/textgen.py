"""Operations and bytes one launch of the `textgen` family's decode step
needs, from its shapes alone: `batch` lanes each taking `seq` new positions
through every layer against the whole padded context (`max_ctx` keys: the
step attends over every page of a slot, filled or not, so that is what the
device computes).

Operations are the matrix multiplications (2 per multiply-add): q/k/v/out
projections, scores and context, the two feed-forward products, the head.
LayerNorm, GELU, softmax and the gathers are left out, so the count is under
XLA's, never over. Bytes are the least traffic to device memory: every weight
once, the lanes' K and V read once, the new rows written, ids in and tokens
out.
"""

from __future__ import annotations


def ops_and_bytes(sz: dict, batch: int, seq: int, weight_bytes: int = 4,
                  act_bytes: int = 4) -> tuple[float, float]:
    L, d, f, v, ctx = sz["layers"], sz["d_model"], sz["d_ff"], sz["vocab_size"], sz["max_ctx"]
    t = batch * seq
    per_layer = (
        4 * 2 * t * d * d        # q, k, v, out projections
        + 2 * 2 * t * ctx * d    # scores and context, all heads
        + 2 * 2 * t * d * f      # feed-forward up and down
    )
    ops = L * per_layer + 2 * t * d * v
    params = L * (4 * d * d + 2 * d * f + 4 * d) + d * v + 2 * d
    nbytes = (
        params * weight_bytes
        + t * d * weight_bytes                    # embedding and position rows gathered
        + L * 2 * batch * ctx * d * act_bytes     # K and V of every lane, read
        + L * 2 * t * d * act_bytes               # the new K and V rows, written
        + 2 * t * 4                               # last token in, sampled token out
    )
    return float(ops), float(nbytes)
