"""Operations and bytes one launch of the `bert` family's executable needs,
from its shapes alone. The whole padded bucket is counted, because that is
what the device computes: a (256, 512) launch does the work of 256 x 512
tokens however many of them are padding.

Operations are the matrix multiplications of the architecture (2 per
multiply-add): q/k/v/out projections, scores and context, the two
feed-forward products, pooler and classifier. Softmax, LayerNorm, GELU and
the embedding gather are left out: they need no MXU work and XLA's own count
puts them at about 2% at the (256, 512) bucket. So the count is a little
under XLA's `cost_analysis()["flops"]`, never over (a test holds it within a
stated margin), and a roofline share over 100% means this file is wrong.

Bytes are the least traffic to device memory: every weight read once, the
gathered embedding rows, ids and mask in, the answer out, and the residual
stream written and read once per layer. Attention scores are assumed to stay
on chip (a lower bound; dense attention in fact spills them).
"""

from __future__ import annotations


def ops_and_bytes(sz: dict, batch: int, seq: int, weight_bytes: int = 2,
                  act_bytes: int = 2) -> tuple[float, float]:
    L, d, f = sz["layers"], sz["d_model"], sz["d_ff"]
    c = sz["num_classes"]
    t = batch * seq
    per_layer = (
        4 * 2 * t * d * d          # q, k, v, out projections
        + 2 * 2 * batch * seq * seq * d  # scores and context, all heads
        + 2 * 2 * t * d * f        # feed-forward up and down
    )
    ops = L * per_layer + 2 * batch * d * d + 2 * batch * d * c
    layer_params = 4 * (d * d + d) + 2 * d * f + f + d + 4 * d
    params = (L * layer_params + sz["positions"] * d + 2 * d
              + d * d + d + d * c + c)
    nbytes = (
        params * weight_bytes
        + t * d * weight_bytes          # embedding rows gathered
        + 2 * t * 4                     # ids and mask, int32
        + L * 2 * t * d * act_bytes     # residual stream, out and back in
        + 2 * batch * c * 4             # probabilities and indices
    )
    return float(ops), float(nbytes)
