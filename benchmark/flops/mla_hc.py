"""Operations and bytes of the `mla_hc` family's two device programs, of their
latent-attention parts and of the hyper-connection's maps and mixes, from the
configuration's sizes and what a window's counters say the programs worked on.
Every count is the LEAST the work needs, so a share of the roofline computed
from it cannot pass 100% (`flops/mla.py` says what that leaves out):

- a layer is `mla`'s (`flops/mla.py`'s counts of the five MLA matrices, of
  attention in the form with the fewer operations, of the dense or shared
  SwiGLU, the router and the picks' products), and each of its two sublayers
  passes through its maps (`hyper_maps`): a token a sublayer, `2 n d (2 n +
  n^2)` operations for the product with `Phi`, `2 n d (n + 2)` for the two
  mixes (`n d` multiply-adds in, `n (n + 1) d` out) and `4 n^2` a Sinkhorn
  iteration (a sum and a division a pass, two passes); bytes THE LEAST A FUSED
  IMPLEMENTATION MOVES: the stream of `n d` values read once and written once
  a sublayer, `Phi` once a launch. The float32 copies, the normed copy and the
  passes a mix makes over the stream in XLA count for nothing.
- the head over the whole vocabulary, for the rows that are sampled.

`ops_and_bytes(sizes, batch, seq)` is what the harness's generic readers call.
"""

from __future__ import annotations

from benchmark import spec

_mla = spec.load_module("flops", "mla")
_matrices, row_bytes, earlier_rows = _mla._matrices, _mla.row_bytes, _mla.earlier_rows
attend_decode, attend_prefill = _mla.attend_decode, _mla.attend_prefill   # the `mla_*` readers'


def hyper_maps(sz: dict, tokens: float) -> tuple[float, float]:
    """The `hc_mix` scopes of one launch that took `tokens` live tokens
    through every sublayer -> (operations, bytes)."""
    n, d, wb = sz["streams"], sz["d_model"], sz["weight_bytes"]
    maps = 2 * n + n * n
    a_token = 2.0 * n * d * maps + 2.0 * n * d * (n + 2) + 4.0 * n * n * sz["hc_iters"]
    return sz["sublayers"] * tokens * a_token, \
        sz["sublayers"] * wb * (2.0 * tokens * n * d + n * d * maps)


def launch(sz: dict, tokens: float, sampled: float, pairs: float, cached_rows: float,
           held_picks: float, experts_hit: float) -> tuple[float, float]:
    """`flops/mla.py` `launch` and the maps of every sublayer."""
    ops, nbytes = _mla.launch(sz, tokens, sampled, pairs, cached_rows, held_picks, experts_hit)
    h_ops, h_bytes = hyper_maps(sz, tokens)
    return ops + h_ops, nbytes + h_bytes


def decode_step(sz: dict, lanes: float, context_sum: float, held_picks: float,
                experts_hit: float) -> tuple[float, float]:
    return launch(sz, lanes, lanes, context_sum, max(0.0, context_sum - lanes),
                  held_picks, experts_hit)


def prefill_chunk(sz: dict, tokens: float, context_sum: float, held_picks: float,
                  experts_hit: float) -> tuple[float, float]:
    return launch(sz, tokens, 1.0, context_sum, earlier_rows(tokens, context_sum),
                  held_picks, experts_hit)


def ops_and_bytes(sz: dict, batch: int, seq: int) -> tuple[float, float]:
    picks = batch * sz["top_k"]
    hit = sz["num_experts"] * (1.0 - (1.0 - 1.0 / sz["num_experts"]) ** picks)
    return decode_step(sz, batch, float(batch * seq), picks * sz["n_sparse"],
                       hit * sz["n_sparse"])
