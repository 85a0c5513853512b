"""One decode step of a gated delta-rule state, every lane and head, in ONE
kernel call a layer (ISSUE 53).

The rule, a head, with the state ``S`` (dk, dv) float32, the token's decay a
CHANNEL ``a`` (dk,) in (0, 1], its key ``k`` and query ``q`` (dk,), its value
``v`` (dv,) and its step ``beta``::

    S' = Diag(a) S                      the decay, before the correction
    S  = S' + beta k (v - S'^T k)^T     the correction reads the decayed state
    o  = S^T q                          the read is of the state AFTER the write

The correction's ``S'^T k`` is a reduction over the state's 128 rows that has
to end before its result can go back into the same block, so plain XLA reads
the state twice and writes a decayed copy between (``delta_step``, the form
every backend but the TPU runs, and what the kernel is held to). ``delta_update``
holds a head's block in fast memory from the decay to the read: A LIVE LANE'S
STATE CROSSES DEVICE MEMORY ONCE IN AND ONCE OUT, written back in place
(``input_output_aliases``); a lane that is not live writes back what it read.

The grid is (lanes, blocks of ``HEADS_BLOCK`` heads). The state lies (dk, dv):
rows on sublanes, a value's columns on lanes. What multiplies a ROW of it (``a``,
``k``, ``beta k``, ``q``: all indexed by dk) has to be a column, so the caller's
four vectors come packed ``(lanes, blocks, 4 x HEADS_BLOCK, dk)`` and a cell
transposes its (4 x HEADS_BLOCK, 128) block once; ``v`` and ``o`` are rows as they come.
Every product is float32 on the vector unit (3 x 2 x dk x dv operations a head
against 2 x 4 x dk x dv bytes: the matrix unit has nothing to do here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a cell takes: its state block is HEADS_BLOCK x 64 KiB at 128 x 128, in
# and out and each double-buffered (4 MiB at 16). 192 lanes of 64 heads took
# 2.878 / 2.560 / 2.551 ms at 8 / 16 / 32 heads a cell, beside 2.464 ms for one
# pass over the same states and 3.627 ms for the plain form
# (scripts/bench_delta.py, my chip run, PR 53).
HEADS_BLOCK = 16


def delta_step(state, q, k, v, a, beta, live):
    """The plain form. ``state`` (B, H, dk, dv) float32; ``q``, ``k``, ``a``
    (B, H, dk), ``v`` (B, H, dv), ``beta`` (B, H), all float32; ``live`` (B,)
    bool -> (o (B, H, dv) float32, the new state: a lane that is not live
    keeps its own)."""
    decayed = a[..., None] * state
    seen = jnp.sum(decayed * k[..., None], axis=-2)                   # S'^T k
    new = decayed + (beta[..., None] * k)[..., None] * (v - seen)[..., None, :]
    o = jnp.sum(new * q[..., None], axis=-2)
    return o, jnp.where(live[:, None, None, None], new, state)


def supported(state) -> bool:
    """Shapes the kernel takes: whole 128-lane rows both ways and blocks of
    ``HEADS_BLOCK`` heads (all of them where they are fewer)."""
    _b, h, dk, dv = state.shape
    return state.dtype == jnp.float32 and dk % 128 == 0 and dv % 128 == 0 \
        and h % min(HEADS_BLOCK, h) == 0


def _cell(live_ref, cols_ref, v_ref, s_ref, o_ref, out_ref, *, hb: int):
    lane = pl.program_id(0)

    @pl.when(live_ref[lane] != 0)
    def _():
        cols = cols_ref[0, 0].T                                       # (dk, 4 hb)
        for j in range(hb):
            a, k, bk, q = (cols[:, n * hb + j:n * hb + j + 1] for n in range(4))
            decayed = s_ref[0, j] * a
            seen = jnp.sum(decayed * k, axis=0, keepdims=True)        # (1, dv)
            new = decayed + bk * (v_ref[0, j:j + 1, :] - seen)
            out_ref[0, j] = new
            o_ref[0, j:j + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)

    @pl.when(live_ref[lane] == 0)
    def _():
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def delta_update(state, q, k, v, a, beta, live, *, heads_block: int = HEADS_BLOCK,
                 interpret: bool = False):
    """``delta_step`` in one kernel call with the state aliased in place:
    same arguments, same results (``o`` of a lane that is not live is zeros)."""
    b, h, dk, dv = state.shape
    hb = min(heads_block, h)
    # (B, 4, H, dk) -> (B, H / hb, 4 x hb, dk): a cell's four vectors, a head a row.
    cols = jnp.stack([a, k, beta[..., None] * k, q], axis=1) \
        .reshape(b, 4, h // hb, hb, dk).transpose(0, 2, 1, 3, 4).reshape(b, h // hb, 4 * hb, dk)
    o, new = pl.pallas_call(
        functools.partial(_cell, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hb),
            in_specs=[pl.BlockSpec((1, 1, 4 * hb, dk), lambda i, j, live: (i, j, 0, 0)),
                      pl.BlockSpec((1, hb, dv), lambda i, j, live: (i, j, 0)),
                      pl.BlockSpec((1, hb, dk, dv), lambda i, j, live: (i, j, 0, 0))],
            out_specs=[pl.BlockSpec((1, hb, dv), lambda i, j, live: (i, j, 0)),
                       pl.BlockSpec((1, hb, dk, dv), lambda i, j, live: (i, j, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={3: 1},   # the state (after the prefetched scalars), in place
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(live.astype(jnp.int32), cols, v, state)
    return o, new
