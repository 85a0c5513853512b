"""One tile's attention over ONE key block, as a Pallas TPU kernel whose
scores never leave the chip's fast memory (ISSUE 34).

``tile_attention(q, k, v, offset)``: ``q`` (H, T, dk), ``k`` (H, C, dk), ``v``
(H, C, dv) in bfloat16, a query row ``i`` sees key ``j`` where ``j <= i +
offset`` (``offset``: a traced int32, the tile's first position less the
block's: a block that lies whole before the tile has every key seen, the
block the tile lies in is causal) -> the block's UN-NORMALISED context (H, T,
dv) float32 with its rows' running max and sum (H, T) float32, for the
caller's merge over key blocks (``paged_lm._merge_key_blocks``). Keys and
values may have different widths (latent attention's expanded form: 192 and
128). Grid (H, T / block_q, C / block_k), the key cells innermost and
sequential, the softmax's state in scratch; a cell that lies whole past the
diagonal is skipped. Every row must see key 0 of the block (``offset >= 0``):
the first cell then leaves a finite max, and a masked score adds nothing.

Why a kernel: XLA's einsum pair writes a tile's scores (32 heads x 1,024 x
1,024 float32 = 134 MB a key block) to device memory and reads them back three
times; at the cell's sizes that traffic, not the products, was a prefill
launch's time (85 of 116 ms under ``mla_prefill`` at context 6,144, my chip
run, PR 34). Off the TPU ``interpret=True`` runs the same code in the Pallas
interpreter (tests); the families call it on the TPU alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BLOCK_Q, BLOCK_K = 512, 512


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bq: int, bk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    off = off_ref[0]

    @pl.when(ki * bk <= qi * bq + bq - 1 + off)   # else: every key of the cell is past every row
    def _():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols <= rows + off, s, NEG)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                                    l_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_ref[:]
        mo_ref[0] = m_ref[:]
        lo_ref[0] = l_ref[:]


def fits(t: int, c: int, dk: int, dv: int, dtype) -> bool:
    """Shapes the kernel takes: bfloat16, whole cells, lanes in whole tiles
    or a dimension's full width."""
    return dtype == jnp.bfloat16 and t % min(BLOCK_Q, t) == 0 and c % min(BLOCK_K, c) == 0 \
        and t % 128 == 0 and c % 128 == 0 and dk % 64 == 0 and dv % 128 == 0


def tile_attention(q: jax.Array, k: jax.Array, v: jax.Array, offset: jax.Array, *,
                   scale: float, interpret: bool = False):
    h, t, dk = q.shape
    c, dv = k.shape[1], v.shape[2]
    bq, bk = min(BLOCK_Q, t), min(BLOCK_K, c)
    stat = pl.BlockSpec((1, bq, 128), lambda hi, qi, ki, off: (hi, qi, 0))
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h, t // bq, c // bk),
            in_specs=[pl.BlockSpec((1, bq, dk), lambda hi, qi, ki, off: (hi, qi, 0)),
                      pl.BlockSpec((1, bk, dk), lambda hi, qi, ki, off: (hi, ki, 0)),
                      pl.BlockSpec((1, bk, dv), lambda hi, qi, ki, off: (hi, ki, 0))],
            out_specs=(pl.BlockSpec((1, bq, dv), lambda hi, qi, ki, off: (hi, qi, 0)), stat, stat),
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32), pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct((h, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, t, 128), jnp.float32),
                   jax.ShapeDtypeStruct((h, t, 128), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="tile_attention",
    )(jnp.reshape(offset, (1,)).astype(jnp.int32), q, k, v)
    return acc, m[..., 0], l[..., 0]
