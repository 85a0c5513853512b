"""The block scores of one picked prefill tile in ONE kernel call (ISSUE 69): what
``models/mixers.py`` ``BlockSelectAttention._block_scores`` computes in plain
``jnp`` through a (KV, g R, J) float32 array of scores (277 MB a tile at the
cell's size) that XLA writes and reads back for the mask, the maximum, the
exponential, the sum, the division and the sum over a group's heads, with
nothing of that array in device memory and over the windows the tile can see
and no more.

For the rows of a tile at positions ``qpos``, q (T, H, hd) in the served type
over ONE prompt's pooled keys ``Kc_g[j]`` (window j covers the keys ``stride j ..
stride j + kernel - 1``), with g = H / KV heads a KV group::

    s_h[t, j]   = scale q_h[t] . Kc_g[j]            window j whole at or before t
    p_h[t, .]   = softmax_j s_h[t, .]               A HEAD, over the windows t sees
    sc_g[t, j]  = sum of p_h[t, j] over the group's heads
    score_g[t, b] = max of sc_g[t, j] over the windows that touch block b:
                    r b - extra .. r b + r - 1      r = block / stride windows begin
                                                    in a block, extra = kernel / stride
                                                    - 1 in the block before
    +inf a forced block (the first ``init``, the ``local`` last at the row's own),
    -inf a block past the row's own.

The product takes its operands in the served type and accumulates in float32;
everything after it is float32, the division by a row's normaliser exact (a
reciprocal a (head, row), no approximate one). The sums run in another order
than XLA's, so a score differs from the plain form's in its last places.

THE WINDOWS LIE ON THE LANES, A GROUP'S HEADS ARE ROWS of one product, (g x
``ROWS``, hd) x (hd, c), as the plain form found right. A WINDOW BLOCK is c = r x
128 windows, the keys' rows gathered so that the r windows that begin in one
key block lie at the same lane of r neighbouring registers (window ``c jb + r i
+ p`` at lane i of register p): a block's maximum is then an elementwise
maximum of r registers and of the last ``extra`` shifted by one lane (the
lane that comes in is the window block before's), 128 block scores a window
block, and no value moves between lanes but that one.

The grid is (KV groups: parallel; row sub-tiles of ``ROWS``: parallel; window
blocks: arbitrary, innermost), THE WINDOW BLOCKS A TRACED BOUND (scalar
prefetch, ``tile_attention``'s and ``index_select.tile_scores``'s grids): as
many as hold the block of the tile's last live position, which are the
windows any live row of the tile sees. A cell makes its product, masks the
windows a row cannot see and leaves the (g x ROWS, c) scores in VMEM scratch
with a running elementwise maximum; the last cell of a sub-tile finishes
there: a row's maximum and normaliser (a chunk of rows at a time, so that the
running sums stay in registers), then a window block at a time the sixteen
heads' ``exp(s - m) / l`` added up, the block maxima, the forced blocks, and ONE
(ROWS, 128) store. Window blocks past the bound are written ``-inf``: no live
row sees them. (The other form, two sweeps that make the product twice and
keep a running maximum and sum, holds nothing but costs a second product and
a second exponential an element: ``PERF.md`` section 6, PR 69, has both
readings.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 16      # query rows a cell takes: with a group's heads, g x ROWS rows of the product
CHUNK = 128    # rows whose normalisers are summed at a time (64: +10% a tile, 32: +30%)
LANES = 128
NEG = -1e9     # a window the row cannot see, before the exponential
VMEM_ROOM = 48 << 20   # what a sub-tile's held scores may take


def window_blocks(windows: int, per_block: int) -> int:
    """Window blocks of ``per_block`` x 128 windows that hold ``windows``."""
    return -(-windows // (per_block * LANES))


def held_bytes(windows: int, per_block: int, group: int) -> int:
    """What a sub-tile's scores over the whole table take in scratch: float32,
    ``group`` heads x ``ROWS`` rows x whole window blocks."""
    return 4 * group * ROWS * window_blocks(windows, per_block) * per_block * LANES


def supported(tile: int, heads: int, kv: int, head_dim: int, windows: int, per_block: int,
              extra: int, dtype) -> bool:
    """Shapes the kernel takes: a tile of whole sub-tiles of ``ROWS`` rows, heads
    of whole 128-lane registers in whole KV groups, the served type bfloat16 or
    float32, no more windows from the block before than begin in a block, and a
    sub-tile's scores over the whole table within ``VMEM_ROOM``."""
    if heads % kv or tile % ROWS or head_dim % LANES or not 0 <= extra <= per_block:
        return False
    return jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32) \
        and held_bytes(windows, per_block, heads // kv) <= VMEM_ROOM


def _cell(need_ref, q_ref, k_ref, vis_ref, own_ref, o_ref, s_ref, m_ref, inv_ref, lim_ref, *,
          g: int, r: int, extra: int, init: int, local: int, scale: float):
    del need_ref   # the grid's bound
    j, n = pl.program_id(2), pl.num_programs(2)
    rb, c = vis_ref.shape[0], k_ref.shape[0]
    rows = g * rb

    @pl.when(j == 0)
    def _():
        for h in range(g):   # the windows a row sees, a head's rows after another's
            lim_ref[h * rb:(h + 1) * rb, :] = vis_ref[...]
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)

    s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    first = j * c + r * jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    top = m_ref[...]
    for p in range(r):
        at = slice(p * LANES, (p + 1) * LANES)
        sp = jnp.where(first + p < lim_ref[...], s[:, at], NEG)
        s_ref[j, :, at] = sp
        top = jnp.maximum(top, sp)
    m_ref[...] = top

    @pl.when(j == n - 1)
    def _():
        rc = math.gcd(CHUNK, rows)   # whole chunks: a group of 12 heads has 192 rows

        def chunk(i, carry):   # a row's maximum and 1 / its normaliser, across the lanes
            at = pl.ds(pl.multiple_of(i * rc, rc), rc)
            top = jnp.broadcast_to(jnp.max(m_ref[at, :], axis=1, keepdims=True), (rc, LANES))

            def block(jb, total):
                for p in range(r):
                    total += jnp.exp(s_ref[jb, at, p * LANES:(p + 1) * LANES] - top)
                return total

            total = jax.lax.fori_loop(0, n, block, jnp.zeros((rc, LANES), jnp.float32))
            m_ref[at, :] = top
            inv_ref[at, :] = jnp.broadcast_to(
                1.0 / jnp.sum(total, axis=1, keepdims=True), (rc, LANES))
            return carry

        jax.lax.fori_loop(0, rows // rc, chunk, 0)
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rb, LANES), 1)
        vis, own = vis_ref[...], own_ref[...]

        def block(jb, before):
            sc = [jnp.zeros((rb, LANES), jnp.float32) for _ in range(r)]
            for h in range(g):
                at = slice(h * rb, (h + 1) * rb)
                top, inv = m_ref[at, :], inv_ref[at, :]
                for p in range(r):
                    sc[p] += jnp.exp(s_ref[jb, at, p * LANES:(p + 1) * LANES] - top) * inv
            sc = [jnp.where(jb * c + r * lane + p < vis, sc[p], -jnp.inf) for p in range(r)]
            best, tail = functools.reduce(jnp.maximum, sc), before
            if extra:   # the windows that begin in the block before: one lane over
                tail = functools.reduce(jnp.maximum, sc[r - extra:])
                best = jnp.maximum(best, jnp.where(lane == 0, pltpu.roll(before, 1, 1),
                                                   pltpu.roll(tail, 1, 1)))
            b = jb * LANES + lane
            forced = (b < init) | (own - b < local)
            o_ref[jb] = jnp.where(b > own, -jnp.inf, jnp.where(forced, jnp.inf, best))
            return tail

        jax.lax.fori_loop(0, n, block, jnp.full((rb, LANES), -jnp.inf, jnp.float32))


def block_scores(q: jax.Array, kc: jax.Array, row: jax.Array, qpos: jax.Array, last: jax.Array,
                 *, spans: int, page: int, kernel: int, stride: int, block: int, init: int,
                 local: int, scale: float, interpret: bool = False) -> jax.Array:
    """A tile's block scores: q (T, H, hd) at positions ``qpos`` (T,), the
    pooled keys' leaf ``kc`` (rows, KV x hd), the prompt's block-table row ``row``
    (n,) of pages of ``page`` positions, ``last`` (traced) the tile's last live
    position -> (KV, T, spans) float32, ``spans`` >= n x page / block: ``+inf`` a
    forced block, ``-inf`` a block past the row's own and every block past the
    window blocks that hold ``last``'s."""
    T, H, hd = q.shape
    kv = kc.shape[1] // hd
    g, per, r, extra = H // kv, page // stride, block // stride, kernel // stride - 1
    c, J = r * LANES, row.shape[0] * per
    nb = window_blocks(J, r)
    # window ``c jb + r i + p`` at lane i of register p of window block jb
    w = (c * jnp.arange(nb)[:, None, None] + jnp.arange(r)[None, :, None]
         + r * jnp.arange(LANES)[None, None, :]).reshape(-1)
    at = jnp.take(row, jnp.minimum(w // per, row.shape[0] - 1)) * per + w % per
    pooled = jnp.take(kc, jnp.where(w < J, at, 0), axis=0)                   # (nb c, KV hd)
    whole = jnp.minimum(qpos, stride * J - 1) - (kernel - 1)
    across = lambda x: jnp.broadcast_to(x.astype(jnp.int32)[:, None], (T, LANES))  # noqa: E731
    vis, own = across(jnp.where(whole >= 0, whole // stride + 1, 0)), across(qpos // block)
    need = jnp.reshape(jnp.clip(last // block // LANES + 1, 1, nb), (1,)).astype(jnp.int32)
    qg = q.reshape(T // ROWS, ROWS, kv, g, hd).transpose(2, 0, 3, 1, 4) \
        .reshape(kv, T // ROWS, g * ROWS, hd)
    by_rows = pl.BlockSpec((ROWS, LANES), lambda k, i, j, need: (i, 0))
    out = pl.pallas_call(
        functools.partial(_cell, g=g, r=r, extra=extra, init=init, local=local, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(kv, T // ROWS, need[0]),
            in_specs=[pl.BlockSpec((None, None, g * ROWS, hd), lambda k, i, j, need: (k, i, 0, 0)),
                      pl.BlockSpec((c, hd), lambda k, i, j, need: (j, k)), by_rows, by_rows],
            out_specs=pl.BlockSpec((None, nb, ROWS, LANES), lambda k, i, j, need: (k, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((nb, g * ROWS, c), jnp.float32),
                            pltpu.VMEM((g * ROWS, LANES), jnp.float32),
                            pltpu.VMEM((g * ROWS, LANES), jnp.float32),
                            pltpu.VMEM((g * ROWS, LANES), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((kv, nb, T, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the held scores, and room for a cell's blocks twice and its product
            vmem_limit_bytes=held_bytes(J, r, g) + (24 << 20)),
        interpret=interpret, name="block_scores",
    )(need, qg, pooled, vis, own)
    score = out.transpose(0, 2, 1, 3).reshape(kv, T, nb * LANES)
    if spans <= nb * LANES:
        return score[:, :, :spans]
    return jnp.pad(score, ((0, 0), (0, 0), (0, spans - nb * LANES)), constant_values=-jnp.inf)
