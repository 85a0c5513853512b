"""One prefill tile's latent attention over ALL its key blocks, as one Pallas
TPU kernel call (ISSUE 34 kept a block's scores on the chip; ISSUE 43 moved
the walk over the blocks in too).

``tile_walk(q, w_kvb, ckv, kr, rows, need, pos0)``: ``q`` (H, T, nope + g x
rope) the tile's queries by head, ``[q_nope | q_rope .. q_rope]`` (the rotary
part ``g`` times, see below); ``w_kvb`` (H, r, nope + v) a head's two sides of
``W_kvb`` side by side; ``ckv`` (pages, P, r) and ``kr`` (pages, P / g, g x
rope) the layer's page pools AS THEY LIE; ``rows`` the prompt's block-table
row padded to whole key blocks of ``block_pages`` pages; ``need`` the key
blocks the walk takes and ``pos0`` the tile's first position (traced int32:
a tile's positions are consecutive, so row ``i`` sees key position ``s`` where
``s <= pos0 + i``) -> the tile's context (T, H, v), normalised, in ``q``'s
type.

Grid (H / hb, ``need``): the key blocks innermost and sequential, their
number a TRACED grid bound, so a prompt's first tile runs one block's cells
and nothing is launched, fetched or skipped for the padded context. A cell
holds ``hb`` heads' queries, their float32 accumulator (hb, T, v) and their
rows' running max and sum in VMEM scratch from the tile's first key block to
its last; the last cell divides and writes the context ONCE, head beside head
as ``W_o`` reads it. ``rows`` and ``pos0`` arrive by scalar prefetch: a cell
reads its block's ``block_pages`` pages straight from the pools through the
block-table row (the pools are passed once a page of a block, each with its
own index map). In a cell the block's latents are laid side by side once, and
for each head ``[k_nope | v] = c_kv W_kvb`` is made on the chip (once a (tile,
key block, head), as the walk in XLA did) and attended in sub-blocks of
``BLOCK_Q`` x ``BLOCK_K`` under the running softmax; a sub-block that lies
whole past the diagonal is skipped. Products in bfloat16 with float32
accumulation, the softmax in float32, ``p.astype(bfloat16) @ v``. No block's
partial context, statistics, expanded keys or values exist in device memory.
Every row sees key 0 (``pos0 >= 0``), so the first sub-block leaves a finite
max and a masked score adds nothing.

THE ROTARY KEY is one row a position for every head: it is written once a
cell beside the heads' ``k_nope`` (never copied a head). Its leaf holds ``g``
positions side by side in a row of 128 lanes, so position ``s`` of a page is
part ``s % g`` of row ``s // g``: a 0/1 product repeats each row ``g`` times
(exact: one term a sum) and a lane mask keeps part ``s % g``, zeros
elsewhere; the query's rotary part repeated ``g`` times then gives ``q_rope .
k_r(s)`` as one contraction of 128 lanes.

ATTENTION OVER PICKS (``keep=``, ISSUES 62 and 65): the rows' index scores and
a threshold a row, made by ``ops/index_select.py`` (in fast memory, by the
kernel that makes the scores). A cell reads its key block's (T, c) float32
columns of the scores beside the pages and the (T, 128) thresholds, and a row
sees a key only where its score is at or above its threshold: one compare
beside the causal one, no mask in device memory, nothing added to the head
loop. Without ``keep`` the kernel's text is what it was.

Why a kernel: at the cell's sizes the walk in XLA moved 150-200 MB a (tile,
key block) (the expanded keys and values written, read once a query block,
a float32 partial context and two statistics padded to 128 lanes written and
merged) beside 0.15 ms of products (ISSUE 43). Off the TPU ``interpret=True``
runs the same code in the Pallas interpreter (tests); the families call it on
the TPU alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BLOCK_Q, BLOCK_K = 1024, 1024
HEAD_ROWS = 4096   # query rows (heads x tile rows) a cell holds


def _kernel(pos0_ref, rows_ref, q_ref, w_ref, *refs, scale: float, kb: int, g: int, dn: int,
            bq: int, bk: int, picked: bool = False):
    del rows_ref   # the index maps read it
    (keep_ref, least_ref), refs = (refs[:2], refs[2:]) if picked else ((None, None), refs)
    ckv_refs, kr_refs, o_ref = refs[:kb], refs[kb:2 * kb], refs[2 * kb]
    c_ref, k_ref, v_ref, m_ref, l_ref, acc_ref = refs[2 * kb + 1:]
    hb, t, _ = q_ref.shape
    P = ckv_refs[0].shape[0]
    c, dt = kb * P, q_ref.dtype
    lanes = kr_refs[0].shape[1]
    j = pl.program_id(1)
    f32 = {"preferred_element_type": jnp.float32}

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the block's latents side by side, and each position's rotary key in its
    # own row: row s // g of a page's leaf repeated g times, part s % g kept
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)  # noqa: E731
    repeat = (iota((P, P // g), 0) // g == iota((P, P // g), 1)).astype(dt)
    own = iota((P, lanes), 1) // (lanes // g) == iota((P, lanes), 0) % g
    for i in range(kb):
        c_ref[i * P:(i + 1) * P, :] = ckv_refs[i][...]
        k_r = kr_refs[i][...]
        if g > 1:
            k_r = jnp.where(own, jnp.dot(repeat, k_r, **f32), 0.0).astype(dt)
        k_ref[i * P:(i + 1) * P, dn:] = k_r
    off = pos0_ref[0] - j * c   # the tile's first position less the block's

    def attend(h, qi, ki):
        at_q, at_k = pl.ds(qi * bq, bq), pl.ds(ki * bk, bk)
        s = jax.lax.dot_general(q_ref[h, at_q, :], k_ref[at_k, :],
                                (((1,), (1,)), ((), ())), **f32) * scale
        see = ki * bk + iota((bq, bk), 1) <= qi * bq + iota((bq, bk), 0) + off
        if picked:   # and the row picked the key: its score is at or above its threshold
            see = see & (keep_ref[at_q, at_k] >= least_ref[at_q, :1])
        s = jnp.where(see, s, NEG)
        m_prev, l_prev = m_ref[h, at_q, :1], l_ref[h, at_q, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[h, at_q, :] = acc_ref[h, at_q, :] * alpha + jnp.dot(
            p.astype(dt), v_ref[at_k, :], **f32)
        m_ref[h, at_q, :] = jnp.broadcast_to(m_new, (bq, m_ref.shape[2]))
        l_ref[h, at_q, :] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), (bq, l_ref.shape[2]))

    def head(h, carry):
        kv = jnp.dot(c_ref[...], w_ref[h], **f32)
        k_ref[:, :dn] = kv[:, :dn].astype(dt)
        v_ref[...] = kv[:, dn:].astype(dt)
        for qi in range(t // bq):
            for ki in range(c // bk):
                # else: every key of the sub-block is past every row
                pl.when(ki * bk <= qi * bq + bq - 1 + off)(
                    functools.partial(attend, h, qi, ki))
        return carry

    jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dv = acc_ref.shape[2]
        for h in range(hb):
            o_ref[:, h * dv:(h + 1) * dv] = (acc_ref[h] / l_ref[h, :, :1]).astype(o_ref.dtype)


def _cell_heads(h: int, t: int) -> int:
    """Heads a cell holds: ``HEAD_ROWS`` query rows' worth, a divisor of ``h``."""
    return next(n for n in range(max(1, min(h, HEAD_ROWS // t)), 0, -1) if h % n == 0)


def fits(t: int, page: int, block_pages: int, r: int, dn: int, dv: int, kr_lanes: int,
         dtype) -> bool:
    """Shapes the kernel takes: bfloat16, whole sub-blocks, whole sublane tiles
    a page, every width whole 128-lane tiles."""
    c = page * block_pages
    return dtype == jnp.bfloat16 and t % min(BLOCK_Q, t) == 0 and c % min(BLOCK_K, c) == 0 \
        and t % 128 == 0 and c % 128 == 0 and page % 16 == 0 \
        and all(w % 128 == 0 for w in (r, dn, dv, kr_lanes))


def tile_walk(q: jax.Array, w_kvb: jax.Array, ckv: jax.Array, kr: jax.Array, rows: jax.Array,
              need: jax.Array, pos0: jax.Array, *, block_pages: int, scale: float,
              keep: tuple | None = None, interpret: bool = False) -> jax.Array:
    """``keep``, or None: ATTENTION OVER PICKS (ISSUE 62), as the pair the
    indexer leaves (ISSUE 65; ``ops/index_select.py`` ``tile_scores``, or
    ``scores_xla`` and ``thresholds``): the rows' index SCORES (T, key blocks x
    c) float32 and each row's THRESHOLD (T, 128) float32, a row's value across
    the lanes (as ``m_ref`` lies). Row ``i`` attends key ``s`` only where
    ``scores[i, s] >= threshold[i]`` (and ``s <= pos0 + i``): a cell reads its
    key block's (T, c) columns of the scores beside the pages and compares; no
    mask is made in device memory. A row whose keys so far are all left out
    carries weights of one until its first kept key, which scales them away
    (every row keeps one somewhere). Operands only where there are any:
    without them the kernel is what it was."""
    h, t, dq = q.shape
    r, dkv = w_kvb.shape[1:]
    pages, P = ckv.shape[:2]
    lanes = kr.shape[2]
    g = P // kr.shape[1]
    dn, kb = dq - lanes, block_pages
    dv, c = dkv - dn, kb * P
    hb, bq, bk = _cell_heads(h, t), min(BLOCK_Q, t), min(BLOCK_K, c)
    rows = jnp.clip(rows, 0, pages - 1).astype(jnp.int32)
    page = lambda i: lambda hi, j, pos0, rows: (rows[j * kb + i], 0, 0)  # noqa: E731
    by_head = lambda hi, j, pos0, rows: (hi, 0, 0)  # noqa: E731
    item = jnp.dtype(q.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers), its scratch, and
    # the float32 values of a head's expansion and of a sub-block's softmax
    vmem = 2 * item * (hb * t * (dq + dv) + hb * r * dkv + c * (r + lanes)) \
        + item * c * (r + dq + dv) + 4 * hb * t * (dv + 256) + 4 * (c * dkv + 4 * bq * bk)
    picked = keep is not None
    if picked:
        vmem += 2 * 4 * t * (c + 128)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, kb=kb, g=g, dn=dn, bq=bq, bk=bk,
                          **({"picked": True} if picked else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(h // hb, need.astype(jnp.int32)),
            in_specs=[pl.BlockSpec((hb, t, dq), by_head), pl.BlockSpec((hb, r, dkv), by_head)]
            + [pl.BlockSpec((t, c), lambda hi, j, pos0, rows: (0, j)),
               pl.BlockSpec((t, 128), lambda hi, j, pos0, rows: (0, 0))] * picked
            + [pl.BlockSpec((None, P, r), page(i)) for i in range(kb)]
            + [pl.BlockSpec((None, P // g, lanes), page(i)) for i in range(kb)],
            out_specs=pl.BlockSpec((t, hb * dv), lambda hi, j, pos0, rows: (0, hi)),
            scratch_shapes=[pltpu.VMEM((c, r), q.dtype), pltpu.VMEM((c, dq), q.dtype),
                            pltpu.VMEM((c, dv), q.dtype),
                            pltpu.VMEM((hb, t, 128), jnp.float32),
                            pltpu.VMEM((hb, t, 128), jnp.float32),
                            pltpu.VMEM((hb, t, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((t, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret, name="tile_walk",
    )(jnp.reshape(pos0, (1,)).astype(jnp.int32), rows, q, w_kvb, *(keep or ()),
      *([ckv] * kb), *([kr] * kb)).reshape(t, h, dv)
