"""A prefill launch's attention over the key pages that exist, every tile of
it, as one Pallas TPU kernel call a layer (ISSUE 58): ``tile_attention``'s
shape (a tile's running max, sum and float32 accumulator in VMEM from its
first key block to its last, pages through scalar-prefetched indices, the mask
made in the cell) for GROUPED heads whose key is in ONE part, walked like
``lane_attention.head_walk`` walks a step (ONE flat work list, its length a
traced grid bound, a cell an item for ALL heads). The ``eva`` family's launch
calls it: every query attends, in ONE softmax, the exact keys of its own
aligned window of ``W`` positions and the summary rows of every earlier window.

``launch_list(...)``, once a launch and shared by its layers: the (tile, key
page) items that EXIST, tile after tile, a tile's items in order. A page is
``P`` rows, a row a position's KV heads side by side; a launch is ``K`` tiles
of ``T`` rows, a tile whole pages of one piece of one prompt inside one window
(``W % T == 0``), its rows at consecutive positions from ``qpos0``. A tile's
items are

(i)   the pages of its RING that hold a position of the tile's own window
      written BEFORE the launch: place ``r`` of the ring holds position ``w0 +
      r`` (``w0`` the window's first) where that is below the piece's ``start``,
      so pages ``ring0 .. ring0 + ceil((start - w0) / P) - 1`` of the pool,
      none where the piece begins its window or the tile lies past a window's
      edge that the launch itself crossed;
(ii)  the launch's OWN rows by page, of the tile's own piece and window, up to
      the tile's own last live page: the second operand, ``(C / P, P, KV x hd)``
      (they are not in the ring yet: the ring is written after the attention,
      because a launch that crosses a window's edge overwrites places its
      earlier tiles still read);
(iii) the SUMMARY pages of the windows before the tile's, through the
      block-table row (``rows[t, n]``, ``n < qpos0 // W``), the windows the
      launch itself closed among them.

A tile of no piece (``has`` False) gets ONE item, the pool's page 0 of which
it sees row 0, so that its rows are finite and nothing uninitialised reaches
the stream. Row ``i`` of an item's page stands at position ``at + i`` (a ring
page's at the place's position in the tile's window, an own page's at its
rows' own, a summary page's inside the window it sums up) and a query at
``qpos`` sees it where ``at + i <= min(qpos, hi)``: ``hi`` is ``start - 1`` for
the ring (what was written before the launch), the piece's last live position
for the own rows (a padded tail is no key), the position before the tile's
window for a summary page (every row). Built in XLA from a cumulative sum and
a search, padded to ``K x (W / P + pps)`` items (a window's ring and own pages
are at most ``W / P`` together); ``items`` (traced) is the list's length.
An operand's page index at an item that reads the OTHER operand is the one it
held last (``_held``): the pipeline fetches nothing for an index that stays.

``launch_walk(q, kp, vp, ko, vo, work)``: ``q`` (C, H, hd) the launch's
queries, ``kp``, ``vp`` (pages, P, KV x hd) the layer's pools AS THEY LIE (ISSUE
63: a position one row with its KV heads side by side), ``ko``, ``vo`` (C / P, P,
KV x hd) the launch's own rows seen as pages (the projections' output, no copy)
-> the normalised context (C, H, hd) float32. Grid (``items``,), sequential. A
cell holds its tile's (T, H x hd) queries (the output's block index is the tile
too, so a tile's context is written back when the tile changes), one page of K
and one of V of the operand the item reads, each ONE contiguous block (P, KV x
hd) of which a query head reads its KV head's columns ``[g hd, (g + 1) hd)``,
whole lane tiles (``lane_attention._head_kernel``'s ``part`` under ``kv=``), and
for each query head takes the scores of its rows over its KV head's keys in
bfloat16 with float32 accumulation, scales them in float32, masks them, carries
ONE running softmax in float32 across the three kinds of item, and adds
``p.astype(bfloat16) @ v`` to the tile's accumulator; a tile's last item
divides and writes. This is
``eva._tile``'s arithmetic term for term, which is the fallback in XLA: no
gathered page, no score over a page that holds no visible key, no mask or
concatenated block in device memory. The exact items come first, and each
holds a key every row of the tile sees or has seen (column 0 of a ring page
and of an earlier own page; of the tile's own pages, the rows see their own),
so a masked score's ``exp`` meets a finite max.

WHAT A CELL COSTS (v5e, on the chip, ISSUE 58, a tile of 128 rows x 32 heads
over a page of 128 keys): 2.6 us for its 2 MiB to arrive, 2.2 us of products,
and the softmax between them, which decides. Written a head at a time with the
row's max and sum each reduced ACROSS LANES a cell it took 7.7 us, 3.6 of them
the 1,024 reductions (without them 4.1; the mask, the exponent, the scale, the
second product each under 0.4). So: (a) THE SUM STAYS A LANE'S OWN: ``l`` is (rows,
P) float32, ``l = l alpha + p`` elementwise, reduced across lanes ONCE a tile
where its last item divides (the same float32 terms in another order): half the
reductions go; (b) two KV heads' rows go through the softmax as ONE block, so
the reductions and the exponents of one head fill the waits of the other (4 and
8 heads a block read the same): 4.7 us a cell together, after which dropping the
max's reduction too buys nothing. A cell of TWO tiles a page read (256 rows) took
9.2 us, the same a tile: the cell is bound by its arithmetic, not by its page.

Off the TPU ``interpret=True`` runs the same code in the Pallas interpreter
(tests); the family calls it on the TPU alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _held(reads: jax.Array, page: jax.Array) -> jax.Array:
    """An operand's page at every item: the item's own where it reads the
    operand, else the one read last (before any: the first to be read), so the
    index stays and nothing is fetched."""
    n = jnp.arange(reads.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(reads, n, -1))
    return page[jnp.where(last < 0, jnp.argmax(reads), last)]


def launch_list(has: jax.Array, qpos0: jax.Array, start: jax.Array, end: jax.Array,
                page0: jax.Array, ring0: jax.Array, rows: jax.Array, *, tile: int, page: int,
                window: int) -> dict:
    """By tile (K,): ``has``, ``qpos0`` its first position, ``start`` and
    ``end`` where its piece begins in this launch and ends, ``page0`` the
    launch's page that holds the piece's first row, ``ring0`` the pool page of
    its ring's place 0; ``rows`` (K, pps) the pool pages of its prompt's
    windows' summaries."""
    K, pps = rows.shape
    T, P, W = tile, page, window
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    qpos0, start, end, page0, ring0 = (i32(x) for x in (qpos0, start, end, page0, ring0))
    w0 = qpos0 // W * W
    n_ring = jnp.where(has, jnp.clip((start - w0 + P - 1) // P, 0, W // P), 0)
    own0 = page0 + jnp.maximum(w0 - start, 0) // P          # the window's first page of the launch
    own1 = jnp.minimum((jnp.arange(K, dtype=jnp.int32) + 1) * (T // P) - 1,
                       page0 + (end - 1 - start) // P)        # the tile's last page with a live row
    n_own = jnp.where(has, own1 - own0 + 1, 0)
    n_sum = jnp.where(has, jnp.minimum(qpos0 // W, pps), 0)
    total = jnp.where(has, n_ring + n_own + n_sum, 1)
    ends = jnp.cumsum(total)
    n = jnp.arange(K * (W // P + pps), dtype=jnp.int32)
    t = i32(jnp.minimum(jnp.searchsorted(ends, n, side="right", method="compare_all"), K - 1))
    i = jnp.maximum(n - (ends - total)[t], 0)               # the item among its tile's
    ring = has[t] & (i < n_ring[t])
    own = has[t] & ~ring & (i < (n_ring + n_own)[t])
    summ = has[t] & ~ring & ~own
    j = own0[t] + i - n_ring[t]                              # an own item's page of the launch
    s = jnp.clip(i - (n_ring + n_own)[t], 0, pps - 1)       # a summary item's window
    pool = jnp.where(ring, ring0[t] + i, jnp.where(summ, rows[t, s], 0))
    at = jnp.where(ring, w0[t] + i * P,
                   jnp.where(own, start[t] + (j - page0[t]) * P, jnp.where(summ, s * W, 0)))
    hi = jnp.where(ring, start[t] - 1,
                   jnp.where(own, end[t] - 1, jnp.where(summ, w0[t] - 1, 0)))
    return {"tile": t, "step": i32(i), "left": i32(total[t] - 1 - i), "own": i32(own),
            "pool": i32(_held(~own, pool)),
            "page": i32(_held(own, jnp.clip(j, 0, K * (T // P) - 1))),
            "at": i32(at), "hi": i32(hi), "qpos0": jnp.where(has, qpos0, 0),
            "items": i32(ends[-1])}


def _kernel(tile_ref, step_ref, left_ref, own_ref, pool_ref, page_ref, at_ref, hi_ref, qpos_ref,
            q_ref, kp_ref, vp_ref, ko_ref, vo_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float):
    del pool_ref, page_ref   # the index maps read them
    P, T, dt, hd = kp_ref.shape[0], q_ref.shape[0], q_ref.dtype, acc_ref.shape[1]
    H, kv = q_ref.shape[1] // hd, kp_ref.shape[1] // hd
    g = H // kv
    pair = 2 if kv % 2 == 0 else 1   # KV heads whose softmax goes as one block (below)
    n = pl.program_id(0)
    f32 = {"preferred_element_type": jnp.float32}
    nt = (((1,), (1,)), ((), ()))

    @pl.when(step_ref[n] == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # row i of the page stands at ``at + i``; a query sees it up to its own position and ``hi``
    row = jax.lax.broadcasted_iota(jnp.int32, (T, P), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, P), 1)
    see = at_ref[n] + col <= jnp.minimum(qpos_ref[tile_ref[n]] + row, hi_ref[n])

    def attend(k_ref, v_ref):
        def part(ref, h: int):   # KV head h of a page's rows, (P, hd): whole lane tiles
            return ref[:, h * hd:(h + 1) * hd]

        for h0 in range(0, kv, pair):
            heads = range(h0 * g, (h0 + pair) * g)   # query heads; head j's rows: j T .. j T + T - 1
            s = [jnp.where(see, jax.lax.dot_general(q_ref[:, j * hd:(j + 1) * hd],
                                                    part(k_ref, j // g), nt, **f32) * scale, NEG)
                 for j in heads]
            s = jnp.concatenate(s, axis=0) if len(s) > 1 else s[0]
            rows = slice(heads[0] * T, (heads[-1] + 1) * T)
            m_prev = m_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            pb = p.astype(dt)
            pv = [jnp.dot(pb[i * T:(i + 1) * T], part(v_ref, j // g), **f32)
                  for i, j in enumerate(heads)]
            acc_ref[rows] = acc_ref[rows] * alpha + (jnp.concatenate(pv, axis=0) if len(pv) > 1
                                                     else pv[0])
            m_ref[rows] = jnp.broadcast_to(m_new, (len(heads) * T, m_ref.shape[1]))
            l_ref[rows] = l_ref[rows] * alpha + p            # a lane's own sum (the module's text)

    pl.when(own_ref[n] == 0)(functools.partial(attend, kp_ref, vp_ref))
    pl.when(own_ref[n] != 0)(functools.partial(attend, ko_ref, vo_ref))

    @pl.when(left_ref[n] == 0)
    def _():
        for j in range(H):
            rows = slice(j * T, (j + 1) * T)
            total = jnp.sum(l_ref[rows], axis=-1, keepdims=True)
            o_ref[:, j * hd:(j + 1) * hd] = acc_ref[rows] / total


def fits(tile: int, page: int, window: int, heads: int, kv: int, hd: int, dtype) -> bool:
    """Shapes the kernel takes: bfloat16, a tile and a page whole 128-row
    tiles, a tile whole pages inside one window, a head's row whole lanes."""
    return dtype == jnp.bfloat16 and tile % 128 == 0 and page % 128 == 0 and tile % page == 0 \
        and window % tile == 0 and heads % kv == 0 and hd % 128 == 0


def launch_walk(q: jax.Array, kp: jax.Array, vp: jax.Array, ko: jax.Array, vo: jax.Array,
                work: dict, *, scale: float, interpret: bool = False) -> jax.Array:
    C, H, hd = q.shape
    n_pages, P, row = kp.shape
    kv = row // hd
    K = work["qpos0"].shape[0]
    T, g = C // K, H // kv
    by_tile = lambda n, tile, *_: (tile[n], 0)  # noqa: E731
    pool = lambda n, tile, step, left, own, pool, page, *_: (pool[n], 0, 0)  # noqa: E731
    launch = lambda n, tile, step, left, own, pool, page, *_: (page[n], 0, 0)  # noqa: E731
    item = jnp.dtype(q.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers), its scratch, and the
    # float32 values of a head's scores, weights and context
    vmem = 2 * (T * H * hd * (item + 4) + 4 * kv * P * hd * item) \
        + 4 * H * T * (hd + P + 128) + 4 * 2 * g * T * (3 * P + 2 * hd)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9, grid=(work["items"],),
            in_specs=[pl.BlockSpec((T, H * hd), by_tile)]
            + [pl.BlockSpec((None, P, row), pool)] * 2
            + [pl.BlockSpec((None, P, row), launch)] * 2,
            out_specs=pl.BlockSpec((T, H * hd), by_tile),
            scratch_shapes=[pltpu.VMEM((H * T, 128), jnp.float32),
                            pltpu.VMEM((H * T, P), jnp.float32),
                            pltpu.VMEM((H * T, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((C, H * hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret, name="launch_walk",
    )(work["tile"], work["step"], work["left"], work["own"],
      jnp.clip(work["pool"], 0, n_pages - 1), work["page"], work["at"], work["hi"],
      work["qpos0"], q.reshape(C, H * hd), kp, vp, ko, vo).reshape(C, H, hd)
