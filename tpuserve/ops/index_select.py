"""The learned indexer of attention over picks (ISSUE 62): the index scores of
a prefill tile's rows or of a step's lanes over a block table, and the EXACT
``k`` largest a query.

THE SCORE of query ``t`` and key ``s``: ``I(t, s) = sum_j w_j(t) ReLU(qI_j(t) .
kI(s))``, ``j`` over the indexer's heads; ``qI`` (.., H, D) and ``w`` (.., H)
float32 are the query's, ``kI`` (D,) is ONE key a position for every head, and
is what the third page leaf keeps (``ik`` (pages, P, D)). Products in the served
type with float32 accumulation, the ReLU, the weights and the sum over heads in
float32.

THE PICKS, exact: a query keeps the ``k`` keys ``s <= t`` of largest score, all
of them where it has no more than ``k``. No sort: a float32's bits map to an
unsigned key of the same order (``sort_keys``; a key the query may not see: 0,
below every score), the ``k``-th largest key of a row is found by a search over
its 32 bits from the top, and the picks are the entries at or above it. Two
EQUAL scores about the threshold are both kept (a row may then keep more than
``k``: all the ReLUs zero on both keys, or bit-equal sums), the two zeros are
alike, a row under ``k`` visible keys keeps them all. ``jax.lax.top_k`` of 2,048
over 32k scores is a whole sort a row on the TPU. The search is made in two
places, to the same bit:

- IN FAST MEMORY, by the kernel that made the scores (``tile_scores``, ISSUE
  65: a prefill tile's). A row sub-tile's order keys stay in VMEM scratch from
  its first key block to its last (as int32 with the top bit turned, so that a
  SIGNED compare orders them), and the last cell searches the needed blocks ONE
  bit a pass: a pass reads nothing from device memory, so 32 passes of one
  compare, one select and one add an element (96 vector operations) are
  cheaper than two bits a pass (three compares an element a pass: 144); the
  compares add elementwise into (rows, 128) across the key blocks and the
  lanes are summed once a pass. It leaves the row's threshold AS A FLOAT32
  (``key_float``: -inf where the row saw fewer than ``k`` keys), and the walk
  keeps key ``s`` where ``score[s] >= threshold`` (``tile_attention.tile_walk``):
  nothing of XLA's runs over a tile's scores and no mask is made. On the chip
  at the cell's widths the search is 0.02 ms a (tile, key block) after 0.1 ms
  (``PERF.md`` section 6, PR 65), where the passes below were 1.8 ms a tile.
- OVER DEVICE MEMORY, in XLA (``kth_key``, ``picks``, ``thresholds``): the
  step's (sixteen lanes' rows: not worth a second kernel) and the exact
  fallback. There a pass is a read of the keys, so it takes TWO bits a pass: of
  the three candidates that set them, the largest that ``k`` entries or more
  are at or above stays (sixteen passes, three compare-and-counts each in one
  read). ``picks`` writes the float32 mask the walks in XLA and ``lane_walk``
  read; ``thresholds`` is the pair's second half where the scores are XLA's and
  the walk is the kernel.

ON THE TPU the scores are made by two Pallas kernels that read the ``ik`` pages
AS THEY LIE through the block table, each once. ``tile_scores``: grid (row
sub-tiles of ``ROWS``, the tile's key blocks), the key blocks innermost, their
number a TRACED bound (``tile_attention``'s grid); a cell lays its block's pages
side by side and takes a head at a time: one (ROWS, D) x (D, c) product, the
ReLU, the head's weight a row, into the (ROWS, c) float32 block it writes.
``lane_scores``: ``lane_attention``'s work list and grid, a cell an item: the
lane's (H, D) queries over the item's pages, the weighted sum over the heads'
rows, one (1, c) row of the lane's scores. Blocks no tile or lane needs are
never written and never searched: nothing past a query's own position counts.
Off the TPU (and in float32, or at shapes no kernel takes) ``scores_xla``
gathers the rows and takes the same sums: the exact fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 256   # query rows a cell of ``tile_scores`` holds
SEARCH_ROWS = 64   # of which the threshold's search takes so many at a time (registers)
TOP, LOW = -(1 << 31), (1 << 31) - 1   # an int32's top bit, and the 31 under it


def sort_keys(x: jax.Array) -> jax.Array:
    """float32 -> uint32 of the same order (negative: every bit turned; else
    the sign bit set; the two zeros alike); nothing finite maps to 0."""
    x = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0, jnp.float32(0), x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def kth_key(keys: jax.Array, k: int, need=None, block: int = 0) -> jax.Array:
    """``keys`` (R, L) uint32 -> (R,) each row's ``k``-th largest; 0 where a row
    has fewer than ``k`` entries above 0. Two bits a pass, from the top: of the
    three candidates that set them, the largest that ``k`` entries or more
    are at or above stays (sixteen passes, each ONE read of the keys for its
    three counts). ``need`` (traced) with ``block``: only the first ``need``
    blocks of ``block`` columns hold anything above 0, and only they are
    read."""
    rows = keys.shape[0]

    def count(cands):   # (R, 3) -> (R, 3): entries at or above each candidate
        def of(blk):   # three sums over the same rows: one fusion, one read
            return jnp.stack([jnp.sum(blk >= cands[:, j:j + 1], axis=1, dtype=jnp.int32)
                              for j in range(3)], axis=1)

        if need is None:
            return of(keys)
        return jax.lax.fori_loop(
            0, need, lambda j, n: n + of(jax.lax.dynamic_slice(keys, (0, j * block),
                                                               (rows, block))),
            jnp.zeros((rows, 3), jnp.int32))

    def two_bits(i, prefix):
        shift = (30 - 2 * i).astype(jnp.uint32)
        cands = prefix[:, None] | (jnp.arange(1, 4, dtype=jnp.uint32)[None, :] << shift)
        # the candidates ascend, so those that stay are a prefix of the three
        stay = jnp.sum(count(cands) >= k, axis=1).astype(jnp.uint32)
        return prefix | (stay << shift)

    return jax.lax.fori_loop(0, 16, two_bits, jnp.zeros((rows,), jnp.uint32))


def picks(scores: jax.Array, qpos: jax.Array, k: int, need=None, block: int = 0) -> jax.Array:
    """``scores`` (R, L) float32 of queries at positions ``qpos`` (R,) over key
    positions 0 .. L - 1 -> (R, L) float32, 1 where the query keeps the key
    (one of its ``k`` largest among ``s <= qpos``; all of them where it has no
    more than ``k``), else 0. ``need``, ``block``: ``kth_key``'s."""
    see = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] <= qpos[:, None]
    keys = jnp.where(see, sort_keys(scores), jnp.uint32(0))
    kth = kth_key(keys, k, need, block)
    return (see & (keys >= kth[:, None])).astype(jnp.float32)


def key_float(keys: jax.Array) -> jax.Array:
    """``sort_keys``' inverse, uint32 -> float32; key 0, which no score maps
    to, -> -inf (at or below every score)."""
    bits = jnp.where(keys >> 31 == 1, keys ^ jnp.uint32(1 << 31), ~keys)
    return jnp.where(keys == 0, -jnp.inf, jax.lax.bitcast_convert_type(bits, jnp.float32))


def thresholds(scores: jax.Array, qpos: jax.Array, k: int, need=None, block: int = 0) -> jax.Array:
    """``picks``' threshold as a float32 a row (R,): the query keeps key ``s <=
    qpos`` where ``scores[s] >= thresholds`` (a float32 compare orders as the
    keys do, the two zeros alike). What ``tile_scores`` finds in fast memory."""
    see = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] <= qpos[:, None]
    return key_float(kth_key(jnp.where(see, sort_keys(scores), jnp.uint32(0)), k, need, block))


def scores_xla(qi: jax.Array, w: jax.Array, ik: jax.Array, pages: jax.Array) -> jax.Array:
    """``qi`` (R, H, D), ``w`` (R, H) float32, the pool ``ik`` (pages, P, D) and
    ONE block-table row ``pages`` (n,) -> (R, n x P) float32: every row's scores
    over the row's pages, gathered. The exact fallback."""
    k = jnp.take(ik, pages, axis=0).reshape(-1, ik.shape[2]).astype(qi.dtype)
    s = jnp.einsum("rhd,cd->rhc", qi, k, preferred_element_type=jnp.float32)
    return jnp.sum(w.astype(jnp.float32)[:, :, None] * jax.nn.relu(s), axis=1)


def fits(page: int, width: int, heads: int, dtype) -> bool:
    """Shapes the two kernels take: bfloat16, whole sublane tiles a page and
    of the heads' rows, keys of whole 128-lane rows."""
    return dtype == jnp.bfloat16 and page % 16 == 0 and width % 128 == 0 and heads % 16 == 0


def _tile_kernel(pos0_ref, rows_ref, q_ref, w_ref, *refs, kb: int, k: int):
    del rows_ref   # the index maps read it
    ik_refs, o_ref, t_ref, k_ref, s_ref = refs[:kb], *refs[kb:]
    P = ik_refs[0].shape[0]
    tq, c = o_ref.shape
    ti, j = pl.program_id(0), pl.program_id(1)
    for i in range(kb):
        k_ref[i * P:(i + 1) * P, :] = ik_refs[i][...]
    o_ref[...] = jnp.zeros_like(o_ref)

    def head(h, carry):
        s = jax.lax.dot_general(q_ref[h], k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[...] += w_ref[h][:, :1] * jnp.maximum(s, 0.0)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], head, 0)

    # The block's ORDER KEYS stay in fast memory until the row's last block:
    # ``sort_keys``' with the top bit turned, so that a SIGNED compare orders
    # them (negative: the 31 low bits turned; the two zeros alike), and a key
    # the row may not see the least there is (``sort_keys``' 0).
    iota = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (tq, c), axis)  # noqa: E731
    x = o_ref[...]
    b = pltpu.bitcast(jnp.where(x == 0, 0.0, x), jnp.int32)
    see = j * c + iota(1) <= pos0_ref[0] + ti * tq + iota(0)
    s_ref[j] = jnp.where(see, jnp.where(b < 0, b ^ jnp.int32(LOW), b), jnp.int32(TOP))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        # each row's ``k``-th largest key by its bits from the top, ONE a
        # pass: a pass is a compare, a select and an add an element, the
        # compares summed elementwise into (rows, 128) across the key blocks
        # and over the lanes once. ``u`` holds the UNSIGNED key's bits
        # (``kth_key``'s prefix); blocks past this one are never read.
        rc, lw = min(SEARCH_ROWS, tq), 128 if c % 128 == 0 else c

        def chunk(r, carry):
            at = pl.ds(pl.multiple_of(r * rc, rc), rc)

            def bit(i, u):
                cand = u | jax.lax.shift_left(jnp.int32(1), 31 - i)
                least = cand ^ jnp.int32(TOP)   # the candidate in the signed order

                def block(jb, n):
                    for lane in range(0, c, lw):
                        n += (s_ref[jb, at, lane:lane + lw] >= least).astype(jnp.int32)
                    return n

                n = jax.lax.fori_loop(0, j + 1, block, jnp.zeros((rc, lw), jnp.int32))
                # under 2 ** 24, so the float32 sum over the lanes is exact
                n = jnp.sum(n.astype(jnp.float32), axis=1, keepdims=True)
                return jnp.where(n >= k, cand, u)

            u = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rc, lw), jnp.int32))
            # the key's float (``key_float``): no key reached ``k`` -> -inf
            least = pltpu.bitcast(jnp.where(u < 0, u ^ jnp.int32(TOP), ~u), jnp.float32)
            t_ref[at, :] = jnp.broadcast_to(jnp.where(u == 0, -jnp.inf, least)[:, :1], (rc, 128))
            return carry

        jax.lax.fori_loop(0, tq // rc, chunk, 0)


def tile_scores(qi: jax.Array, w: jax.Array, ik: jax.Array, rows: jax.Array, need: jax.Array,
                pos0: jax.Array, *, k: int, block_pages: int, interpret: bool = False):
    """A tile's scores and each row's threshold: ``qi`` (T, H, D), ``w`` (T, H)
    float32, ``rows`` the prompt's block-table row padded to whole key blocks
    of ``block_pages`` pages, ``need`` (traced) the key blocks the tile's last
    position needs, ``pos0`` (traced) the tile's first position (its rows'
    are consecutive) -> (T, key blocks x c) float32, of which the first
    ``need`` blocks are written, and (T, 128) float32, a row's value across the
    lanes: ``thresholds(scores, pos0 + arange(T), k)``, to the bit."""
    t, h, d = qi.shape
    pages, P = ik.shape[:2]
    kb, c = block_pages, block_pages * P
    tq, nb = min(ROWS, t), rows.shape[0] // kb
    rows = jnp.clip(rows, 0, pages - 1).astype(jnp.int32)
    by_rows = lambda ti, j, pos0, rows: (0, ti, 0)  # noqa: E731
    page = lambda i: lambda ti, j, pos0, rows: (rows[j * kb + i], 0, 0)  # noqa: E731
    item = jnp.dtype(qi.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers), the keys side by
    # side, the float32 values of a head's scores, and the sub-tile's order keys
    vmem = 2 * (h * tq * (item * d + 4 * 128) + item * c * d + 4 * tq * (c + 128)) \
        + item * c * d + 3 * 4 * tq * c + 4 * nb * tq * c
    return pl.pallas_call(
        functools.partial(_tile_kernel, kb=kb, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t // tq, need.astype(jnp.int32)),
            in_specs=[pl.BlockSpec((h, tq, d), by_rows), pl.BlockSpec((h, tq, 128), by_rows)]
            + [pl.BlockSpec((None, P, d), page(i)) for i in range(kb)],
            out_specs=[pl.BlockSpec((tq, c), lambda ti, j, pos0, rows: (ti, j)),
                       pl.BlockSpec((tq, 128), lambda ti, j, pos0, rows: (ti, 0))],
            scratch_shapes=[pltpu.VMEM((c, d), qi.dtype), pltpu.VMEM((nb, tq, c), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((t, nb * c), jnp.float32),
                   jax.ShapeDtypeStruct((t, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret, name="tile_scores",
    )(jnp.reshape(pos0, (1,)).astype(jnp.int32), rows, qi.transpose(1, 0, 2),
      jnp.broadcast_to(w.astype(jnp.float32).T[:, :, None], (h, t, 128)), *([ik] * kb))


def _lane_kernel(lane_ref, block_ref, pages_ref, last_ref, q_ref, w_ref, *refs, kb: int):
    del lane_ref, block_ref, pages_ref, last_ref   # the index maps read them
    ik_refs, o_ref = refs[:kb], refs[kb]
    P = ik_refs[0].shape[0]
    q, w = q_ref[...], w_ref[:, :1]
    for i in range(kb):
        s = jax.lax.dot_general(q, ik_refs[i][...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[:, i * P:(i + 1) * P] = jnp.sum(w * jnp.maximum(s, 0.0), axis=0, keepdims=True)


def lane_scores(qi: jax.Array, w: jax.Array, ik: jax.Array, work: dict, *,
                interpret: bool = False) -> jax.Array:
    """A step's scores: ``qi`` (B, H, D), ``w`` (B, H) float32, ``work`` the
    step's work list (``lane_attention.work_list``: each lane's own key blocks)
    -> (B, key blocks x c) float32, of which each lane's own blocks are
    written."""
    b, h, d = qi.shape
    n_pages, P = ik.shape[:2]
    kb = work["pages"].shape[0] // work["lane"].shape[0]
    nb = work["lane"].shape[0] // b
    pages = jnp.clip(work["pages"], 0, n_pages - 1)
    by_lane = lambda n, lane, block, pages, last: (lane[n], 0, 0)  # noqa: E731
    page = lambda i: lambda n, lane, block, pages, last: (pages[n * kb + i], 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_lane_kernel, kb=kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(work["items"],),
            in_specs=[pl.BlockSpec((None, h, d), by_lane), pl.BlockSpec((None, h, 128), by_lane)]
            + [pl.BlockSpec((None, P, d), page(i)) for i in range(kb)],
            out_specs=pl.BlockSpec((None, 1, kb * P),
                                   lambda n, lane, block, pages, last: (lane[n], 0, block[n]))),
        out_shape=jax.ShapeDtypeStruct((b, 1, nb * kb * P), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret, name="lane_scores",
    )(work["lane"], work["block"], pages, work["last"], qi,
      jnp.broadcast_to(w.astype(jnp.float32)[:, :, None], (b, h, 128)), *([ik] * kb)
      ).reshape(b, nb * kb * P)
