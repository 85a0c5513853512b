"""A decode step's latent attention, every live lane over its OWN key blocks,
as one Pallas TPU kernel call an attention (ISSUE 44): ``tile_attention``'s
sibling for tiles of one query a lane, in the ABSORBED form.

``work_list(last, bt, page, block_pages)``, once a step and shared by every
attention of it: the (lane, key block) items that exist, lane after lane, a
lane's blocks in order: ``last`` (B,) the last position a lane attends (0
for a lane that is not live: ONE item, its row's first block, of which it sees
key 0, so its output row is finite and nothing uninitialised reaches the
stream), ``bt`` (B, pps) the block table. Built in XLA from a cumulative sum
and a search: ``items`` (traced) is the list's length, the sum of the lanes'
own ``last // (block_pages x page) + 1``; ``lane``, ``block`` (N,) and
``pages`` (N x block_pages,) are padded to the most a table holds, N = B x
ceil(pps / block_pages). A page of an item that lies whole past the lane's
position reads page 0 (the engine's sentinel): a neighbour that does the same
fetches nothing.

``lane_walk(q_lat, q_rope, ckv, kr, work)``: ``q_lat`` (B, H, r) = ``q_nope
W_kb^T`` and ``q_rope`` (B, H, g x rope), the rotary part ``g`` times (below);
``ckv`` (pages, P, r) and ``kr`` (pages, P / g, g x rope) the layer's pools AS
THEY LIE -> the normalised latent context (B, H, r) in ``q_lat``'s type;
``ctx W_vb`` stays the caller's.

Grid (``items``,): ONE sequential axis over the list, its length a TRACED
grid bound, so no cell is launched, fetched or skipped for a block no lane
has (a cell costs about as much skipped as worked, and a padded (lanes,
longest) grid is mostly skipped cells). ``lane``, ``block``, ``pages`` and
``last`` arrive by scalar prefetch: a cell reads its item's ``block_pages``
pages straight from the pools through ``pages`` (the pools are passed once a
page of a block, each with its own index map) and the lane's queries through
``lane``; the output's block index is the lane too, so a lane's context is
written back when the lane changes. A lane's running max, sum and (H, r)
float32 accumulator stay in VMEM scratch from its first block (``block ==
0``) to its last (the one that holds ``last``), which divides and writes. A
page's latents are read ONCE and serve both products from fast memory: scores
``[q_lat | q_rope] . [c_kv | k_r]`` and context ``p . c_kv``. Products in
bfloat16 with float32 accumulation, the softmax in float32, ``p.astype(dtype)
@ c_kv``: ``mla._attend_tile``'s, which is the fallback in XLA. No lane's
gathered rows, scores or partial context exist in device memory.

THE ROTARY KEY's leaf holds ``g`` positions side by side in a row of 128
lanes, so position ``s`` of a page is part ``s % g`` of row ``s // g``. A cell
lays a page's leaf out a position a row with ``g`` strided stores into a
float32 scratch (rows ``a, a + g, ...`` take the leaf's rows with every part
but ``a`` zeroed: exact), and the query's rotary part repeated ``g`` times
then gives ``q_rope . k_r(s)`` as one contraction of 128 lanes. Nothing but
loads, selects and stores: ``tile_attention``'s 0/1 product put a second trip
through the matrix unit in front of the scores' product, a fifth of a cell's
time at a step's sizes (ISSUE 44, on the chip).

WHAT A CELL COSTS (v5e, on the chip, ISSUE 44): its parts add up, they do not
overlap: about 0.06 us an operand for the pipeline's bookkeeping whether the
cell works or not, 0.2 us for the two reductions across lanes, 0.13 us a page
for the three products at 64 query rows; the pages' copies hide behind them.
So the pages a cell (``block_pages``, the caller's) trade cells for rows past
a lane's end: 4 read fastest at contexts of hundreds, 8 and more at thousands.

ATTENTION BY HEAD walks the same list (ISSUE 49): ``head_walk(q_pass, q_turn,
kn, kr, v, work)`` is ``lane_walk``'s grid, work list and scalar prefetch for
GROUPED heads whose keys are wider than their values and lie in parts (or in
ONE part: the last paragraph but one). The
pools AS THEY LIE: ``kn`` (KV, pages, P, dn) the part of a key that passes the
rotary by, ``kr`` (KV / pack, pages, P, pack x dr) the part that turns, ``pack``
KV heads side by side in a row of 128 lanes (2 at the 64 columns that turn of
192), ``v`` (KV, pages, P, dv) the values, a pool of their own. ``q_pass`` (B, H,
dn) and ``q_turn`` (B, H, pack x dr), each query head's turning part in its OWN
KV head's place of the row and zeros in the others', so that its product with
the whole row is its product with its own head's key (``paged_lm._pad_queries``) ->
the
normalised context (B, H, dv). A cell is one (lane, key block) item for EVERY
KV head: it reads the block's pages of the three pools through ``pages``, all
heads of a page in one block, holds the lane's H query rows (H / KV a KV head:
query head h reads KV head h // (H / KV)), their running max, sum and (H, dv)
float32 accumulator in VMEM from the lane's first block to its last, and
writes the context once. Scores ``q_pass . kn + q_turn . kr`` a KV head, one
softmax pass over all H rows, context ``p . v`` a KV head. Nothing of a lane
exists in device memory but its pages.

THE SAME CELL READS A WINDOW LAYER'S RING IN PLACE (ISSUE 50). A slot's ring is
one page of ``W`` positions, a position a ROW with its KV heads side by side
(``kn`` (slots + 1, W, KV x dn), ``kr`` (slots + 1, W, KV x dr), ``v`` (slots +
1, W, KV x dv): a token is one row of each to write; pools that lie so are told
by their three dimensions and ``kv``). ``ring_work`` is the work list: ONE item
a lane, its ring's index for the page, ``min(pos, W - 1)`` for the last place
that holds a key; places lie in a ring in no order, which a softmax does not
mind, so nothing is rotated or copied. A cell reads a ring of each leaf as one
block and cuts a head's columns out of it on whole lane tiles (``part``). THE
SINK (``sink`` (H,) float32) is one more operand where there is one: ``exp(sink
- m)`` joins the sum where a lane's last block divides, and the numerator gets
nothing. QUERY GROUPS OF 8 ROWS (64 query heads on 8 KV heads) are no whole
sublane tile of bfloat16: there EVERY query row goes over each KV head's keys
and keeps its own head's scores, and the context sums each head's values under
that head's rows' weights alone (``_split`` says which from the shapes: groups
of 16 rows take the path they took, to the bit). On the chip (v5e, 384 lanes of
full rings, ``scripts/bench_head_walk.py --only ring``, ISSUE 50): 0.44 ms a
layer alone and 0.385 in the cell's step, against 0.38 for the same rings with
each group padded to 16 rows by the caller, 0.72 for a plain pass that reads
and writes the rings, and 1.97 for the gather and softmax in XLA.

A KEY IN ONE PART (ISSUE 56): ``head_walk(q, None, k, None, v, work)``. Where
``kr`` and ``q_turn`` are None the cell has no second key operand: it fetches
nothing for it and takes ONE product a KV head for the scores, ``q . k``; the
work list, the scalar prefetch, the lane's running max, sum and accumulator and
the last block's divide are the same cell (told from the operands when the call
is traced; with both parts the kernel lowers to the text it had). The ``eva``
family's step walks its virtual block table so (a lane's summary pages, then its
ring's pages, both in ONE pool by head): 32 query rows on 32 KV heads, one row a
KV head, so every row goes over each head's keys and keeps its own head's. A
(head, page) is then two products whose 128 x 128 key or value tile the matrix
unit holds as weights, 128 cycles each whatever the rows that pass: 2.2 us a
page of 128 rows over four units, behind the 2.6 us its 2 MiB take to arrive.
On the chip (v5e, 24 lanes at the cell's mix of contexts, 26,663 rows,
``scripts/bench_eva_walk.py``, ISSUE 56): 0.61 / 0.65 / 0.70 / 0.79 ms a layer at
1 / 2 / 4 / 8 pages a cell against a least of 0.53 (0.56 for the 220 pages read),
where jax's ``paged_attention`` (one query row a cell, a compute block read
whole) took 3.07 / 1.88 / 1.34 / 1.04.

Off the TPU ``interpret=True`` runs the same code in the Pallas interpreter
(tests); the families call it on the TPU alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def work_list(last: jax.Array, bt: jax.Array, page: int, block_pages: int) -> dict:
    B, pps = bt.shape
    kb, c = block_pages, block_pages * page
    nb = -(-pps // kb)
    last = jnp.clip(last, 0, pps * page - 1).astype(jnp.int32)
    need = last // c + 1
    ends = jnp.cumsum(need)
    n = jnp.arange(B * nb, dtype=jnp.int32)
    lane = jnp.minimum(jnp.searchsorted(ends, n, side="right", method="compare_all"),
                       B - 1).astype(jnp.int32)
    block = jnp.clip(n - (ends - need)[lane], 0, nb - 1).astype(jnp.int32)
    at = block[:, None] * kb + jnp.arange(kb, dtype=jnp.int32)[None, :]
    pages = jnp.pad(bt, ((0, 0), (0, nb * kb - pps)))[lane[:, None], at]
    pages = jnp.where(at * page <= last[lane][:, None], pages, 0)
    return {"lane": lane, "block": block, "pages": pages.reshape(-1).astype(jnp.int32),
            "last": last, "items": ends[-1].astype(jnp.int32)}


def _kernel(lane_ref, block_ref, pages_ref, last_ref, ql_ref, qr_ref, *refs, scale: float,
            kb: int, g: int, picked: bool = False):
    del pages_ref   # the index maps read it
    keep_ref, refs = (refs[0], refs[1:]) if picked else (None, refs)
    ckv_refs, kr_refs, o_ref = refs[:kb], refs[kb:2 * kb], refs[2 * kb]
    m_ref, l_ref, acc_ref, kx_ref = refs[2 * kb + 1:]
    P, dt = ckv_refs[0].shape[0], ql_ref.dtype
    h, lanes = qr_ref.shape
    n = pl.program_id(0)
    j, last = block_ref[n], last_ref[lane_ref[n]]
    f32 = {"preferred_element_type": jnp.float32}
    nt = (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)  # noqa: E731
    part = iota((P // g, lanes), 1) // (lanes // g)
    q_lat, q_rope = ql_ref[...], qr_ref[...]
    s = []
    for i in range(kb):
        k_r = kr_refs[i][...]
        if g > 1:   # a position a row: rows a, a + g, ... keep part a of the leaf's rows
            for a in range(g):
                kx_ref[pl.ds(a, P // g, stride=g), :] = jnp.where(
                    part == a, k_r.astype(jnp.float32), 0.0)
            k_r = kx_ref[...].astype(dt)
        s.append(jax.lax.dot_general(q_lat, ckv_refs[i][...], nt, **f32)
                 + jax.lax.dot_general(q_rope, k_r, nt, **f32))
    s = jnp.concatenate(s, axis=1) if kb > 1 else s[0]
    see = j * (kb * P) + iota(s.shape, 1) <= last
    if picked:   # and the lane picked the key
        see = see & (keep_ref[...] > 0)
    s = jnp.where(see, s * scale, NEG)
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    pv = sum(jnp.dot(p[:, i * P:(i + 1) * P].astype(dt), ckv_refs[i][...], **f32)
             for i in range(kb))
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                                  l_ref.shape)

    @pl.when((j + 1) * (kb * P) > last)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def fits(page: int, r: int, kr_lanes: int, dtype) -> bool:
    """Shapes the kernel takes: bfloat16, whole sublane tiles a page, the
    latent's and the rotary leaf's rows whole 128-lane tiles."""
    return dtype == jnp.bfloat16 and page % 16 == 0 and r % 128 == 0 and kr_lanes % 128 == 0


def lane_walk(q_lat: jax.Array, q_rope: jax.Array, ckv: jax.Array, kr: jax.Array, work: dict, *,
              scale: float, keep: jax.Array | None = None,
              interpret: bool = False) -> jax.Array:
    """``keep`` (B, key blocks x block_pages x P) float32, or None: ATTENTION
    OVER PICKS (ISSUE 62). A lane attends key ``s`` only where ``keep[lane, s] >
    0`` (and ``s <= last``): a cell reads its item's columns of the lane's row
    beside the pages. A lane whose keys so far are all left out carries weights
    of one until its first kept key, which scales them away (every lane keeps
    one somewhere). An operand only where there is one: without it the kernel
    is what it was."""
    b, h, r = q_lat.shape
    n_pages, P = ckv.shape[:2]
    lanes = kr.shape[2]
    g = P // kr.shape[1]
    kb = work["pages"].shape[0] // work["lane"].shape[0]
    pages = jnp.clip(work["pages"], 0, n_pages - 1)
    page = lambda i: lambda n, lane, block, pages, last: (pages[n * kb + i], 0, 0)  # noqa: E731
    by_lane = lambda n, lane, block, pages, last: (lane[n], 0, 0)  # noqa: E731
    item = jnp.dtype(q_lat.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers), its scratch, and
    # the float32 values of a block's scores and of its context
    vmem = 2 * item * (h * (2 * r + lanes) + kb * P * (r + lanes)) \
        + 4 * h * (r + 256) + 4 * (3 * h * kb * P + 2 * h * r + P * lanes)
    picked = keep is not None
    if picked:   # a lane's row as (1, columns): the block's rows are the whole dimension
        keep = keep.reshape(b, 1, -1)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, kb=kb, g=g, **({"picked": True} if picked else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(work["items"],),
            in_specs=[pl.BlockSpec((None, h, r), by_lane), pl.BlockSpec((None, h, lanes), by_lane)]
            + [pl.BlockSpec((None, 1, kb * P),
                            lambda n, lane, block, pages, last: (lane[n], 0, block[n]))] * picked
            + [pl.BlockSpec((None, P, r), page(i)) for i in range(kb)]
            + [pl.BlockSpec((None, P // g, lanes), page(i)) for i in range(kb)],
            out_specs=pl.BlockSpec((None, h, r), by_lane),
            scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32), pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, r), jnp.float32), pltpu.VMEM((P, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret, name="lane_walk",
    )(work["lane"], work["block"], pages, work["last"], q_lat, q_rope, *([keep] * picked),
      *([ckv] * kb), *([kr] * kb))


# -- attention by head: grouped queries, keys in two parts or one, values of their own -----

def _head_kernel(lane_ref, block_ref, pages_ref, last_ref, qn_ref, *refs, scale: float,
                 kb: int, kv: int, pack: int, split: bool, turns: bool, sunk: bool):
    del pages_ref   # the index maps read it
    qr_ref, refs = (refs[0], refs[1:]) if turns else (None, refs)
    kn_refs, refs = refs[:kb], refs[kb:]
    kr_refs, refs = (refs[:kb], refs[kb:]) if turns else ((), refs)
    v_refs, refs = refs[:kb], refs[kb:]
    sink_ref, refs = (refs[0], refs[1:]) if sunk else (None, refs)
    o_ref, m_ref, l_ref, acc_ref = refs
    P, dt = kn_refs[0].shape[-2], qn_ref.dtype
    g = qn_ref.shape[0] // kv
    n = pl.program_id(0)
    j, last = block_ref[n], last_ref[lane_ref[n]]
    f32 = {"preferred_element_type": jnp.float32}
    nt = (((1,), (1,)), ((), ()))

    def part(ref, h: int, like):
        """Head (or packed row of heads) ``h`` of a page's block, (P, width):
        a block by head is (heads, P, width); a block of rows with their
        heads side by side, (P, heads x width), is cut on whole lane tiles."""
        if len(ref.shape) == 3:
            return ref[h]
        width = like.shape[-1]
        return ref[:, h * width:(h + 1) * width]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pass, q_turn = qn_ref[...], qr_ref[...] if turns else None

    def passed(q, h: int, i: int):   # rows of the passing part over KV head h's keys of page i
        return jax.lax.dot_general(q, part(kn_refs[i], h, q_pass), nt, **f32)

    def turned(q, r: int, i: int):   # and of the turning part, over row r of ``pack`` heads
        return jax.lax.dot_general(q, part(kr_refs[i], r, q_turn), nt, **f32)

    if split:   # KV head h: its g query rows over its own keys of every page
        s = []
        for h in range(kv):
            rows = slice(h * g, (h + 1) * g)
            of_pages = [passed(q_pass[rows], h, i) + turned(q_turn[rows], h // pack, i) if turns
                        else passed(q_pass[rows], h, i) for i in range(kb)]
            s.append(jnp.concatenate(of_pages, axis=1) if kb > 1 else of_pages[0])
        s = jnp.concatenate(s, axis=0) if kv > 1 else s[0]            # (H, kb x P)
    else:       # EVERY query row over KV head h's keys, and a row keeps its own head's
        own = jax.lax.broadcasted_iota(jnp.int32, (kv * g, kb * P), 0) // g
        if turns:   # a row of ``pack`` heads once: ``q_turn``'s zeros
            rows_turned = [[turned(q_turn, r, i) for i in range(kb)] for r in range(kv // pack)]
        s = None
        for h in range(kv):
            of_pages = [passed(q_pass, h, i) + rows_turned[h // pack][i] if turns
                        else passed(q_pass, h, i) for i in range(kb)]
            s_h = jnp.concatenate(of_pages, axis=1) if kb > 1 else of_pages[0]
            s = s_h if s is None else jnp.where(own == h, s_h, s)
    at = j * (kb * P) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(at <= last, s * scale, NEG)
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if split:
        pv = [sum(jnp.dot(p[h * g:(h + 1) * g, i * P:(i + 1) * P].astype(dt),
                          part(v_refs[i], h, o_ref), **f32)
                  for i in range(kb)) for h in range(kv)]
    else:
        pv = [sum(jnp.dot(jnp.where(own[:, :P] == h, p[:, i * P:(i + 1) * P], 0.0).astype(dt),
                          part(v_refs[i], h, o_ref), **f32) for i in range(kb) for h in range(kv))]
    acc_ref[...] = acc_ref[...] * alpha + (jnp.concatenate(pv, axis=0) if len(pv) > 1 else pv[0])
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                                  l_ref.shape)

    @pl.when((j + 1) * (kb * P) > last)
    def _():
        acc, total = acc_ref[...], l_ref[:, :1]
        if sunk:   # one more term of the denominator, nothing of the numerator
            total = total + jnp.exp(sink_ref[:, :1] - m_ref[:, :1])
        o_ref[...] = (acc / total).astype(o_ref.dtype)


def _split(heads: int, kv: int) -> bool:
    """Whether a KV head's group of query rows is whole sublane tiles (16 rows
    of bfloat16), so that a cell takes each group's rows apart. Where it is
    not (8 rows a KV head), EVERY row goes over each KV head's keys and keeps
    its own head's scores: the matrix unit loads a block of keys once whatever
    the rows that pass it, so the rows of the other groups cost it little, and
    no slice cuts a tile."""
    return (heads // kv) % 16 == 0


def head_fits(page: int, heads: int, kv: int, dn: int, kr_lanes: int, dv: int, dtype) -> bool:
    """Shapes ``head_walk`` takes: bfloat16, whole sublane tiles a page and a
    KV head's group of query rows (``_split``) or else all the query rows
    together, every part's rows whole 128-lane tiles (``kr_lanes`` 0: a key in
    one part, ``dn`` the whole of it)."""
    return dtype == jnp.bfloat16 and page % 16 == 0 and heads % kv == 0 \
        and (_split(heads, kv) or heads % 16 == 0) \
        and dn % 128 == 0 and kr_lanes % 128 == 0 and dv % 128 == 0


def ring_work(ring: jax.Array, last: jax.Array) -> dict:
    """``work_list``'s dict for lanes that each read ONE block and no more: a
    window layer's ring, a page of ``W`` positions a slot. ``ring`` (B,) the
    page a lane reads (the sentinel for a lane that is not live), ``last`` (B,)
    the last place of it that holds a key (0: one key, a finite row). One item
    a lane, in the lanes' order; positions lie in a ring in no order, which a
    softmax does not mind."""
    b = ring.shape[0]
    lane = jnp.arange(b, dtype=jnp.int32)
    return {"lane": lane, "block": jnp.zeros_like(lane), "pages": ring.astype(jnp.int32),
            "last": last.astype(jnp.int32), "items": jnp.int32(b)}


def head_walk(q_pass: jax.Array, q_turn: jax.Array | None, kn: jax.Array, kr: jax.Array | None,
              v: jax.Array, work: dict, *, scale: float, kv: int | None = None,
              sink: jax.Array | None = None, interpret: bool = False) -> jax.Array:
    """``q_turn`` and ``kr`` None: A KEY IN ONE PART (``kn`` is the whole key,
    ``q_pass`` the whole query). The cell then has no second key operand,
    fetches nothing for it and takes one product a KV head for the scores; all
    else is the same cell. With both parts the kernel is what it was.

    ``sink`` (H,) float32, or None: a learned logit a query head that joins
    the softmax's denominator where a lane's last block divides, ``exp(sink -
    m)`` beside the keys' sum, and adds nothing to the context. An operand
    only where there is one: without it the kernel is what it was.

    ``kv``: the KV heads of pools that hold a position a ROW with its heads
    side by side, ``kn`` (pages, P, KV x dn), ``kr`` (pages, P, KV x dr), ``v``
    (pages, P, KV x dv): a window layer's rings, a page a slot, written a row
    a token. A cell reads a page of each as ONE block and cuts a head's
    columns out of it on whole lane tiles; all else is the same cell."""
    b, h, dn = q_pass.shape
    turns, flat = kr is not None, kn.ndim == 3
    lanes = q_turn.shape[2] if turns else 0
    if flat:
        (n_pages, P), dv = kn.shape[:2], v.shape[2] // kv
        pack = kv * lanes // kr.shape[2] if turns else 1
        blocks = [(None, P, x.shape[2]) for x in (kn, kr, v) if x is not None]
    else:
        (kv, n_pages, P), dv = kn.shape[:3], v.shape[3]
        pack = kv // kr.shape[0] if turns else 1
        blocks = [(kv, None, P, dn), (kv // pack, None, P, lanes), (kv, None, P, dv)]
        blocks = blocks if turns else blocks[::2]
    heads = () if flat else (0,)   # a block by head: every head of its page
    page = lambda i: lambda n, lane, block, pages, last: (*heads, pages[n * kb + i], 0, 0)  # noqa: E731
    kb = work["pages"].shape[0] // work["lane"].shape[0]
    pages = jnp.clip(work["pages"], 0, n_pages - 1)
    by_lane = lambda n, lane, block, pages, last: (lane[n], 0, 0)  # noqa: E731
    item = jnp.dtype(q_pass.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers), its scratch, and
    # the float32 values of a block's scores and of its context
    vmem = 2 * item * (h * (dn + lanes + dv) + kb * P * (kv * (dn + dv) + kv // pack * lanes)) \
        + 4 * h * (dv + 256) + 4 * (3 * h * kb * P + 2 * h * dv)
    sunk = sink is not None
    sinks = [jnp.broadcast_to(sink.astype(jnp.float32)[:, None], (h, 128))] if sunk else []
    return pl.pallas_call(
        functools.partial(_head_kernel, scale=scale, kb=kb, kv=kv, pack=pack,
                          split=_split(h, kv), turns=turns, sunk=sunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(work["items"],),
            in_specs=[pl.BlockSpec((None, h, dn), by_lane)]
            + [pl.BlockSpec((None, h, lanes), by_lane)] * turns
            + [pl.BlockSpec(block, page(i)) for block in blocks for i in range(kb)]
            + [pl.BlockSpec((h, 128), lambda n, lane, block, pages, last: (0, 0))] * sunk,
            out_specs=pl.BlockSpec((None, h, dv), by_lane),
            scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32), pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q_pass.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        interpret=interpret, name="head_walk",
    )(work["lane"], work["block"], pages, work["last"], q_pass, *([q_turn] * turns),
      *([kn] * kb), *([kr] * kb * turns), *([v] * kb), *sinks)
