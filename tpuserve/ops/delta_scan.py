"""The chunked gated delta rule of one layer of one prefill launch in ONE kernel
call (ISSUE 54): what ``models/mixers.py`` ``DeltaMixer._delta_heads`` and
``_delta_chunks`` compute in some 140 device operations over float32 arrays of
(tiles, heads, T, D) and (tiles, heads, T, T), each a round trip through device
memory, with a tile's tables, inverse and products held in fast memory and the
state passed from tile to tile inside the call.

A launch is ``K`` tiles of ``T`` rows; a tile belongs to one PIECE (a slot's next
run of prompt tokens), a piece takes whole tiles in order. For a head, with what
the convolution gives for the tile's q, k and v (T, D) each (the kernel takes the
SiLU, then q to length 1 / sqrt(D) and k to length 1), its log-decay a channel
``g`` (T, D) <= 0 and its step ``beta`` (T,), both zero at a row that is not live,
and the state ``S`` (D, D) the tile starts from::

    G   = the running sum of g down the tile              e^G the decay so far
    kk  = sum_c k_t k_s e^(G_t - G_s)   s <  t            the two pair tables,
    qk  = sum_c q_t k_s e^(G_t - G_s)   s <= t            zero elsewhere
    [U0 | W] = (I + beta kk)^-1 beta [v | k e^G]          ONE inverse a tile
    U   = U0 - W S
    o   = (q e^G) S + qk U
    S'  = Diag(e^(G_T)) S + (k e^(G_T - G))^T U           what the next tile starts from

(``S' = keep S + add`` with ``keep = Diag(e^(G_T)) - Kd^T W`` and ``add = Kd^T U0``
of the module docstring there, regrouped round ``U``: neither is formed.) EVERY
EXPONENT TAKEN IS <= 0, as there: the pair tables are made by sub-blocks of
``SUB`` rows. The blocks ON the diagonal come from the differences themselves,
a DIAGONAL of the table at a time (``_diagonals``): with channels on sublanes and
rows on lanes, the rows rolled d lanes against themselves give every entry (s +
d, s) at once, the sum over channels is a sum of registers, and what comes out
is the blocks AS DIAGONALS (sub, T). A row block's part UNDER the diagonal comes
from the two factors ``e^(G_t - G_i)`` and ``e^(G_i - G_s)`` about its own first
row i, one product a row block (``_under``). The inverse is
``unit_lower_inverse``'s: the diagonal blocks by their finite series ``(I - a)(I
+ a^2)(I + a^4)(I + a^8)``, here on the diagonals themselves (a product of two
block-diagonal matrices is a few rolled multiplies of (sub, T) registers:
``_times``; on the matrix unit it would be a whole 128^3 product at six passes,
seven eighths of it zeros), then pairs of neighbours joined, ``inv - inv a21
inv`` (``_join``), until one block is left. State, tables, inverse and every
product are float32, the products at ``Precision.HIGHEST``.

The grid is (blocks of ``HEADS_BLOCK`` heads: parallel; the launch's tiles:
arbitrary, in order). A head's state stays in scratch from tile to tile: a tile
that OPENS its piece takes the piece's ``s0`` (``opens`` and ``piece`` are
scalar-prefetched: the block of ``s0`` and of ``s_end`` a tile maps is its
piece's), any other the state the tile before left; every tile writes the state
it ends with into its piece's block of ``s_end``, which goes back to device
memory when the next piece's tiles begin, so what is there is the state after
the piece's LAST tile. ``s0`` is aliased to ``s_end``: a piece of no tiles keeps
what it came with. EVERY ARRAY IS READ WHERE XLA KEEPS IT, so that no copy is
made for the call: the convolution's result (K, T, 3 H D) 128 lanes a head (the
same array under three block maps), g and o (K, T, H, D) a row of eight heads a
register, a head's rows read and written with a sublane stride. Per layer and
launch the call reads the convolved rows, g and beta once and a piece's state
once, and writes o and a piece's state once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16          # rows of a sub-block of the pair tables and of the inverse's diagonal
HEADS_BLOCK = 8   # heads a cell takes: a register's sublanes of g and o
_HI = {"precision": jax.lax.Precision.HIGHEST, "preferred_element_type": jnp.float32}


def supported(tile: int, heads: int, head_dim: int) -> bool:
    """Shapes the kernel takes: tiles of whole 128-lane tables whose ``SUB``-row
    blocks pair up to one (a power of two of them), heads of 128 channels, in
    blocks of ``HEADS_BLOCK`` (all of them where they are fewer)."""
    n = tile // SUB
    return tile % 128 == 0 and n & (n - 1) == 0 and head_dim == 128 \
        and heads % min(HEADS_BLOCK, heads) == 0


def _mm(x, y):
    return jnp.dot(x, y, **_HI)


def _mm_nt(x, y):
    """x (m, c) and y (n, c) -> x y^T (m, n)."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), **_HI)


def _mm_tn(x, y):
    """x (t, m) and y (t, n) -> x^T y (m, n)."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())), **_HI)


def _at(rows: int, cols: int):
    """(row index, column index) of a (rows, cols) table."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0),
            jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _turned(x):
    """A row (1, n) as a column (n, 1) or a column as a row: on a diagonal,
    summed the other way."""
    n = max(x.shape)
    row, col = _at(n, n)
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=int(x.shape[0] == 1), keepdims=True)


def _spread(stack):
    """Diagonals (sub, T), row d at lane s the entry (s + d, s), zero where the
    entry would leave its diagonal block -> the block-diagonal matrix (T, T)."""
    sub, T = stack.shape
    row, col = _at(T, T)
    out = jnp.zeros((T, T), jnp.float32)
    for d in range(sub):
        out = jnp.where(row - col == d, stack[d:d + 1], out)
    return out


def _diagonals(q, k, G, b, sub: int):
    """The pair tables ON the diagonal blocks of ``sub`` rows, a diagonal of the
    table at a time: q, k, G (T, D), b (T, 1) -> (``beta kk`` for s < t, ``qk`` for
    s <= t), each as diagonals (sub, T): row d at lane s is the entry (s + d, s),
    zero where s + d is past the block s is in. Channels lie on sublanes (the sum
    over them is a sum of registers); lane s of the rows rolled by d holds row s +
    d, so the exponent taken, ``G_(s+d) - G_s``, is <= 0 wherever the entry is
    kept (a lane that wrapped round the tile's end is not inside a block)."""
    T = G.shape[0]
    Gt, kt, qt = G.T, k.T, q.T                                          # (D, T)
    at, lane = _at(sub, T)
    bt = _turned(b)                                                     # (1, T)
    kk = jnp.zeros((sub, T), jnp.float32)
    qk = jnp.where(at == 0, jnp.sum(qt * kt, axis=0, keepdims=True), 0.0)
    for d in range(1, sub):
        e = jnp.exp(jnp.minimum(pltpu.roll(Gt, T - d, 1) - Gt, 0.0)) * kt
        kd, qd, bd = (pltpu.roll(x, T - d, 1) for x in (kt, qt, bt))
        kk = jnp.where(at == d, jnp.sum(kd * e, axis=0, keepdims=True) * bd, kk)
        qk = jnp.where(at == d, jnp.sum(qd * e, axis=0, keepdims=True), qk)
    inside = lane % sub + at < sub
    return jnp.where(inside, kk, 0.0), jnp.where(inside, qk, 0.0)


def _under(q, k, G, sub: int):
    """The pair tables UNDER the diagonal blocks, a row block against every
    column before it, from the two factors ``e^(G_t - G_i)`` and ``e^(G_i - G_s)``
    about the block's own first row i -> (kk, qk), each (T, T), zero elsewhere."""
    T = G.shape[0]
    kk, qk = [jnp.zeros((sub, T), jnp.float32)], [jnp.zeros((sub, T), jnp.float32)]
    for i in range(1, T // sub):
        rows = slice(i * sub, (i + 1) * sub)
        first = G[i * sub:i * sub + 1]                                  # (1, D)
        left = jnp.exp(G[rows] - first)
        right = k * jnp.exp(jnp.minimum(first - G, 0.0))                # (T, D)
        both = _mm_nt(jnp.concatenate([k[rows] * left, q[rows] * left], axis=0), right)
        kk.append(both[:sub])
        qk.append(both[sub:])
    row, col = _at(T, T)
    under = row // sub > col // sub
    return (jnp.where(under, jnp.concatenate(kk, axis=0), 0.0),
            jnp.where(under, jnp.concatenate(qk, axis=0), 0.0))


def _times(x, y, first: int):
    """The product of two block-diagonal matrices as diagonals (sub, T): entry
    (s + d, s) is the sum over e of x's (s + d, s + e) and y's (s + e, s);
    ``first``: y's first diagonal that is not zero."""
    sub, T = x.shape
    at, _ = _at(sub, T)
    out = jnp.zeros((sub, T), jnp.float32)
    for e in range(first, sub):
        moved = x if e == 0 else jnp.where(
            at >= e, pltpu.roll(pltpu.roll(x, T - e, 1), e, 0), 0.0)   # row d - e at lane s + e
        out = out + moved * y[e:e + 1]
    return out


def _block_inverses(a):
    """``(I + a)^-1`` of the diagonal blocks, ``a`` strictly lower as diagonals
    (sub, T), by their finite series ``(I - a)(I + a^2)(I + a^4)...`` -> diagonals."""
    sub, T = a.shape
    inv = jnp.where(_at(sub, T)[0] == 0, 1.0, 0.0) - a
    power, terms = a, 1
    while 2 * terms < sub:
        power = _times(power, power, terms)
        terms *= 2
        inv = inv + _times(inv, power, terms)
    return inv


def _join(inv, a, sub: int):
    """Pairs of neighbouring blocks joined until one is left: ``inv`` (T, T) the
    inverses of the diagonal blocks of ``sub`` rows where they lie, ``a`` (T, T)
    what lies under them: ``[[P, 0], [-Q a21 P, Q]]`` for every pair at once, a
    product of block-diagonal matrices being the blocks' products."""
    T = a.shape[0]
    row, col = _at(T, T)
    m = sub
    while m < T:
        a21 = jnp.where(((row // m) % 2 == 1) & (col // m == row // m - 1), a, 0.0)
        inv = inv - _mm(_mm(inv, a21), inv)
        m *= 2
    return inv


def _head(q, k, v, g, b, S):
    """One head of one tile (module docstring): q, k, v, g (T, D), b (T, 1),
    S (D, D) -> (o (T, D), the state the tile ends with)."""
    T = g.shape[0]
    row, col = _at(T, T)
    G = _mm(jnp.where(row >= col, 1.0, 0.0), g)                         # the running sum
    eG = jnp.exp(G)
    on_kk, on_qk = _diagonals(q, k, G, b, SUB)
    under_kk, under_qk = _under(q, k, G, SUB)
    inv = _join(_spread(_block_inverses(on_kk)), b * under_kk, SUB)
    qk = _spread(on_qk) + under_qk
    u0, w = _mm(inv, b * v), _mm(inv, b * (k * eG))
    u = u0 - _mm(w, S)
    o = _mm(q * eG, S) + _mm(qk, u)
    last = G[T - 1:T]                                                   # (1, D)
    new = _turned(jnp.exp(last)) * S + _mm_tn(k * jnp.exp(last - G), u)
    return o, new


def _unit(x, eps: float):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _cell(opens_ref, piece_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, end_ref,
          s_ref, *, hb: int, eps: float):
    del piece_ref                                                       # the index maps read it
    tile, D = pl.program_id(1), s_ref.shape[-1]

    @pl.when(opens_ref[tile] != 0)
    def _():
        s_ref[...] = s0_ref[0]

    def heads(pair, carry):
        # Two heads an iteration: one's products run beside the other's diagonals.
        for j in (pair * per + n for n in range(per)):
            at = pl.ds(pl.multiple_of(j * D, D), D)
            q, k, v = (jax.nn.silu(ref[0, :, at]) for ref in (q_ref, k_ref, v_ref))
            o, new = _head(_unit(q, eps) * D ** -0.5, _unit(k, eps), v, g_ref[0, :, j, :],
                           b_ref[0, j], s_ref[j])
            o_ref[0, :, j, :] = o
            s_ref[j] = new
            end_ref[0, j] = new
        return carry

    per = 2 if hb % 2 == 0 else 1
    jax.lax.fori_loop(0, hb // per, heads, 0)


@functools.partial(jax.jit, static_argnames=("l2_eps", "heads_block", "interpret"))
def delta_scan(conv, g, beta, s0, opens, piece, *, l2_eps: float, heads_block: int = HEADS_BLOCK,
               interpret: bool = False):
    """``conv`` (K, T, 3 H D) float32, what the convolution gives for a launch's
    packed rows by tile, q's channels then k's then v's, BEFORE the SiLU (the
    kernel takes it, then q to length 1 / sqrt(D) and k to length 1 with
    ``l2_eps`` under the root, as ``DeltaMixer._delta_heads`` does); ``g`` (K, T,
    H, D) and ``beta`` (K, T, H) float32; ``s0`` (K, H, D, D) float32 by PIECE;
    ``opens`` (K,) bool, a tile that opens its piece; ``piece`` (K,) the piece a
    tile is of -> (o (K, T, H, D) float32, by piece the state after its last
    tile (K, H, D, D); a piece of no tiles: its ``s0``). Every array is read
    where XLA keeps it: the convolved rows 128 lanes a head, g and o a row of
    eight heads a register (a head's rows are read and written sublane by
    sublane), so no copy of any is made for the call."""
    K, T, H, D = g.shape
    hb, blocks = min(heads_block, H), H // min(heads_block, H)
    part = [pl.BlockSpec((1, T, hb * D), lambda j, t, opens, piece, n=n: (t, 0, n * blocks + j))
            for n in range(3)]
    rows = pl.BlockSpec((1, T, hb, D), lambda j, t, opens, piece: (t, 0, j, 0))
    state = pl.BlockSpec((1, hb, D, D), lambda j, t, opens, piece: (piece[t], j, 0, 0))
    return pl.pallas_call(
        functools.partial(_cell, hb=hb, eps=l2_eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks, K),
            in_specs=[*part, rows,
                      pl.BlockSpec((1, hb, T, 1), lambda j, t, opens, piece: (t, j, 0, 0)),
                      state],
            out_specs=[rows, state],
            scratch_shapes=[pltpu.VMEM((hb, D, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((K, T, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        input_output_aliases={7: 1},   # s0 (after the prefetched scalars) is s_end, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(opens.astype(jnp.int32), piece.astype(jnp.int32), conv, conv, conv, g,
      beta.transpose(0, 2, 1)[..., None], s0)             # beta a column a head
