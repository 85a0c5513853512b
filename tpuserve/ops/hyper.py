"""A residual of several streams with per-sublayer maps: manifold-constrained
hyper-connections (Xie et al., "mHC", arXiv:2512.24880, over Zhu et al.,
"Hyper-Connections", arXiv:2409.19606), as ISSUE 46 writes them out. The maps
in float32 whatever the served type; in plain XLA (``maps``, ``mix_in``,
``mix_out``: any backend, any type, any shape, and the tests' oracle) and as
two Pallas TPU kernel calls a sublayer (``enter``, ``leave``: ISSUE 47).

A token's stream is ``X`` in R^(n x d), held as ONE row of ``n d`` values
(stream ``j`` in columns ``[j d, (j + 1) d)``: whole 128-lane tiles at any
width that is a multiple of 128, where a middle axis of ``n`` = 4 would pad
every tile fourfold). A sublayer ``F`` with its own ``Phi`` (n d, 2 n + n^2),
scalars ``alpha`` (pre, post, res) and biases ``b_pre``, ``b_post`` (n,),
``b_res`` (n, n) computes, a token::

    vt          = vec(X) / sqrt(mean(vec(X)^2) + eps)        RMSNorm, no gain
    [p | q | r] = vt Phi                                     float32, HIGHEST
    H_pre  = sigmoid(alpha_pre p + b_pre)                    (n,)   in (0, 1)
    H_post = 2 sigmoid(alpha_post q + b_post)                (n,)   in (0, 2)
    M      = exp(clip(alpha_res mat(r) + b_res, lo, hi))     (n, n)
    iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps)
    H_res  = M                      doubly stochastic to the iteration's error
    u      = sum_j H_pre[j] X[j]                             float32, rounded
    y      = F(u)                                            (d,) float32
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y            float32, rounded

Rows of ``H_res`` index the OUTGOING stream. ``maps`` returns the three with
the tokens LAST, (n, T) and (n, n, T): the Sinkhorn iterations are then
elementwise passes over whole lane rows of tokens (sixteen rows of T), where
(T, n, n) would put a 4 x 4 block into every tile of 8 x 128.

THE KERNELS (one grid axis over tiles of ``ROW_TILE`` rows; a tile of whole
rows lies in fast memory from its read to its write). In XLA a sublayer cost
twelve passes' worth of the bfloat16 stream (a float32 normed copy written
for the product with ``Phi``, the six-pass product reading it, each mix
reading float32 slices again); the two calls read the stream twice and write
it once, ``u`` and ``y`` beside them.

``enter(x, hp, ...) -> (u, h)`` reads a tile ONCE, in the served type, and
from that one read makes: the rows' sums of squares (float32, on the vector
unit); ``x Phi`` on the matrix unit; the three maps; ``u``. ``h`` (T, 128)
float32 holds a token's maps side by side (``unpack`` says where), ``u`` is
``mix_in``'s. No float32 copy of the stream goes to device memory. Two
identities, both exact in what they keep:

- the norm's ``rsqrt`` is one scalar a token, so it multiplies ``p``, ``q``,
  ``r`` AFTER the product (``(s x) Phi = s (x Phi)``) and the stream is never
  scaled;
- the stream IS bfloat16, so a HIGHEST product, which splits each float32
  operand into three bfloat16 terms and sums six of the nine products in
  float32, has nothing to split on the stream's side: ``Phi``'s terms (three
  of a float32 ``Phi``, ONE of a bfloat16 one, whose others are zero) stacked
  as rows against the stream as it lies are every product there is, in one
  trip through the matrix unit with float32 accumulation.

The maps are made with the TOKENS ON THE LANES as ``maps`` orders them:
``Phi``'s columns lie as rows in groups of eight (``_layout``: ``H_pre`` and
``H_post`` in the first group, a row of ``H_res`` in each group after), so
every slab of the Sinkhorn is whole (8, 128) tiles and its column sums are
sums of slabs, its row sums a reduction over sublanes; true divisions,
``iters`` iterations. One transposition of a (128, 128) block then puts a
token's maps side by side in ITS row, from where a mix reads a map as a
column and spreads it over the lanes.

``leave(x, y, h) -> x'`` reads the tile and ``y`` once and writes ``mix_out``'s
``X'`` once. Both mixes sum in float32 in ``_weighted``'s order (``j``
ascending, then ``H_post y``) and round once, so given the same maps they are
``mix_in`` / ``mix_out`` bit for bit.

``fits`` says which launches the kernels take; the family asks it when a
program is traced. Off the TPU ``interpret=True`` runs the same code in the
Pallas interpreter (tests); the family calls the kernels on the TPU alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a grid cell holds from its read to its write: one lane tile of tokens for the
# product with Phi, the maps and their transposition. 128 / 256 / 512 read 0.276 /
# 0.285 / 0.297 ms an ``enter`` and 0.439 / 0.441 / 0.444 a ``leave`` at 4,096 rows
# of 4 x 3,584 (scripts/bench_hyper.py, my chip run, PR 47).
ROW_TILE = 128
# Rows a mix holds its maps spread over the lanes for: at 32, ``enter`` reads 0.276 ms
# where at 16 (one bfloat16 tile) it reads 0.308; ``leave`` is the same at both.
MIX_ROWS = 32
LANES = 128


def sinkhorn(m: jax.Array, iters: int, hc_eps: float) -> jax.Array:
    """``m`` (n, n, ...) positive: ``iters`` times columns then rows, each
    over its sum plus ``hc_eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)
    return m


def maps(x: jax.Array, hp: dict, n: int, eps: float, iters: int, hc_eps: float,
         clamp: tuple[float, float]):
    """The stream ``x`` (T, n d) in the served type and one sublayer's
    tensors ``hp`` (``phi``, ``alpha``, ``b_pre``, ``b_post``, ``b_res``) ->
    ``H_pre`` (n, T), ``H_post`` (n, T), ``H_res`` (n, n, T), float32."""
    f32 = jnp.float32
    v = x.astype(f32)
    vt = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    pqr = jnp.matmul(vt, hp["phi"].astype(f32), precision=jax.lax.Precision.HIGHEST).T
    a = hp["alpha"].astype(f32)
    h_pre = jax.nn.sigmoid(a[0] * pqr[:n] + hp["b_pre"].astype(f32)[:, None])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * pqr[n:2 * n] + hp["b_post"].astype(f32)[:, None])
    logits = a[2] * pqr[2 * n:].reshape(n, n, -1) + hp["b_res"].astype(f32)[:, :, None]
    return h_pre, h_post, sinkhorn(jnp.exp(jnp.clip(logits, *clamp)), iters, hc_eps)


def _weighted(x: jax.Array, w) -> jax.Array:
    """``sum_j w[j] X[j]`` in float32: ``x`` (T, n d), ``w`` n weights (T,) -> (T, d)."""
    d = x.shape[-1] // len(w)
    acc = None
    for j, wj in enumerate(w):
        term = wj[:, None] * x[:, j * d:(j + 1) * d].astype(jnp.float32)
        acc = term if acc is None else acc + term
    return acc


def mix_in(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """What the sublayer reads: ``u = sum_j H_pre[j] X[j]``, (T, n d) -> (T, d),
    a float32 sum rounded to the stream's type."""
    return _weighted(x, list(h_pre)).astype(x.dtype)


def mix_out(x: jax.Array, h_res: jax.Array, h_post: jax.Array, y: jax.Array) -> jax.Array:
    """What the sublayer leaves: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i]
    y`` with ``y`` (T, d) float32, a float32 sum rounded to the stream's type
    -> (T, n d)."""
    y = y.astype(jnp.float32)
    return jnp.concatenate([_weighted(x, list(h_res[i])) + h_post[i][:, None] * y
                            for i in range(h_post.shape[0])], axis=-1).astype(x.dtype)


# -- the two kernels ------------------------------------------------------------------------------

def _layout(n: int) -> tuple[int, int, int]:
    """Where a token's maps lie among the ``rows`` rows the product with
    ``Phi`` makes, and among the columns of ``h`` after the transposition:
    ``H_pre[j]`` at ``j``, ``H_post[i]`` at ``n + i``, ``H_res[i, j]`` at
    ``first + i * group + j`` -> (first, group, rows). Groups of whole
    sublane tiles of float32; ``rows`` whole tiles of bfloat16."""
    first, group = -(-2 * n // 8) * 8, -(-n // 8) * 8
    return first, group, -(-(first + n * group) // 16) * 16


def _places(n: int) -> list[int]:
    """The row of each of ``Phi``'s ``2 n + n^2`` columns, in its order."""
    first, group, _ = _layout(n)
    return [*range(2 * n), *(first + i * group + j for i in range(n) for j in range(n))]


def _in_groups(rows: jax.Array, n: int) -> jax.Array:
    """``rows`` (2 n + n^2, w), a row a column of ``Phi`` in its order, laid
    where ``_places`` says with zeros between: (``_layout``'s rows, w)."""
    first, group, m_rows = _layout(n)
    parts = [(rows[:2 * n], first), *((rows[2 * n + i * n:2 * n + (i + 1) * n], group)
                                      for i in range(n)), (rows[:0], m_rows - first - n * group)]
    return jnp.concatenate([jnp.pad(part, ((0, to - part.shape[0]), (0, 0))) for part, to in parts])


def fits(rows: int, n: int, d: int, dtype) -> bool:
    """Launches the kernels take: a bfloat16 stream (the product's identity
    needs it), every stream whole 128-lane tiles, whole row tiles, a token's
    maps within one lane tile."""
    return dtype == jnp.bfloat16 and d % LANES == 0 and rows % ROW_TILE == 0 \
        and _layout(n)[2] <= LANES


def _lane_blocks(d: int) -> int:
    """128-lane blocks a pass of a mix's inner loop takes: a divisor of ``d / 128``."""
    return next(k for k in (4, 2, 1) if (d // LANES) % k == 0)


def _spread(h_ref, rows, k: int):
    """Column ``k`` of the tokens' maps, over the lanes: (rows, 128)."""
    return jnp.broadcast_to(h_ref[rows, k:k + 1], (rows.size, LANES))


def _enter_kernel(x_ref, phi_ref, coef_ref, u_ref, h_ref, t_ref, *, n: int, d: int, terms: int,
                  eps: float, iters: int, hc_eps: float, clamp: tuple[float, float]):
    f32 = jnp.float32
    tm, nd = x_ref.shape
    first, group, m_rows = _layout(n)
    kb, mr = _lane_blocks(d), min(MIX_ROWS, tm)
    precision = jax.lax.Precision.HIGHEST if x_ref.dtype == f32 else None
    cw = kb * LANES

    # one read of the tile: x Phi's terms with the tokens on the lanes, and the squares
    def chunk(c, acc):
        pq, ss = acc
        at = pl.ds(pl.multiple_of(c * cw, cw), cw)
        xc = x_ref[:, at]
        pq = pq + jax.lax.dot_general(phi_ref[:, at], xc, (((1,), (1,)), ((), ())),
                                      precision=precision, preferred_element_type=f32)
        sq = jnp.square(xc.astype(f32))
        for k in range(kb):
            ss = ss + sq[:, k * LANES:(k + 1) * LANES]
        return pq, ss

    pq, ss = jax.lax.fori_loop(0, nd // cw, chunk, (jnp.zeros((terms * m_rows, tm), f32),
                                                    jnp.zeros((tm, LANES), f32)))
    pqr = pq[:m_rows]
    for k in range(1, terms):
        pqr = pqr + pq[k * m_rows:(k + 1) * m_rows]
    scale = jax.lax.rsqrt(jnp.sum(ss.T, axis=0, keepdims=True) / nd + eps)   # (1, tm)
    z = coef_ref[:, 0:1] * (pqr * scale) + coef_ref[:, 1:2]
    gate = 1.0 / (1.0 + jnp.exp(-z[:first]))
    at_row = jax.lax.broadcasted_iota(jnp.int32, (first, tm), 0)
    t_ref[:first, :] = jnp.where(at_row < n, gate, jnp.where(at_row < 2 * n, 2.0 * gate, 0.0))
    # H_res: slab i holds row i, its n columns in the slab's first n sublanes; the
    # sublanes under them hold zeros, over a column sum of 1 and never of hc_eps alone
    held = jax.lax.broadcasted_iota(jnp.int32, (group, tm), 0) < n
    m = [jnp.where(held, jnp.exp(jnp.clip(z[first + i * group:first + (i + 1) * group], *clamp)),
                   0.0) for i in range(n)]
    for _ in range(iters):
        col = m[0]
        for mi in m[1:]:
            col = col + mi
        col = jnp.where(held, col + hc_eps, 1.0)
        m = [mi / col for mi in m]
        m = [mi / (jnp.sum(mi, axis=0, keepdims=True) + hc_eps) for mi in m]
    for i, mi in enumerate(m):
        t_ref[first + i * group:first + (i + 1) * group, :] = mi
    t_ref[first + n * group:, :] = jnp.zeros((LANES - first - n * group, tm), f32)
    h_ref[...] = t_ref[...].T

    # u = sum_j H_pre[j] X[j], the maps of MIX_ROWS tokens spread over the lanes once
    def mix(b, carry):
        rr = pl.ds(pl.multiple_of(b * mr, mr), mr)
        w = [_spread(h_ref, rr, j) for j in range(n)]

        def lanes(c, carry):
            for k in range(kb):
                c0 = pl.multiple_of(c * cw + k * LANES, LANES)
                acc = None
                for j in range(n):
                    term = w[j] * x_ref[rr, pl.ds(j * d + c0, LANES)].astype(f32)
                    acc = term if acc is None else acc + term
                u_ref[rr, pl.ds(c0, LANES)] = acc.astype(u_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, d // cw, lanes, carry)

    jax.lax.fori_loop(0, tm // mr, mix, 0)


def _leave_kernel(x_ref, y_ref, h_ref, o_ref, *, n: int, d: int):
    f32 = jnp.float32
    tm = x_ref.shape[0]
    first, group, _ = _layout(n)
    kb, mr = _lane_blocks(d), min(MIX_ROWS, tm)
    cw = kb * LANES

    def mix(b, carry):
        rr = pl.ds(pl.multiple_of(b * mr, mr), mr)
        post = [_spread(h_ref, rr, n + i) for i in range(n)]
        res = [[_spread(h_ref, rr, first + i * group + j) for j in range(n)] for i in range(n)]

        def lanes(c, carry):
            for k in range(kb):
                c0 = pl.multiple_of(c * cw + k * LANES, LANES)
                xs = [x_ref[rr, pl.ds(j * d + c0, LANES)].astype(f32) for j in range(n)]
                y = y_ref[rr, pl.ds(c0, LANES)].astype(f32)
                for i in range(n):
                    acc = None
                    for j in range(n):
                        term = res[i][j] * xs[j]
                        acc = term if acc is None else acc + term
                    o_ref[rr, pl.ds(i * d + c0, LANES)] = (acc + post[i] * y).astype(o_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, d // cw, lanes, carry)

    jax.lax.fori_loop(0, tm // mr, mix, 0)


def _terms(phi: jax.Array, stream) -> list[jax.Array]:
    """``phi`` as the bfloat16 terms that sum to it, for a bfloat16 stream: one
    of a bfloat16 matrix, three of a float32 one (8 + 8 + 8 bits of its 24).
    Beside a float32 stream (the interpreter's: ``fits`` takes none) ``phi``
    itself, and the kernel's product is a HIGHEST one."""
    if stream == jnp.float32 or phi.dtype == jnp.bfloat16:
        return [phi.astype(stream)]
    rest, out = phi.astype(jnp.float32), []
    for _ in range(3):
        out.append(rest.astype(jnp.bfloat16))
        rest = rest - out[-1].astype(jnp.float32)
    return out


def enter(x: jax.Array, hp: dict, n: int, eps: float, iters: int, hc_eps: float,
          clamp: tuple[float, float], *, tile: int = ROW_TILE, interpret: bool = False):
    """The stream ``x`` (T, n d) and one sublayer's tensors ``hp`` ->
    ``u`` (T, d) in the stream's type (``mix_in``'s) and ``h`` (T, 128)
    float32, a token's maps side by side (``unpack``), for ``leave``."""
    T, nd = x.shape
    d = nd // n
    m_rows = _layout(n)[2]
    f32 = jnp.float32
    terms = _terms(hp["phi"], x.dtype)
    # Phi's columns as rows in their groups, a term under a term; and a row's alpha and bias
    phi = jnp.concatenate([_in_groups(t.T, n) for t in terms])
    a = hp["alpha"].astype(f32)
    alpha = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]), jnp.full((n * n,), a[2])])
    bias = jnp.concatenate([hp["b_pre"].astype(f32), hp["b_post"].astype(f32),
                            hp["b_res"].astype(f32).reshape(-1)])
    coef = jnp.pad(_in_groups(jnp.stack([alpha, bias], axis=1), n), ((0, 0), (0, LANES - 2)))
    whole = lambda i: (0, 0)  # noqa: E731
    by_rows = lambda i: (i, 0)  # noqa: E731
    item = jnp.dtype(x.dtype).itemsize
    # the cell's blocks twice (the pipeline's two buffers) and room for the compiler's own
    vmem = 2 * (item * (tile * (nd + d) + phi.shape[0] * nd) + 4 * LANES * (tile + m_rows)) \
        + (16 << 20)
    return pl.pallas_call(
        functools.partial(_enter_kernel, n=n, d=d, terms=len(terms), eps=eps, iters=iters,
                          hc_eps=hc_eps, clamp=clamp),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, nd), by_rows), pl.BlockSpec(phi.shape, whole),
                  pl.BlockSpec(coef.shape, whole)],
        out_specs=[pl.BlockSpec((tile, d), by_rows), pl.BlockSpec((tile, LANES), by_rows)],
        out_shape=[jax.ShapeDtypeStruct((T, d), x.dtype), jax.ShapeDtypeStruct((T, LANES), f32)],
        scratch_shapes=[pltpu.VMEM((LANES, tile), f32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret, name="hc_enter",
    )(x, phi, coef)


def leave(x: jax.Array, y: jax.Array, h: jax.Array, n: int, *, tile: int = ROW_TILE,
          interpret: bool = False) -> jax.Array:
    """The stream ``x`` (T, n d), the sublayer's ``y`` (T, d) and
    ``enter``'s ``h`` -> ``X'`` (T, n d) in the stream's type (``mix_out``'s)."""
    T, nd = x.shape
    d = nd // n
    by_rows = lambda i: (i, 0)  # noqa: E731
    vmem = 2 * tile * (2 * jnp.dtype(x.dtype).itemsize * nd + jnp.dtype(y.dtype).itemsize * d
                       + 4 * LANES) + (16 << 20)
    return pl.pallas_call(
        functools.partial(_leave_kernel, n=n, d=d),
        grid=(T // tile,),
        in_specs=[pl.BlockSpec((tile, nd), by_rows), pl.BlockSpec((tile, d), by_rows),
                  pl.BlockSpec((tile, LANES), by_rows)],
        out_specs=pl.BlockSpec((tile, nd), by_rows),
        out_shape=jax.ShapeDtypeStruct((T, nd), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret, name="hc_leave",
    )(x, y, h)


def unpack(h: jax.Array, n: int):
    """``enter``'s ``h`` (T, 128) as ``maps`` returns the three: ``H_pre`` (n,
    T), ``H_post`` (n, T), ``H_res`` (n, n, T)."""
    at = jnp.asarray(_places(n))
    rows = h[:, at].T
    return rows[:n], rows[n:2 * n], rows[2 * n:].reshape(n, n, -1)
