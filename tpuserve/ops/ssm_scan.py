"""The chunked Mamba-2 recurrence of one layer of one prefill launch in ONE kernel
call (ISSUE 67): what ``models/mixers.py`` ``Mamba2Mixer._scan_pieces`` computes in
plain ``jnp`` through a ``(tiles, heads, T, T)`` float32 table of decays, its copy
in the served type and three ``(tiles, heads, P, N)`` float32 arrays that a
``lax.scan`` stacks slice by slice, between a gather and a scatter of the pieces'
states, each a round trip through device memory, with a tile's quadratic form
held in fast memory, the state passed from tile to tile inside the call and the
slots' states read and written where they lie.

A launch is ``K`` tiles of ``T`` rows; a tile belongs to one PIECE (a slot's next
run of prompt tokens), a piece takes whole tiles in order. For a head h of group
g, with the tile's x (T, P), B and C (T, N) as the convolution and the SiLU give
them (the served type), its step ``delta`` (T,) and the running sum ``cum`` (T,) of
its log-decay ``-exp(A_log) delta`` down the tile (float32; a row that is not live
has ``delta = 0`` and adds nothing to ``cum``), and the state ``S`` (P, N) float32
the tile starts from::

    m[t, s] = exp(cum_t - cum_s) delta_s (C_t . B_s)      s <= t, 0 elsewhere
    y       = m x + exp(cum) (S C^T)^T + D x
    S'      = exp(cum_T) S + (x exp(cum_T - cum) delta)^T B

EVERY EXPONENT TAKEN IS <= 0 (``cum`` never rises). The three products take their
operands in the served type and accumulate in float32, as the plain form's
einsums do; ``cum``, the exponentials, the state and y are float32, and every sum
is made in the plain form's order (on the chip the two agree bit for bit). ``C .
B`` is made once a (tile, block of heads) in fast memory and shared by the
block's heads, which are of one group.

The grid is (blocks of ``HEADS_BLOCK`` heads: parallel; the launch's tiles:
arbitrary, in order). A block's states stay in scratch from tile to tile, as ONE
(heads x P, N) table, so that the state's two products are one product a block:
``C S^T`` for all its heads (T, heads x P) and ``(x to_end)^T B`` (heads x P, N).
THE STATES ARE READ AND WRITTEN IN THE SLOTS' OWN BLOCK (slots, H, P, N), which is
aliased to the result: ``begins``, ``at`` and ``alive`` are scalar-prefetched, the
block of the state a tile maps is its piece's SLOT's (``at``); a tile that opens
its piece (``begins``) takes what the slot holds (``STORED``) or zeros where the
piece opens its prompt (``ZEROS``), any other goes on from what the tile before
left (``GOES_ON``); every tile writes the state it ends with into its slot's
block, which goes back to device memory when the next piece's tiles begin. So a
slot is read once and written once a launch, a slot with no piece is not
touched, and no (K, H, P, N) copy of the pieces' states is gathered before the
call nor scattered after it. A tile with NO live row (``alive`` 0; such tiles
follow the live ones and map the last live tile's slot, so nothing moves for
them) does no product and leaves the state as it found it; its y rows, which
belong to no prompt, are zeros.

EVERY ARRAY IS READ WHERE XLA KEEPS IT: x, B and C out of the activated rows (K,
T, H P + 2 G N), the same array under three block maps, x a block of heads'
columns at a time (P = 64 is half a 128-lane register, so the heads of a
register, ``128 / P`` of them, are taken together: each one's table times the
register's columns, and a select by lane); ``delta`` and ``cum`` (K, T, H) whole,
the block's heads picked out of them as columns and as rows by two products with
a 0/1 matrix (exact at ``HIGHEST``: a float32 is the sum of its three bfloat16
parts); y is written with the ROWS on lanes, (H P, K T), which is how XLA keeps
the gate z that y meets next. Per layer and launch the call reads x, B, C,
``delta``, ``cum`` and a piece's state once and writes y and a piece's state once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS_BLOCK = 8   # heads a cell takes: a register's sublanes of the decays' rows
LANES = 128
GOES_ON, STORED, ZEROS = 0, 1, 2   # what a tile begins from: ``begins``
_EXACT = jax.lax.Precision.HIGHEST   # a product with a 0/1 matrix gives the float32 back


def heads_block(heads: int, groups: int) -> int:
    """Heads a cell takes: ``HEADS_BLOCK`` of one group, a whole group where it
    has fewer."""
    return min(HEADS_BLOCK, heads // groups)


def supported(tile: int, heads: int, head_dim: int, state: int, groups: int, held) -> bool:
    """Shapes the kernel takes: tiles of one or two 128-row tables, a state of
    whole 128-lane registers held in float32 (``held``: the slots' block's
    type), groups of whole blocks of eight heads whose columns are whole
    registers."""
    hb = heads_block(heads, groups)
    return tile in (128, 256) and state % LANES == 0 and heads % groups == 0 \
        and jnp.dtype(held) == jnp.float32 \
        and hb % 8 == 0 and (heads // groups) % hb == 0 and (hb * head_dim) % LANES == 0 \
        and (LANES % head_dim == 0 or head_dim % LANES == 0) and (heads * head_dim) % state == 0


def _mm_nt(x, y, precision=None):
    """x (m, c) and y (n, c) -> x y^T (m, n), float32."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _mm_tn(x, y):
    """x (t, m) and y (t, n) -> x^T y (m, n), float32."""
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _at(rows: int, cols: int):
    """(row index, column index) of a (rows, cols) table."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0),
            jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _by_lane(parts, width: int):
    """(T, 1) columns or (T, w) tables, one a head of a register's heads -> one
    (T, w) table whose lanes ``[i width, (i + 1) width)`` are part i's."""
    w = len(parts) * width
    out = jnp.broadcast_to(parts[0], (parts[0].shape[0], w))
    if len(parts) > 1:
        lane = _at(out.shape[0], w)[1]
        for i, part in enumerate(parts[1:], 1):
            out = jnp.where(lane // width == i, part, out)
    return out


def _cell(begins_ref, at_ref, alive_ref, x_ref, b_ref, c_ref, dl_ref, cum_ref, d_ref, held_ref,
          y_ref, end_ref, s_ref, *, hb: int, P: int):
    del at_ref                                                          # the index maps read it
    block, tile = pl.program_id(0), pl.program_id(1)
    T, H = dl_ref.shape[1:]
    per = max(1, min(hb, LANES // P))                                   # heads of a register
    w = per * P

    @pl.when(begins_ref[tile] == STORED)
    def _():
        s_ref[...] = held_ref[0]

    @pl.when(begins_ref[tile] == ZEROS)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(alive_ref[tile] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(alive_ref[tile] != 0)
    def _():
        # The block's heads out of (T, H), as columns (T, hb and zeros) and as rows (hb, T).
        head, lane = _at(H, LANES)
        pick = jnp.where(head == block * hb + lane, 1.0, 0.0)           # (H, a register): hb used
        lane, head = _at(hb, H)
        pick_t = jnp.where(head == block * hb + lane, 1.0, 0.0)         # (hb, H)
        cum, dl = cum_ref[0], dl_ref[0]
        cum_c, dl_c = (jnp.dot(a, pick, precision=_EXACT, preferred_element_type=jnp.float32)
                       for a in (cum, dl))
        cum_r, dl_r = _mm_nt(pick_t, cum, _EXACT), _mm_nt(pick_t, dl, _EXACT)
        last = cum_c[T - 1:T]                                           # (1, hb)
        so_far = jnp.exp(cum_c)                                         # e^cum: what is left of S
        to_end = jnp.exp(last - cum_c) * dl_c                           # a row's part in S'
        B, C = b_ref[0], c_ref[0]
        cb = _mm_nt(C, B)                                               # (T, T), the group's
        ys = _mm_nt(C, s_ref[...].astype(C.dtype))                      # (T, hb P): S C^T by head
        row, col = _at(T, T)
        causal = row >= col
        xg = []
        for first in range(0, hb, per):
            at, heads = slice(first * P, first * P + w), range(first, first + per)
            x = x_ref[0, :, at]                                         # (T, w): a register's heads
            parts = []
            for h in heads:
                m = jnp.exp(jnp.where(causal, cum_c[:, h:h + 1] - cum_r[h:h + 1], -jnp.inf)) \
                    * cb * dl_r[h:h + 1]
                parts.append(jnp.dot(m.astype(x.dtype), x, preferred_element_type=jnp.float32))
            y = _by_lane(parts, P) + _by_lane([so_far[:, h:h + 1] for h in heads], P) * ys[:, at]
            y_ref[at] = (y + d_ref[:, at] * x.astype(jnp.float32)).T
            xg.append((x * _by_lane([to_end[:, h:h + 1] for h in heads], P)).astype(x.dtype))
        add = _mm_tn(jnp.concatenate(xg, axis=1) if len(xg) > 1 else xg[0], B)   # (hb P, N)
        of, lane = _at(hb * P, LANES)                                   # a state row's head
        keep = jnp.sum(jnp.where(of // P == lane, jnp.exp(last), 0.0), axis=1, keepdims=True)
        s_ref[...] = keep * s_ref[...] + add

    end_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("head_dim", "state", "interpret"))
def ssm_scan(rows, delta, cum, D, ssm, begins, at, alive, *, head_dim: int, state: int,
             interpret: bool = False):
    """``rows`` (K, T, H P + 2 G N): a launch's packed rows by tile as the
    convolution and the SiLU give them, in the served type, x's columns by head,
    then B's and C's by group (the SAME array under three block maps: no copy of
    a part is made for the call); ``delta`` and ``cum`` (K, T, H) float32, the
    step and the running sum of the log-decay down each tile; ``D`` (H,)
    float32; ``ssm`` (slots, H, P, N) float32, the slots' block; by tile (K,):
    ``begins``, what it begins from (``GOES_ON``, ``STORED``, ``ZEROS``), ``at``,
    the slot whose state it maps (a tile's that goes on: the tile's before),
    ``alive``, whether it has a live row -> (y (K T, H, P) float32 by packed row,
    zeros in a tile that is not alive, written with the ROWS on lanes, (H P, K
    T), which is how XLA keeps the gate z it meets next: neither is copied; the
    slots' block, IN PLACE, each slot a tile maps holding the state after the
    last of them)."""
    (K, T, H), P, N = delta.shape, head_dim, state
    G = (rows.shape[2] - H * P) // (2 * N)
    if rows.shape[2] != H * P + 2 * G * N or H % G or (H * P) % N:
        raise ValueError(f"ssm_scan: rows of {rows.shape[2]} columns are not {H} heads of {P} "
                         f"and two parts of whole groups of {N} behind whole blocks of {N}")
    hb = heads_block(H, G)
    blocks, of_group = H // hb, H // G // hb                            # blocks: all, a group
    cols = pl.BlockSpec((1, T, hb * P), lambda j, t, *_: (t, 0, j))
    part = [pl.BlockSpec((1, T, N), lambda j, t, *_, first=first: (t, 0, first + j // of_group))
            for first in (H * P // N, H * P // N + G)]
    whole = pl.BlockSpec((1, T, H), lambda j, t, *_: (t, 0, 0))
    held = pl.BlockSpec((1, hb * P, N), lambda j, t, begins, at, alive: (at[t], j, 0))
    y, ssm = pl.pallas_call(
        functools.partial(_cell, hb=hb, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(blocks, K),
            in_specs=[cols, *part, whole, whole,
                      pl.BlockSpec((1, hb * P), lambda j, t, *_: (0, j)), held],
            out_specs=[pl.BlockSpec((hb * P, T), lambda j, t, *_: (j, t)), held],
            scratch_shapes=[pltpu.VMEM((hb * P, N), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((H * P, K * T), jnp.float32),
                   jax.ShapeDtypeStruct((ssm.shape[0], H * P, N), jnp.float32)],
        input_output_aliases={9: 1},   # the slots' block (after the prefetched scalars), in place
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(begins.astype(jnp.int32), at.astype(jnp.int32), alive.astype(jnp.int32),
      rows, rows, rows, delta, cum, jnp.repeat(D.astype(jnp.float32), P)[None],
      ssm.reshape(ssm.shape[0], H * P, N))
    return y.reshape(H, P, K * T).transpose(2, 0, 1), ssm.reshape(ssm.shape[0], H, P, N)
