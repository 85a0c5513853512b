"""Two expert layers. First a Switch-style mixture-of-experts FFN with expert
parallelism (EP), below; then, from ``topk_route`` on, the top-k layer of a
published sparse decoder of which one chip holds a share (``held_experts``:
held, absent and zero-compute picks; its own header comment further down).

Expert parallelism is the last axis in SURVEY.md §2.1's strategy table;
none of the judged configs is an MoE, so it was scoped out of v1 — this
module makes the seam real. Design is TPU-first throughout:

- **Everything static.** Top-1 (Switch) routing with a fixed per-expert
  capacity: dispatch and combine are dense one-hot tensors, the expert
  compute is three einsums — no gather/scatter, no dynamic shapes, all MXU
  work. Tokens past an expert's capacity are dropped (contribute zero; the
  caller's residual connection passes them through), the standard Switch
  trade.
- **Group-wise routing.** Each batch row routes independently with capacity
  ``C = ceil(S/E * capacity_factor)``, so the (group, S, E, C) routing
  tensors stay LINEAR in total tokens (a single global routing pool would
  be quadratic and OOM at real sequence lengths).
- **Padding-aware.** Masked tokens never claim expert capacity and don't
  drive the load-balancing aux loss — otherwise pad tokens evict real ones
  first-come-first-served and the router trains on garbage embeddings.
- **EP via shardings, not hand-written collectives.** The expert dim of the
  expert buffers and the ``(E, D, F)`` weights shards over the mesh's
  "model" axis (see ``tpuserve.train.TRAIN_PARTITION_RULES``); XLA lowers
  the dispatch/combine einsums to the token all-to-alls over ICI. The op
  stays a pure function — the same code runs 1-device and expert-parallel.

Reference: Switch Transformer (Fedus et al. 2021) routing math, re-derived
for the static-shape formulation.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


def switch_route(logits: jax.Array, capacity: int,
                 token_mask: jax.Array | None = None):
    """Top-1 routing of ONE group -> static (T, E, C) dispatch/combine.

    ``token_mask`` (T,): 0-tokens (padding) never claim capacity and are
    excluded from the aux statistics. Returns (dispatch, combine, aux):
    ``dispatch`` is 0/1 routing of token t to (expert e, queue slot c);
    ``combine`` additionally carries the gate probability; ``aux`` is the
    load-balancing loss (fraction-routed x gate mass per expert, scaled by
    E — Switch eq. 4).
    """
    n_experts = logits.shape[-1]
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    expert = jnp.argmax(gates, axis=-1)                          # (T,)
    gate = jnp.max(gates, axis=-1)                               # (T,)
    onehot = jax.nn.one_hot(expert, n_experts, dtype=gates.dtype)
    if token_mask is None:
        token_mask = jnp.ones(logits.shape[0], gates.dtype)
    token_mask = token_mask.astype(gates.dtype)
    onehot = onehot * token_mask[:, None]
    # Position of each token in its expert's queue, -1 where unrouted.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    pos = jnp.max(pos, axis=-1).astype(jnp.int32)                # (T,)
    keep = pos >= 0
    keep &= pos < capacity
    dispatch = (onehot * keep[:, None])[..., None] * jax.nn.one_hot(
        jnp.clip(pos, 0, capacity - 1), capacity, dtype=gates.dtype)[:, None, :]
    combine = dispatch * gate[:, None, None]
    # Load-balance aux over REAL tokens only (differentiable via the gates).
    n_real = jnp.maximum(token_mask.sum(), 1.0)
    frac_routed = onehot.sum(axis=0) / n_real
    gate_mass = (gates * token_mask[:, None]).sum(axis=0) / n_real
    aux = n_experts * jnp.sum(frac_routed * gate_mass)
    return dispatch, combine, aux


class SwitchFFN(nn.Module):
    """Drop-in MoE replacement for a transformer FFN block.

    Expert weights carry a leading (E, ...) dim; shard it on "model" for
    expert parallelism. bf16-safe: routing softmax/argmax in f32.
    """

    experts: int
    d_ff: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array,
                 mask: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
        b, s, d = x.shape
        # Per-group (batch-row) routing keeps the (b, s, E, C) routing
        # tensors linear in total tokens.
        capacity = int(math.ceil(s / self.experts * self.capacity_factor))
        router = self.param("router", nn.initializers.normal(0.02),
                            (d, self.experts))
        w_up = self.param("w_up", nn.initializers.normal(0.02),
                          (self.experts, d, self.d_ff))
        w_down = self.param("w_down", nn.initializers.normal(0.02),
                            (self.experts, self.d_ff, d))
        logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                            router.astype(jnp.float32))
        if mask is None:
            mask = jnp.ones((b, s), jnp.float32)
        dispatch, combine, aux = jax.vmap(
            lambda lg, mg: switch_route(lg, capacity, mg))(logits, mask)
        dispatch = dispatch.astype(self.dtype)   # (g, s, E, C)
        combine = combine.astype(self.dtype)
        xe = jnp.einsum("gsec,gsd->gecd", dispatch, x.astype(self.dtype))
        h = nn.gelu(jnp.einsum("gecd,edf->gecf", xe, w_up.astype(self.dtype)))
        ye = jnp.einsum("gecf,efd->gecd", h, w_down.astype(self.dtype))
        y = jnp.einsum("gsec,gecd->gsd", combine, ye)
        # Token-weighted aux: fully/mostly padded rows must not dilute the
        # balance pressure.
        n_real = mask.astype(jnp.float32).sum(axis=1)
        aux = jnp.sum(aux * n_real) / jnp.maximum(jnp.sum(n_real), 1.0)
        return y.astype(x.dtype), aux


# -- top-k routing over a share of the experts --------------------------------
# The other expert layer of this module (ISSUE 28): what a published sparse
# decoder runs, held by one chip of several. The router keeps its published
# width and scores EVERY output; this chip holds experts
# [first, first + count). A live token's pick is one of THREE kinds (ISSUE 42):
#
# - HELD: an expert this chip holds. Its row goes through the expert's
#   products here and adds weight x expert(token).
# - ABSENT: a real expert another chip holds. It adds nothing here (the chip
#   that holds it adds its part in a deployment, through an exchange this
#   module does not have: on one chip the layer runs without it).
# - ZERO-COMPUTE: where the caller says how many of the router's outputs are
#   real experts (``real``), a pick at ``real`` or above is an identity: it has
#   no weights, enters no gather and no product, and adds weight x token. Every chip of the layer computes that term alike for the tokens it
#   has (it counts once), so it is neither held nor absent. Its key sorts with
#   the absent picks', and its weights are summed a token under the scope
#   ``moe_zero``.
#
# No capacity, no dropped token: the held picks are sorted by expert and go
# through a grouped product (``_grouped_dot``) in groups of whatever size the
# router gave.
#
# Three scopes name the layer's operations in a device trace: ``moe_route``
# (``topk_route``: scores, the k largest, their weights), ``moe_dispatch``
# (what carries rows to the products and back, and the weighted sum) and
# ``moe_experts`` (the grouped products and the body); ``moe_zero`` where there
# are zero-compute picks. The layer takes nothing by a gather of scalars and
# checks no index it made itself (ISSUE 61): the picks' weights are read by
# comparison, and both takes of rows are the gather alone.
#
# Two branches bring the sorted picks through the products (ISSUE 37), chosen
# on the device by the launch's own count of held picks. ``wide`` carries all
# ``t * k`` picks, held or not, as this layer always did. ``compact`` carries
# the first ``R`` of the sorted picks, a static bound (``_row_bound``) sized
# from the share of the router's width that is held: the held picks sort
# first, so where they number at most ``R`` they are all among those rows.
# Both hand the SAME ``(k * t, D)`` block of rows in pick order (a held pick's
# row is its product's, bit for bit, however many rows went with it) to ONE
# weighted sum after the ``cond``, so a program's ``y`` is bit-identical
# whichever branch ran: which one runs depends on who else is in the launch,
# a request's tokens do not. Where every expert is held, or ``R`` would not
# be below ``t * k``, there is one branch and no ``cond``: the program this
# layer always traced to (ANOTHER program: its sum over k may associate
# otherwise, which on the chip moved the last bit of 2% of a test's sums).

# The compact branch's rows over the EXPECTED held picks of a launch
# (``t * k * count / of``). Were picks independent, a launch's held picks
# would stand within a few hundredths of their mean (Nemotron's prefill
# launch: 22,528 picks, 5,632 held, sd 65; its step 5,632 / 1,408 / 33;
# Laguna's 10,240 / 5,120 / 51 and 1,280 / 640 / 18), so 1.25 and the tile's
# rounding leave 11 and 14 sd over the two steps' means and over 20 over the
# launches' (bounds 7,040 / 1,792 / 6,400 / 896: whole row tiles of 128, what
# ``_row_tile`` gives at 5-44 rows an expert). Real routers are skewed
# and correlated across a prompt's tokens: a launch that exceeds the bound
# takes ``wide`` and loses nothing but time, and ``stats["compact"]`` says how
# often. A larger slack gives rows back (each costs its gather, its convert
# and its way back); the bound is rounded up to a row tile anyway, which at
# these sizes adds 0-12%.
COMPACT_SLACK = 1.25


def _kept_groups(by: jax.Array, n_group: int, topk_group: int) -> jax.Array:
    """``by`` (T, E), what the picks go by -> ``by`` with every entry outside
    the token's ``topk_group`` best of ``n_group`` equal groups of neighbouring
    outputs at -inf. A group's score is the sum of its TWO largest entries,
    both by maxima (the second: the largest with the first's place left out,
    so two equal entries count twice); a group stays where fewer than
    ``topk_group`` groups beat it (a higher score, or the same at a lower
    number: ``top_k``'s order), counted by comparison over the n_group^2
    pairs. No sort and no gather."""
    t, e = by.shape
    g = by.reshape(t, n_group, e // n_group)
    first = jnp.max(g, axis=-1, keepdims=True)
    place = jnp.arange(e // n_group, dtype=jnp.int32)
    at = jnp.min(jnp.where(g == first, place, e), axis=-1, keepdims=True)
    score = first[..., 0] + jnp.max(jnp.where(place == at, -jnp.inf, g), axis=-1)   # (T, G)
    number = jnp.arange(n_group, dtype=jnp.int32)
    beats = (score[:, None, :] > score[:, :, None]) \
        | ((score[:, None, :] == score[:, :, None]) & (number[None, :] < number[:, None]))
    kept = jnp.sum(beats, axis=-1, dtype=jnp.int32) < topk_group
    return jnp.where(kept[..., None], g, -jnp.inf).reshape(t, e)


def router_logits(u: jax.Array, w: jax.Array) -> jax.Array:
    """The router's product of the rows ``u`` (T, D) with ``w`` (D, E), both as
    float32 at the highest precision (a pick is a comparison of two of them),
    under the scope ``topk_route`` has."""
    with jax.named_scope("moe_route"):
        return jnp.matmul(u.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)


def topk_route(logits: jax.Array, k: int, *, normalize: bool = True,
               scale: float = 1.0, scoring: str = "softmax",
               select_bias: "jax.Array | None" = None, eps: float = 0.0,
               groups: "tuple[int, int] | None" = None) -> tuple[jax.Array, jax.Array]:
    """(T, E) router logits -> (weights (T, k) float32, experts (T, k)
    int32): scores over all E in float32 (``scoring``: a ``softmax``, or a
    ``sigmoid`` of each logit alone), the k largest, their weights over the
    k's own sum (plus ``eps``, where a model's published block adds one to
    the denominator) where ``normalize``, times ``scale``. With ``select_bias``
    (E,) the k are picked by score plus bias and weighted by the score
    alone: the bias moves picks, never weights. With ``groups`` = (n_group,
    topk_group) the picks are GROUP-LIMITED (ISSUE 62): the outputs lie in
    ``n_group`` equal groups of neighbours, a group's score is the sum of the
    two largest of what the picks go by (score plus bias) in it, and the k are
    the largest among the ``topk_group`` best groups' outputs
    (``_kept_groups``). Without ``groups``: the program this function always
    traced to."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    if groups is not None and (select_bias is None or logits.shape[-1] % groups[0]
                               or k > groups[1] * (logits.shape[-1] // groups[0])):
        raise ValueError(f"groups {groups!r} of {logits.shape[-1]} outputs for {k} picks, "
                         "by score plus bias")
    with jax.named_scope("moe_route"):
        x = logits.astype(jnp.float32)
        p = jax.nn.softmax(x, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(x)
        if select_bias is None:
            w, e = jax.lax.top_k(p, k)
        else:
            by = p + select_bias.astype(jnp.float32)
            if groups is not None:
                by = _kept_groups(by, *groups)
            _, e = jax.lax.top_k(by, k)
            # The picks' scores by comparison, not by a gather of scalars: the
            # chip takes those one after another (16 ns each: 1.77 ms of
            # Nemotron's launch, my chip runs, PR 37). ONE entry of a row is at
            # a pick, so the maximum over the row of (the score there, nothing
            # elsewhere) is ``p[t, e[t, j]]`` to the bit. A maximum and not a
            # sum of (the score there, zero elsewhere), which is as exact alone:
            # XLA merges that sum over E with the sum over k below into one
            # over both, whose order moved a total's last bit (ISSUE 61).
            at = e[..., None] == jnp.arange(p.shape[-1], dtype=e.dtype)
            w = jnp.max(jnp.where(at, p[..., None, :], -jnp.inf), axis=-1)
        if normalize:
            total = jnp.sum(w, axis=-1, keepdims=True)
            w = w / (total + jnp.float32(eps) if eps else total)
        return w * jnp.float32(scale), e.astype(jnp.int32)


def _tile(n: int) -> int:
    """The grouped product's tile of a kernel dimension ``n`` where it has to be
    cut: the largest multiple of 128, at most 1024, that divides it (1024 ->
    1024, 3072 -> 1024, 2688 -> 896); 0 where none does."""
    return next((t for t in range(min(1024, n) // 128 * 128, 0, -128)
                 if n % t == 0), 0)


# The grouped product's three tiles (ISSUE 48; the sweep is PERF.md section 6,
# PR 48, ``scripts/bench_gmm.py``). megablox walks a grid of (N tiles, row-tile
# visits, K pieces), a row tile visited once for every expert that has rows in
# it, and its kernel block is (expert, K piece, N tile). On the chip a product
# of these sizes runs at the rate its kernel blocks arrive (one block in flight
# at a time: 300-380 GB/s of the chip's 819), so what the tiles decide is how
# many bytes of kernel a launch fetches:
#
# - With K in ONE piece the kernel block's index stays put over an expert's
#   consecutive visits, and an expert's kernel is fetched once an N tile. With K
#   cut, the block changes at every grid step and the kernel is fetched again
#   for every row tile that touches the expert: a drawn router's groups start
#   anywhere, so nearly every tile is shared and the kernels arrive twice. So K
#   stays whole wherever the blocks fit the kernel's fast memory, and N's tile
#   shrinks to make the room (down to ``TN_LEAST``: each N tile walks the rows
#   again).
# - The row tile matters little beside that (the rows' blocks are small): the
#   matrix unit's own 128, and 256 where an expert expects a whole tile of
#   that and the blocks still fit.
#
# The kernel's tiles depend on the kernel's shape and the dtype ALONE, never on
# the rows: a row's sum over K must not depend on who shares its launch (the
# ``compact`` and ``wide`` branches below give bit-identical rows).
TILE_BUDGET = 14.5 * 2 ** 20  # of 16 MiB: ``_tile_bytes`` ran up to 14.4, the chip refused 15.25
ROW_TILE = 128                # the matrix unit's own height
ROW_TILE_WIDE = 256           # where an expert expects that many rows or more
TN_LEAST = 512                # under it the rows' extra walks cost what K whole saves


def _tile_bytes(tm: int, tk: int, tn: int) -> int:
    """Fast memory megablox's blocks take at tiles ``(tm, tk, tn)`` of 128 or
    256 bfloat16 rows, as the chip's compiler counted them in the sweep: the
    kernel's block four times (the pipeline's two buffers and the product's
    operand), the rows' block and the float32 result's twice."""
    return (4 * tk * tn + 2 * tm * tk) * 2 + 2 * tm * tn * 4


def _kernel_tiles(k: int, n: int) -> tuple[int, int]:
    """``(tk, tn)`` of a ``(K, N)`` kernel: K whole and the largest multiple
    of 128 dividing N whose blocks fit ``TILE_BUDGET`` at ``ROW_TILE`` rows;
    where none of ``TN_LEAST`` or more fits (or K is no multiple of 128),
    ``_tile`` of each."""
    if k % 128 == 0:
        for tn in range(n // 128 * 128, TN_LEAST - 1, -128):
            if n % tn == 0 and _tile_bytes(ROW_TILE, k, tn) <= TILE_BUDGET:
                return k, tn
    return _tile(k), _tile(n)


def _row_tile(expects: float, tk: int = 0, tn: int = 0) -> int:
    """The grouped product's tile of the rows, from the rows an expert
    ``expects`` of the launch (its picks over the router's width) and the
    kernel's tiles the rows go through (none: what the widest product of the
    launch may take, which ``_row_bound`` rounds to): megablox wants whole
    tiles."""
    wide = expects >= ROW_TILE_WIDE and _tile_bytes(ROW_TILE_WIDE, tk, tn) <= TILE_BUDGET
    return ROW_TILE_WIDE if wide else ROW_TILE


def _grouped_dot(rows: int, dtype, *kernel_shapes, expects: "float | None" = None):
    """``(lhs (rows, K), rhs (G, K, N), group sizes (G,)) -> (rows, N)``
    float32, rows of group g through ``rhs[g]``. On the TPU, for bfloat16
    rows in whole tiles, the megablox Pallas kernel at the tiles of the comment
    above (``expects``: the rows an expert expects, ``rows`` over the groups
    where the caller does not say); ``jax.lax.ragged_dot`` everywhere else.
    Chosen when the program is traced, from what can be seen then."""
    if expects is None:
        expects = rows / kernel_shapes[0][0]
    tiles = {}
    for _g, kk, n in kernel_shapes:
        tk, tn = _kernel_tiles(kk, n)
        tiles[kk, n] = (_row_tile(expects, tk, tn), tk, tn)
    if jax.default_backend() == "tpu" and dtype == jnp.bfloat16 \
            and all(tk and tn and rows % tm == 0 for tm, tk, tn in tiles.values()):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return lambda lhs, rhs, sizes: gmm(
            lhs, rhs, sizes, preferred_element_type=jnp.float32, tiling=tiles[rhs.shape[1:]])
    return lambda lhs, rhs, sizes: jax.lax.ragged_dot(
        lhs, rhs, sizes, preferred_element_type=jnp.float32)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """An expert's hidden rows from its two in-products: silu(gate) * up."""
    return jax.nn.silu(gate) * up


def relu2(a: jax.Array) -> jax.Array:
    """An un-gated expert's hidden rows from its one in-product: relu(a)^2."""
    return jnp.square(jax.nn.relu(a))


def _row_bound(picks: int, count: int, of: "int | None") -> int:
    """The compact branch's static rows for a launch of ``picks`` = t * k
    picks where ``count`` of the router's ``of`` experts are held: the
    expected held picks times ``COMPACT_SLACK``, up to a whole row tile of
    ``_grouped_dot``. ``picks`` where that is no fewer (tiny launches), where
    every expert is held, or where the caller gave no width: no compaction."""
    if of is None or count >= of:
        return picks
    want = math.ceil(picks * count / of * COMPACT_SLACK)
    tm = _row_tile(picks / of)
    return min(picks, -(-want // tm) * tm)


def held_experts_swiglu(x, weights, experts, first, w_gate, w_up, w_down, live=None,
                        of=None, real=None):
    """This chip's part of a routed SwiGLU layer: :func:`held_experts` with
    the two in-kernels ``w_gate``/``w_up`` and the SiLU gate."""
    return held_experts(x, weights, experts, first, (w_gate, w_up), w_down, swiglu,
                        live=live, of=of, real=real)


def held_experts(x: jax.Array, weights: jax.Array, experts: jax.Array,
                 first: int, w_in: "tuple[jax.Array, ...]", w_out: jax.Array,
                 body, live: "jax.Array | None" = None, of: "int | None" = None,
                 real: "int | None" = None) -> tuple[jax.Array, dict]:
    """This chip's part of a routed expert layer, whatever an expert is.

    ``x`` (T, D); ``weights``/``experts`` (T, k) from :func:`topk_route`;
    the held experts' in-kernels ``w_in`` (each (count, D, F)) and
    ``w_out`` (count, F, D), expert ``first + i`` at row i; ``body`` makes an
    expert's hidden rows from its in-products (:func:`swiglu` of two,
    :func:`relu2` of one). ``live`` (T,)
    bool marks the tokens that are real (padding and frozen lanes route too,
    since shapes are static, but count for nothing and add nothing). ``of``
    is the router's width (the experts ``experts`` ranges over), from which
    the compact branch's row bound is sized; without it there is no such
    branch. ``real`` is how many of those outputs are real experts: a pick at
    ``real`` or above is ZERO-COMPUTE (the header comment above) and adds
    weight x token; without it every pick outside the held range is absent,
    and the program is the one this function always traced to.

    The sorted picks go through gather, products and body in one of two
    branches (the header comment above): ``compact`` carries
    ``_row_bound(T * k, count, of)`` rows where the launch's held live picks
    fit them, ``wide`` all ``T * k`` otherwise. Whichever runs, the program
    gives bit-identical ``y`` on the same input, and where the bound is not
    below ``T * k`` the function is ``wide`` alone, with no ``cond``. Call it
    once a layer, not under ``vmap`` (a ``cond`` there runs both branches).

    Returns ``y`` (T, D) float32, the sum over the held experts a token
    picked of weight x expert(token) (plus, with ``real``, the zero-compute
    picks' weight x token), and the counts the serving loop sums into its
    counters: ``routed_held``/``routed_absent`` (picks of live tokens on held
    experts / on real experts held elsewhere), with ``real`` ``routed_zero``
    (their zero-compute picks; the three sum to k x live tokens),
    ``experts_hit`` (held experts with at least one live pick) and
    ``compact`` (1 where the compact branch ran, else 0)."""
    t, k = experts.shape
    count = w_out.shape[0]
    bound = _row_bound(k * t, count, of)
    # The two scopes name this layer's operations in a device trace
    # (scripts/op_table.py): what carries rows, and the experts themselves.
    with jax.named_scope("moe_dispatch"):
        local = experts - jnp.int32(first)
        held = (local >= 0) & (local < count)
        absent = ~held
        if real is not None:
            zero_live = experts >= jnp.int32(real)
            absent = absent & ~zero_live
        if live is not None:
            held_live = held & live[:, None]
            absent_live = absent & live[:, None]
            if real is not None:
                zero_live = zero_live & live[:, None]
        else:
            held_live, absent_live = held, absent
        # Absent (and dead) picks sort behind every held expert, into rows
        # past the groups' sum. Picks are laid out pick-major (pick j of token
        # i at j * T + i), so that the way back is a split of the leading
        # dimension.
        key = jnp.where(held_live, local, count).T.reshape(k * t)
        order = jnp.argsort(key, stable=True)
        # Counted by comparison, not by a scatter-add of ones: the chip runs a
        # scatter's updates one after another (0.2 ms a layer for 22,528 picks,
        # my chip runs, PR 37) and a compare-and-sum in microseconds.
        sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :], axis=0,
                        dtype=jnp.int32)
        n_held = jnp.sum(held_live, dtype=jnp.int32)

    def rows_of(picks):
        """The sorted picks ``picks`` (a prefix of ``order``) through the
        experts, then every pick's row in pick order: (k * T, D) float32.
        Neither take checks an index this function made (``clip`` lowers to
        the gather alone; jax's own ``fill`` adds a bounds compare and a select
        over the whole block): ``picks % t`` lies in [0, T) and the way back is
        a permutation of [0, k * T), of which ``compact`` carries the first
        rows and a pick past them reads the last."""
        with jax.named_scope("moe_dispatch"):
            xs = jnp.take(x, picks % t, axis=0, mode="clip")
        with jax.named_scope("moe_experts"):
            dot = _grouped_dot(picks.shape[0], x.dtype, w_in[0].shape, w_out.shape,
                               expects=k * t / (of or count))
            h = body(*(dot(xs, w, sizes) for w in w_in)).astype(x.dtype)
            out = dot(h, w_out, sizes)
        with jax.named_scope("moe_dispatch"):
            return jnp.take(out, jnp.argsort(order), axis=0, mode="clip")

    # Rows past the groups' sum hold whatever the product left there (seen on
    # the chip: neither implementation zeroes them), and a pick that
    # ``compact`` did not carry reads its last row: both are selected away
    # below, not multiplied by zero.
    if bound < k * t:
        fits = n_held <= bound
        out = jax.lax.cond(fits, lambda: rows_of(order[:bound]), lambda: rows_of(order))
    else:
        fits = jnp.bool_(False)
        out = rows_of(order)
    with jax.named_scope("moe_dispatch"):
        # Weighted, summed over a token's k picks.
        out = out.reshape(k, t, -1)
        y = jnp.sum(jnp.where(held_live.T[:, :, None], out * weights.T[:, :, None], 0.0),
                    axis=0)
        stats = {"routed_held": n_held,
                 "routed_absent": jnp.sum(absent_live, dtype=jnp.int32),
                 "experts_hit": jnp.sum(sizes > 0, dtype=jnp.int32),
                 "compact": fits.astype(jnp.int32)}
    if real is not None:
        with jax.named_scope("moe_zero"):
            w_zero = jnp.sum(jnp.where(zero_live, weights, 0.0), axis=1)
            y = y + w_zero[:, None] * x.astype(jnp.float32)
            stats["routed_zero"] = jnp.sum(zero_live, dtype=jnp.int32)
    return y, stats
