"""Attention without a score matrix in device memory: two Pallas TPU kernels.

**``fused_attention``: a whole short sequence a step (S <= 512).** What
BERT's (x, 512) buckets run on one TPU chip since PR 29, chosen by
``attention_path`` while a bucket is traced (platform, dtype, sequence
length, head width; no option names it). With S <= 512 a head's whole K and
V sit in VMEM, so the softmax is one pass with no rescale; bf16 operands go
into both MXU products with float32 accumulation, maximum, exponentials and
sum are float32, probabilities are rounded to bf16 for the second product
as the dense path rounds them, and a padded key weighs exactly 0.0. It
reads and writes ``(B, H, D, S)``, the layout XLA gives the projections on
the TPU, so no transpose goes through device memory on either side.
Measured on the v5e (2026-09-28, jax 0.9.0, ``scripts/bench_flash.py``,
PERF.md section 6, PR 29), attention alone at the serving shapes, inputs as
the projections leave them: (256, 512, 16, 64) 4.0 ms against 15.8 ms for
the XLA pair the dense path lowers to (12.5 ms a layer inside BERT-large's
program), 9.9 ms for jax's own ``pallas.ops.tpu.flash_attention`` with
whole-sequence blocks and its transposes, and 47.0 ms for
``flash_attention`` below; (256, 512, 12, 64) 3.2 against 12.0, 7.6 and
35.6 ms. The whole (256, 512) forward: BERT-large 847.8 -> 646.9 ms,
BERT-base 306.4 -> 204.1 ms; (256, 256): 346.3 -> 325.2 and 111.8 -> 101.5.
At S = 128 the XLA pair wins or draws inside the program ((256, 128):
148.1 against 152.7 ms; (32, 128): 21.2 against 22.7) though the kernel
alone is faster there, so ``attention_path`` routes 256..512 only. What
bounds the kernel now is the vector unit: five to six vector operations a
score.

**``flash_attention``: tiled, online softmax, any length.** For each query
tile, K/V stream through the MXU in ``block_k`` tiles while the running max
``m``, normalizer ``l`` and f32 accumulator live in VMEM scratch: O(S)
memory, one HBM write per output tile. It is the single-device realization
of the recurrence ``tpuserve.ops.ring_attention`` runs *across* chips
(there the blocks arrive over ICI via ppermute; here from HBM via the
BlockSpec pipeline), and ``return_stats`` hands ring and Ulysses the
unnormalised accumulator with ``(m, l)``. Its use is memory and those
merges, not speed at serving shapes: it casts its operands to float32
before both products, works in 128 x 128 tiles one head a step and
transposes through device memory around the call, and reads 3.0x the dense
pair's time at (256, 512, 16, 64) (above; BASELINE.md's "0.45-0.70x" of
2026-07-30 was the same verdict at (16, 512)). ``options.attention =
"flash"`` and ``ring/ulysses local_impl="auto"`` past the dense score
tile's memory budget are its callers; at SD-UNet head widths 40/80 the
zero-padded lanes cost it another 2.4-2.8x (BASELINE.md "SD 1.5 chip
profile"), so the SD 1.5 UNet stays dense.

Kernel shape of ``flash_attention``: grid = (B*H, Sq/block_q, Sk/block_k).
The TPU grid executes the innermost dimension sequentially, so the k-block
axis lives in the GRID and the online-softmax state persists in scratch
across k iterations: no in-kernel dynamic slicing. State is initialized at
ki == 0 and the output tile is written once at the last ki. Interface:
(B, S, H, D) layout, optional additive per-key bias (B, S), exactly what
BERT's padding mask lowers to. Padded keys get -1e9 bias => exp underflows
to 0 => they contribute nothing to ``l`` or ``acc``; a row with at least
one live key (BERT always has [CLS]) never divides by zero.

CPU/test story, both kernels: ``pallas_call(interpret=True)`` runs them in
the Pallas interpreter, so the same code is unit-tested on the CI's
fake-device CPU mesh and compiled for real on TPU. ``interpret=None``
decides from the effective default device (honoring
``jax.default_device(cpu)`` blocks like the runtime's CPU-pinned param
init): interpret on ``cpu``, compile on ``tpu``, raise on anything else.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_step(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref,
             scale: float) -> None:
    """Shared online-softmax update for one (query tile, key tile) cell."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale           # (bq, D)
    k_blk = k_ref[0].astype(jnp.float32)               # (bk, D)
    v_blk = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(                           # (bq, bk) on the MXU
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = s + bias_ref[0, 0, 0][None, :]

    m_prev = m_ref[:, :1]                              # (bq, 1)
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _fa_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
               m_ref, l_ref, acc_ref, *, scale: float):
    """Standard variant: normalized output only."""
    _fa_step(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref, scale)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def _fa_kernel_stats(q_ref, k_ref, v_ref, bias_ref, o_ref, mo_ref, lo_ref,
                     m_ref, l_ref, acc_ref, *, scale: float):
    """Stats variant: emit the UNNORMALIZED f32 accumulator plus the
    online-softmax (m, l) per query row so a caller can merge this block's
    result with other blocks' — the recurrence ring attention runs ACROSS
    chips (blockwise-parallel combine). No divide happens in-kernel, which
    keeps fully-masked blocks harmless two different ways depending on the
    mask encoding (do NOT use l == 0 to detect masked blocks): with a true
    -inf bias the exps underflow and l really is 0, so skipping the divide
    avoids 0/0; with the conventional -1e9 padding bias (BERT masks) l is
    ~block_k and m is ~-1e9 — the zero contribution then comes from the
    exp(m_blk - m_new) weight underflowing in the CALLER'S merge against
    any live block. Either way the f32 accumulator never round-trips
    through the input dtype."""
    _fa_step(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref, scale)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_ref[:]
        mo_ref[0] = m_ref[:]
        lo_ref[0] = l_ref[:]


def _dense_stats(q, k, v, bias, return_stats):
    """Pure-XLA twin of the kernel's math: the VJP reference.

    Same function value as the kernel (scores = scaled q.k + per-key bias,
    online softmax); used only to define gradients, so the O(S^2) score
    materialization here costs backward passes, never serving."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = s + bias[:, None, None, :].astype(jnp.float32)
    m = jnp.max(s, axis=-1)                              # (B, H, Sq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    if return_stats:
        return (acc, m.transpose(0, 2, 1), l.transpose(0, 2, 1))
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, block_q, block_k, interpret, return_stats):
    """Kernel dispatch with a dense-recompute VJP: forward runs the Pallas
    kernel; backward differentiates the mathematically-identical dense
    reference (a fused backward kernel is future work — training through
    flash pays the dense O(S^2) memory, serving never does)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nk = sk // block_k
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    biasf = bias.astype(jnp.float32).reshape(b, nk, 1, block_k)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, 1, 1, block_k),
                     lambda bh, qi, ki, h=h: (bh // h, ki, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    scratch = [
        pltpu.VMEM((block_q, 128), jnp.float32),   # running max m
        pltpu.VMEM((block_q, 128), jnp.float32),   # normalizer l
        pltpu.VMEM((block_q, d), jnp.float32),     # weighted accumulator
    ]
    grid = (b * h, sq // block_q, nk)

    # Inside shard_map (the sharded-BERT / ring-local composition) outputs
    # must declare which mesh axes they vary over; inherit the inputs' union
    # (outside shard_map these are empty sets — no-op).
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v, bias)))

    def out_struct(shape, dtype):
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
        return jax.ShapeDtypeStruct(shape, dtype)

    if not return_stats:
        out = pl.pallas_call(
            functools.partial(_fa_kernel, scale=d ** -0.5),
            grid=grid, in_specs=in_specs, out_specs=o_spec,
            out_shape=out_struct((b * h, sq, d), q.dtype),
            scratch_shapes=scratch, interpret=interpret,
        )(qf, kf, vf, biasf)
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    # Stats outputs mirror the scratch layout: (B*H, Sq, 128) f32 with the
    # row value broadcast along the 128 lane dim (Mosaic-aligned tiles);
    # lane 0 is sliced out after the call. The accumulator comes back
    # UNNORMALIZED in f32 (see _fa_kernel_stats).
    stat_spec = pl.BlockSpec((1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0))
    out, m, l = pl.pallas_call(
        functools.partial(_fa_kernel_stats, scale=d ** -0.5),
        grid=grid, in_specs=in_specs,
        out_specs=(o_spec, stat_spec, stat_spec),
        out_shape=(out_struct((b * h, sq, d), jnp.float32),
                   out_struct((b * h, sq, 128), jnp.float32),
                   out_struct((b * h, sq, 128), jnp.float32)),
        scratch_shapes=scratch, interpret=interpret,
    )(qf, kf, vf, biasf)
    out = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    m = m[..., 0].reshape(b, h, sq).transpose(0, 2, 1)   # (B, Sq, H)
    l = l[..., 0].reshape(b, h, sq).transpose(0, 2, 1)
    return out, m, l


def _flash_fwd(q, k, v, bias, block_q, block_k, interpret, return_stats):
    out = _flash(q, k, v, bias, block_q, block_k, interpret, return_stats)
    return out, (q, k, v, bias)


def _flash_bwd(block_q, block_k, interpret, return_stats, res, ct):
    q, k, v, bias = res
    _, vjp = jax.vjp(
        lambda a, b_, c, d_: _dense_stats(a, b_, c, d_, return_stats),
        q, k, v, bias)
    return vjp(ct)


_flash.defvjp(_flash_fwd, _flash_bwd)


def platform_here() -> str:
    """The platform a trace started now would run on.

    Honors ``with jax.default_device(cpu)`` (the runtime pins param init
    there): default_backend() alone would still say 'tpu' and compile the TPU
    kernel for a CPU trace."""
    dev = jax.config.jax_default_device  # a Device, a platform name, or None
    return (dev if isinstance(dev, str)
            else getattr(dev, "platform", None)) or jax.default_backend()


def _interpret_here() -> bool:
    """Interpret on ``cpu``, compile on ``tpu``, raise on anything else."""
    platform = platform_here()
    if platform not in ("cpu", "tpu"):  # tps-ok[TPS503]: a platform name, host-side
        raise ValueError(
            f"flash_attention is a Mosaic TPU kernel: platform {platform!r} "
            "can neither compile it nor should silently run the "
            "interpreter; use dense attention there")
    return platform == "cpu"


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret",
                                    "return_stats"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: jax.Array | None = None, *,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None,
                    return_stats: bool = False):
    """Blockwise fused attention, (B, S, H, D) in/out.

    ``bias``: optional additive per-key scores, (B, Sk) — e.g. a padding
    mask's (1 - mask) * -1e9. Block sizes clamp to divisors of the sequence
    lengths (exact for power-of-two-aligned buckets like {64, 128, 256, 512};
    192/320-style buckets fall back to 64-row blocks).

    Differentiable: the VJP recomputes through the dense reference
    (O(S^2) memory on backward only — fine for fine-tuning, not for
    long-context pretraining; a fused backward kernel is the upgrade path).

    ``return_stats=True`` returns ``(acc, m, l)`` — the UNNORMALIZED f32
    accumulator plus the online-softmax row stats (B, Sq, H) — letting the
    caller merge this result with other key blocks (ring attention's
    per-device inner step) without NaN on fully-masked blocks and without
    rounding partial results to the input dtype. The merge is::

        m12 = max(m1, m2); a1 = exp(m1-m12); a2 = exp(m2-m12)
        l12 = l1*a1 + l2*a2
        o12 = (acc1*a1 + acc2*a2) / l12
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    # Clamp blocks to divisors of the sequence lengths (gcd keeps the common
    # power-of-two alignment: 192 -> 64, 320 -> 64). TPU lowering needs tile
    # rows divisible by 8 unless the block spans the whole axis.
    block_q = math.gcd(min(block_q, sq), sq)
    block_k = math.gcd(min(block_k, sk), sk)
    for name, blk, size in (("query", block_q, sq), ("key", block_k, sk)):
        if blk != size and blk % 8:
            raise ValueError(
                f"seq_{name} {size} only admits a {blk}-row {name} block, "
                "which the TPU lowering rejects; use a multiple of 8")
    if interpret is None:
        interpret = _interpret_here()
    if bias is None:
        bias = jnp.zeros((b, sk), jnp.float32)
    return _flash(q, k, v, bias, block_q, block_k, interpret, return_stats)


# -- whole-sequence kernel: the serving shapes (S <= 512, head 64) ---------------
_LANES = 128
# Sequence lengths at which the whole forward was faster with the
# whole-sequence kernel than with the XLA pair on the v5e
# (scripts/bench_flash.py --forward; PERF.md section 6, PR 29): at 512 by
# 24-33%, at 256 by 6-9% at batch 256 and even at batch 32, at 128 slower.
FUSED_SEQ_RANGE = (256, 512)
# Contraction rows the whole-sequence kernel adds to its first product for the
# mask (one bfloat16 tile): row 0 for padding, one for each document of a row.
MASK_ROWS = 16


def attention_path(platform: str, dtype, seq: int, head_dim: int) -> str:
    """"fused" or "dense" for self-attention over one bucket, from what a
    trace can see: the platform it runs on, the compute dtype, the bucket's
    sequence length and the head width. A pure function, so that the rule
    is tested where no TPU is. Only what was measured is routed."""
    lo, hi = FUSED_SEQ_RANGE
    if platform == "tpu" and jnp.dtype(dtype) == jnp.bfloat16 \
            and head_dim == 64 and seq % _LANES == 0 and lo <= seq <= hi:
        return "fused"
    return "dense"


def _fused_kernel(q_ref, k_ref, v_ref, seg_ref, o_ref):
    """One row's block of ``(heads, head_dim, S)``: a head's features on
    sublanes, the sequence on lanes, which is how XLA lays the projections'
    outputs out on the TPU. Scores are held transposed, keys on sublanes and
    queries on lanes, so that the softmax's maximum and sum run down the
    sublanes (plain vector maxima and adds) and come out as rows, the shape
    that normalises the ``(head_dim, S)`` output. The mask rides in the
    first product: sixteen more contraction rows, which the MXU takes in
    the same pass. Row j of the key side holds -1e9 where the key's segment
    is j; row j of the query side holds 1 where the query's segment is NOT
    j, and row 0 (padding) holds 1 for every query. A pair of one segment
    sums to exactly 0.0 and any other pair to exactly one -1e9: nothing
    large is ever cancelled. (A padded query's rows past 0 hold 0: it sees
    every document's keys, as it saw the row's one document before rows
    were shared, and means as little.) The heads are unrolled, so that one head's
    products overlap the next one's softmax."""
    _, heads, head_dim, s = q_ref.shape
    dt = q_ref.dtype
    scale = head_dim ** -0.5
    exact = math.frexp(scale)[0] == 0.5     # a power of two: exact in bfloat16
    row = jax.lax.broadcasted_iota(jnp.int32, (MASK_ROWS, s), 0)
    seg = jnp.broadcast_to(seg_ref[0], (MASK_ROWS, s))
    k_mask = jnp.where(row == seg, -1e9, 0.0).astype(dt)
    q_mask = jnp.where((row == 0) | ((row != seg) & (seg != 0)),
                       1.0, 0.0).astype(dt)
    for h in range(heads):
        q = q_ref[0, h]
        if exact:                           # on (head_dim, S), not on (S, S)
            q = q * jnp.asarray(scale, dt)
        sc = jax.lax.dot_general(                        # (keys, queries)
            jnp.concatenate([k_ref[0, h], k_mask], axis=0),
            jnp.concatenate([q, q_mask], axis=0),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if not exact:                       # a masked key stays at -1e9 * scale
            sc = sc * scale
        p = jnp.exp(sc - jnp.max(sc, axis=0, keepdims=True))
        norm = 1.0 / jnp.sum(p, axis=0, keepdims=True)             # (1, queries)
        out = jnp.dot(v_ref[0, h], p.astype(dt),
                      preferred_element_type=jnp.float32)  # (head_dim, queries)
        o_ref[0, h] = (out * norm).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    segments: jax.Array, *, block_h: int | None = None,
                    interpret: bool | None = None):
    """Attention over a whole short sequence in one kernel, (B, S, H, D) in
    and out. ``segments`` (B, S) numbers the documents that share a row: 0
    is padding, 1 .. ``MASK_ROWS`` - 1 a document, and a query attends over
    the keys of its own number only. A plain 0 / 1 key mask is the case of
    one document a row.

    The mathematics of ``models.bert._masked_attention``: scaled scores (the
    products' float32 accumulators, where the dense path rounds them to the
    input dtype first), float32 softmax over the query's own keys, weights
    rounded to the input dtype for the product with the values; the
    normaliser is applied to the float32 output. A key of padding or of
    another document weighs exactly 0.0; a padded query gets finite values
    that mean nothing. S in whole lanes (128), head width in whole bf16
    tiles (16); ``block_h`` heads a grid step (default: all, which is
    fastest where it fits VMEM: 512 x 16 x 64 does). No VJP: it serves."""
    b, s, h, d = q.shape
    block_h = h if block_h is None else block_h
    if d % 16 or s % _LANES or h % block_h:
        raise ValueError(
            f"fused_attention wants head width {d} in whole bfloat16 tiles, "
            f"sequence {s} in whole lanes and {block_h} heads a step that "
            f"divide {h}; use dense attention")
    if interpret is None:
        interpret = _interpret_here()
    # (B, S, H, D) -> (B, H, D, S): on the TPU XLA writes the projections
    # sequence-minor already, so these are views there, not copies.
    spec = pl.BlockSpec((1, block_h, d, s), lambda bi, hi: (bi, hi, 0, 0))
    out = pl.pallas_call(
        _fused_kernel,
        grid=(b, h // block_h),
        in_specs=[spec, spec, spec,
                  pl.BlockSpec((1, 1, s), lambda bi, hi: (bi, 0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d, s), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(*(x.transpose(0, 2, 3, 1) for x in (q, k, v)),
      segments.astype(jnp.int32).reshape(b, 1, s))
    return out.transpose(0, 3, 1, 2)
