"""Attention over a whole short sequence (S <= 512) in one Pallas TPU kernel.

``fused_attention`` is what BERT's (x, 512) buckets run on one TPU chip since
PR 29, chosen by ``attention_path`` while a bucket is traced (platform, dtype,
sequence length, head width; no option names it). A head's whole K and V sit
in VMEM, so the softmax is one pass with no rescale: bf16 operands into both
MXU products with float32 accumulation, float32 maximum, exponentials and sum,
probabilities rounded to bf16 for the second product as the dense path rounds
them, a padded key weighing exactly 0.0. It reads and writes ``(B, H, D, S)``,
the layout XLA gives the projections on the TPU: no transpose goes through
device memory on either side. What bounds it is the vector unit.

Measured on the v5e (2026-09-28, jax 0.9.0, ``scripts/bench_flash.py``; PERF.md,
PR 29), attention alone at (256, 512, 16, 64) and (256, 512, 12, 64): 4.0 and
3.2 ms against 15.8 and 12.0 for the XLA pair the dense path lowers to, 9.9 and
7.6 for jax's own ``pallas.ops.tpu.flash_attention``, and 47.0 and 35.6 for
this repo's tiled, online-softmax ``flash_attention``, which never served a
cell and was deleted in PR 57 with ring and Ulysses, whose per-device step it
was. The whole (256, 512) forward: BERT-large 847.8 -> 646.9 ms, BERT-base
306.4 -> 204.1; at (256, 256) 346.3 -> 325.2 and 111.8 -> 101.5; at S = 128
the XLA pair wins or draws inside the program (148.1 against 152.7 ms at
(256, 128)), so ``attention_path`` routes 256..512 only.

``interpret=None`` runs the Pallas interpreter on ``cpu`` (how tier-1 tests
the same code), compiles on ``tpu`` and raises anywhere else.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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
            f"fused_attention is a Mosaic TPU kernel: platform {platform!r} "
            "can neither compile it nor should silently run the "
            "interpreter; use dense attention there")
    return platform == "cpu"


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
