"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context design (SURVEY.md §5 "Long-context/sequence parallelism"): for
sequences that exceed one device's HBM — or whose O(seq^2) score matrix does —
the sequence dim is sharded over the mesh's ``"seq"`` axis. Each device holds
one block of Q/K/V. K/V blocks then rotate around the ring with
``jax.lax.ppermute`` (nearest-neighbor ICI traffic, no all-gather), and every
device folds each visiting block into its queries' result with an online
softmax (running max ``m``, normalizer ``l``, weighted accumulator ``acc`` —
the same recurrence flash/blockwise attention uses). After ``seq_devices``
steps every query has attended to the full sequence while no device ever
materialized more than a (q_local, k_local) score tile.

The rotation runs inside ``lax.scan`` so XLA emits one compiled loop body;
``ppermute`` of the *next* block is issued before the current block's math,
letting the compiler overlap ICI transfer with MXU compute.

Layouts: (batch, seq, heads, head_dim) throughout — matching
``nn.MultiHeadDotProductAttention`` — with seq sharded and heads replicated.
Bidirectional (encoder) attention; an additive bias (e.g. padding mask) can be
passed sharded the same way as K.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# Dense-vs-flash local-math decision threshold: the per-device f32 score
# tile (x2 for the softmax temp XLA keeps alive). Above ~2 GiB dense
# attention starts evicting everything else from a 16 GiB v5e; below it,
# dense is simply FASTER (measured 1.4-2.2x at every serving shape —
# BASELINE.md "Flash vs dense, chip level", 2026-07-30).
DENSE_SCORE_BYTES_MAX = 2 << 30


def auto_local_impl(b_loc: int, h_loc: int, s_loc: int, d: int) -> str:
    """Memory-derived per-device attention impl choice (pure; unit-tested
    directly in tests/test_flash_attention.py because no CPU-testable
    shape can cross the threshold for real)."""
    kernel_ok = d % 64 == 0 and s_loc % 8 == 0
    dense_score_bytes = 2 * 4 * b_loc * h_loc * s_loc * s_loc
    return ("flash" if kernel_ok and dense_score_bytes > DENSE_SCORE_BYTES_MAX
            else "dense")


def _spec_axis_size(mesh: Mesh, entry) -> int:
    """Product of mesh-axis sizes a PartitionSpec entry shards over."""
    if entry is None:
        return 1
    axes = entry if isinstance(entry, (tuple, list)) else [entry]
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: jax.Array | None = None) -> jax.Array:
    """Reference single-device attention, (B, S, H, D) layout.

    ``bias`` is additive on the scores, shaped (B, 1|H, S_q, S_k).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_body(q, k, v, kbias, axis_name: str, vary_axes: tuple = (),
               local_impl: str = "dense"):
    """Per-device ring loop: local Q stays, K/V (+ per-key bias) rotate.

    ``local_impl="flash"`` runs each visiting block's math through the fused
    Pallas kernel (``flash_attention(..., return_stats=True)``) instead of a
    dense einsum that materializes the (Sq_local, Sk_local) score tile — the
    composition VERDICT r3 next 3 asked for: the kernel is the single-device
    realization of the same online-softmax recurrence, so the ring merge
    just folds (o, m, l) triples.
    """
    n = jax.lax.psum(1, axis_name)
    scale = q.shape[-1] ** -0.5
    b, sq, h, d = q.shape

    # Online-softmax state, (B, H, Sq) / (B, Sq, H, D). pcast marks the
    # constants as varying over every sharded axis so scan carry types match
    # the loop outputs (which inherit q/k/v's varying axes).
    vary = vary_axes or (axis_name,)
    m0 = jax.lax.pcast(jnp.full((b, h, sq), -jnp.inf, jnp.float32), vary,
                       to="varying")
    l0 = jax.lax.pcast(jnp.zeros((b, h, sq), jnp.float32), vary, to="varying")
    acc0 = jax.lax.pcast(jnp.zeros((b, sq, h, d), jnp.float32), vary,
                         to="varying")
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k_blk, v_blk, bias_blk, m, l, acc = carry
        # Issue the rotation first so ICI overlaps the tile's compute.
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        bias_nxt = jax.lax.ppermute(bias_blk, axis_name, perm)

        if local_impl == "flash":
            from tpuserve.ops.flash_attention import flash_attention

            # Kernel returns the UNNORMALIZED f32 accumulator + (m, l): the
            # merge folds raw triples in f32 — no per-block divide (a fully
            # masked visiting block is a harmless zero contribution, not
            # 0/0 NaN) and no bf16 round-trip of partial results.
            acc_blk, m_blk, l_blk = flash_attention(
                q, k_blk, v_blk, bias_blk, return_stats=True)
            m_blk = m_blk.transpose(0, 2, 1)           # (B, H, Sq)
            l_blk = l_blk.transpose(0, 2, 1)
            m_new = jnp.maximum(m, m_blk)
            a_prev = jnp.exp(m - m_new)
            a_blk = jnp.exp(m_blk - m_new)
            l = l * a_prev + l_blk * a_blk
            acc = (acc * a_prev.transpose(0, 2, 1)[..., None]
                   + acc_blk * a_blk.transpose(0, 2, 1)[..., None])
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
            s = s + bias_blk[:, None, None, :]  # (B, Sk) per-key additive bias
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)  # rescale of previous state
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
                "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        return (k_nxt, v_nxt, bias_nxt, m_new, l, acc), None

    (_, _, _, _, l, acc), _ = jax.lax.scan(
        step, (k, v, kbias, m0, l0, acc0), None, length=n)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis_name: str = "seq",
                   key_padding: jax.Array | None = None,
                   spec: P | None = None,
                   local_impl: str = "auto") -> jax.Array:
    """Sequence-parallel attention; call inside or outside jit.

    Args:
      q, k, v: (batch, seq, heads, head_dim), seq sharded on ``axis_name``
        (global arrays; shard_map slices them).
      mesh: the device mesh containing ``axis_name``.
      key_padding: optional (batch, seq) additive bias per key position
        (0 = attend, -inf/-1e9 = masked), sharded like K's seq dim.
      spec: optional full PartitionSpec for q/k/v, e.g.
        ``P("data", "seq", "model", None)`` to keep batch data-parallel and
        heads tensor-parallel through the ring (position 1 must be
        ``axis_name``). Default shards only the seq dim.
      local_impl: per-device block math — "dense" (einsum, materializes the
        local score tile), "flash" (fused Pallas kernel), or "auto".
        "auto" is MEMORY-derived, not speed-derived: the v5e measurement
        (BASELINE.md "Flash vs dense, chip level", 2026-07-30) shows dense
        FASTER at every serving shape (flash = 0.45-0.70x), so auto picks
        dense whenever the local score tile plausibly fits HBM and only
        switches to flash when the O(s_loc^2) dense scores grow into the
        GB range — the regime flash exists for (it also needs the usual
        kernel alignment: head_dim % 64 == 0, s_loc % 8 == 0).

    Returns (batch, seq, heads, head_dim), sharded like q.
    """
    if key_padding is None:
        key_padding = jnp.zeros(k.shape[:2], jnp.float32)
    qkv_spec = spec if spec is not None else P(None, axis_name, None, None)
    if qkv_spec[1] != axis_name:
        raise ValueError(f"spec {qkv_spec} must put {axis_name!r} on the seq dim")
    if local_impl == "auto":
        n = int(mesh.shape[axis_name])
        b, _, h, d = q.shape
        # The decision models the PER-DEVICE tile: divide batch and heads
        # by whatever mesh axes the spec shards them over (r5 review:
        # using global shapes overestimated by dp*tp and flipped sharded
        # serving onto the measured-slower kernel).
        b_loc = b // _spec_axis_size(mesh, qkv_spec[0])
        h_loc = h // _spec_axis_size(mesh, qkv_spec[2])
        local_impl = auto_local_impl(b_loc, h_loc, q.shape[1] // n, d)
    elif local_impl not in ("dense", "flash"):
        raise ValueError(f"unknown local_impl {local_impl!r}")
    bias_spec = P(qkv_spec[0], axis_name)
    vary_axes = []
    for entry in qkv_spec:
        if entry is None:
            continue
        vary_axes.extend(entry if isinstance(entry, (tuple, list)) else [entry])
    fn = jax.shard_map(
        partial(_ring_body, axis_name=axis_name, vary_axes=tuple(vary_axes),
                local_impl=local_impl),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec),
        out_specs=qkv_spec,
        # The Pallas interpreter can't propagate vma through its internal
        # block slicing (jax-ml/jax: "pass check_vma=False as a temporary
        # workaround"); the dense path keeps the stronger checking.
        check_vma=local_impl != "flash",
    )
    return fn(q, k, v, key_padding)
