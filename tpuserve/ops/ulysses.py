"""Ulysses sequence parallelism: all-to-all head resharding (SURVEY.md §2.1).

The second of the two sequence-parallel schemes SURVEY.md §5 names (ring
attention being the first, ``tpuserve.ops.ring_attention``). Where the ring
keeps queries resident and rotates K/V blocks around the ICI ring in
``seq_devices`` steps, Ulysses pays one collective each way: an all-to-all
reshards activations from sequence-sharded/heads-replicated to
heads-sharded/sequence-complete, every device then runs ordinary dense
attention for its head slice over the FULL sequence, and a second all-to-all
restores sequence sharding. On TPU both all-to-alls ride ICI and cost
O(B*S*H*D / n) bytes per device — the same traffic the ring moves in total,
but concentrated in two dispatches instead of n, which wins when per-step
latency (not bandwidth) dominates, i.e. short-to-medium sequences on many
chips.

Trade-off vs ring, honestly stated: Ulysses holds the full (S, S/n-free)
sequence of K/V per device after the first all-to-all, so per-device memory
for activations is O(B*S*H/n*D) — fine until S^2 scores dominate (the local
dense attention still materializes (H/n, S, S) scores). Ring never holds more
than a (S/n, S/n) tile and wins for very long sequences. The two share one
interface so the train step can pick per config.

Constraint: attention heads (after any tensor-parallel split of the heads
dim) must be divisible by the seq-axis size, because the all-to-all deals
heads out across it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpuserve.ops.ring_attention import dense_attention


def _ulysses_body(q, k, v, kbias, axis_name: str, local_impl: str = "dense"):
    """Per-device: reshard seq->heads, attend the full sequence, reshard back.

    ``local_impl="flash"`` runs the per-device full-sequence attention
    through the fused Pallas kernel instead of a dense einsum — the local
    (H/n, S, S) score materialization was Ulysses's memory weak spot."""
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # (B, S/n, H, D) -> (B, S, H/n, D): split the heads dim across the axis,
    # concatenate the sequence back together.
    qh = a2a(q, split_axis=2, concat_axis=1)
    kh = a2a(k, split_axis=2, concat_axis=1)
    vh = a2a(v, split_axis=2, concat_axis=1)
    # Per-key bias needs the full sequence on every device.
    bias = jax.lax.all_gather(kbias, axis_name, axis=1, tiled=True)  # (B, S)
    if local_impl == "flash":
        from tpuserve.ops.flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, bias.astype(jnp.float32))
    else:
        out = dense_attention(qh, kh, vh,
                              bias[:, None, None, :].astype(jnp.float32))
    # (B, S, H/n, D) -> (B, S/n, H, D): the inverse deal. Cast back first:
    # the f32 bias promoted the scores, but the op's contract (shared with
    # ring_attention) is out.dtype == q.dtype.
    return a2a(out.astype(q.dtype), split_axis=1, concat_axis=2)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mesh: Mesh, axis_name: str = "seq",
                      key_padding: jax.Array | None = None,
                      spec: P | None = None,
                      local_impl: str = "auto") -> jax.Array:
    """Sequence-parallel attention via head all-to-all; ring_attention's twin.

    Args:
      q, k, v: (batch, seq, heads, head_dim) global arrays, seq sharded on
        ``axis_name``.
      mesh: device mesh containing ``axis_name``.
      key_padding: optional (batch, seq) additive per-key bias (0 = attend,
        -1e9 = masked), sharded like K's seq dim.
      spec: optional full PartitionSpec for q/k/v (position 1 must be
        ``axis_name``), e.g. ``P("data", "seq", "model", None)``.

    Returns (batch, seq, heads, head_dim), sharded like q.
    """
    if key_padding is None:
        key_padding = jnp.zeros(k.shape[:2], jnp.float32)
    qkv_spec = spec if spec is not None else P(None, axis_name, None, None)
    if qkv_spec[1] != axis_name:
        raise ValueError(f"spec {qkv_spec} must put {axis_name!r} on the seq dim")
    n = mesh.shape[axis_name]
    h = q.shape[2]
    head_axes = qkv_spec[2]
    if head_axes is not None:
        for a in (head_axes if isinstance(head_axes, (tuple, list)) else [head_axes]):
            h //= mesh.shape[a]
    if h % n:
        raise ValueError(
            f"ulysses needs local heads ({h}) divisible by the {axis_name!r} "
            f"axis size ({n}); use ring_attention for this shape")
    if local_impl == "auto":
        # Memory-derived, shared with ring_attention (see its docstring and
        # BASELINE.md "Flash vs dense, chip level": dense measured FASTER
        # at every serving shape on v5e; flash is for when the full-seq
        # dense scores stop fitting). Ulysses' local attention sees the
        # FULL sequence with h/n heads per device; batch divides over
        # whatever the spec shards it on (h already divided above).
        from tpuserve.ops.ring_attention import _spec_axis_size, auto_local_impl

        b_loc = q.shape[0] // _spec_axis_size(mesh, qkv_spec[0])
        local_impl = auto_local_impl(b_loc, h // n, q.shape[1], q.shape[-1])
    elif local_impl not in ("dense", "flash"):
        raise ValueError(f"unknown local_impl {local_impl!r}")
    bias_spec = P(qkv_spec[0], axis_name)
    fn = jax.shard_map(
        partial(_ulysses_body, axis_name=axis_name, local_impl=local_impl),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec),
        out_specs=qkv_spec,
        # See ring_attention: the Pallas interpreter needs check_vma off.
        check_vma=local_impl != "flash",
    )
    return fn(q, k, v, key_padding)
