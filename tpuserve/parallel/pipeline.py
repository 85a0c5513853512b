"""Pipeline parallelism over a mesh "stage" axis (SURVEY.md §2.1 PP seam).

For models whose layer stack exceeds one device's memory, the remaining
partitioning axis after dp/tp/sp is DEPTH: split the stack into S equal
stages, one per device along a ``"stage"`` mesh axis, and stream
microbatches through GPipe-style. TPU-native realization:

- Stage parameters are a STACKED pytree — every leaf gains a leading
  ``(S, ...)`` dim sharded on the stage axis, so each device materializes
  only its own stage's weights (the point of PP: S-fold parameter memory).
- The schedule is one ``lax.scan`` over ``n_micro + S - 1`` ticks inside
  ``shard_map``: each tick, every stage ``ppermute``s its previous output to
  the next stage (nearest-neighbor ICI traffic, like the ring-attention
  rotation), then runs the stage function on what arrived — stage 0 feeds
  the next microbatch instead. The pipeline bubble is the standard
  ``(S - 1) / (n_micro + S - 1)`` fraction; raise ``n_micro`` to amortize.
- Outputs: only the last stage produces real results; a ``psum`` over the
  stage axis replicates them (fine at completed-activation sizes; a
  production variant for huge outputs would keep them stage-sharded).

``stage_fn`` must be shape/dtype-preserving — the homogeneous-transformer
case where depth splits into equal-shaped chunks, which is when PP applies.

SURVEY.md §2.1 scoped PP out of the v1 critical path because every judged
config fits one v5e core; this makes the seam real (compiled and executed
on the fake-device mesh in CI) for the models that don't.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

STAGE_AXIS = "stage"


def make_stage_mesh(n_stages: int, devices: list | None = None) -> Mesh:
    """A 1-D ("stage",) mesh over the first n_stages devices."""
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_stages:
        raise ValueError(f"need {n_stages} devices, have {len(devices)}")
    grid = np.empty(n_stages, dtype=object)
    grid[:] = devices[:n_stages]
    return Mesh(grid, (STAGE_AXIS,))


def stack_stage_params(per_stage: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading (S, ...) leaves.

    All stages must share one tree structure (same block architecture).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def _pp_body(params: Any, xs: jax.Array, *, stage_fn: Callable,
             n_stages: int, n_micro: int, axis_name: str) -> jax.Array:
    """Per-device GPipe schedule: my stage, every tick."""
    s = jax.lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda x: x[0], params)  # strip stage dim
    send_perm = [(i, i + 1) for i in range(n_stages - 1)]
    mb_shape = xs.shape[1:]

    def tick(prev_out, t):
        # What I computed last tick moves one stage down the line.
        recv = jax.lax.ppermute(prev_out, axis_name, send_perm)
        # Stage 0 feeds microbatch t; stage s>0 works on what arrived
        # (microbatch t - s, by induction).
        x0 = jax.lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        x_in = jnp.where(s == 0, x0, recv)
        y = stage_fn(params, x_in)
        # Idle ticks (pipeline fill/drain) must not leak garbage downstream.
        active = (t >= s) & (t < s + n_micro)
        y = jnp.where(active, y, jnp.zeros_like(y))
        out = jnp.where(active & (s == n_stages - 1), y,
                        jnp.zeros(mb_shape, y.dtype))
        return y, out

    # pcast: the zero init must carry the same varying-over-stage type the
    # loop outputs have (cf. the ring-attention scan carries).
    init = jax.lax.pcast(jnp.zeros(mb_shape, xs.dtype), (axis_name,),
                         to="varying")
    _, outs = jax.lax.scan(tick, init, jnp.arange(n_micro + n_stages - 1))
    # Only the last stage contributed non-zeros; replicate its results.
    outs = jax.lax.psum(outs, axis_name)
    # Microbatch j completes at tick j + (S - 1).
    return outs[n_stages - 1:]


def pipeline_forward(stage_fn: Callable, stacked_params: Any, xs: jax.Array,
                     mesh: Mesh, axis_name: str = STAGE_AXIS) -> jax.Array:
    """Pipelined application of S stacked stages to microbatched input.

    Args:
      stage_fn: ``(stage_params, x) -> y`` with ``y.shape == x.shape`` and
        the same dtype (one stage's slice of a homogeneous layer stack).
      stacked_params: pytree whose leaves have leading dim S (see
        ``stack_stage_params``), sharded/shardable on ``axis_name``.
      xs: ``(n_micro, microbatch, ...)`` input microbatches.
      mesh: mesh containing ``axis_name`` of size S.

    Returns ``(n_micro, microbatch, ...)`` outputs, replicated.
    """
    n_stages = mesh.shape[axis_name]
    n_micro = int(xs.shape[0])
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            # An exact multiple would shard silently and run only every
            # k-th stage; make any mismatch loud.
            raise ValueError(
                f"stacked params have {leaf.shape[0]} stages but the "
                f"{axis_name!r} axis has {n_stages} devices")
    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    body = partial(_pp_body, stage_fn=stage_fn, n_stages=n_stages,
                   n_micro=n_micro, axis_name=axis_name)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(param_specs, P()), out_specs=P())
    return fn(stacked_params, xs)
