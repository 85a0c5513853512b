"""Mesh construction over TPU devices (SURVEY.md §2.1).

Axis conventions used throughout tpuserve:

- ``"data"``  — data parallel: batches sharded across it, params replicated.
- ``"model"`` — tensor parallel: weight matrices sharded across it.
- ``"seq"``   — sequence/context parallel: ``textgen`` shards its KV pages
  over it and the training dry run its activations (GSPMD partitions both).

An inference mesh is usually ``("data",)`` or ``("data", "model")``; the
training step used by the multi-chip dry run adds ``"seq"``. The same code
path handles 1 local chip, 8 chips (v5e-8), and — via
``jax.distributed`` — multi-host slices: the mesh is always built from
``jax.devices()``, never hard-coded counts (SURVEY.md §7 hard part 7).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"


def local_device_count() -> int:
    return len(jax.devices())


@dataclass(frozen=True)
class MeshPlan:
    """How to carve the device grid into named axes."""

    dp: int = -1  # -1 = "everything not claimed by other axes"
    tp: int = 1
    sp: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int]:
        tp, sp = self.tp, self.sp
        if n_devices % (tp * sp) != 0:
            raise ValueError(f"{n_devices} devices not divisible by tp*sp={tp * sp}")
        dp = self.dp if self.dp != -1 else n_devices // (tp * sp)
        if dp * tp * sp != n_devices:
            raise ValueError(f"dp*tp*sp={dp * tp * sp} != device count {n_devices}")
        return dp, tp, sp


def host_major_grid(devices: list, dp: int, tp: int, sp: int) -> np.ndarray:
    """(dp, tp, sp) device grid with every (tp, sp) block inside one host.

    Multi-host layout rule (SURVEY.md §5 "Distributed comm backend"): the
    data axis is host-major — hosts ordered by ``process_index``, each host's
    devices filling whole dp rows — so tensor- and sequence-parallel
    collectives stay on a host's ICI domain and only data-parallel traffic
    crosses DCN. Single-host input (all ``process_index`` equal) reduces to a
    plain reshape, preserving device order.
    """
    hosts: dict[int, list] = {}
    for d in devices:
        hosts.setdefault(getattr(d, "process_index", 0), []).append(d)
    counts = {len(v) for v in hosts.values()}
    if len(counts) != 1:
        raise ValueError("hosts contribute unequal device counts: "
                         f"{ {h: len(v) for h, v in sorted(hosts.items())} }")
    if counts.pop() % (tp * sp) != 0:
        raise ValueError(
            f"tp*sp={tp * sp} must divide each host's device count "
            f"({len(devices) // len(hosts)}): tensor/sequence axes must not "
            "cross DCN")
    ordered = [d for _, host in sorted(hosts.items()) for d in host]
    grid = np.empty(len(ordered), dtype=object)
    grid[:] = ordered
    return grid.reshape(dp, tp, sp)


def make_mesh(plan: MeshPlan | None = None, devices: list | None = None) -> Mesh:
    """Build a Mesh with axes (data, model[, seq]).

    Axes of size 1 for model/seq are still materialized so PartitionSpecs
    mentioning them remain valid regardless of configuration; XLA treats a
    size-1 axis as free. Works unchanged from 1 local chip to a multi-host
    pod: the grid is host-major (see ``host_major_grid``), which for a
    single host is the identity layout.
    """
    plan = plan or MeshPlan()
    devices = devices if devices is not None else jax.devices()
    dp, tp, sp = plan.resolve(len(devices))
    return Mesh(host_major_grid(devices, dp, tp, sp),
                (DATA_AXIS, MODEL_AXIS, SEQ_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Inputs/outputs: shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Params (DP mode): fully replicated."""
    return NamedSharding(mesh, P())


def pad_batch_to_mesh(batch_size: int, mesh: Mesh) -> int:
    """Smallest batch >= batch_size divisible by the data-axis size."""
    d = mesh.shape[DATA_AXIS]
    return ((batch_size + d - 1) // d) * d


def axis_size(mesh: Mesh, axis: str) -> int:
    """Size of a named mesh axis (1 when the axis is free)."""
    return int(mesh.shape.get(axis, 1))


def can_shard(mesh: Mesh, axis: str, dim: int) -> bool:
    """True when ``dim`` divides evenly over a >1-sized mesh axis — the
    gate generative state specs apply before pinning a heads/pages dim to
    an axis, so a layout that doesn't divide falls back to replication
    instead of an XLA error."""
    n = axis_size(mesh, axis)
    return n > 1 and dim % n == 0


def select_devices(n_chips: int = 0, devices: list | None = None) -> list:
    """The device set a ``[parallel]`` plan serves on.

    ``n_chips = 0`` takes every visible device; a positive count takes the
    first ``n_chips`` (stable ``jax.devices()`` order, so replica indices
    in metrics map to the same physical chips across restarts). Asking for
    more devices than exist is a configuration error, not a silent clamp —
    a deployment that believes it serves on 8 chips must never quietly run
    on 1 (SURVEY.md §7 hard part 7: never hard-code counts, never lie
    about them either)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_chips <= 0:
        return devs
    if n_chips > len(devs):
        raise ValueError(
            f"parallel.n_chips={n_chips} but only {len(devs)} device(s) "
            "visible")
    return devs[:n_chips]


def plan_for(parallel: "object", tp: int = 1, sp: int = 1) -> MeshPlan:
    """MeshPlan for a sharded-batch serving mesh from a ``[parallel]``
    block (config.ParallelConfig): an explicit ``data`` pins the data-axis
    size, otherwise it derives from whatever device count ``select_devices``
    returned (dp = -1)."""
    data = int(getattr(parallel, "data", 0) or 0)
    return MeshPlan(dp=data if data > 0 else -1, tp=tp, sp=sp)
