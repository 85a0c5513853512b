"""Multi-host runtime initialization (SURVEY.md §5 "Distributed comm backend").

There is no user-managed collective backend on TPU — no NCCL/MPI/Gloo to
configure. Cross-chip traffic is XLA collectives over ICI; cross-host traffic
rides DCN, and the only runtime plumbing a multi-host deployment needs is
``jax.distributed.initialize`` so every process sees the global device set
and compiles identical SPMD programs. This module is that seam:

- ``init_distributed(cfg)`` — call ONCE, before any other JAX API touches a
  device (backend init freezes the topology). No-op unless
  ``DistributedConfig.coordinator_address`` is set, so single-host serving
  never pays anything.
- ``process_info()`` — rank/host facts for /stats and logs.

Mesh layout for the multi-host case lives in ``tpuserve.parallel.mesh``: the
data axis is host-major (consecutive global batch shards stay on one host's
chips; DP gradient/collective hops cross DCN only between host blocks) and
tensor/sequence axes never leave a host's ICI domain.
"""

from __future__ import annotations

import logging

import jax

from tpuserve.config import DistributedConfig

log = logging.getLogger("tpuserve.distributed")


def init_distributed(cfg: DistributedConfig) -> bool:
    """Initialize the multi-process JAX runtime if configured.

    Returns True when ``jax.distributed.initialize`` was called. Must run
    before the first device-touching JAX call in the process; ``serve()``
    honors that ordering.
    """
    if not cfg.coordinator_address:
        return False
    kwargs: dict = {"coordinator_address": cfg.coordinator_address}
    # -1 means "let jax read the cluster environment" (TPU metadata, SLURM,
    # etc.) — only pin what the config explicitly sets.
    if cfg.num_processes >= 0:
        kwargs["num_processes"] = cfg.num_processes
    if cfg.process_id >= 0:
        kwargs["process_id"] = cfg.process_id
    jax.distributed.initialize(**kwargs)
    log.info("distributed runtime up: process %d/%d, %d global / %d local devices",
             jax.process_index(), jax.process_count(),
             len(jax.devices()), len(jax.local_devices()))
    return True


def process_info() -> dict:
    """Rank/topology facts for logs and the /stats ``topology`` block.

    ``process_index``/``process_count`` are this process's coordinates in
    the jax.distributed cluster (0/1 single-host); the device counts split
    what this process can SEE (global) from what it OWNS (local). Behind
    the router tier every worker serves this from its own /stats, so the
    host-domain layout and the device topology are inspectable side by
    side (ISSUE 13)."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved",
                "bytes_limit")


def local_devices_info() -> list[dict]:
    """This process's devices with their memory, for /stats
    ``topology.devices``: read from ``device.memory_stats()`` when asked,
    nothing kept and nothing on the hot path. ``memory`` is None where the
    backend reports none (the CPU). On the TPU ``peak_bytes_in_use`` counts
    live buffers and ``peak_bytes_reserved`` the loaded programs' scratch:
    what a chip had committed at its peak is their sum (PERF.md)."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append({
            "id": d.id, "kind": d.device_kind,
            "memory": ({k: int(stats[k]) for k in _MEMORY_KEYS if k in stats}
                       if stats else None)})
    return out
