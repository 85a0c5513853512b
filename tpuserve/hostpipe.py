"""Pipelined host execution engine primitives (ISSUE 3; PAPERS.md P3/P4).

The host path used to run assemble -> device_put -> blocking fetch
sequentially per batch on one shared ThreadPoolExecutor, so stage time summed
instead of overlapping and the "compute" phase absorbed every wait (the last
driver record of that design, 2026-07-31 on an earlier installation, had HTTP
serving at 606 img/s beside an executable that ran 10,628; not re-measured on
today's code). Clockwork (P3) treats each serving stage as deterministic-duration
work that must be scheduled, not queued behind unrelated stages; Orca (P4)
re-forms work at stage granularity. This module provides the primitives
the batcher composes into that staged pipeline:

- :class:`StageExecutors` — one dedicated thread pool per pipeline stage
  (``assemble`` / ``h2d`` / ``fetch`` / ``postproc``), so consecutive batches
  occupy *different* stages concurrently instead of contending for one shared
  pool. Per-(model, stage) queue-depth gauges feed /metrics and /stats.
- :class:`AssemblyArena` — preallocated per-bucket host-batch buffers recycled
  through a free-list, replacing the per-batch ``np.stack`` allocation on the
  hot path. A buffer is only returned to the free-list when its batch's D2H
  fetch has completed (on the CPU backend ``device_put`` may alias host
  memory, so the buffer must outlive the compute that reads it).
- :class:`SlotPool` — a bounded pool of integer slots with async acquire.
  The batcher uses one per replica to keep a configurable depth-k of batches
  in flight on the device ([h2d..fetch]).
- :class:`AdmissionGate` — the batcher's admission: a FIFO gate whose
  opening is a function of the device time still queued, so a batch closes
  when the device is about to need it rather than when a count frees.

Knobs live in ``config.PipelineConfig`` (``[pipeline]`` TOML); semantics and
how to read the metrics are documented in docs/PERFORMANCE.md.
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import logging
from collections import deque
from typing import Any, Callable

import jax
import numpy as np

from tpuserve.config import PipelineConfig
from tpuserve.obs import PIPELINE_STAGES, Metrics, trace_call
from tpuserve.utils.locks import new_lock

log = logging.getLogger("tpuserve.hostpipe")


class SlotsClosed(Exception):
    """The pool was closed while (or before) a waiter held on for a slot."""


class SlotPool:
    """Fixed set of integer slots [0, n) with async acquire.

    Event-loop-side only (no thread safety needed): ``acquire`` waits until a
    slot frees, bounded by ``timeout`` (raises ``asyncio.TimeoutError``);
    ``close`` wakes every waiter with :class:`SlotsClosed`. Construction
    touches no event loop.

    ``spare`` further slots are handed out only while ``spare_ok()`` holds:
    the batcher's device section counts ``n`` launches, but a launch of a
    few milliseconds queued behind a long one must not hold one of them
    against the batch the device needs next, so past ``n`` it goes by the
    device time queued (``spare_ok``), up to ``n + spare`` batches."""

    def __init__(self, n: int, spare: int = 0,
                 spare_ok: Callable[[], bool] | None = None) -> None:
        self.capacity = max(1, n)
        self._spare = spare if spare_ok is not None else 0
        self._spare_ok = spare_ok
        self._free: list[int] = list(range(self.capacity + self._spare))
        self._waiters: deque[asyncio.Future] = deque()
        self._closed = False

    @property
    def in_use(self) -> int:
        return self.capacity + self._spare - len(self._free)

    def _has_room(self) -> bool:
        return self.in_use < self.capacity or (
            bool(self._free) and self._spare_ok())

    def _wake_one(self) -> None:
        while self._waiters:
            fut = self._waiters.popleft()
            if not fut.done():
                fut.set_result(None)
                return

    def try_acquire(self) -> int | None:
        if self._closed or not self._has_room():
            return None
        return self._free.pop()

    async def acquire(self, timeout_s: float | None = None) -> int:
        while True:
            if self._closed:
                raise SlotsClosed("slot pool closed")
            if self._has_room():
                return self._free.pop()
            fut = asyncio.get_running_loop().create_future()
            self._waiters.append(fut)
            try:
                await asyncio.wait_for(fut, timeout_s)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                if fut in self._waiters:
                    self._waiters.remove(fut)
                # Pass the baton: if a release woke us concurrently with the
                # timeout, another waiter must get the free slot we abandon.
                if self._has_room():
                    self._wake_one()
                raise

    def release(self, slot: int) -> None:
        self._free.append(slot)
        self._wake_one()

    def close(self) -> None:
        """Wake every waiter with SlotsClosed; held slots may still be
        released afterwards (no-op bookkeeping)."""
        self._closed = True
        while self._waiters:
            fut = self._waiters.popleft()
            if not fut.done():
                fut.set_exception(SlotsClosed("slot pool closed"))


class AdmissionGate:
    """FIFO gate whose opening is computed, not counted.

    The batcher's admission: ``wait_s(held, eager)`` says how long until
    the next place may be taken (0 = now, ``inf`` = not before a release),
    from the device time still queued and the time a batch takes to be
    staged, so a batch closes when the device is about to need it; ``eager``
    is what the waiter's own ``eager()`` answers at that moment (its batch
    is full: waiting adds nothing). Places go to waiters in arrival order,
    ONE per decision: the one admitted changes what ``wait_s`` reads (it
    registers its batch) and calls :meth:`poke` for the next decision.
    Event-loop-side only, like :class:`SlotPool`."""

    def __init__(self, wait_s: Callable[[int, bool], float]) -> None:
        self._wait_s = wait_s
        self.held = 0
        self._waiters: deque[tuple[asyncio.Future, Callable[[], bool]]] = \
            deque()
        self._timer: asyncio.TimerHandle | None = None

    def poke(self) -> None:
        """Decide again: what ``wait_s`` reads has changed."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        while self._waiters and self._waiters[0][0].done():
            self._waiters.popleft()  # gave up (timeout, cancellation)
        if not self._waiters:
            return
        fut, eager = self._waiters[0]
        wait = self._wait_s(self.held, eager())
        if wait <= 0:
            self.held += 1
            self._waiters.popleft()
            fut.set_result(None)
        elif wait != float("inf"):
            self._timer = asyncio.get_running_loop().call_later(
                wait, self.poke)

    async def acquire(self, timeout_s: float | None = None,
                      eager: Callable[[], bool] = lambda: False) -> None:
        """Take one place, waiting at most ``timeout_s`` (raises
        ``asyncio.TimeoutError``)."""
        if not self._waiters and self._wait_s(self.held, eager()) <= 0:
            self.held += 1
            return
        fut = asyncio.get_running_loop().create_future()
        self._waiters.append((fut, eager))
        self.poke()
        try:
            await asyncio.wait_for(fut, timeout_s)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            if fut.done() and not fut.cancelled():
                self.release()  # admitted as we gave up: pass it on
            else:
                self.poke()
            raise

    def release(self) -> None:
        self.held -= 1
        self.poke()


class StageExecutors:
    """Dedicated thread pool per pipeline stage (PIPELINE_STAGES).

    One instance is shared by every direct-mode batcher in the server
    (stage-granularity scheduling, P4): an h2d transfer for model A never
    queues behind a blocking fetch for model B the way the old single shared
    pool allowed. ``run`` hops the callable onto the stage's pool and keeps
    per-(model, stage) submitted-but-unfinished counts as
    ``pipeline_stage_depth{model=,stage=}`` gauges."""

    def __init__(self, cfg: PipelineConfig | None = None,
                 metrics: Metrics | None = None) -> None:
        cfg = cfg or PipelineConfig()
        sizes = {
            "assemble": cfg.assemble_workers,
            "h2d": cfg.h2d_workers,
            "fetch": cfg.fetch_workers,
            "postproc": cfg.postproc_workers,
        }
        assert set(sizes) == set(PIPELINE_STAGES)
        self.metrics = metrics
        self._pools = {
            stage: cf.ThreadPoolExecutor(
                max_workers=max(1, n), thread_name_prefix=f"pipe-{stage}")
            for stage, n in sizes.items()
        }
        self.workers = {s: max(1, n) for s, n in sizes.items()}
        self._depth: dict[tuple[str, str], int] = {}
        self._submitted: dict[str, int] = {s: 0 for s in PIPELINE_STAGES}
        self._shut = False

    async def run(self, model: str, stage: str, fn: Callable, *args,
                  span: dict | None = None) -> Any:
        """Run ``fn(*args)`` on the stage's pool; returns its result. In the
        pool thread the call sits inside a ``tpuserve.<stage>`` span on the
        profiler's clock, carrying ``model`` and whatever ``span`` holds
        (the batcher's batch id, bucket and item count)."""
        loop = asyncio.get_running_loop()
        key = (model, stage)
        self._depth[key] = self._depth.get(key, 0) + 1
        self._submitted[stage] += 1
        if self.metrics is not None:
            self.metrics.gauge(
                f"pipeline_stage_depth{{model={model},stage={stage}}}"
            ).set(self._depth[key])
        try:
            return await loop.run_in_executor(
                self._pools[stage], trace_call, f"tpuserve.{stage}",
                {"model": model, **(span or {})}, fn, *args)
        finally:
            self._depth[key] -= 1
            if self.metrics is not None:
                self.metrics.gauge(
                    f"pipeline_stage_depth{{model={model},stage={stage}}}"
                ).set(self._depth[key])

    def stats(self) -> dict:
        per_stage_depth = {s: 0 for s in PIPELINE_STAGES}
        for (_, stage), d in self._depth.items():
            per_stage_depth[stage] += d
        return {
            "workers": dict(self.workers),
            "depth": per_stage_depth,
            "submitted_total": dict(self._submitted),
        }

    def shutdown(self) -> None:
        if self._shut:
            return
        self._shut = True
        for p in self._pools.values():
            p.shutdown(wait=False, cancel_futures=True)


class _ArenaLease:
    """One acquired assembly buffer; hand back via AssemblyArena.release."""

    __slots__ = ("bucket", "buf", "pooled")

    def __init__(self, bucket: tuple, buf: Any, pooled: bool) -> None:
        self.bucket = bucket
        self.buf = buf
        self.pooled = pooled


class AssemblyArena:
    """Preallocated host-batch buffers per bucket, recycled via a free-list.

    Buffers are pytrees of np arrays shaped like ``model.input_signature``
    for the bucket (the host batch layout). ``acquire`` never blocks and
    never hands out a buffer that is currently leased: when the per-bucket
    pool (``slots`` buffers, allocated lazily) is exhausted it falls back to
    a fresh *overflow* allocation that is GC'd instead of pooled, counted in
    ``arena_overflow_total{model=}`` — persistent overflow means the arena is
    undersized relative to the admission depth ([pipeline] arena_slots)."""

    def __init__(self, model: Any, slots: int,
                 metrics: Metrics | None = None) -> None:
        self.model = model
        self.slots = max(1, slots)
        self.metrics = metrics
        self._lock = new_lock("hostpipe.AssemblyArena")
        self._free: dict[tuple, list] = {}
        self._made: dict[tuple, int] = {}
        self.overflow_total = 0
        self.leased = 0

    def _alloc(self, bucket: tuple) -> Any:
        sig = self.model.input_signature(bucket)
        return jax.tree_util.tree_map(
            lambda s: np.zeros(tuple(s.shape), s.dtype), sig)

    def acquire(self, bucket: tuple) -> _ArenaLease:
        with self._lock:
            self.leased += 1
            free = self._free.setdefault(bucket, [])
            if free:
                return _ArenaLease(bucket, free.pop(), True)
            if self._made.get(bucket, 0) < self.slots:
                self._made[bucket] = self._made.get(bucket, 0) + 1
                pooled = True
            else:
                pooled = False
                self.overflow_total += 1
        if not pooled and self.metrics is not None:
            self.metrics.counter(
                f"arena_overflow_total{{model={self.model.name}}}").inc()
        # Allocation outside the lock: zeroing a multi-MB buffer must not
        # serialize concurrent acquires for other buckets.
        return _ArenaLease(bucket, self._alloc(bucket), pooled)

    def release(self, lease: _ArenaLease) -> None:
        """Return a lease. Only call once the device is provably done reading
        the buffer (after the batch's D2H fetch) — on the CPU backend
        ``device_put`` may alias this host memory."""
        with self._lock:
            self.leased -= 1
            if lease.pooled:
                self._free[lease.bucket].append(lease.buf)

    def stats(self) -> dict:
        with self._lock:
            return {
                "slots_per_bucket": self.slots,
                "leased": self.leased,
                "overflow_total": self.overflow_total,
                "buckets": {
                    str(list(b)): {"pooled": self._made.get(b, 0),
                                   "free": len(free)}
                    for b, free in self._free.items()
                },
            }
