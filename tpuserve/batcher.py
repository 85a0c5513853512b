"""Static-shape request batching engine (SURVEY.md §2 C2, §3c).

The reference accumulates requests into dynamic batches; XLA wants static
shapes, so this batcher assembles **padded, bucketed** batches:

- Requests are routed to a *group* (model-defined: e.g. seq-len bucket for
  text; vision models have one group). Each group has its own accumulation
  task and queue.
- A group flushes when the largest batch bucket fills, or when the oldest
  request has waited ``deadline_ms`` (flush-on-deadline), whichever is first.
- The flush picks the smallest configured batch bucket >= the ready count and
  zero-pads up to it; ``host_postprocess`` only reads the valid rows, and
  padded lanes are tested to never perturb real lanes
  (tests/test_runtime.py::test_padding_lanes_do_not_affect_real_lanes).

Dispatch is a **staged pipeline** (ISSUE 3; docs/PERFORMANCE.md): instead of
one shared threadpool running assemble -> device_put -> blocking fetch
sequentially per batch, each stage has its own executor
(tpuserve.hostpipe.StageExecutors) so consecutive batches occupy different
stages concurrently — batch N+1 assembles and transfers while batch N
computes. Assembly writes into preallocated per-bucket arena buffers
(AssemblyArena) recycled through a free-list instead of np.stack-allocating
per batch, and a depth-k staging-slot pool per replica (SlotPool) bounds how
many batches occupy the device section [h2d..fetch] at once.

A batch **closes as late as keeps the device fed** (ISSUE 26;
docs/PERFORMANCE.md "Admission"). Its membership is fixed by the final drain
of ``_group_loop``, and the drain runs only when the admission gate opens:
when the device time still queued in the device section (predicted from
the launch durations seen per bucket) has fallen to the *reserve*, twice the
time a batch has lately taken from its close to its launch (assemble, h2d
and the hops between), or at once when the batch is full. Until then its
requests stay where late arrivals join them. Outstanding work divided by
the batches in the pipeline is the batch size (Little's law), so every batch
frozen early shrinks all of them: where staging is small against a launch
(BERT-large at 512 tokens: 7-70 ms against 847) one batch runs and the next
closes just before it ends; where it is not (a vision model whose launch
takes milliseconds, a host too busy to stage in time) the same rule closes
batches ahead, up to ``depth x replicas + assemble_ahead`` of them, and the
device-section slots stay occupied. Nothing is set: both sides are measured
here, and before the first batch has been the gate counts ``depth x
replicas``. The device section itself counts ``depth`` launches per replica,
but goes by queued device time past that (``assemble_ahead`` spare slots):
a launch of a few milliseconds queued behind a long one does not hold a slot
against the batch the device needs next. The close does not grow a batch
past the edge of the bucket it occupies unless what is queued makes the
larger launch no dearer per item by the durations measured per bucket.

Flush scheduling is **SLO-aware and adaptive** (ISSUE 5; docs/PERFORMANCE.md
"Adaptive batching"): instead of always accumulating toward the largest
bucket under a fixed max-wait timer, each group keeps an AIMD-adjusted
*target batch size* (Clipper, PAPERS.md P1) — a batch that fills to target
with work still queued grows it additively, a timer-driven partial flush
shrinks it multiplicatively — so light load converges to target 1 (flush immediately,
no deadline_ms wait) while sustained load converges to the bucket
(throughput). A per-bucket EWMA of observed batch duration (Clockwork, P3:
inference duration is predictable) bounds the wait further: a batch whose
earliest member deadline leaves less than EWMA + slack of headroom flushes
NOW rather than discovering the deadline at dispatch. ``deadline_ms``
remains the max-wait backstop, and ``[adaptive] enabled = false`` restores
the fixed-timer behavior exactly.

Failure containment (SURVEY.md §5, docs/ROBUSTNESS.md): a failed dispatch
first re-assembles and re-runs the batch once (``batch_retry``); if the
retry also fails the batch recursively bisects (``retry_split``) so a single
poison item fails only its own future while the other lanes succeed. Only
then do futures carry the error. Dispatch outcomes feed the per-model
circuit breaker, an optional FaultInjector supplies deterministic chaos at
the dispatch call sites, and dead group tasks are revived by the server
watchdog (``revive_group_loops``). Client disconnects cancel futures, which
are dropped at flush time. Requests carrying a per-request deadline
(``timeout_ms``) that expires while queued fail fast with DeadlineExceeded
at flush time or while waiting for admission/staging capacity — rejected in
microseconds, not computed for nobody (P3).
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import logging
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Hashable

from tpuserve.config import AdaptiveConfig, PipelineConfig
from tpuserve.hostpipe import (AdmissionGate, AssemblyArena, SlotPool,
                               StageExecutors)
from tpuserve.models.base import ServingModel
from tpuserve.obs import (BATCH_PHASES, PRIORITIES, Counter, Metrics,
                          trace_mark)
from tpuserve.runtime import ModelRuntime, bucket_label

log = logging.getLogger("tpuserve.batcher")

# Durations the close rule goes by are estimated from this many samples.
_RECENT = 8


def _second(samples, largest: bool = False) -> float:
    """The second smallest (or largest) of a few recent samples: an
    estimate on the safe side that one stray sample cannot move."""
    ranked = sorted(samples, reverse=largest)
    return ranked[min(1, len(ranked) - 1)]


class QueueFull(Exception):
    """Raised by submit() when the model queue is at capacity (-> HTTP 429)."""


def clamp_retry_after_s(est: "float | None") -> "int | None":
    """The [1, 30] s Retry-After hint derived from a raw queue-clear
    estimate. Deliberately split from ``estimate_clear_s`` (ISSUE 10
    satellite): the clamp is a client-facing hint policy, not a property of
    the estimate — the fleet scheduler's admission math needs the RAW
    number (clamping a 90 s backlog to 30 s would admit work that provably
    cannot meet a 45 s deadline)."""
    if est is None:
        return None
    return max(1, min(30, math.ceil(est)))


class DeadlineExceeded(Exception):
    """A request's absolute deadline expired while it was still queued
    (-> fast HTTP 504). Clockwork discipline (PAPERS.md P3): work nobody is
    waiting for is rejected before dispatch, not computed and discarded."""


@dataclass
class _Request:
    item: Any  # decoded input (np arrays), model-specific
    group: Hashable
    future: asyncio.Future = field(repr=False)
    enqueued_at: float = 0.0  # time.perf_counter()
    # Absolute per-request deadline (perf_counter clock), stamped at
    # admission from the client's timeout_ms; None = model default only.
    deadline_at: float | None = None
    # Priority class ("interactive"/"batch"; obs.PRIORITIES) resolved at
    # admission from X-Priority or the model default; None = unscheduled.
    priority: str | None = None
    # Request trace context (obs.TraceContext, ISSUE 12): the batcher
    # appends per-request queue + phase spans (tagged with the batch id)
    # to it; None when the caller doesn't trace (tests, embedding).
    ctx: Any = None
    # Joined its batch at the close (the final drain) rather than while the
    # group accumulated: batcher_batch_items_total{joined=}.
    at_close: bool = False
    # What the item takes of a row (ServingModel.item_units), and the row of
    # its batch it was placed in (_Rows.place).
    units: int = 1
    row: int = 0


class _Rows:
    """The rows a batch occupies while it fills: what the batcher compares
    with a batch bucket. An item goes into the open row it fits most
    tightly (best fit), else into a new row while the batch may still grow
    one; a row stays open while it has units left and fewer than
    ``per_row`` items. Open rows are kept by their free units with one bit
    a size, so a placement is a shift and a lowest set bit, not a walk of
    the rows. A model of one item a row (width 1) never has an open row:
    every item takes a new one and rows are items."""

    __slots__ = ("width", "per_row", "n", "units", "_items", "_free",
                 "_open", "_sizes")

    def __init__(self, width: int, per_row: int) -> None:
        self.width = width
        self.per_row = per_row
        self.n = 0                          # rows occupied
        self.units = 0                      # units placed
        self._items: list[int] = []         # items by row
        self._free: list[int] = []          # free units by row
        self._open: dict[int, list[int]] = {}   # free units -> open rows
        self._sizes = 0                     # bit f: a row with f free is open

    @property
    def has_open(self) -> bool:
        return self._sizes != 0

    def place(self, req: _Request, limit: int) -> bool:
        """Give ``req`` its row; False if no open row takes it and the batch
        already occupies ``limit`` rows."""
        units = req.units
        fits = self._sizes >> units
        if fits:
            free = units + (fits & -fits).bit_length() - 1
            rows = self._open[free]
            row = rows.pop()
            if not rows:
                self._sizes ^= 1 << free
        elif self.n < limit:
            free, row = self.width, self.n
            self.n += 1
            self._items.append(0)
            self._free.append(free)
        else:
            return False
        self._items[row] += 1
        self._free[row] = free - units
        self._keep_open(row)
        self.units += units
        req.row = row
        return True

    @classmethod
    def of(cls, reqs: "list[_Request]", width: int, per_row: int) -> "_Rows":
        """The rows of ``reqs`` as they were placed, renumbered from 0 in
        the order they first appear: what is left of a batch when some of it
        has gone (expired, cancelled, a retry's half). Keeping each item's
        row, rather than placing again, can never need more rows."""
        rows = cls(width, per_row)
        for r, row in zip(reqs, _renumbered(reqs)):
            r.row = row
            if row == rows.n:
                rows.n += 1
                rows._items.append(0)
                rows._free.append(width)
            rows._items[row] += 1
            rows._free[row] -= r.units
            rows.units += r.units
        for row in range(rows.n):
            rows._keep_open(row)
        return rows

    def _keep_open(self, row: int) -> None:
        """``row`` takes more while it has units left and room for an item."""
        free = self._free[row]
        if free > 0 and self._items[row] < self.per_row:
            self._open.setdefault(free, []).append(row)
            self._sizes |= 1 << free


def _renumbered(reqs: "list[_Request]") -> list[int]:
    """The row of each request, numbered from 0 in the order the rows first
    appear: what is left of a batch (expired, cancelled, a retry's half)
    keeps each item in the row it had."""
    renumber: dict[int, int] = {}
    return [renumber.setdefault(r.row, len(renumber)) for r in reqs]


class ModelBatcher:
    """One batching engine per served model."""

    def __init__(
        self,
        model: ServingModel,
        runtime: "ModelRuntime | Any",
        metrics: Metrics,
        breaker: "Any | None" = None,
        injector: "Any | None" = None,
        stages: "StageExecutors | None" = None,
        pipeline_cfg: "PipelineConfig | None" = None,
        adaptive_cfg: "AdaptiveConfig | None" = None,
    ) -> None:
        self.model = model
        self.runtime = runtime
        self.metrics = metrics
        self.cfg = model.cfg
        self.pipeline_cfg = pipeline_cfg or PipelineConfig()
        self.adaptive_cfg = adaptive_cfg or AdaptiveConfig()
        # Adaptive scheduler state (event loop only): AIMD target batch size
        # per group, batch-duration EWMA per bucket key.
        self._targets: dict[Hashable, float] = {}
        self._ewma_ms: dict[tuple, float] = {}
        # What the close rule reads (event loop only). Per bucket: the
        # device time of a launch (from the later of its launch and the
        # previous completion on its replica to its own completion; the
        # device runs them in order), a LOW estimate of the recent samples:
        # an end is only ever seen late, and a launch thought longer than
        # it is leaves the device dry. Model-wide: the time from a batch's
        # close to its launch, waits for a slot left out, a HIGH estimate:
        # a batch staged late costs far more than one staged early. Per
        # replica: the launches staged and not yet complete ([predicted ms,
        # staged at, still running as far as known], oldest first) and when
        # the last one completed. And the batches closed but not yet staged
        # (batch id -> predicted ms).
        self._device_ms: dict[tuple, float] = {}
        self._device_seen: dict[tuple, deque[float]] = {}
        self._stage_ms: float | None = None
        self._stage_seen: deque[float] = deque(maxlen=_RECENT)
        self._launches: list[deque[list]] = []
        self._last_done: list[float] = []
        self._closed_ms: dict[int, float] = {}
        # Hot-path metric handles, prebound once (ISSUE 5 satellite: the
        # per-request/per-flush f-string format + registry lookup was pure
        # overhead on every submit).
        name = model.cfg.name
        self._g_queue_depth = metrics.gauge(f"queue_depth{{model={name}}}")
        self._g_fill = metrics.gauge(f"batch_fill_ratio{{model={name}}}")
        self._g_inflight = metrics.gauge(f"pipeline_inflight{{model={name}}}")
        self._g_target = metrics.gauge(f"adaptive_target_batch{{model={name}}}")
        self._g_ewma = metrics.gauge(f"batch_duration_ewma_ms{{model={name}}}")
        self._c_shed = metrics.counter(f"shed_total{{model={name}}}")
        self._c_deadline = metrics.counter(
            f"deadline_exceeded_total{{model={name}}}")
        self._c_batches = metrics.counter(f"batches_total{{model={name}}}")
        self._c_items = metrics.counter(f"items_total{{model={name}}}")
        # The same items by when they joined their batch: while the group
        # accumulated, or at the close (the final drain, after the wait for
        # a place). The close's share is what an earlier close turns away.
        self._c_joined = {
            j: metrics.counter(
                f"batcher_batch_items_total{{model={name},joined={j}}}")
            for j in ("accumulate", "close")}
        # Rows those items occupied in their launches: items over rows is
        # items a row (1.0 for a model of one item a row).
        self._c_rows = metrics.counter(
            f"batcher_batch_rows_total{{model={name}}}")
        self._c_batch_errors = metrics.counter(
            f"batch_errors_total{{model={name}}}")
        self._c_retries = metrics.counter(f"batch_retries_total{{model={name}}}")
        self._c_retry_failures = metrics.counter(
            f"batch_retry_failures_total{{model={name}}}")
        self._c_poison = metrics.counter(f"poison_items_total{{model={name}}}")
        # Why a group stopped accumulating: it reached its target, or its
        # timer (deadline_ms / the deadline headroom) ran out first.
        self._c_flushes = {
            reason: metrics.counter(
                f"batcher_flushes_total{{model={name},reason={reason}}}")
            for reason in ("target", "timer")}
        # Batch ids, minted where a batch is formed (_group_loop) so that
        # its accumulation and slot wait carry the id its stages will.
        self._bid_seq = 0
        self._h_phase = {
            p: metrics.histogram(f"latency_ms{{model={name},phase={p}}}")
            for p in BATCH_PHASES}
        # Per-priority queue-wait split (tpuserve.scheduler): requests
        # without a resolved priority land under the model's default class.
        self._default_priority = getattr(model.cfg, "priority", "interactive")
        self._h_qwait = {p: metrics.queue_wait_histogram(name, p)
                         for p in PRIORITIES}
        # Fleet-scheduler device-time ledger hook: called with each batch's
        # device-section seconds (compute phase) when a scheduler is
        # attached; None otherwise. Event-loop-only, like the ledger.
        self.device_time_cb = None
        # Per-replica device-seconds counters (ISSUE 14): ticked with every
        # batch's device section regardless of scheduler presence — the
        # telemetry sampler derives device_utilization{model=,replica=}
        # from their rates. Sized to the replica count at start().
        self._c_device_seconds: list[Counter] = []
        # Stage executors are normally server-owned and shared across models
        # (stage-granularity scheduling); a batcher built without one (tests,
        # embedding) creates and later shuts down its own.
        self._own_stages = stages is None
        self.stages = stages if stages is not None \
            else StageExecutors(self.pipeline_cfg, metrics)
        self._queues: dict[Hashable, asyncio.Queue[_Request]] = {}
        # Per group: the requests a close looked past (they fitted no row of
        # its batch), ahead of the queue in arrival order, and the units of
        # everything waiting in either.
        self._skipped: dict[Hashable, deque[_Request]] = {}
        # What a row holds and what an item takes of it (ServingModel); a
        # stand-in model that says nothing has one item a row.
        self._row_shape = getattr(model, "row_shape", lambda group: (1, 1))
        self._item_units = getattr(model, "item_units", lambda item, group: 1)
        self._waiting_units: dict[Hashable, int] = {}
        self._tasks: dict[Hashable, asyncio.Task] = {}
        self._dispatch_tasks: set[asyncio.Task] = set()
        self._gate: AdmissionGate | None = None
        self._staging: list[SlotPool] = []
        self._g_replica_inflight: list[Any] = []
        self.arena: AssemblyArena | None = None
        self.depth = 0
        self._device_cap = 0
        self._inflight_now = 0
        self._inflight_peak = 0
        self._idle_event: asyncio.Event | None = None
        self._pending = 0
        self._running = False
        self._loop: asyncio.AbstractEventLoop | None = None
        # Arena assembly requires assemble_into to produce exactly what
        # assemble would: provable only when assemble is the base
        # implementation, or the family overrode assemble_into alongside its
        # custom assemble. Wrappers that monkey with assemble (tests) fall
        # back to the allocating path automatically.
        t = type(model)
        a = getattr(t, "assemble", None)
        ai = getattr(t, "assemble_into", None)
        self._use_arena = (a is ServingModel.assemble
                           or (ai is not None
                               and ai is not ServingModel.assemble_into))
        # Per-model circuit breaker (tpuserve.faults.CircuitBreaker): fed
        # dispatch outcomes here, consulted by the HTTP layer.
        self.breaker = breaker
        # Deterministic chaos (tpuserve.faults.FaultInjector); None in prod.
        self.injector = injector

    # -- lifecycle ----------------------------------------------------------
    async def start(self) -> None:
        self._running = True
        # The loop that owns every queue/future/counter below; captured so
        # submit_threadsafe (the parallel-ingest entry) can hop onto it.
        self._loop = asyncio.get_running_loop()
        pcfg = self.pipeline_cfg
        n_rep = max(1, int(getattr(self.runtime, "n_replicas", 1)))
        # Transfer-completion gate ([pipeline] h2d_sync): the h2d stage
        # owns the wire wait, so the "compute" phase measures
        # dispatch-to-ready only (roofline attribution).
        self.runtime.h2d_sync = pcfg.h2d_sync
        self.depth = max(1, pcfg.depth or self.cfg.max_inflight)
        if n_rep == 1 and getattr(self.runtime, "n_chips", 1) > 1:
            import jax

            if jax.default_backend() == "cpu":
                # Forced-host-device meshes (CPU CI/smokes/bench): the
                # fake devices share the host's cores, and CONCURRENT
                # multi-device program dispatches spin-wait against
                # each other — observed wedging every request past a
                # 60 s deadline at depth 4 (ISSUE 11). Serialize the
                # device section; depth > 1 buys nothing on a shared
                # core anyway. Real accelerator backends keep the
                # configured depth (per-device execution streams
                # serialize safely there).
                self.depth = 1
        # depth-k launches per device section; past that, by the device
        # time queued there (_section_is_short), not by count.
        self._staging = [
            SlotPool(self.depth, spare=pcfg.assemble_ahead,
                     spare_ok=lambda r=r: self._section_is_short(r))
            for r in range(n_rep)]
        self._launches = [deque() for _ in range(n_rep)]
        self._last_done = [0.0] * n_rep
        # Replica-aware admission: depth-k batches per DEVICE section —
        # with 8 replicas the pipeline admits 8x the single-chip batch
        # count, which is what keeps every chip's staging slots full
        # instead of one chip's (ISSUE 7).
        self._device_cap = self.depth * n_rep
        # Per-chip occupancy gauges (docs/PERFORMANCE.md "Serving on
        # the mesh"), prebound once per replica.
        self._g_replica_inflight = [
            self.metrics.replica_inflight_gauge(self.cfg.name, i)
            for i in range(n_rep)]
        # Per-replica device-seconds ledger (ISSUE 14): the telemetry
        # sampler turns these rates into device_utilization gauges.
        self._c_device_seconds = [
            self.metrics.device_seconds_counter(self.cfg.name, i)
            for i in range(n_rep)]
        arena_slots = pcfg.arena_slots or (self.depth + pcfg.assemble_ahead)
        self.arena = (AssemblyArena(self.model, arena_slots, self.metrics)
                      if self._use_arena else None)
        self._gate = AdmissionGate(self._close_wait_s)
        self._idle_event = asyncio.Event()
        self._idle_event.set()

    async def stop(self) -> None:
        """Cancel accumulation, fail queued requests, drain in-flight batches."""
        self._running = False
        for t in self._tasks.values():
            t.cancel()
        for group, t in self._tasks.items():
            try:
                await t
            except asyncio.CancelledError:
                pass  # the cancellation we just requested — expected
            except Exception:
                # A loop that already died must not abort stop(), but its
                # death is a real failure, not shutdown noise — surface it
                # instead of swallowing it with the cancellation.
                log.exception("group loop %r for %s failed during stop",
                              group, self.model.name)
        self._tasks.clear()
        # Requests still queued (never dispatched) must not hang their
        # clients: fail them explicitly (ADVICE r1: stop() cleared queues
        # without resolving futures).
        err = RuntimeError(f"server shutting down; {self.model.name} not served")
        for group, q in self._queues.items():
            waiting = list(self._skipped.pop(group, ()))
            while not q.empty():
                waiting.append(q.get_nowait())
            for req in waiting:
                self._pending -= 1
                if not req.future.done():
                    req.future.set_exception(err)
        self._queues.clear()
        self._waiting_units.clear()
        if self._dispatch_tasks:
            await asyncio.gather(*self._dispatch_tasks, return_exceptions=True)
        self._maybe_idle()
        if self._own_stages:
            self.stages.shutdown()

    # -- submission (event loop) --------------------------------------------
    def submit(self, item: Any, group: Hashable = None,
               deadline_at: float | None = None,
               priority: str | None = None,
               ctx: Any = None) -> asyncio.Future:
        """Enqueue one decoded request; returns a Future of its result.

        ``deadline_at`` (perf_counter clock) is the request's absolute
        deadline: if it expires while the request is still queued, the
        future fails with DeadlineExceeded instead of dispatching.
        ``priority`` labels the request's queue-wait histogram (the fleet
        scheduler's arbitration happened BEFORE submit — by here the
        request is admitted either way). ``ctx`` (obs.TraceContext)
        collects the request's queue/phase spans when the HTTP layer is
        tracing it."""
        if not self._running or self._gate is None:
            raise RuntimeError(f"batcher for {self.model.name} not started")
        if self._pending >= self.cfg.max_queue:
            self._c_shed.inc()
            raise QueueFull(self.model.name)
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        req = _Request(item=item, group=group, future=fut,
                       enqueued_at=time.perf_counter(), deadline_at=deadline_at,
                       priority=priority, ctx=ctx,
                       units=self._item_units(item, group))
        q = self._queues.get(group)
        if q is None:
            q = self._queues[group] = asyncio.Queue()
            self._skipped[group] = deque()
            self._waiting_units[group] = 0
            self._tasks[group] = loop.create_task(self._group_loop(group, q))
        q.put_nowait(req)
        self._waiting_units[group] += req.units
        self._pending += 1
        self._idle_event.clear()
        self._g_queue_depth.set(self._pending)
        return fut

    def submit_threadsafe(self, item: Any, group: Hashable = None,
                          deadline_at: float | None = None,
                          priority: str | None = None,
                          ctx: Any = None) -> cf.Future:
        """Loop-safe submit for callers OFF the batcher's event loop — the
        parallel ingest loops (ISSUE 11; ``[server] ingest_loops``) and any
        embedding thread. Schedules the real ``submit`` on the owning loop
        (captured at ``start``) and returns a ``concurrent.futures.Future``
        of the result; submit-time errors (QueueFull, RuntimeError) arrive
        through the returned future instead of raising here. Cancelling the
        returned future does NOT cancel the queued request (cancel
        propagation across loops would race the flush; the request's own
        deadline bounds it instead). On the owning loop, call ``submit``
        directly — the hop would deadlock a caller that blocks on the
        result."""
        loop = self._loop
        if not self._running or loop is None:
            raise RuntimeError(f"batcher for {self.model.name} not started")
        out: cf.Future = cf.Future()

        def _do() -> None:
            try:
                fut = self.submit(item, group=group, deadline_at=deadline_at,
                                  priority=priority, ctx=ctx)
            except Exception as e:  # QueueFull / stopped: through the future
                out.set_exception(e)
                return

            def _done(f: asyncio.Future) -> None:
                if out.cancelled():
                    return
                if f.cancelled():
                    out.cancel()
                elif f.exception() is not None:
                    out.set_exception(f.exception())
                else:
                    out.set_result(f.result())

            fut.add_done_callback(_done)

        loop.call_soon_threadsafe(_do)
        return out

    def revive_group_loops(self) -> int:
        """Watchdog hook: restart group-accumulation tasks that died.

        A group loop only exits on stop() (cancelled while not running); any
        other completion — an escaped exception, an injected kill — orphans
        its queue and hangs every future routed to that group. The watchdog
        calls this on its sweep; requests the dead loop had already pulled
        into its local batch are lost (their futures resolve at the server's
        request timeout), but everything still queued is served by the
        revived task."""
        if not self._running:
            return 0
        revived = 0
        loop = asyncio.get_running_loop()
        for group, q in self._queues.items():
            t = self._tasks.get(group)
            if t is not None and not t.done():
                continue
            if t is not None and not t.cancelled() and t.exception() is not None:
                log.error("group loop %r for %s died: %r — restarting",
                          group, self.model.name, t.exception())
            self._tasks[group] = loop.create_task(self._group_loop(group, q))
            revived += 1
        return revived

    def _maybe_idle(self) -> None:
        """Signal drain() waiters when no accepted work remains. Spurious
        sets are fine — drain re-checks under its clear/recheck discipline."""
        if self._idle_event is not None and self._pending == 0 \
                and not self._dispatch_tasks:
            self._idle_event.set()

    async def drain(self, deadline: float) -> bool:
        """Graceful drain: wait until every accepted request (queued or in
        flight) has resolved, bounded by ``deadline`` (event-loop time).
        The caller stops admitting new work first (server.draining).

        Wakes on the idle event set by the last completion instead of
        polling on an interval (the old 20 ms sleep loop added avoidable
        shutdown latency and jitter at high batch rates)."""
        loop = asyncio.get_running_loop()
        while self._pending > 0 or self._dispatch_tasks:
            timeout = deadline - loop.time()
            if timeout <= 0:
                break
            # clear-then-recheck: the loop is single-threaded, so a
            # completion between the recheck and wait() is impossible and
            # no wakeup can be missed.
            self._idle_event.clear()
            if self._pending == 0 and not self._dispatch_tasks:
                break
            try:
                await asyncio.wait_for(self._idle_event.wait(), timeout)
            except asyncio.TimeoutError:
                break
        self._maybe_idle()  # leave the event consistent for the next drain
        return self._pending == 0 and not self._dispatch_tasks

    def _expire_dead(self, reqs: list[_Request],
                     adjust_pending: bool) -> list[_Request]:
        """Fail requests whose per-request deadline has passed (-> fast 504,
        ``deadline_exceeded_total``) and drop already-done futures; returns
        the still-live rest. ``adjust_pending`` settles the queue-depth
        accounting for dropped requests when the batch-wide decrement has
        not run yet (the admission-wait call sites)."""
        now = time.perf_counter()
        live: list[_Request] = []
        n_expired = 0
        for r in reqs:
            if r.future.done():  # cancelled while queued (client gone)
                if adjust_pending:
                    self._pending -= 1
                continue
            if r.deadline_at is not None and now >= r.deadline_at:
                r.future.set_exception(DeadlineExceeded(
                    "deadline expired after "
                    f"{(now - r.enqueued_at) * 1e3:.0f} ms in queue"))
                n_expired += 1
                if adjust_pending:
                    self._pending -= 1
                continue
            live.append(r)
        if n_expired:
            self._c_deadline.inc(n_expired)
        if adjust_pending and len(live) != len(reqs):
            self._g_queue_depth.set(self._pending)
            self._maybe_idle()
        return live

    # -- adaptive flush scheduling (event loop) ------------------------------
    def _flush_headroom(self, batch: list[_Request],
                        rows: "_Rows | None" = None) -> float:
        """Earliest-deadline flush bound (perf_counter clock): the batch must
        dispatch while ~EWMA(batch duration) + slack still fits before the
        earliest per-request deadline (Clockwork P3 — duration is
        predictable, so schedule against it instead of discovering the
        deadline at dispatch). +inf when no member carries a deadline."""
        earliest = min((r.deadline_at for r in batch
                        if r.deadline_at is not None), default=None)
        if earliest is None:
            return float("inf")
        bucket = self.model.bucket_for(
            len(batch) if rows is None else rows.n, group=batch[0].group)
        est_ms = self._ewma_ms.get(bucket, 0.0)
        return earliest - (est_ms + self.adaptive_cfg.slack_ms) / 1e3

    def _aimd_update(self, group: Hashable, tgt: float, n: int,
                     target_n: int, timer_flush: bool,
                     pressure: bool) -> None:
        """AIMD (Clipper P1): a batch that filled to target WITH more work
        still queued (``pressure``) grows the target additively; a
        timer-driven partial flush shrinks it multiplicatively toward
        min_target. A batch that fills with an empty queue is equilibrium —
        growing on it would make lone sequential requests at target 1 flap
        between immediate and full-timer flushes. Light load therefore
        converges to immediate single-request flushes, saturation to full
        buckets."""
        acfg = self.adaptive_cfg
        if n >= target_n and pressure:
            tgt = min(float(max(self.cfg.batch_buckets)), tgt + acfg.increase)
        elif timer_flush and n < target_n:
            tgt = max(float(acfg.min_target), tgt * acfg.decrease)
        self._targets[group] = tgt
        self._g_target.set(tgt)

    def _observe_batch_duration(self, bucket: tuple, dur_ms: float) -> None:
        prev = self._ewma_ms.get(bucket)
        alpha = self.adaptive_cfg.ewma_alpha
        ewma = dur_ms if prev is None else prev + alpha * (dur_ms - prev)
        self._ewma_ms[bucket] = ewma
        self._g_ewma.set(ewma)

    # -- the close rule (event loop) ------------------------------------------
    def _predicted_ms(self, bucket: tuple) -> float:
        """Device time a launch of ``bucket`` is expected to take; the
        longest launch seen where this bucket has not run yet."""
        return self._device_ms.get(bucket) or max(
            self._device_ms.values(), default=0.0)

    def _queued_ms(self, replica: int, now: float) -> float:
        """Device time still queued on a replica: its staged launches, less
        what the oldest has run (never more than was predicted for it)."""
        staged = self._launches[replica]
        if not staged:
            return 0.0
        head_ms, head_at, _ = staged[0]
        ran_ms = (now - max(head_at, self._last_done[replica])) * 1e3
        return sum(e[0] for e in staged) - min(head_ms, max(0.0, ran_ms))

    def _observe_launch_end(self, bucket: tuple, replica: int, entry: list,
                            t_launched: float, t_done: float) -> None:
        """A launch's fetch has returned. The device runs a replica's
        launches in order, so those staged before it have ended too, even
        where their fetch is still on its way back (two fetch threads under
        one GIL: a 5 ms launch's fetch can overtake the 300 ms launch's it
        ran behind); they count as ended from here on. Only a launch whose
        predecessors' ends were all seen gives a sample of device time:
        from the later of its launch and the previous end to its own."""
        in_order = entry[2]
        for ahead in self._launches[replica]:
            if ahead is entry:
                break
            ahead[0], ahead[2] = 0.0, False
            in_order = False
        if in_order:
            seen = self._device_seen.setdefault(bucket, deque(maxlen=_RECENT))
            seen.append(
                (t_done - max(t_launched, self._last_done[replica])) * 1e3)
            self._device_ms[bucket] = _second(seen)
        self._last_done[replica] = max(t_done, self._last_done[replica])

    def _fetch_timed(self, outputs: Any) -> tuple[Any, float]:
        """``runtime.fetch``, and when it returned by the fetch thread's own
        clock: with the host busy the event loop comes to it much later."""
        return self.runtime.fetch(outputs), time.perf_counter()

    def _section_is_short(self, replica: int) -> bool:
        """Less than ``depth - 1`` full launches of device time are queued
        behind the running one: the section is full by count, not by time
        (never so at depth 1, which serialises it)."""
        return self._queued_ms(replica, time.perf_counter()) < \
            (self.depth - 1) * max(self._device_ms.values(), default=0.0)

    def _reserve_ms(self) -> float:
        """How long before the device runs dry a batch must close: twice
        what a batch has lately taken from its close to its launch."""
        return 2.0 * (self._stage_ms or 0.0)

    def _close_wait_s(self, held: int, full: bool) -> float:
        """Seconds until the next batch may close, ``held`` being closed and
        not yet through the pipeline (AdmissionGate). Before a batch has
        been measured, by count: the device section. After: when the replica
        that runs dry first has no more than the reserve queued, batches
        closed but not yet staged going to the emptiest — or at once if
        the batch is ``full``: waiting adds nothing to it, and staging it
        early is what hides a slow host. Never more than ``assemble_ahead``
        batches past the device section."""
        if self._stage_ms is None:
            return 0.0 if held < self._device_cap else math.inf
        if held >= self._device_cap + self.pipeline_cfg.assemble_ahead:
            return math.inf
        if full:
            return 0.0
        now = time.perf_counter()
        queued = [self._queued_ms(r, now) for r in range(len(self._launches))]
        for ms in self._closed_ms.values():
            queued[queued.index(min(queued))] += ms
        return max(0.0, min(queued) - self._reserve_ms()) / 1e3

    def _close_limit(self, n: int, queued: int, group: Hashable) -> int:
        """How far the close may grow a batch of ``n`` rows with ``queued``
        more rows' worth waiting: to the largest bucket, unless that takes the batch past
        the edge of the bucket it occupies into a launch that is dearer per
        item than filling this one, by the device time measured per bucket
        plus the staging every launch pays (128 outstanding items must not
        ride 64 at a time in a 256-wide launch that costs the same full or
        empty). A bucket not measured yet is not held against the batch."""
        max_bucket = max(self.cfg.batch_buckets)
        total = min(n + queued, max_bucket)
        here = self.model.bucket_for(n, group=group)
        there = self.model.bucket_for(total, group=group)
        if there == here:
            return max_bucket
        ms_here = self._device_ms.get(here)
        ms_there = self._device_ms.get(there)
        if ms_here is None or ms_there is None:
            return max_bucket
        stage_ms = self._stage_ms or 0.0
        if (ms_there + stage_ms) / total <= (ms_here + stage_ms) / here[0]:
            return max_bucket
        return here[0]

    # -- accumulation (event loop) ------------------------------------------
    def _join(self, batch: list[_Request], rows: _Rows, req: _Request,
              limit: int) -> bool:
        """Place ``req`` in a row of ``batch`` if one takes it, or a new row
        while the batch occupies fewer than ``limit``."""
        if not rows.place(req, limit):
            return False
        batch.append(req)
        self._waiting_units[req.group] -= req.units
        return True

    def _still_live(self, batch: list[_Request], rows: _Rows
                    ) -> tuple[list[_Request], _Rows]:
        """``batch`` less what has expired or gone while it waited, and the
        rows of what is left."""
        live = self._expire_dead(batch, adjust_pending=True)
        if len(live) != len(batch):
            rows = _Rows.of(live, rows.width, rows.per_row)
        return live, rows

    async def _group_loop(self, group: Hashable, q: asyncio.Queue) -> None:
        # A batch is counted in ROWS against the batch buckets. For a model
        # of one item a row (ServingModel.row_shape's default) rows are
        # items and every comparison below is the count of requests.
        max_bucket = max(self.cfg.batch_buckets)
        width, per_row = self._row_shape(group)
        skipped = self._skipped[group]
        deadline_s = self.cfg.deadline_ms / 1e3
        acfg = self.adaptive_cfg
        adaptive = acfg.enabled
        init_target = float(acfg.initial_target or max_bucket)
        while True:
            if self.injector is not None:
                # Chaos: an escaped exception kills this task, exactly the
                # failure revive_group_loops exists to repair.
                self.injector.check("kill_group_loop", self.model.name)
            # What the last close looked past is older than the queue.
            req = skipped.popleft() if skipped else await q.get()
            batch: list[_Request] = []
            rows = _Rows(width, per_row)
            self._join(batch, rows, req, max_bucket)
            tgt = self._targets.get(group, init_target)
            target_n = (min(max_bucket, max(acfg.min_target, math.ceil(tgt)))
                        if adaptive else max_bucket)
            timer_flush = False
            try:
                # Max-wait backstop: adaptive mode additionally bounds the
                # wait by the deadline headroom, and stops accumulating at
                # the AIMD target instead of the largest bucket.
                flush_at = req.enqueued_at + deadline_s
                while rows.n < target_n:
                    limit = flush_at
                    if adaptive:
                        limit = min(limit, self._flush_headroom(batch, rows))
                    timeout = limit - time.perf_counter()
                    if timeout <= 0:
                        timer_flush = True
                        break
                    if skipped:
                        self._join(batch, rows, skipped.popleft(), max_bucket)
                        continue
                    try:
                        self._join(batch, rows,
                                   await asyncio.wait_for(q.get(), timeout),
                                   max_bucket)
                    except asyncio.TimeoutError:
                        timer_flush = True
                        break
                # The flush decision: the batch exists from here on.
                t_flush = time.perf_counter()
                bid = self._mint_bid()
                reason = "timer" if timer_flush else "target"
                self._c_flushes[reason].inc()
                trace_mark("tpuserve.accumulate", req.enqueued_at, t_flush,
                           model=self.model.name, batch=bid, n=len(batch),
                           reason=reason)
                if adaptive:
                    self._aimd_update(
                        group, tgt, rows.n, target_n, timer_flush,
                        pressure=bool(skipped) or not q.empty())
                # Backpressure, and the close: the gate opens when the
                # device time still queued has fallen to the reserve
                # (_close_wait_s), so the group task waits HERE, with its
                # membership still open, until the device is about to need
                # the batch — not two launches earlier, frozen behind
                # assembled batches while later arrivals go to the batch
                # behind. The wait is bounded by the earliest per-request
                # deadline in the batch (P3): a request that dies behind
                # slow in-flight work fails fast AT its deadline, instead of
                # being discovered dead only when capacity finally frees.
                batch, rows = self._still_live(batch, rows)

                def is_full() -> bool:
                    # What waits would fill the largest bucket's rows.
                    return rows.units + self._waiting_units[group] >= \
                        max_bucket * width

                while batch:
                    earliest = min((r.deadline_at for r in batch
                                    if r.deadline_at is not None),
                                   default=None)
                    if earliest is None:
                        await self._gate.acquire(None, is_full)
                        break
                    slot_wait = earliest - time.perf_counter()
                    if slot_wait > 0:
                        try:
                            await self._gate.acquire(slot_wait, is_full)
                            break
                        except asyncio.TimeoutError:
                            pass
                    batch, rows = self._still_live(batch, rows)
                if not batch:
                    continue  # everything expired; no admission was taken
                t_slot = time.perf_counter()
                self._h_phase["slot_wait"].observe((t_slot - t_flush) * 1e3)
                trace_mark("tpuserve.slot_wait", t_flush, t_slot,
                           model=self.model.name, batch=bid)
            except asyncio.CancelledError:
                # stop() cancelled us mid-accumulation: requests already
                # pulled off the queue must fail, not hang their clients
                # (what a close looked past is stop()'s, with the queue).
                err = RuntimeError(
                    f"server shutting down; {self.model.name} not served")
                self._pending -= len(batch)
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(err)
                self._maybe_idle()
                raise
            # The close: anything that queued while we waited (deadline or
            # admission) would only wait longer — fold it into this batch, up
            # to the largest bucket or the edge _close_limit holds it to. This
            # makes batch size track device speed instead of deadline x
            # arrival-rate (SURVEY.md §7 hard-part 2). Where rows are shared
            # the close looks past a request that fits no row for later ones
            # that fill holes, at most as many as the batch could hold: those
            # keep their place ahead of the queue, so the oldest request
            # waiting is always in the next batch. The close is never put
            # off for it.
            limit = self._close_limit(
                rows.n, -(-self._waiting_units[group] // width), group)
            past: list[_Request] = []
            while (rows.n < limit or rows.has_open) \
                    and len(past) < limit * per_row:
                if skipped:
                    late = skipped.popleft()
                elif not q.empty():
                    late = q.get_nowait()
                else:
                    break
                if self._join(batch, rows, late, limit):
                    late.at_close = True
                else:
                    past.append(late)
            skipped.extendleft(reversed(past))
            self._pending -= len(batch)
            self._g_queue_depth.set(self._pending)
            live = [r for r in batch if not r.future.cancelled()]
            # Last deadline check at flush: requests drained from the queue
            # above may have expired too. Their pending count was already
            # settled in the batch-wide decrement.
            live = self._expire_dead(live, adjust_pending=False)
            if not live:
                self._gate.release()
                self._maybe_idle()
                continue
            now = time.perf_counter()
            now_wall = time.time()
            for r in live:
                wait_ms = (now - r.enqueued_at) * 1e3
                tid = r.ctx.trace_id if r.ctx is not None else None
                self._h_phase["queue"].observe(wait_ms, trace_id=tid)
                self._h_qwait[r.priority or self._default_priority].observe(
                    wait_ms, trace_id=tid)
                if r.ctx is not None:
                    r.ctx.span("queue", now_wall - wait_ms / 1e3, now_wall,
                               tid=self.model.name)
            # What the gate reads next: this batch's device time is queued
            # from now on, before it has reached a replica.
            self._closed_ms[bid] = self._predicted_ms(self.model.bucket_for(
                len({r.row for r in live}), group=group))
            self._gate.poke()
            task = asyncio.get_running_loop().create_task(
                self._dispatch(live, group, bid, t_slot))
            self._dispatch_tasks.add(task)
            task.add_done_callback(self._dispatch_tasks.discard)
            task.add_done_callback(lambda _t: self._maybe_idle())

    # -- dispatch (stage executors do the blocking work) ---------------------
    def _mint_bid(self) -> int:
        """A batch id, unique per model (event loop only)."""
        self._bid_seq += 1
        return self._bid_seq

    async def _dispatch(self, reqs: list[_Request], group: Hashable,
                        bid: int, t_close: float) -> None:
        """Run one batch through the pipeline; on failure, retry/split per
        config before failing futures. Failure is contained to this batch
        either way: the group task and server keep serving."""
        name = self.model.name
        self._inflight_now += 1
        self._inflight_peak = max(self._inflight_peak, self._inflight_now)
        self._g_inflight.set(self._inflight_now)
        try:
            try:
                await self._execute(reqs, group, bid, t_close)
            except Exception as e:
                log.exception("batch dispatch failed for %s", name)
                self._c_batch_errors.inc()
                if self.breaker is not None:
                    self.breaker.record_failure()
                live = [r for r in reqs if not r.future.done()]
                if self.cfg.batch_retry and live:
                    try:
                        await self._retry(live, group)
                    except Exception as retry_err:
                        # The retry machinery itself must never leave
                        # futures unresolved (clients would hang to 504).
                        log.exception("batch retry machinery failed for %s", name)
                        for r in live:
                            if not r.future.done():
                                r.future.set_exception(retry_err)
                else:
                    for r in live:
                        r.future.set_exception(e)
        finally:
            self._inflight_now -= 1
            self._g_inflight.set(self._inflight_now)
            self._closed_ms.pop(bid, None)  # failed before it was staged
            self._gate.release()

    async def _acquire_staging(self, reqs: list[_Request]) -> tuple[int | None, int | None]:
        """Pick a replica and take one of its depth-k staging slots, bounded
        by the earliest per-request deadline. The first choice is the
        runtime's least-loaded pick (fed each pool's live occupancy); when
        that pool is exhausted the fallback scans the REMAINING pools in
        ascending-occupancy order — the old fixed index-order scan
        systematically filled low-index replicas first and starved
        high-index chips under bursty load (ISSUE 7 satellite). Returns
        (replica, slot), or (None, None) when every request expired while
        waiting — their futures already carry DeadlineExceeded (fast
        504)."""
        live = [r for r in reqs if not r.future.done()]
        n = len(self._staging)
        while True:
            loads = [p.in_use for p in self._staging]
            first = self.runtime.pick_replica(loads) if n > 1 else 0
            slot = self._staging[first].try_acquire()
            if slot is not None:
                return self._staged(first), slot
            for i in sorted((j for j in range(n) if j != first),
                            key=lambda j: (loads[j], (j - first) % n)):
                slot = self._staging[i].try_acquire()
                if slot is not None:
                    return self._staged(i), slot
            live = self._expire_dead(live, adjust_pending=False)
            if not live:
                return None, None
            earliest = min((r.deadline_at for r in live
                            if r.deadline_at is not None), default=None)
            timeout = (None if earliest is None
                       else max(0.0, earliest - time.perf_counter()))
            try:
                slot = await self._staging[first].acquire(timeout)
                return self._staged(first), slot
            except asyncio.TimeoutError:
                continue

    def _staged(self, replica: int) -> int:
        """Record a staging acquire on the replica's occupancy gauge."""
        self._g_replica_inflight[replica].set(self._staging[replica].in_use)
        return replica

    def _release_staging(self, replica: int, slot: int) -> None:
        self._staging[replica].release(slot)
        self._g_replica_inflight[replica].set(self._staging[replica].in_use)

    async def _execute(self, reqs: list[_Request], group: Hashable,
                       bid: int | None = None,
                       t_close: float | None = None) -> None:
        """Assemble + run + postprocess one batch through the stage
        pipeline, resolving futures on success. Raises on failure WITHOUT
        failing futures — the caller owns the retry policy."""
        name = self.model.name
        rows = _renumbered(reqs)    # as the close placed them
        n_rows = max(rows) + 1
        bucket = self.model.bucket_for(n_rows, group=group)
        fill = n_rows / bucket[0]
        self._g_fill.set(fill)
        self._c_batches.inc()
        # Batch identity for trace correlation (ISSUE 12), minted where the
        # batch was formed (_group_loop), unique per model. The ring's batch
        # span carries its member trace ids; each member's per-phase spans
        # carry this id back, so a request tree and the batch timeline join
        # both ways, and the profiler's spans (tpuserve.accumulate ..
        # tpuserve.postproc) carry it too. Retries/splits re-enter here
        # without one and get their own — a retried request's tree visibly
        # contains BOTH attempts.
        if bid is None:
            bid = self._mint_bid()
        span = {"batch": bid, "bucket": bucket_label(bucket), "n": len(reqs),
                "rows": n_rows}
        ctxs = [r.ctx for r in reqs if r.ctx is not None]
        ex_tid = ctxs[0].trace_id if ctxs else None

        wall0 = time.time()
        t0 = time.perf_counter()

        def mark(phase: str, t_a: float, t_b: float) -> None:
            """Observe one batch phase (exemplar = a member trace id) and
            append the span to every traced member, batch-tagged."""
            self._h_phase[phase].observe((t_b - t_a) * 1e3, trace_id=ex_tid)
            for c in ctxs:
                c.span(phase, wall0 + (t_a - t0), wall0 + (t_b - t0),
                       tid=name, batch=bid)

        items = [r.item for r in reqs]
        # Assemble stage: into a recycled arena buffer when provably
        # equivalent, else the model's allocating assemble.
        # Only a batch whose items share rows says which row each is in
        # (ServingModel.row_shape); one item a row is the order they come in.
        placed = (rows,) if n_rows < len(reqs) else ()
        lease = self.arena.acquire(bucket) if self.arena is not None else None
        try:
            if lease is not None:
                host_batch = await self.stages.run(
                    name, "assemble", self.model.assemble_into,
                    items, bucket, lease.buf, *placed, span=span)
            else:
                host_batch = await self.stages.run(
                    name, "assemble", self.model.assemble, items, bucket,
                    *placed, span=span)
            t1 = time.perf_counter()
            mark("preproc", t0, t1)

            # Device section: a staging slot bounds batches inside
            # [h2d..fetch] to depth-k per replica; the wait is
            # deadline-bounded (fast 504 for work nobody awaits).
            replica, slot = await self._acquire_staging(reqs)
            if replica is None:
                return  # every request expired; nothing to run
            t_staged = time.perf_counter()
            trace_mark("tpuserve.staging_wait", t1, t_staged,
                       model=name, batch=bid, replica=replica)
            entry = [self._closed_ms.pop(bid, None)
                     or self._predicted_ms(bucket), t_staged, True]
            self._launches[replica].append(entry)
            try:
                if self.injector is not None:
                    delay = self.injector.delay_s("slow_dispatch", name)
                    if delay > 0:
                        await asyncio.sleep(delay)
                    self.injector.check("batch_error", name)
                # h2d stage: batched device_put of the whole pytree +
                # async dispatch of the compiled call.
                outputs = await self.stages.run(
                    name, "h2d", self.runtime.run, bucket, host_batch,
                    replica, span=span)
                t2 = time.perf_counter()
                mark("h2d", t1, t2)

                # fetch stage: "compute" = dispatch-to-ready wall time.
                # With per-stage executors this is the device's own
                # queue + MXU time; it no longer absorbs other batches'
                # transfer waits the way the shared-pool path did
                # (docs/PERFORMANCE.md "Phase semantics").
                np_out, t_ready = await self.stages.run(
                    name, "fetch", self._fetch_timed, outputs, span=span)
                t3 = time.perf_counter()
                mark("compute", t2, t3)
                self._observe_launch_end(bucket, replica, entry, t2, t_ready)
                self._stage_seen.append(
                    ((t2 - (t_close or t0)) - (t_staged - t1)) * 1e3)
                self._stage_ms = _second(self._stage_seen, largest=True)
                self._c_device_seconds[replica].inc(t3 - t2)
                if self.device_time_cb is not None:
                    # Fleet device-time ledger: the device section
                    # (dispatch-to-ready) is what models compete for.
                    self.device_time_cb(t3 - t2)
            finally:
                self._launches[replica].remove(entry)
                self._release_staging(replica, slot)
                self._gate.poke()  # less is queued: decide again
        finally:
            if lease is not None:
                # Safe only now: the fetch completing proves the device is
                # done reading the batch (CPU-backend device_put may alias
                # this buffer).
                self.arena.release(lease)

        results = await self.stages.run(
            name, "postproc", self.model.host_postprocess, np_out, len(reqs),
            span=span)
        t4 = time.perf_counter()
        mark("postproc", t3, t4)
        self._c_items.inc(len(reqs))
        self._c_rows.inc(n_rows)
        n_close = sum(r.at_close for r in reqs)
        self._c_joined["close"].inc(n_close)
        self._c_joined["accumulate"].inc(len(reqs) - n_close)
        # Feed the adaptive scheduler's per-bucket duration model (tracked
        # even with adaptive off: the gauge is useful on its own).
        self._observe_batch_duration(bucket, (t4 - t0) * 1e3)
        # Span start/duration from the same wall-clock capture: mixing a
        # perf_counter delta into a fresh time.time() read skewed span
        # starts by the time spent between the two calls.
        self.metrics.tracer.add(
            f"batch[{bucket}]", wall0, wall0 + (t4 - t0),
            tid=name, trace_id=ex_tid, n=len(reqs), fill=fill, batch=bid,
            # Member trace ids, capped: joins the ring's batch timeline to
            # the flight recorder's per-request trees without letting a
            # 64-wide bucket bloat every ring event.
            trace_ids=[c.trace_id for c in ctxs[:8]],
        )
        if self.breaker is not None:
            self.breaker.record_success()
        for r, res in zip(reqs, results):
            if not r.future.done():
                r.future.set_result(res)

    async def _retry(self, reqs: list[_Request], group: Hashable) -> None:
        """One-shot batch retry with poison isolation.

        The whole batch re-assembles and re-runs once (absorbing transient
        faults); if that fails and ``retry_split`` is on, the batch bisects
        recursively — each sub-batch runs once — so a single poison item
        fails only its own future while every other lane succeeds. Worst
        case a lane re-runs O(log batch) times; every path ends with all
        futures resolved."""
        name = self.model.name
        self._c_retries.inc()

        async def run_split(rs: list[_Request]) -> None:
            live = [r for r in rs if not r.future.done()]
            if not live:
                return
            try:
                await self._execute(live, group)
            except Exception as e:
                self._c_retry_failures.inc()
                if len(live) == 1 or not self.cfg.retry_split:
                    if len(live) == 1 and self.cfg.retry_split:
                        self._c_poison.inc()
                    for r in live:
                        if not r.future.done():
                            r.future.set_exception(e)
                else:
                    mid = (len(live) + 1) // 2
                    await run_split(live[:mid])
                    await run_split(live[mid:])

        await run_split(reqs)

    # -- introspection -------------------------------------------------------
    @property
    def pending(self) -> int:
        """Requests accepted but not yet flushed into a batch (the
        scheduler's demand signal and the idle-demotion guard)."""
        return self._pending

    def predicted_service_s(self, n_items: int = 1) -> float | None:
        """Predicted seconds of service time for a request of ``n_items``
        once it reaches the front of the queue: the batch-duration EWMA of
        the smallest bucket that covers it (Clockwork P3 — duration is
        predictable per (model, bucket)). Falls back to the largest
        observed bucket when nothing that small has run; None before any
        batch has completed."""
        if not self._ewma_ms:
            return None
        covering = [(b, ms) for b, ms in self._ewma_ms.items()
                    if ms > 0 and b[0] >= n_items]
        if covering:
            _, ms = min(covering, key=lambda kv: kv[0][0])
        else:
            _, ms = max(self._ewma_ms.items(), key=lambda kv: kv[0][0])
            if ms <= 0:
                return None
        return ms / 1e3

    def estimate_clear_s(self) -> float | None:
        """Estimated seconds for the current queue to clear at the observed
        serving rate. Deliberately UNCLAMPED (ISSUE 10 satellite): the
        fleet scheduler's admission math consumes this raw number;
        ``clamp_retry_after_s`` derives the [1, 30] s client-facing
        Retry-After hint for queue-full 429s from it (docs/ROBUSTNESS.md).
        Rate = the best items/s any bucket has
        demonstrated (its size over its batch-duration EWMA), so the hint
        tracks what the device is actually doing instead of a constant.
        None before any batch has completed (no EWMA yet) or with an empty
        queue."""
        if self._pending <= 0:
            return None
        rate = max((b[0] / (ms / 1e3)
                    for b, ms in self._ewma_ms.items() if ms > 0),
                   default=0.0)
        if rate <= 0:
            return None
        return self._pending / rate

    def pipeline_stats(self) -> dict:
        """The /stats "pipeline" block entry for this model
        (docs/PERFORMANCE.md "Reading the metrics")."""
        # Per-chip serving attribution (ISSUE 7): dispatch count and live
        # device-section occupancy per replica, so an operator (or the
        # multichip smoke) sees a starved chip as a row of zeros instead of
        # a vaguely-low aggregate.
        batches = (self.runtime.replica_batches()
                   if hasattr(self.runtime, "replica_batches")
                   else [None] * len(self._staging))
        return {
            "mode": "direct",
            "admission": self._device_cap + self.pipeline_cfg.assemble_ahead,
            # What the close rule goes by (ms): a batch closes when the
            # device time queued has fallen to the reserve.
            "close": {
                "reserve_ms": round(self._reserve_ms(), 2),
                "stage_ms": (None if self._stage_ms is None
                             else round(self._stage_ms, 2)),
                "device_ms": {repr(b): round(v, 2)
                              for b, v in self._device_ms.items()},
            },
            # Batches are counted in rows: what the launches carried. Items
            # over rows is items a row (1.0 where rows are not shared);
            # looked_past: requests the last closes fitted into no row,
            # waiting ahead of their queue.
            "rows": {
                "launched": int(self._c_rows.value),
                "items": int(self._c_items.value),
                "items_per_row": (round(self._c_items.value
                                        / self._c_rows.value, 3)
                                  if self._c_rows.value else None),
                "looked_past": sum(len(d) for d in self._skipped.values()),
            },
            "inflight": self._inflight_now,
            "inflight_peak": self._inflight_peak,
            "adaptive": {
                "enabled": self.adaptive_cfg.enabled,
                "targets": {repr(g): round(t, 2)
                            for g, t in self._targets.items()},
                "batch_ewma_ms": {repr(b): round(v, 2)
                                  for b, v in self._ewma_ms.items()},
            },
            "depth": self.depth,
            "replicas": len(self._staging),
            "staging_in_use": [p.in_use for p in self._staging],
            "arena": self.arena.stats() if self.arena is not None else None,
            "per_replica": [
                {"replica": i,
                 "batches_total": batches[i],
                 "staging_in_use": p.in_use,
                 "occupancy": round(p.in_use / self.depth, 3)
                 if self.depth else 0.0}
                for i, p in enumerate(self._staging)],
        }
