"""HTTP serving layer (SURVEY.md §2 C1, §3c).

The reference's web layer is a Flask/WSGI predict handler (BASELINE.json);
threads + blocking handlers don't suit a batching TPU server, so this layer is
a single asyncio event loop (aiohttp) where handlers only:

1. read the body,
2. decode it in the shared threadpool (``model.host_decode``),
3. submit to the batcher and await the per-request Future,
4. JSON-encode the result.

All device work happens behind the batcher. Endpoints:

- ``POST /v1/models/{name}:predict`` (aliases ``:classify``, ``:detect``,
  ``:generate``) — body is an image (``image/jpeg``, ``image/png``,
  ``application/x-npy``) or JSON (``{"text": ...}``, ``{"prompt": ...}``).
- ``GET  /healthz``     — liveness + per-model canary status.
- ``GET  /metrics``     — Prometheus text format.
- ``GET  /stats``       — JSON latency/throughput summary.
- ``GET  /debug/trace`` — Chrome trace JSON: the span ring (``?limit=``,
  ``?since_us=``) or one recorded request's tree (``?trace_id=``).
- ``GET  /debug/slow``  — flight recorder: slowest-N span trees per model
  plus every errored/shed request (docs/OBSERVABILITY.md).
- ``GET  /v1/models``   — model inventory (buckets, mesh, dtype).
- ``GET  /``            — minimal HTML upload page for manual poking.
- ``POST /admin/models/{name}:reload``   — staged, canary-gated weight swap
  (tpuserve.lifecycle); ``:rollback`` restores the retained previous
  version; ``GET /admin/models/{name}/versions`` lists the history.

Error mapping: decode failure -> 400, unknown model -> 404, queue full -> 429,
request deadline exceeded -> 504, batch failure (after retry) -> 500, breaker
open / draining -> 503. Shed responses (429/503) carry ``Retry-After``.

Every predict response — success OR error — carries an ``X-Trace-Id``
header (ISSUE 12): the request's 128-bit trace id, minted at ingest or
adopted from the router tier, joining the response to its recorded span
tree in the flight recorder. Error JSON bodies repeat it as ``trace_id``.
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import contextlib
import functools
import json
import logging
import math
import os
import signal
import socket
import threading
import time

from aiohttp import web

import jax

from tpuserve import frame as frame_wire
from tpuserve import models as modelzoo
from tpuserve import preproc
from tpuserve.analysis import witness
from tpuserve.batcher import (DeadlineExceeded, ModelBatcher, QueueFull,
                              clamp_retry_after_s)
from tpuserve.bench.roofline import compute_split, phase_p50
from tpuserve.cache import ModelCache
from tpuserve.config import ServerConfig, SloConfig
from tpuserve.faults import CircuitBreaker, FaultInjector, Watchdog
from tpuserve.genserve import GenEngine, GenEngineGroup, KVPressure
from tpuserve.hostpipe import StageExecutors
from tpuserve.lifecycle import ModelLifecycle, ReloadRejected
from tpuserve.obs import (PRIORITIES, FlightRecorder, HostClocks, Metrics,
                          TraceContext, exposition_content_type,
                          spans_to_chrome, trace_call)
from tpuserve.runtime import ModelRuntime, build_runtime, configure_jax
from tpuserve.scheduler import FleetScheduler
from tpuserve.scheduler.tenants import TenantLedger
from tpuserve.telemetry import (AuditLog, BlackBoxWriter, EventLog,
                                MetricSampler, PostmortemLog, ProfileCapture,
                                SloEngine, TimeSeriesStore,
                                UtilizationDeriver)
from tpuserve.telemetry import events as events_mod
from tpuserve.telemetry.profile import CaptureBusy

log = logging.getLogger("tpuserve.server")

_VERBS = ("predict", "classify", "detect", "generate")

# Typed aiohttp app keys (string keys are deprecated).
STATE_KEY: "web.AppKey[ServerState]" = web.AppKey("tpuserve_state", object)
# Per-app ingest handles: which accept loop this app serves (ISSUE 11).
INGEST_KEY: "web.AppKey[IngestHandles]" = web.AppKey("tpuserve_ingest", object)

# Client batches at least this big JSON-encode off the event loop (the
# encode for a full bucket of top-k results is hundreds of microseconds —
# enough to stall every other in-flight response at high request rates).
# Smaller responses stay inline: the executor hop costs more than it saves.
_JSON_OFFLOAD_MIN_ITEMS = 32

# Injected worker_hang wedge duration: long enough that the request never
# answers within any sane deadline (the router's hedging/504 owns it), short
# enough that a forgotten armed rule can't pin a connection forever.
_WORKER_HANG_S = 3600.0


def _dumps_utf8(obj) -> bytes:
    return json.dumps(obj).encode("utf-8")


class ModelHandles:
    """Per-model hot-path state hoisted out of handle_predict (ISSUE 5):
    prebound metric objects and config, built once at start(). The handler
    previously paid an f-string format plus a locked registry lookup per
    counter per request, and a linear config scan per request."""

    __slots__ = ("mcfg", "requests", "bad_requests", "timeouts", "total_hist",
                 "body_read_hist", "parse_hist", "frame_errors",
                 "native_fallback")

    def __init__(self, name: str, mcfg, metrics: Metrics) -> None:
        self.mcfg = mcfg
        self.requests = metrics.counter(f"requests_total{{model={name}}}")
        self.bad_requests = metrics.counter(
            f"bad_requests_total{{model={name}}}")
        self.timeouts = metrics.counter(f"timeouts_total{{model={name}}}")
        self.total_hist = metrics.histogram(
            f"latency_ms{{model={name},phase=total}}")
        # Ingest-phase attribution (ISSUE 11, docs/PERFORMANCE.md "The
        # ingest fast path"): body_read = socket-to-memory time for the
        # request body (the HTTP ingress wire), parse = host decode /
        # zero-copy frame parse. Request-scoped twins of the batcher's
        # batch-scoped phases, same latency_ms{phase=} family.
        self.body_read_hist = metrics.histogram(
            f"latency_ms{{model={name},phase=body_read}}")
        self.parse_hist = metrics.histogram(
            f"latency_ms{{model={name},phase=parse}}")
        # Malformed application/x-tpuserve-frame bodies (every one also
        # counts in bad_requests_total; this isolates wire-format trouble).
        self.frame_errors = metrics.counter(
            f"frame_errors_total{{model={name}}}")
        # yuv420 decode served by the 2x-slower PIL fallback although the
        # native shim path was attempted (missing/failed libjpegyuv.so or
        # a non-4:2:0 input); fed by the preproc hook installed at start().
        self.native_fallback = metrics.counter(
            f"native_decode_fallback_total{{model={name}}}")


class IngestHandles:
    """Per-accept-loop prebound ingest counters (ISSUE 11): loop 0 is the
    main serving loop, 1..N-1 the dedicated SO_REUSEPORT ingest threads.
    Balance across loops proves no single accept loop chokes the mesh."""

    __slots__ = ("index", "requests", "bytes")

    def __init__(self, index: int, metrics: Metrics) -> None:
        self.index = index
        self.requests = metrics.ingest_requests_counter(index)
        self.bytes = metrics.ingest_bytes_counter(index)


class ServerState:
    """Everything a running server owns."""

    def __init__(self, cfg: ServerConfig) -> None:
        self.cfg = cfg
        self.metrics = Metrics(cfg.trace_capacity,
                               exemplars=cfg.trace.exemplars)
        # The host's own time (ISSUE 51): the collector's pauses and the
        # CPU by kind of thread, read when the registry is.
        self.host_clocks = HostClocks(self.metrics)
        # Tail-latency flight recorder (ISSUE 12, docs/OBSERVABILITY.md):
        # complete span trees for the slowest-N requests per model plus
        # every errored/shed request, served at /debug/slow and
        # /debug/trace?trace_id=. Thread-safe — every ingest accept loop
        # finishes its own requests into it.
        self.recorder = FlightRecorder(
            slow_n=cfg.trace.slow_n,
            error_capacity=cfg.trace.error_capacity,
            always_record_errors=cfg.trace.always_record_errors,
            metrics=self.metrics)
        self.pool = cf.ThreadPoolExecutor(max_workers=cfg.decode_threads, thread_name_prefix="tpuserve")
        # Pipelined host execution engine (tpuserve.hostpipe): one dedicated
        # thread pool per stage, shared across every model's batcher so work
        # is scheduled at stage granularity (docs/PERFORMANCE.md).
        self.stages = StageExecutors(cfg.pipeline, self.metrics)
        self.models: dict[str, object] = {}
        self.runtimes: dict[str, ModelRuntime] = {}
        # Per-model dispatch engine: ModelBatcher (one-shot locked batches)
        # or GenEngine (iteration-level continuous batching) — both expose
        # the same submit/start/stop/drain/revive surface, so every caller
        # below (canaries, drain, watchdog, handle_predict) is agnostic.
        self.batchers: "dict[str, ModelBatcher | GenEngine]" = {}
        # The GenEngine subset of batchers (feeds the /stats genserve block;
        # built in build() so program compilation happens at startup).
        self.engines: dict[str, GenEngine] = {}
        self.breakers: dict[str, CircuitBreaker] = {}
        # Versioned reload lifecycle (tpuserve.lifecycle).
        self.lifecycles: dict[str, ModelLifecycle] = {}
        # Demand-shaping layer (tpuserve.cache): per-model result cache +
        # single-flight coalescing; empty unless [cache] enabled.
        self.caches: dict[str, ModelCache] = {}
        # Prebound per-model hot-path handles (metrics + config), built at
        # start() so handle_predict does zero registry lookups per request.
        self.handles: dict[str, ModelHandles] = {}
        # Fleet-level SLO scheduler (tpuserve.scheduler): cross-model
        # admission, priority arbitration, warm/cold weight paging. None
        # unless [scheduler] enabled — the per-model batchers then stay
        # fully independent, exactly as before.
        self.scheduler = (FleetScheduler(cfg.scheduler, self.metrics)
                          if cfg.scheduler.enabled else None)
        # Tenant containment (ISSUE 16): X-Api-Key resolution + the
        # weighted device-seconds ledger, enforced at admission in
        # _predict_traced. The fleet scheduler's saturation signal gates
        # fair-share shedding; without a scheduler only rate + quota run.
        self.tenants = (TenantLedger(cfg.tenants, self.metrics)
                        if cfg.tenants.enabled else None)
        if self.tenants is not None and self.scheduler is not None:
            self.tenants.saturated_fn = self.scheduler.saturated
        self.canary_ok: dict[str, bool] = {}
        # Telemetry plane (ISSUE 14, docs/OBSERVABILITY.md "The telemetry
        # plane"): bounded time-series history over every metric, the SLO
        # burn-rate engine over [model.slo] objectives, device-utilization
        # derivation, and on-demand deep profiling. All None when
        # [telemetry] enabled = false.
        self.store: TimeSeriesStore | None = None
        self.sampler: MetricSampler | None = None
        self.slo: SloEngine | None = None
        self.tenant_slo: SloEngine | None = None
        self.util: UtilizationDeriver | None = None
        self.profiler: ProfileCapture | None = None
        if cfg.telemetry.enabled:
            tcfg = cfg.telemetry
            self.store = TimeSeriesStore(
                self.metrics,
                capacity=int(tcfg.history_s / tcfg.sample_interval_s))
            self.slo = SloEngine(self.metrics, self.store,
                                 tcfg.burn_windows_s)
            self.util = UtilizationDeriver(self.metrics, self.store,
                                           tcfg.utilization_window_s)
            hooks = [self.slo.tick, self.util.tick]
            if self.tenants is not None and cfg.tenants.slo_latency_ms > 0:
                # Per-tenant SLO burn (ISSUE 16 satellite): the same
                # burn-rate machinery over tenant_latency_ms{tenant=},
                # one shared objective from [tenants].
                self.tenant_slo = SloEngine(
                    self.metrics, self.store, tcfg.burn_windows_s,
                    metric_fmt="tenant_latency_ms{{tenant={name}}}",
                    label="tenant")
                tenant_slo_cfg = SloConfig(
                    latency_ms=cfg.tenants.slo_latency_ms,
                    availability=cfg.tenants.slo_availability,
                    burn_alert=cfg.tenants.slo_burn_alert)
                for tname in self.tenants.names():
                    self.tenant_slo.register(tname, tenant_slo_cfg)
                hooks.append(self.tenant_slo.tick)
            self.sampler = MetricSampler(
                self.store, tcfg.sample_interval_s, hooks=hooks)
            self.profiler = ProfileCapture(self.metrics)
        # Structured event plane (ISSUE 15, docs/OBSERVABILITY.md "The
        # third pillar"): bounded event ring + logging bridge, admin audit
        # trail, and the postmortem ledger (populated behind the router
        # tier by the supervisors; a worker's own log records its view of
        # the world for the black-box snapshot). All None when [events]
        # enabled = false.
        self.events: EventLog | None = None
        self.audit: AuditLog | None = None
        self.postmortems: PostmortemLog | None = None
        self.blackbox: BlackBoxWriter | None = None
        if cfg.events.enabled:
            ecfg = cfg.events
            self.events = EventLog(self.metrics, ecfg.capacity,
                                   jsonl_path=ecfg.jsonl_path)
            self.audit = AuditLog(self.metrics, ecfg.audit_capacity,
                                  events=self.events)
            self.postmortems = PostmortemLog(
                self.metrics, ecfg.postmortem_capacity,
                tail_bytes=ecfg.stderr_tail_bytes, events=self.events)
            events_mod.install_bridge(self.events, ecfg.bridge_level)
            events_mod.set_active(self.events)
        # The event loop that owns the batchers/engines/cache/scheduler
        # (set in start()). Handlers running on a parallel ingest loop
        # (cfg.ingest_loops > 1) hop their submission onto it; on the main
        # loop the hop is a no-op (_on_main).
        self.main_loop: asyncio.AbstractEventLoop | None = None
        # Per-accept-loop ingest counters, keyed by loop index (built
        # lazily by make_app; the /stats "ingest" block reads them).
        self.ingest: dict[int, IngestHandles] = {}
        self._canary_task: asyncio.Task | None = None
        # Next periodic-canary fire time (time.monotonic clock): the live
        # basis for breaker-503 Retry-After hints (the canary IS the
        # recovery probe, so "retry after the next canary" is exact).
        self._next_canary_at: float | None = None
        # Worker-process id when this server runs behind the router tier
        # (tpuserve.workerproc); None in single-process mode.
        self.worker_id: int | None = None
        # Chaos layer (docs/ROBUSTNESS.md): None unless [faults] is armed.
        self.injector = (FaultInjector(cfg.faults, self.metrics)
                         if cfg.faults.enabled else None)
        self.watchdog = Watchdog(cfg.watchdog_interval_s, self.metrics)
        # Graceful drain: True once shutdown began — new requests shed with
        # 503 + Retry-After while accepted ones finish.
        self.draining = False
        # Bound (host, port) pairs once serve_async is listening.
        self.serving_addresses: list = []

    def build(self) -> None:
        # Retrace witness (docs/ANALYSIS.md): every build starts a fresh
        # warmup window; the barrier is declared once startup compilation
        # below is done, after which an unsanctioned compile raises.
        witness.reset_retrace()
        configure_jax(self.cfg)
        if self.cfg.profiler_port:
            jax.profiler.start_server(self.cfg.profiler_port)
        # [parallel] mode override (docs/PERFORMANCE.md "Serving on the
        # mesh"): applied at the CONFIG level, before the model is built,
        # so family-level mode validation and the model's own batch_spec
        # see the real serving mode.
        if self.cfg.parallel.mode:
            for mcfg in self.cfg.models:
                if mcfg.parallelism != self.cfg.parallel.mode:
                    log.info("model %s: [parallel] mode overrides "
                             "parallelism %r -> %r", mcfg.name,
                             mcfg.parallelism, self.cfg.parallel.mode)
                    mcfg.parallelism = self.cfg.parallel.mode
        compile_pool = cf.ThreadPoolExecutor(max_workers=4, thread_name_prefix="compile")
        try:
            for mcfg in self.cfg.models:
                t0 = time.perf_counter()
                model = modelzoo.build(mcfg)
                if mcfg.cold_start and self.scheduler is None:
                    log.warning("model %s: cold_start ignored — [scheduler] "
                                "is not enabled", mcfg.name)
                if mcfg.cold_start and self.scheduler is not None:
                    if self.cfg.genserve.enabled \
                            and getattr(model, "generative", False):
                        raise ValueError(
                            f"model {mcfg.name}: cold_start does not compose "
                            "with the generation engine yet (its programs "
                            "compile against the live param structure)")
                    # Cold boot (weight paging, docs/ROBUSTNESS.md "Fleet
                    # isolation & SLO admission"): meshes are planned but NO
                    # params are loaded and NO variants compiled — zero HBM
                    # resident. The first request (or :warm) stages weights
                    # through the lifecycle path; the scheduler sheds with
                    # 503 + Retry-After until the publish lands.
                    rt = ModelRuntime(model, metrics=self.metrics,
                                      parallel=self.cfg.parallel)
                    rt.injector = self.injector
                elif self.cfg.genserve.enabled \
                        and getattr(model, "generative", False):
                    # Iteration-level engine (docs/PERFORMANCE.md "The
                    # generation engine"): the engine's insert/step/extract
                    # programs replace the forward bucket set — compiling
                    # both would double startup compile time for nothing.
                    rt = build_runtime(model, metrics=self.metrics,
                                       parallel=self.cfg.parallel,
                                       compile_forward=False)
                    if getattr(rt, "n_replicas", 1) > 1:
                        # Replica-per-chip engines (docs/PERFORMANCE.md
                        # "Generation on the mesh"): one engine per replica
                        # mesh, least-loaded placement, the engine surface
                        # aggregated — everything downstream (watchdog,
                        # lifecycle, scheduler, /stats) wires unchanged.
                        eng = GenEngineGroup(model, rt, self.metrics,
                                             self.cfg.genserve,
                                             stages=self.stages,
                                             pipeline_cfg=self.cfg.pipeline)
                    else:
                        eng = GenEngine(model, rt, self.metrics,
                                        self.cfg.genserve, stages=self.stages,
                                        pipeline_cfg=self.cfg.pipeline)
                    eng.compile()  # registers + prewarms the programs
                    self.engines[mcfg.name] = eng
                    # Armed after compile/prewarm, like the batcher path.
                    eng.injector = self.injector
                    rt.injector = self.injector
                else:
                    rt = build_runtime(model, pool=compile_pool,
                                       metrics=self.metrics,
                                       parallel=self.cfg.parallel)
                    if self.cfg.prewarm_executables:
                        rt.prewarm()
                    if self.cfg.roofline_probe_iters > 0:
                        # Raw-executable ceilings per bucket (inputs
                        # resident, dependent read): the device-time term
                        # of /stats' roofline compute split. After prewarm
                        # (program load out of the window), before the
                        # injector arms (probes are not chaos targets).
                        rt.probe_all_raw(int(self.cfg.roofline_probe_iters))
                    # Armed after prewarm: chaos targets the serving path,
                    # not startup.
                    rt.injector = self.injector
                self.models[mcfg.name] = model
                self.runtimes[mcfg.name] = rt
                log.info("model %s ready in %.1fs: %s", mcfg.name, time.perf_counter() - t0, rt.describe())
        finally:
            compile_pool.shutdown()
        # Startup compilation done: from here on the steady-state
        # compile-delta-0 invariant is LIVE. Under
        # TPUSERVE_RETRACE_WITNESS=1 any further unsanctioned compile
        # raises RetraceViolation naming its (tag, variant), and implicit
        # device->host transfers are disallowed (utils.retrace).
        witness.declare_warmup_complete()
        if witness.retrace_enabled():
            from tpuserve.utils.retrace import arm_transfer_guard

            arm_transfer_guard()
            log.info("retrace witness armed (TPUSERVE_RETRACE_WITNESS)")

    def ingest_handles(self, index: int) -> IngestHandles:
        """Prebound ingest counters for accept loop ``index`` (idempotent)."""
        h = self.ingest.get(index)
        if h is None:
            h = self.ingest[index] = IngestHandles(index, self.metrics)
        return h

    async def start(self) -> None:
        self.main_loop = asyncio.get_running_loop()
        # Debug-mode race detection (docs/ANALYSIS.md): with
        # TPUSERVE_LOCK_WITNESS=1 every task created on this loop checks at
        # each suspension that no witnessed threading lock is held across an
        # await, and every lock built via utils.locks feeds the global
        # lock-order graph. The chaos drill runs with this armed in CI.
        if witness.maybe_install():
            log.info("lock witness installed (TPUSERVE_LOCK_WITNESS)")
        for name, model in self.models.items():
            rt = self.runtimes[name]
            br = CircuitBreaker(name, model.cfg.breaker_threshold,
                                self.metrics,
                                retry_after_s=model.cfg.breaker_retry_after_s)
            self.breakers[name] = br
            eng = self.engines.get(name)
            if eng is not None:
                # Iteration-level engine: same front-door surface as the
                # batcher, so everything below (canary, cache, watchdog,
                # lifecycle, drain) composes unchanged.
                eng.breaker = br
                await eng.start()
                b: "ModelBatcher | GenEngine" = eng
            else:
                b = ModelBatcher(model, rt, self.metrics,
                                 breaker=br, injector=self.injector,
                                 stages=self.stages,
                                 pipeline_cfg=self.cfg.pipeline,
                                 adaptive_cfg=self.cfg.adaptive)
                await b.start()
            self.batchers[name] = b
            self.handles[name] = ModelHandles(name, model.cfg, self.metrics)
            model.bind_metrics(self.metrics)
            if self.cfg.cache.enabled and getattr(model, "cacheable", True):
                # Keys carry the LIVE runtime version, so a lifecycle
                # publish/rollback atomically invalidates older entries.
                # Models with cacheable = false never get a cache: their
                # results are not a pure function of the decoded item.
                self.caches[name] = ModelCache(
                    name, self.cfg.cache, self.metrics,
                    version_fn=functools.partial(getattr, rt, "version"))
            self.watchdog.register(name, "group_loop", b.revive_group_loops)
            # functools.partial, not a lambda: late binding would hand every
            # lifecycle the last loop iteration's name. Engine models swap
            # in the engine's staged canary: a SHORT generation end-to-end
            # through the real compiled programs against the candidate tree.
            lc = self.lifecycles[name] = ModelLifecycle(
                name, rt, model, self.cfg.lifecycle, self.metrics,
                breaker=br,
                canary=functools.partial(self.run_canary, name),
                canary_status=functools.partial(self.canary_ok.get, name),
                injector=self.injector,
                staged_canary_fn=eng.staged_canary_sync
                if eng is not None else None)
            if self.scheduler is not None:
                # Fleet registration: the scheduler reads each batcher's
                # demand (pending, raw clear estimate, duration EWMAs) and
                # feeds its device-seconds ledger from dispatch timings;
                # cold models warm through the lifecycle's staged path so
                # no request is ever answered by unvalidated weights.
                self.scheduler.register(
                    name, batcher=b, mcfg=model.cfg, runtime=rt,
                    warm_fn=lc.reload,
                    cold=bool(model.cfg.cold_start))
        if self.tenants is not None:
            # Tenant-partitioned cache capacity (ISSUE 16): each tenant's
            # weighted share bounds how many entries its misses may pin,
            # so one tenant's flood churns its OWN share first. Hits stay
            # content-addressed across tenants.
            weights = self.tenants.weights()
            for c in self.caches.values():
                c.set_tenant_weights(weights)
        # Native-decode fallback observability (ISSUE 11 satellite): the
        # preproc yuv420 decoder reports every PIL fallback on a
        # native-eligible request; route it to the prebound per-model
        # counter (Counter.inc is locked — decode threads and ingest loops
        # may call this concurrently).
        preproc.set_native_fallback_hook(self._note_native_fallback)
        if self.slo is not None:
            # SLO registration: models whose [model.slo] names a latency
            # objective get burn-rate gauges + an /alerts row; the rest
            # are simply not evaluated.
            for mcfg in self.cfg.models:
                self.slo.register(mcfg.name, mcfg.slo)
                # First-token objective (ISSUE 17): a separate subject
                # over the engine's gen_first_unit_ms histogram, so the
                # autopilot's shed-on-burn seam sees streaming health —
                # a model can meet its total-latency SLO while its
                # time-to-first-token burns.
                if mcfg.slo is not None and mcfg.slo.first_unit_ms > 0:
                    self.slo.register(
                        f"{mcfg.name}:first_unit",
                        SloConfig(latency_ms=mcfg.slo.first_unit_ms,
                                  availability=mcfg.slo.availability,
                                  burn_alert=mcfg.slo.burn_alert),
                        metric=f"gen_first_unit_ms{{model={mcfg.name}}}")
        if self.scheduler is not None:
            # Shed-on-burn seam (ISSUE 14): the scheduler can read each
            # model's live alert state (FleetScheduler.slo) — future PRs
            # shed batch-class work while a model burns budget instead of
            # waiting for fleet saturation.
            self.scheduler.slo = self.slo
        if self.sampler is not None:
            self.sampler.start()
        if self.scheduler is not None:
            await self.scheduler.start()
        if self.cfg.startup_canary:
            await self.run_canaries()
        if self.cfg.canary_interval_s > 0:
            self._canary_task = asyncio.create_task(self._canary_loop())
        if self.events is not None and self.cfg.events.snapshot_path \
                and self.cfg.events.snapshot_interval_s > 0:
            # Black box (ISSUE 15): checkpoint a postmortem snapshot to the
            # per-slot file (once immediately, then on the interval) so a
            # SIGKILL at any point after boot leaves last-N events, flight
            # summaries, and key counters for the supervisor's reap.
            self.blackbox = BlackBoxWriter(
                self.cfg.events.snapshot_path,
                self.cfg.events.snapshot_interval_s,
                self._blackbox_snapshot)
            self.blackbox.start()
        self.watchdog.start()
        # Ready: every model compiled and prewarmed, every loop started, the
        # canaries through. What the process holds now it holds while it
        # serves: out of the collector's sight (ISSUE 52), until ``stop``.
        self.host_clocks.freeze_heap()

    # Counter families worth carrying in the black-box snapshot: the
    # serving volume and failure tallies a postmortem reader checks first.
    _BLACKBOX_COUNTERS = frozenset((
        "requests_total", "bad_requests_total", "timeouts_total",
        "deadline_exceeded_total", "batches_total",
        "watchdog_restarts_total", "events_logged_total"))

    def _blackbox_snapshot(self) -> dict:
        """One postmortem checkpoint (tpuserve.telemetry.events
        BlackBoxWriter `collect`): the last-N event records, compact
        flight-recorder summaries (trace ids, not span trees — the
        snapshot must stay small), and the key counters. Runs on the
        black-box thread; everything it reads is locked."""
        counters = {
            name: v for name, v in self.metrics.counter_values().items()
            if name.split("{", 1)[0] in self._BLACKBOX_COUNTERS}
        slow = []
        dumped = self.recorder.dump()
        for model, recs in sorted(dumped.get("slow", {}).items()):
            slow.extend({"model": model, "trace_id": r["trace_id"],
                         "status": r["status"],
                         "duration_ms": r["duration_ms"]}
                        for r in recs[:4])
        errors = [{"model": r["model"], "trace_id": r["trace_id"],
                   "status": r["status"], "duration_ms": r["duration_ms"]}
                  for r in dumped.get("errors", [])[:8]]
        return {
            "ts": round(time.time(), 3),
            "pid": os.getpid(),
            "worker_id": self.worker_id,
            "events": self.events.tail(50) if self.events is not None else [],
            "flight": {"slow": slow, "errors": errors},
            "counters": counters,
        }

    def _note_native_fallback(self, model: str) -> None:
        h = self.handles.get(model)
        if h is not None:
            h.native_fallback.inc()
        else:  # decode racing startup/teardown: count unlabeled-but-visible
            self.metrics.counter(
                f"native_decode_fallback_total{{model={model}}}").inc()

    async def _canary_loop(self) -> None:
        """Re-run the per-model canary on an interval so /healthz reflects
        live serving health (degrades on failure, recovers on success).
        Canary inferences ride the normal serving path, so they are visible
        in /metrics like any synthetic probe; the per-cycle timeout is
        bounded by the interval so one hung model can't stretch staleness
        to the startup canary's 60 s budget — but never drops below a
        model's own request_timeout_ms (ADVICE r3: a 2 s floor made slow
        models like sd15, ~1.6 s+ device time per image, flap /healthz
        under ordinary load when canary_interval_s was small)."""
        timeouts = self.canary_timeouts()
        while True:
            self._next_canary_at = time.monotonic() + self.cfg.canary_interval_s
            await asyncio.sleep(self.cfg.canary_interval_s)
            try:
                await self.run_canaries(timeouts=timeouts)
            except asyncio.CancelledError:
                raise
            except Exception:  # one bad cycle must not end re-canarying
                log.exception("periodic canary cycle failed")

    def canary_timeouts(self) -> dict[str, float]:
        """Per-model periodic-canary timeout: bounded by the interval but
        floored at the model's own request_timeout_ms (ADVICE r3)."""
        base = min(60.0, max(2.0, 2.0 * self.cfg.canary_interval_s))
        return {
            name: max(base, m.cfg.request_timeout_ms / 1e3)
            for name, m in self.models.items()
        }

    async def run_canary(self, name: str, timeout_s: float = 60.0) -> bool:
        """Tiny end-to-end inference for one model; feeds /healthz and
        half-opens/closes the circuit breaker (canaries ride the batcher
        regardless of breaker state — they ARE the recovery probe)."""
        if self.scheduler is not None and not self.scheduler.is_warm(name):
            # Cold/warming model (weight paging): there are no live params
            # to probe, and the staged canary inside the warm-up path owns
            # candidate validation. Never-measured reads as healthy.
            return self.canary_ok.get(name, True)
        model = self.models[name]
        br = self.breakers.get(name)
        try:
            if self.injector is not None:
                self.injector.check("canary_fail", name)
            if br is not None:
                br.probe()
            item = model.canary_item()
            fut = self.batchers[name].submit(item, group=model.group_key(item))
            await asyncio.wait_for(fut, timeout=timeout_s)
            self.canary_ok[name] = True
        except QueueFull:
            # A full queue is load shedding doing its job, not ill health;
            # flipping /healthz to 503 here would pull the busiest instance
            # from rotation and cascade the overload. Keep the last status.
            log.info("canary for %s skipped: queue full (shedding)", name)
        except Exception:
            log.exception("canary failed for %s", name)
            self.canary_ok[name] = False
        # .get: a shed canary with no prior status (startup_canary=False)
        # must not KeyError — treat never-measured as healthy.
        return self.canary_ok.get(name, True)

    async def run_canaries(self, timeout_s: float = 60.0,
                           timeouts: dict[str, float] | None = None) -> None:
        # Concurrent: one hung model must not stall (or stale) the others.
        await asyncio.gather(
            *(self.run_canary(name, timeout_s=(timeouts or {}).get(name, timeout_s))
              for name in self.models))

    # -- graceful drain ------------------------------------------------------
    def begin_drain(self) -> None:
        """Stop admitting requests: predict answers 503 + Retry-After and
        /healthz flips so load balancers pull this replica."""
        self.draining = True

    async def drain(self) -> bool:
        """SIGTERM path: refuse new work, then wait (<= drain_timeout_s) for
        every accepted request to finish — a rolling restart drops zero
        accepted requests. Returns False if the budget expired first.

        The revival machinery stops FIRST: the watchdog must not revive a
        group loop that this drain is intentionally quiescing, and the
        periodic canary must not inject new probe work after admission
        closed. The old ordering left both running until state.stop() — a
        stop/revive race window where a post-drain sweep could recreate
        machinery stop() was about to tear down."""
        t_drain = time.perf_counter()
        await self.watchdog.stop()
        await self._stop_canary_loop()
        if self.scheduler is not None:
            # Same discipline: the idle-demotion sweep (and any in-flight
            # warm-up) must not mutate model state under the drain.
            await self.scheduler.stop()
        self.begin_drain()
        if self.sampler is not None:
            # The telemetry sampler joins during the drain too (no orphan
            # thread ticking a dying registry) — but AFTER the draining
            # flag: it only READS metrics, so it is not revival machinery,
            # and admission must close before anything that can suspend.
            # stop() is idempotent for the non-drain teardown path.
            await asyncio.get_running_loop().run_in_executor(
                None, self.sampler.stop)
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.cfg.drain_timeout_s
        ok = True
        for b in self.batchers.values():
            ok &= await b.drain(deadline)
        if self.blackbox is not None:
            # Final checkpoint, then stop: the last snapshot records the
            # drained state (counters at rest) for whoever reads the slot.
            await loop.run_in_executor(None, self.blackbox.stop)
        if self.audit is not None:
            # Drain is an admin action like any other: the audit trail is
            # how an operator later tells a rolling restart from a crash.
            self.audit.record(
                "drain", "server", "ok" if ok else "budget_expired",
                duration_ms=(time.perf_counter() - t_drain) * 1e3,
                drain_timeout_s=self.cfg.drain_timeout_s)
        return ok

    def roofline(self, latency_summary: dict) -> dict:
        """The /stats ``roofline`` block (docs/PERFORMANCE.md "Reading the
        roofline"): per model the resident specialized variants, lifetime
        compile count, per-bucket raw-executable ms (when
        ``roofline_probe_iters`` armed the startup probe), and the serving
        compute phase split into device-time vs host-wait."""
        out: dict = {}
        for name, rt in self.runtimes.items():
            row: dict = {
                "variants": rt.variants_summary(),
                "compiles_total": rt.compiles_total,
                "raw_ms_per_batch": {
                    str(list(b)): v
                    for b, v in sorted(rt.raw_ms_per_batch.items())},
            }
            if self.util is not None:
                # Chip-occupancy context (ISSUE 14): the roofline's ceiling
                # percentages read differently at 0.2 vs 0.9 utilization —
                # carry the live busy fractions beside the raw-ms terms.
                u = self.util.stats().get(name)
                if u:
                    row["utilization"] = u
            raw_vals = [v for v in rt.raw_ms_per_batch.values() if v]
            if raw_vals:
                # The largest probed bucket prices the split: it is what a
                # saturated loop overwhelmingly serves, and using the
                # biggest raw time makes host_wait a LOWER bound.
                split = compute_split(
                    phase_p50(latency_summary, name, "compute"),
                    max(raw_vals))
                if split is not None:
                    row["compute_split"] = split
            out[name] = row
        return out

    def parallel_stats(self) -> dict:
        """The /stats ``parallel`` block (docs/PERFORMANCE.md "Serving on
        the mesh"): per model the live serving layout and per-chip dispatch
        attribution — replica mode lists one count per chip; sharded mode
        has one mesh-wide count, reported with its per-chip share."""
        out: dict = {}
        for name, rt in self.runtimes.items():
            batches = rt.replica_batches()
            out[name] = {
                "mode": rt.mode,
                "signature": rt.parallel_signature,
                "n_chips": rt.n_chips,
                "replicas": rt.n_replicas,
                "replica_batches_total": batches,
                "batches_per_chip": round(sum(batches) / rt.n_chips, 2)
                if rt.n_chips else 0.0,
            }
        return out

    def shed_retry_after(self) -> int:
        """Retry-After seconds for drain 503 responses (hint to hit another
        replica — this one is going away, so there is no live state to
        derive a better number from)."""
        return max(1, math.ceil(self.cfg.shed_retry_after_s))

    def queue_retry_after(self, name: str) -> int:
        """Retry-After seconds for queue-full 429s, derived from live state:
        the batcher's estimated queue-clear time at the observed serving
        rate (per-bucket duration EWMAs), clamped to [1, 30] s by
        ``batcher.clamp_retry_after_s`` (the estimate itself stays raw for
        the fleet scheduler's admission math). Falls back
        to the configured constant before any batch has completed."""
        b = self.batchers.get(name)
        hint = clamp_retry_after_s(b.estimate_clear_s()
                                   if b is not None else None)
        return hint if hint is not None else self.shed_retry_after()

    def kv_retry_after(self, name: str, exc: Exception) -> int:
        """Retry-After seconds for paged-KV pressure 503s (ISSUE 18): the
        engine's page-clear estimate carried on the KVPressure itself,
        clamped like every shed hint; falls back to the queue-clear hint
        before the engine has duration evidence."""
        hint = clamp_retry_after_s(getattr(exc, "retry_after_s", None))
        return hint if hint is not None else self.queue_retry_after(name)

    def breaker_retry_after(self, name: str) -> int:
        """Retry-After seconds for breaker 503s, derived from live state:
        the time until the NEXT periodic canary — the probe that half-opens
        and closes the breaker — when canaries drive recovery (the interval
        itself before the loop has armed a fire time), else the model's
        configured hint."""
        if self.cfg.canary_interval_s > 0:
            if self._next_canary_at is not None:
                eta = self._next_canary_at - time.monotonic()
                if eta > 0:
                    return max(1, math.ceil(eta))
                return 1  # probe due now: retry immediately after it lands
            return max(1, math.ceil(self.cfg.canary_interval_s))
        br = self.breakers.get(name)
        return max(1, math.ceil(br.retry_after_s if br else 1.0))

    async def _stop_canary_loop(self) -> None:
        """Cancel the periodic canary task (idempotent; drain + stop)."""
        if self._canary_task is not None:
            self._canary_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._canary_task
            self._canary_task = None

    async def stop(self) -> None:
        await self.watchdog.stop()
        if self.blackbox is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self.blackbox.stop)
        if self.sampler is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self.sampler.stop)
        if self.scheduler is not None:
            await self.scheduler.stop()
        for lc in self.lifecycles.values():
            lc.close()  # stop soak monitors
        await self._stop_canary_loop()
        for b in self.batchers.values():
            await b.stop()
        self.stages.shutdown()
        self.pool.shutdown(wait=False, cancel_futures=True)
        self.host_clocks.close()
        if self.events is not None:
            self.events.close()  # flush/close the JSONL sink fd


# -- handlers ----------------------------------------------------------------

class NotServing(RuntimeError):
    """Batcher refused the submit (stopped / racing shutdown) -> 503."""


async def _on_main(state: ServerState, factory):
    """Run ``factory()`` (a coroutine factory) on the main serving loop.

    On the main loop this is a plain await — the single-loop hot path pays
    nothing. On a parallel ingest loop (cfg.ingest_loops > 1) the coroutine
    is scheduled onto the main loop, which owns every batcher/cache/
    scheduler structure (all deliberately lock-free and loop-only), and the
    result/exception crosses back through a concurrent future. Cancelling
    the ingest-side await (client disconnect) cancels the main-loop task —
    asyncio.wrap_future propagates cancellation both ways."""
    loop = asyncio.get_running_loop()
    if state.main_loop is None or loop is state.main_loop:
        return await factory()
    cfut = asyncio.run_coroutine_threadsafe(factory(), state.main_loop)
    return await asyncio.wrap_future(cfut)


def _main_loop_handler(handler):
    """Route an admin/stats handler onto the main serving loop when the
    request landed on a parallel ingest loop. These handlers touch
    loop-only state (lifecycles, scheduler, batcher stats) and read only
    ``request.match_info`` — synchronous data, safe to carry across the
    loop boundary; the Response is built unprepared and returned."""

    @functools.wraps(handler)
    async def wrapped(request: web.Request) -> web.StreamResponse:
        state: ServerState = request.app[STATE_KEY]
        loop = asyncio.get_running_loop()
        if state.main_loop is None or loop is state.main_loop:
            return await handler(request)
        cfut = asyncio.run_coroutine_threadsafe(handler(request),
                                                state.main_loop)
        return await asyncio.wrap_future(cfut)

    return wrapped


async def _submit_and_gather(state: ServerState, name: str, model,
                             items: list, deadline_at: float,
                             priority: str | None,
                             timeout_ms: float | None,
                             ctx: "TraceContext | None" = None,
                             tenant: str | None = None,
                             ) -> tuple[list, "object | None"]:
    """Cache/single-flight lookup + batcher submission + deadline-bounded
    gather for one decoded request — everything that must run on the main
    serving loop. Returns (results, hit_entry). Raises QueueFull (-> 429),
    NotServing (-> 503), DeadlineExceeded (-> fast 504),
    asyncio.TimeoutError (-> backstop 504), or the batch failure (-> 500);
    the HTTP handler owns the status mapping on whichever loop it runs."""
    cache = state.caches.get(name)
    batcher = state.batchers[name]
    results: list = [None] * len(items)
    futs: list[asyncio.Future] = []
    slots: list[int] = []
    hit_entry = None
    try:
        for i, item in enumerate(items):
            if cache is not None:
                key = cache.key_for(item)
                entry = cache.get(key)
                if entry is not None:
                    results[i] = entry.value
                    hit_entry = entry
                    if ctx is not None:
                        now = time.time()
                        ctx.span("cache_hit", now, now, tid=name)
                    continue
                fut = cache.submit_through(
                    key, lambda it=item: batcher.submit(
                        it, group=model.group_key(it),
                        deadline_at=deadline_at, priority=priority,
                        ctx=ctx), ctx=ctx, tenant=tenant)
            else:
                fut = batcher.submit(item, group=model.group_key(item),
                                     deadline_at=deadline_at,
                                     priority=priority, ctx=ctx)
            futs.append(fut)
            slots.append(i)
    except QueueFull:
        for f in futs:
            f.cancel()
        raise
    except RuntimeError as e:
        # Batcher stopped/not started: requests racing shutdown get a clean
        # retryable status instead of an unhandled 500.
        for f in futs:
            f.cancel()
        raise NotServing(str(e)) from e

    if futs:
        try:
            remaining = max(0.0, deadline_at - time.perf_counter())
            # With an explicit client deadline the batcher enforces it
            # precisely at flush time (fast 504 + deadline_exceeded_total);
            # the timer here then runs slightly late as a pure backstop so
            # the two never race.
            grace = 0.25 if timeout_ms is not None else 0.0
            done = await asyncio.wait_for(asyncio.gather(*futs),
                                          timeout=remaining + grace)
        except BaseException:
            # TimeoutError, DeadlineExceeded, batch failure, cancellation:
            # nothing may leave dangling single-item futures behind.
            for f in futs:
                f.cancel()
            raise
        for i, res in zip(slots, done):
            results[i] = res
    return results, hit_entry


async def handle_predict(request: web.Request) -> web.Response:
    """Predict entry: mints (or adopts, behind the router) the request's
    trace context, delegates to the traced handler, then stamps
    ``X-Trace-Id`` on the response — EVERY response, success or error —
    records the root span, and offers the finished trace to the flight
    recorder (ISSUE 12, docs/OBSERVABILITY.md)."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    # Behind the router tier the worker's spans land on their own process
    # lane (pid = worker id + 1; the router is lane 0), which is what makes
    # the cross-process hop visible as a gap in a stitched Chrome trace.
    ctx = TraceContext.from_headers(
        request.headers,
        pid=state.worker_id + 1 if state.worker_id is not None else 0)
    wall0 = time.time()
    t0 = time.perf_counter()
    resp = await _predict_traced(request, state, name, ctx)
    dur_s = time.perf_counter() - t0
    ctx.root_span("request", wall0, wall0 + dur_s, tid=name,
                  status=resp.status)
    if "X-Trace-Id" not in resp.headers:
        resp.headers["X-Trace-Id"] = ctx.trace_id
    # Streamed responses score by max(first-unit, largest gap) — set by
    # _predict_stream — so a slow STREAM is catchable while a long healthy
    # generation isn't misfiled as slow (ISSUE 17 satellite).
    score_ms = getattr(resp, "tpuserve_stream_score_ms", None)
    kinds = state.recorder.finish(
        ctx, name, resp.status,
        score_ms if score_ms is not None else dur_s * 1e3)
    if state.events is not None:
        # Trace-correlated flight data (ISSUE 15): errored/shed and
        # retained-slow requests leave an event carrying the trace id, so
        # /debug/trace?trace_id= interleaves what the process was saying.
        if resp.status >= 400:
            state.events.emit(
                "error" if resp.status >= 500 else "warning", "http",
                "request_error", model=name, trace_id=ctx.trace_id,
                status=resp.status, duration_ms=round(dur_s * 1e3, 3))
        elif "slow" in kinds:
            state.events.emit(
                "info", "http", "slow_request", model=name,
                trace_id=ctx.trace_id, status=resp.status,
                duration_ms=round(dur_s * 1e3, 3))
    return resp


async def _predict_traced(request: web.Request, state: ServerState,
                          name: str, ctx: TraceContext) -> web.Response:
    model = state.models.get(name)
    if model is None:
        return _err(404, f"unknown model {name!r}", trace=ctx)
    # Query validation (shared validator, ISSUE 15 idiom): predict knows
    # exactly two parameters; junk keys or a junk stream= value are a 400
    # before any body work.
    try:
        events_mod.reject_unknown_query(request.query,
                                        {"timeout_ms", "stream"})
        want_stream = _requested_stream(request)
    except ValueError as e:
        return _err(400, str(e), trace=ctx)
    # Shed checks run BEFORE the body read: a draining replica or tripped
    # model answers in microseconds, with a Retry-After hint, instead of
    # paying decode + a doomed dispatch.
    if state.draining:
        return _err(503, "server draining; retry against another replica",
                    retry_after=state.shed_retry_after(), trace=ctx)
    # Tenant containment (ISSUE 16): identity, rate, quota, and fair
    # share are judged pre-body — a flooding tenant is refused in
    # microseconds and never reaches decode or the batcher. Behind the
    # router tier the ROUTER admits (it fronts clients); the worker's
    # [tenants] block is normally disabled there.
    tenant: str | None = None
    if state.tenants is not None:
        tenant = state.tenants.resolve(request.headers.get("X-Api-Key"))
        if tenant is None:
            t_shed = state.tenants.shed_unknown()
            return _err(t_shed.status, t_shed.message, reason=t_shed.reason,
                        trace=ctx)
        t_shed = state.tenants.admit(tenant)
        if t_shed is not None:
            return _err(t_shed.status, t_shed.message,
                        retry_after=t_shed.retry_after,
                        reason=t_shed.reason, trace=ctx)
    breaker = state.breakers.get(name)
    if breaker is not None and not breaker.allow():
        breaker.on_shed()
        return _err(503, f"circuit open for model {name!r}; recovery probe "
                         "in progress",
                    retry_after=state.breaker_retry_after(name), trace=ctx)
    # Fleet scheduler admission, part 1 (pre-body; tpuserve.scheduler):
    # warm/cold state and priority arbitration need only headers, so a
    # cold model or shed batch-class request answers in microseconds. The
    # deadline check runs after the deadline is stamped, below. Scheduler
    # state is main-loop-only; on a parallel ingest loop the check hops
    # (_on_main) — microseconds of coroutine scheduling, still pre-body.
    raw_priority = request.headers.get("X-Priority")
    priority: str | None = None
    if state.scheduler is not None:
        async def _precheck():
            p = state.scheduler.resolve_priority(name, raw_priority)
            shed = state.scheduler.check_admission(name, p)
            if shed is None:
                state.scheduler.touch(name)
            return p, shed

        try:
            priority, shed = await _on_main(state, _precheck)
        except ValueError as e:
            return _err(400, str(e), trace=ctx)
        if shed is not None:
            return _err(shed.status, shed.message,
                        retry_after=shed.retry_after, reason=shed.reason,
                        trace=ctx)
    elif raw_priority:
        # No scheduler = no arbitration, but the class still labels the
        # queue-wait split (header -> batcher); junk degrades to the
        # model default rather than 400ing an unscheduled server.
        value = raw_priority.strip().lower()
        priority = value if value in PRIORITIES else None
    h = state.handles[name]
    mcfg = h.mcfg
    h.requests.inc()
    t_start = time.perf_counter()

    if state.injector is not None:
        # Process-boundary chaos (docs/ROBUSTNESS.md "Process failure
        # domains"): simulate a degraded (worker_slow), wedged (worker_hang
        # — the request simply never answers), or natively-crashed
        # (worker_crash — the whole process exits, taking every in-flight
        # request with it) serving process. Behind the router tier these
        # prove hedging, retry, and supervision; in single-process mode
        # they demonstrate exactly the blast radius the split removes.
        delay = state.injector.delay_s("worker_slow", name)
        if delay > 0:
            await asyncio.sleep(delay)
        if state.injector.fire("worker_hang", name) is not None:
            await asyncio.sleep(_WORKER_HANG_S)
            return _err(503, "wedged worker unwedged; retry")
        if state.injector.fire("worker_crash", name) is not None:
            log.error("chaos: worker_crash fired for %s — exiting process",
                      name)
            os._exit(17)

    # Ingest phase 1 (ISSUE 11): the body read is the HTTP ingress wire —
    # on a framed multi-item POST this is megabytes off the socket, and
    # with ingest_loops > 1 it runs on whichever accept loop the kernel's
    # SO_REUSEPORT spread picked, not serialized on the batcher's loop.
    ing: IngestHandles = request.app[INGEST_KEY]
    t_read = time.perf_counter()
    w_read = time.time()
    body = await request.read()
    read_s = time.perf_counter() - t_read
    h.body_read_hist.observe(read_s * 1e3, trace_id=ctx.trace_id)
    ctx.span("body_read", w_read, w_read + read_s, tid=name,
             loop=ing.index, bytes=len(body))
    ing.requests.inc()
    ing.bytes.inc(len(body))
    ctype = request.content_type or ""

    # Per-request deadline (docs/ROBUSTNESS.md): the client's timeout_ms
    # (JSON body key, ?timeout_ms= query, or X-Timeout-Ms header) overrides
    # the model's request_timeout_ms. The absolute deadline is stamped at
    # admission and travels with each queued item, so the batcher can fail
    # already-dead work in microseconds instead of dispatching it.
    try:
        timeout_ms = _requested_timeout_ms(request, body, ctype)
    except ValueError as e:
        return _err(400, str(e), trace=ctx)
    timeout_s = (timeout_ms if timeout_ms is not None
                 else mcfg.request_timeout_ms) / 1e3
    deadline_at = t_start + timeout_s

    # Fleet scheduler admission, part 2 (Clockwork P3): a deadline that
    # provably cannot be met — predicted queue-clear + service time exceed
    # the remaining budget — sheds with a fast 504 BEFORE decode or
    # enqueue, instead of dying at the back of the queue.
    if state.scheduler is not None:
        async def _deadline_check():
            return state.scheduler.check_deadline(name, deadline_at)

        shed = await _on_main(state, _deadline_check)
        if shed is not None:
            return _err(shed.status, shed.message,
                        retry_after=shed.retry_after, reason=shed.reason,
                        trace=ctx)

    try:
        if state.injector is not None:
            state.injector.check("decode_corrupt", name)
        # Ingest phase 2: (items, is_batch) with one parse; a 1-element
        # client batch still answers in the {"results": [...]} shape.
        # Framed bodies parse as zero-copy views (tpuserve.frame) — the
        # "parse" phase for them is offset-table validation, not pixel work.
        t_parse = time.perf_counter()
        w_parse = time.time()
        # The tpuserve.parse span is taken in the thread that decodes:
        # unlike the phase=parse histogram, timed here around the executor
        # hop, it holds no wait for a decode thread.
        span = {"model": name, "bytes": len(body)}
        if state.cfg.decode_inline:
            items, batched = trace_call(
                "tpuserve.parse", span, model.host_decode_items, body, ctype)
        else:
            loop = asyncio.get_running_loop()
            items, batched = await loop.run_in_executor(
                state.pool, trace_call, "tpuserve.parse", span,
                model.host_decode_items, body, ctype)
        if not items:
            raise ValueError("empty batch")
        parse_s = time.perf_counter() - t_parse
        h.parse_hist.observe(parse_s * 1e3, trace_id=ctx.trace_id)
        ctx.span("parse", w_parse, w_parse + parse_s, tid=name,
                 items=len(items))
    except frame_wire.FrameError as e:
        # Malformed frame: machine-readable 400 (message is "frame: ..."),
        # never a 500 — and counted apart from generic decode failures.
        h.frame_errors.inc()
        h.bad_requests.inc()
        return _err(400, str(e), trace=ctx)
    except Exception as e:
        h.bad_requests.inc()
        return _err(400, f"could not decode request: {e}", trace=ctx)

    if want_stream:
        # Streaming dispatch (ISSUE 17): straight to the generation
        # engine's emission channel — no result cache, no single-flight
        # (a stream must never coalesce onto a buffered leader or be
        # answered from a cached body; it force-misses by construction).
        eng = state.engines.get(name)
        if eng is None:
            h.bad_requests.inc()
            return _err(400, f"model {name!r} does not support streaming "
                             "(stream=true needs a [genserve]-served "
                             "generative model)", trace=ctx)
        if len(items) != 1:
            h.bad_requests.inc()
            return _err(400, "stream=true requires a single-item request",
                        trace=ctx)
        return await _predict_stream(request, state, name, model, h, eng,
                                     items[0], deadline_at, timeout_s,
                                     priority, tenant, ctx, t_start)

    # Demand-shaping layer (tpuserve.cache): per item, answer from the
    # content-addressed result cache, join an identical in-flight miss
    # (single-flight: one batch slot, the result fanned out), or lead a
    # fresh batcher submission. Hit/miss/coalesced are counted disjointly
    # so cache traffic never masquerades as model throughput. Everything
    # below the decode runs on the MAIN loop (_submit_and_gather): cache,
    # single-flight, batcher, and scheduler state are loop-only by design,
    # so a parallel ingest loop makes exactly ONE hop per request here.
    w_dispatch = time.time()
    t_dispatch = time.perf_counter()
    try:
        results, hit_entry = await _on_main(
            state, lambda: _submit_and_gather(
                state, name, model, items, deadline_at, priority,
                timeout_ms, ctx, tenant))
    except KVPressure as e:
        # Paged-KV admission shed (ISSUE 18): the fast-shed contract of
        # queue-full, but 503 with reason "kv_pressure" so clients (and
        # the router) can tell memory pressure from queue pressure.
        return _err(503, str(e), retry_after=state.kv_retry_after(name, e),
                    reason="kv_pressure", trace=ctx)
    except QueueFull:
        return _err(429, "queue full, retry later",
                    retry_after=state.queue_retry_after(name), trace=ctx)
    except NotServing as e:
        return _err(503, f"server not accepting requests: {e}", trace=ctx)
    except DeadlineExceeded as e:
        # The batcher rejected the queued work before dispatch: same 504
        # as the timer path, but fast, in deadline_exceeded_total.
        return _err(504, f"deadline_exceeded: {e}", trace=ctx)
    except asyncio.TimeoutError:
        h.timeouts.inc()
        return _err(504,
                    f"request deadline ({timeout_s * 1e3:.0f} ms) exceeded",
                    trace=ctx)
    except Exception as e:
        return _err(500, f"inference failed: {e}", trace=ctx)
    finally:
        # The ingest-loop→main-loop hop plus everything the main loop ran
        # (cache, single-flight, batcher/engine): its children are the
        # queue/phase spans the batcher recorded; a gap between "parse"
        # and "queue" inside this span IS the cross-loop hop.
        ctx.span("dispatch", w_dispatch,
                 w_dispatch + (time.perf_counter() - t_dispatch), tid=name)

    total_ms = (time.perf_counter() - t_start) * 1e3
    h.total_hist.observe(total_ms, trace_id=ctx.trace_id)
    if state.tenants is not None and tenant is not None:
        # Charge the tenant's sliding-window ledger with the wall time
        # the request occupied the server (the device-time proxy quota
        # and fair share enforce) and feed its latency series (the
        # per-tenant SLO burn input).
        state.tenants.record(tenant, total_ms / 1e3, latency_ms=total_ms)
    if batched:
        payload = {"results": results}
        if len(results) >= _JSON_OFFLOAD_MIN_ITEMS and not state.cfg.decode_inline:
            # Large batched responses encode off the loop (egress fast
            # path); single-core hosts (decode_inline) stay inline — the
            # executor hop costs more than the encode there.
            raw = await asyncio.get_running_loop().run_in_executor(
                state.pool, _dumps_utf8, payload)
            return web.Response(body=raw, content_type="application/json")
        return web.json_response(payload)
    result = results[0]
    if isinstance(result, bytes):  # e.g. SD PNG output
        return web.Response(body=result, content_type="image/png")
    if hit_entry is not None and hit_entry.body is not None:
        # Cache-hit egress fast path: the response bytes were serialized
        # once at population time; a hit is one memcpy, zero json.dumps.
        return web.Response(body=hit_entry.body,
                            content_type="application/json")
    return web.json_response(result)


def _requested_stream(request: web.Request) -> bool:
    """The ``?stream=`` query flag; ValueError (-> 400) on junk values —
    a typo'd flag must fail loudly, not silently serve unary."""
    raw = request.query.get("stream")
    if raw is None:
        return False
    val = raw.strip().lower()
    if val in ("true", "1"):
        return True
    if val in ("false", "0"):
        return False
    raise ValueError(
        f'stream must be "true", "1", "false" or "0", got {raw!r}')


def _stream_error_status(reason: str) -> int:
    """Pre-first-unit terminal -> plain HTTP status (the fast-504 half of
    the deadline contract: no bytes were written, so no stream semantics
    are owed and the router's hedge/retry stays legal)."""
    return {"deadline_exceeded": 504, "shutdown": 503, "drain": 503}.get(
        reason, 500)


async def _predict_stream(request: web.Request, state: ServerState,
                          name: str, model, h: ModelHandles, eng,
                          item, deadline_at: float, timeout_s: float,
                          priority: str | None, tenant: str | None,
                          ctx: TraceContext,
                          t_start: float) -> web.StreamResponse:
    """One streamed generation end-to-end (ISSUE 17 tentpole layer 2).

    The engine's GenStream queue is the single channel: units flush per
    engine iteration, heartbeats cover idle gaps, and exactly one terminal
    ("done" with finish reason + usage, or "error" naming the cause)
    closes every started stream. The deadline contract splits here: until
    the first unit no bytes are written and failures stay plain statuses
    (fast 504 — and the router's first-byte latch sees no body, keeping
    hedges legal); after it, failures become in-stream error events. A
    client disconnect cancels the engine future, freeing the slot for
    fold-in (gen_client_disconnects_total ticks engine-side)."""

    async def _submit():
        try:
            return eng.submit_stream(item, deadline_at=deadline_at,
                                     priority=priority, ctx=ctx)
        except QueueFull:
            raise
        except RuntimeError as e:
            raise NotServing(str(e)) from e

    try:
        fut, stream = await _on_main(state, _submit)
    except KVPressure as e:
        # Shed before any stream byte: plain 503 + reason, no SSE involved.
        return _err(503, str(e), retry_after=state.kv_retry_after(name, e),
                    reason="kv_pressure", trace=ctx)
    except QueueFull:
        return _err(429, "queue full, retry later",
                    retry_after=state.queue_retry_after(name), trace=ctx)
    except NotServing as e:
        return _err(503, f"server not accepting requests: {e}", trace=ctx)

    hb_s = eng.gcfg.stream_heartbeat_s
    hb = model.stream_heartbeat()
    encode = model.encode_stream_unit
    resp: web.StreamResponse | None = None
    terminal: dict | None = None
    n_units = 0
    last_write: float | None = None
    first_unit_ms: float | None = None
    max_gap_ms = 0.0
    max_gap_end = 0.0
    try:
        while terminal is None:
            if resp is None:
                # Admission -> first unit: bounded by the request deadline
                # plus the same 0.25 s backstop grace the unary path uses
                # (the engine's fast-504 eviction normally answers first).
                budget = max(0.0, deadline_at - time.perf_counter()) + 0.25
                try:
                    unit = await _on_main(state, lambda: asyncio.wait_for(
                        stream.get(), budget))
                except asyncio.TimeoutError:
                    h.timeouts.inc()
                    return _err(
                        504,
                        f"request deadline ({timeout_s * 1e3:.0f} ms) "
                        "exceeded", trace=ctx)
                if unit["type"] == "error":
                    status = _stream_error_status(unit.get("error", ""))
                    if status == 504:
                        h.timeouts.inc()
                    return _err(status,
                                f"{unit.get('error', 'error')}: "
                                f"{unit.get('message', '')}", trace=ctx)
                resp = web.StreamResponse(status=200)
                resp.content_type = model.stream_content_type()
                resp.headers["X-Tpuserve-Stream"] = "1"
                resp.headers["X-Trace-Id"] = ctx.trace_id
                await resp.prepare(request)
            else:
                try:
                    unit = await _on_main(state, lambda: asyncio.wait_for(
                        stream.get(), hb_s if hb_s > 0 else None))
                except asyncio.TimeoutError:
                    if hb:
                        await resp.write(hb)
                    continue
            now = time.perf_counter()
            if first_unit_ms is None:
                first_unit_ms = (now - t_start) * 1e3
            elif last_write is not None:
                gap = (now - last_write) * 1e3
                if gap > max_gap_ms:
                    max_gap_ms, max_gap_end = gap, time.time()
            last_write = now
            if unit["type"] in ("done", "error"):
                terminal = unit
            try:
                await resp.write(encode(unit))
            except (ConnectionResetError, ConnectionError):
                # Client went away mid-write: the finally below cancels
                # the engine future (slot frees for fold-in;
                # gen_client_disconnects_total ticks engine-side).
                return resp
            n_units += 1
            if state.injector is not None and terminal is None:
                # Chaos on a STARTED stream (docs/ROBUSTNESS.md):
                # stream_stall wedges the writer (the reader sees
                # heartbeats dry up — the router's idle timeout owns it);
                # stream_disconnect tears the transport with NO terminal
                # event (the torn-stream shape clients must error on).
                if state.injector.fire("stream_stall", name) is not None:
                    await asyncio.sleep(_WORKER_HANG_S)
                    return resp
                if state.injector.fire("stream_disconnect",
                                       name) is not None:
                    if request.transport is not None:
                        request.transport.close()
                    return resp
    finally:
        if terminal is None:
            # Abandoned mid-stream (client disconnect, handler
            # cancellation, injected tear): cancel the engine future so
            # the slot frees for fold-in, and close the stream so a
            # blocked producer wakes. Scheduled, not awaited — this
            # finally may itself be running a cancellation.
            def _abandon():
                fut.cancel()
                stream.close()

            (state.main_loop
             or asyncio.get_running_loop()).call_soon_threadsafe(_abandon)

    # Stream health spans + recorder score (ISSUE 17 satellite): a
    # stream's slowness is first-unit latency and the largest inter-unit
    # gap — total wall time would score every long generation "slow".
    wall_end = time.time()
    if max_gap_ms > 0:
        ctx.span("stream_gap", max_gap_end - max_gap_ms / 1e3, max_gap_end,
                 tid=name, gap_ms=round(max_gap_ms, 3))
    ctx.span("stream_terminal", wall_end, wall_end, tid=name,
             type=terminal["type"],
             finish_reason=(terminal.get("finish_reason")
                            if terminal["type"] == "done"
                            else terminal.get("error")),
             units=n_units)
    resp.tpuserve_stream_score_ms = max(first_unit_ms or 0.0, max_gap_ms)
    if state.tenants is not None and tenant is not None:
        # Charge wall occupancy; the tenant latency series gets the
        # client-perceived responsiveness (first unit), not stream length.
        total_ms = (time.perf_counter() - t_start) * 1e3
        state.tenants.record(tenant, total_ms / 1e3,
                             latency_ms=first_unit_ms or total_ms)
    await resp.write_eof()
    return resp


async def handle_models(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    return web.json_response({n: rt.describe() for n, rt in state.runtimes.items()})


async def handle_healthz(request: web.Request) -> web.Response:
    state: ServerState = request.app[STATE_KEY]
    if state.draining:
        return web.json_response(
            {"status": "draining", "models": state.canary_ok}, status=503)
    ok = all(state.canary_ok.values()) if state.canary_ok else True
    return web.json_response(
        {"status": "ok" if ok else "degraded", "models": state.canary_ok},
        status=200 if ok else 503,
    )


async def handle_metrics(request: web.Request) -> web.Response:
    """GET /metrics — Prometheus/OpenMetrics exposition. The body always
    ends with the OpenMetrics ``# EOF`` terminator; the Content-Type is
    negotiated from the Accept header (ISSUE 14 satellite)."""
    state: ServerState = request.app[STATE_KEY]
    ctype = exposition_content_type(request.headers.get("Accept"))
    return web.Response(
        body=state.metrics.render_prometheus().encode("utf-8"),
        headers={"Content-Type": ctype})


async def handle_history(request: web.Request) -> web.Response:
    """GET /stats/history?metric=&window_s= — time-resolved metric history
    from the telemetry rings: raw samples plus derived counter rates and
    histogram window-delta quantiles (docs/OBSERVABILITY.md "The telemetry
    plane"). Without ``metric=``, lists the recorded series names.
    ``metric=`` may be a full labeled name or a bare base name (every
    matching series is returned)."""
    state: ServerState = request.app[STATE_KEY]
    if state.store is None:
        return _err(409, "[telemetry] is disabled; no history is recorded")
    metric = request.query.get("metric")
    if not metric:
        return web.json_response({"metrics": state.store.metric_names(),
                                  **state.store.stats()})
    try:
        window_s = (float(request.query["window_s"])
                    if "window_s" in request.query else None)
        if window_s is not None and window_s <= 0:
            raise ValueError(window_s)
    except (TypeError, ValueError):
        return _err(400, "window_s must be a positive number")
    names = state.store.match(metric)
    if not names:
        return _err(404, f"no recorded series matches {metric!r} "
                         "(GET /stats/history lists the inventory)")
    series = [state.store.history(n, window_s) for n in names]
    return web.json_response(
        {"series": [s for s in series if s is not None]})


async def handle_alerts(request: web.Request) -> web.Response:
    """GET /alerts — the SLO engine's burn-rate alert states: per model
    ok/pending/firing with live burn per window. Models without a
    [model.slo] latency objective are absent; with [telemetry] disabled
    the endpoint says so instead of guessing."""
    state: ServerState = request.app[STATE_KEY]
    if state.slo is None:
        return _err(409, "[telemetry] is disabled; no SLO evaluation runs")
    return web.json_response(state.slo.alerts())


async def handle_profile(request: web.Request) -> web.Response:
    """POST /debug/profile?duration_ms= — arm a jax.profiler device trace
    for the window and answer ONE merged Chrome trace: device lanes (pids
    >= 1000) beside the span ring's serving-path events from the same
    window. 409 while a capture is already armed; device-trace
    unavailability degrades (the span half still answers), never 5xx."""
    state: ServerState = request.app[STATE_KEY]
    if state.profiler is None:
        return _err(409, "[telemetry] is disabled; profiling is not armed")
    try:
        duration_ms = float(request.query.get("duration_ms", "500"))
    except (TypeError, ValueError):
        return _err(400, "duration_ms must be a number")
    if not (1.0 <= duration_ms <= state.cfg.telemetry.profile_max_ms):
        return _err(400, f"duration_ms must be in [1, "
                         f"{state.cfg.telemetry.profile_max_ms:g}], "
                         f"got {duration_ms:g}")
    t0 = time.perf_counter()
    try:
        merged = await state.profiler.capture(duration_ms)
    except CaptureBusy:
        if state.audit is not None:
            state.audit.record("profile", "server", "busy",
                               requested_ms=duration_ms)
        return _err(409, "a profile capture is already armed "
                         "(jax.profiler is one-at-a-time)")
    if state.audit is not None:
        state.audit.record(
            "profile", "server", "ok",
            duration_ms=(time.perf_counter() - t0) * 1e3,
            requested_ms=duration_ms)
    return web.json_response(merged)


async def handle_stats(request: web.Request) -> web.Response:
    from tpuserve.parallel import local_devices_info, process_info

    state: ServerState = request.app[STATE_KEY]
    out = state.metrics.summary()
    # Topology block (ISSUE 13 satellite): the multi-machine seam's
    # process coordinates (tpuserve.parallel.distributed.process_info —
    # rank/host facts once jax.distributed runs under a coordinator) plus
    # this process's place in the router tier when it serves as a worker.
    # This is what a future multi-machine `[router] hosts` maps onto.
    out["topology"] = {
        **process_info(),
        "devices": local_devices_info(),
        "worker_id": state.worker_id,
        "distributed": bool(state.cfg.distributed.coordinator_address),
    }
    # Shed/breaker state for operators (docs/ROBUSTNESS.md): what is tripped,
    # what is draining, and what chaos is armed.
    out["robustness"] = {
        "draining": state.draining,
        "breakers": {n: br.describe() for n, br in state.breakers.items()},
    }
    if state.injector is not None:
        out["robustness"]["faults"] = state.injector.snapshot()
    # Flight-recorder occupancy (docs/OBSERVABILITY.md): how many slow/
    # errored span trees are retained per model (the trees themselves live
    # at /debug/slow and /debug/trace?trace_id=).
    out["trace"] = state.recorder.stats()
    # Structured event plane (docs/OBSERVABILITY.md "The third pillar"):
    # ring occupancy + per-level/subsystem tallies, audit/postmortem
    # ledger sizes. The records themselves live at /debug/events,
    # /debug/audit, /debug/postmortems.
    if state.events is not None:
        out["events"] = {
            **state.events.stats(),
            "audit": state.audit.stats(),
            "postmortems": state.postmortems.stats(),
        }
    # Telemetry plane (docs/OBSERVABILITY.md "The telemetry plane"):
    # sampler heartbeat + ring occupancy, per-chip device utilization, and
    # profiling state. History itself lives at /stats/history, alerts at
    # /alerts.
    if state.store is not None:
        out["telemetry"] = {
            **state.store.stats(),
            "sample_interval_s": state.cfg.telemetry.sample_interval_s,
            "profile": state.profiler.stats()
            if state.profiler is not None else None,
        }
    if state.util is not None:
        util = state.util.stats()
        if util:
            out["utilization"] = util
    if state.slo is not None:
        alerts = state.slo.alerts()
        if alerts["models"]:
            out["slo"] = alerts
    if witness.enabled():
        # Observed lock-order graph + any violations (docs/ANALYSIS.md).
        out["robustness"]["lock_witness"] = witness.snapshot()
    if witness.retrace_enabled():
        # Warmup barrier + post-barrier compile ledger (docs/ANALYSIS.md).
        out["robustness"]["retrace_witness"] = witness.retrace_snapshot()
    # Versioned lifecycle state: what version is live per model, what is
    # retained for rollback, and the recent transition history.
    if state.lifecycles:
        out["lifecycle"] = {n: lc.describe()
                            for n, lc in state.lifecycles.items()}
    # Ingest fast path (ISSUE 11, docs/PERFORMANCE.md "The ingest fast
    # path"): per-accept-loop request/byte balance, malformed-frame counts,
    # and the native-decode fallback tallies (a nonzero fallback row under
    # JPEG load means the 2x-slower PIL path is serving — fix the shim).
    out["ingest"] = {
        "loops": {str(i): {"requests": ih.requests.value,
                           "bytes": ih.bytes.value}
                  for i, ih in sorted(state.ingest.items())},
        "frame_errors_total": {n: hd.frame_errors.value
                               for n, hd in state.handles.items()},
        "native_decode_fallback_total": {
            n: hd.native_fallback.value for n, hd in state.handles.items()},
    }
    # Host-pipeline state (docs/PERFORMANCE.md "Reading the metrics"):
    # per-stage executor sizes/queue depth and, per model, the in-flight
    # occupancy, staging-slot usage, and assembly-arena recycling stats.
    out["pipeline"] = {
        "stages": state.stages.stats(),
        "models": {n: b.pipeline_stats() for n, b in state.batchers.items()},
    }
    # Multi-chip serving layout + per-chip dispatch attribution
    # (docs/PERFORMANCE.md "Serving on the mesh").
    parallel = state.parallel_stats()
    if parallel:
        out["parallel"] = parallel
    # Iteration-level generation engines (docs/PERFORMANCE.md "The
    # generation engine"): slot occupancy, fold-in/early-exit/eviction
    # counts, step timing — per engine-served model.
    if state.engines:
        out["genserve"] = {n: e.pipeline_stats()
                           for n, e in state.engines.items()}
    # Fleet scheduler (docs/ROBUSTNESS.md "Fleet isolation & SLO
    # admission"): saturation, per-model paging state, device-time shares,
    # live completion predictions, and shed accounting.
    if state.scheduler is not None:
        out["scheduler"] = state.scheduler.stats()
    # Tenant containment (ISSUE 16): per-tenant envelopes + live window
    # usage; the full view (with SLO burn) is at /tenants.
    if state.tenants is not None:
        out["tenants"] = state.tenants.usage()
    # Demand-shaping layer: per-model result-cache occupancy and the
    # hit/miss/coalesced/stale accounting (docs/PERFORMANCE.md).
    if state.caches:
        out["cache"] = {n: c.stats() for n, c in state.caches.items()}
    # Compute fast path (docs/PERFORMANCE.md "Reading the roofline"):
    # resident specialized variants, lifetime compile count, per-bucket
    # raw-executable ceilings, and the compute device/host-wait split.
    roofline = state.roofline(out["latency"])
    if roofline:
        out["roofline"] = roofline
    return web.json_response(out)


async def handle_trace(request: web.Request) -> web.Response:
    """GET /debug/trace — Chrome trace JSON.

    ``?trace_id=`` pulls ONE recorded request's complete span tree from the
    flight recorder (``&format=record`` returns the raw record instead —
    the router tier stitches worker records into one cross-process trace),
    with matching structured events interleaved (``events`` key on the
    record; instant ``ph: "i"`` marks in the Chrome output — ISSUE 15).
    Without it, the span ring is dumped, bounded by ``?limit=`` (default
    5000 — an unbounded 65536-event dump built a multi-hundred-MB body on
    the event loop of a loaded server) and ``?since_us=`` (epoch µs)."""
    state: ServerState = request.app[STATE_KEY]
    trace_id = request.query.get("trace_id")
    if trace_id:
        rec = state.recorder.get(trace_id)
        if rec is None:
            return _err(404, f"trace {trace_id!r} is not in the flight "
                             "recorder (evicted or never retained)")
        events = (state.events.query(trace_id=trace_id, limit=200)
                  if state.events is not None else [])
        if request.query.get("format") == "record":
            rec = dict(rec)
            rec["events"] = events
            return web.json_response(rec)
        return web.Response(text=spans_to_chrome(rec["spans"],
                                                 events=events),
                            content_type="application/json")
    try:
        limit = int(request.query.get("limit", "5000"))
        since_us = (float(request.query["since_us"])
                    if "since_us" in request.query else None)
    except ValueError as e:
        return _err(400, f"limit/since_us must be numbers: {e}")
    if limit < 0:
        return _err(400, f"limit must be >= 0, got {limit}")
    return web.Response(
        text=state.metrics.tracer.chrome_trace(limit=limit,
                                               since_us=since_us),
        content_type="application/json")


async def handle_slow(request: web.Request) -> web.Response:
    """GET /debug/slow — the flight recorder's reservoirs: slowest-N span
    trees per model (slowest first) plus the errored-request FIFO (newest
    first). ``?model=`` filters to one model."""
    state: ServerState = request.app[STATE_KEY]
    return web.json_response(state.recorder.dump(
        model=request.query.get("model")))


async def handle_events(request: web.Request) -> web.Response:
    """GET /debug/events?since_us=&level=&subsystem=&trace_id=&limit= —
    the structured event ring (docs/OBSERVABILITY.md "The third pillar"),
    oldest-first within the newest ``limit`` matches. Junk query params
    400 (the /debug/trace hardening discipline)."""
    state: ServerState = request.app[STATE_KEY]
    if state.events is None:
        return _err(409, "[events] is disabled; no events are recorded")
    try:
        q = events_mod.parse_events_query(request.query)
    except ValueError as e:
        return _err(400, str(e))
    return web.json_response({"events": state.events.query(**q),
                              **state.events.stats()})


async def handle_postmortems(request: web.Request) -> web.Response:
    """GET /debug/postmortems — the crash-forensics ledger: one record per
    reaped process death (exit code/signal, stderr tail, last black-box
    snapshot), newest first. Populated by the supervisors behind the
    router tier; a leaf worker answers its (empty) own ledger so the
    endpoint shape is uniform across tiers."""
    state: ServerState = request.app[STATE_KEY]
    if state.postmortems is None:
        return _err(409, "[events] is disabled; no postmortems are kept")
    return web.json_response({"postmortems": state.postmortems.dump(),
                              **state.postmortems.stats()})


async def handle_audit(request: web.Request) -> web.Response:
    """GET /debug/audit — the admin audit trail: every :reload /
    :rollback / :warm / /debug/profile / drain with outcome, duration, and
    verb-specific fields, newest first."""
    state: ServerState = request.app[STATE_KEY]
    if state.audit is None:
        return _err(409, "[events] is disabled; no audit trail is kept")
    return web.json_response({"audit": state.audit.dump(),
                              **state.audit.stats()})


_INDEX_HTML = """<!doctype html><title>tpuserve</title>
<h1>tpuserve</h1>
<p>POST an image to <code>/v1/models/&lt;name&gt;:classify</code>.
See <a href="/v1/models">models</a>, <a href="/metrics">metrics</a>,
<a href="/stats">stats</a>, <a href="/healthz">health</a>.</p>
<form method=post enctype=multipart/form-data onsubmit="
  event.preventDefault();
  const f=document.getElementById('f').files[0];
  const m=document.getElementById('m').value;
  fetch('/v1/models/'+m+':predict',{method:'POST',body:f,
    headers:{'Content-Type':f.type}})
   .then(r=>r.json()).then(j=>document.getElementById('out').textContent=
     JSON.stringify(j,null,2));
">
<input type=text id=m value=resnet50> <input type=file id=f>
<button>predict</button></form><pre id=out></pre>
"""


async def handle_reload(request: web.Request) -> web.Response:
    """POST /admin/models/{name}:reload — staged, reversible weight swap.

    Lifecycle-backed (tpuserve.lifecycle): the candidate is integrity-checked
    and canaried against its STAGED params before publishing as a numbered
    version; same shapes slot into the compiled executables with zero
    recompilation. Any gate failure 409s with the failing ``stage`` and the
    old version keeps serving — including a post-publish canary failure,
    which auto-rolls back (500 + ``rolled_back: true``) instead of leaving
    bad weights live."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    if name not in state.runtimes:
        return _err(404, f"unknown model {name!r}")
    lc = state.lifecycles[name]
    t0 = time.perf_counter()

    def _audit(outcome: str, **fields) -> None:
        if state.audit is not None:
            state.audit.record(
                "reload", name, outcome,
                duration_ms=(time.perf_counter() - t0) * 1e3, **fields)

    try:
        info = await lc.reload()
    except ReloadRejected as e:
        body = {"error": str(e), "stage": e.stage,
                "rolled_back": e.rolled_back,
                "version": state.runtimes[name].version}
        _audit("rolled_back" if e.rolled_back else "rejected",
               stage=e.stage, version=state.runtimes[name].version,
               error=str(e))
        # Pre-publish rejection = client/artifact conflict (409); a
        # post-publish rollback means the server briefly published bad
        # weights and recovered (500 so operators page on it).
        return web.json_response(body, status=500 if e.rolled_back else 409)
    except Exception as e:  # noqa: BLE001
        _audit("error", error=str(e))
        return _err(500, f"reload failed: {e}")
    _audit("ok", version=info.get("version"))
    return web.json_response(info)


async def handle_rollback(request: web.Request) -> web.Response:
    """POST /admin/models/{name}:rollback — restore version N-1 (the
    retained last-known-good tree). 409 when nothing is retained."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    if name not in state.runtimes:
        return _err(404, f"unknown model {name!r}")
    lc = state.lifecycles[name]
    t0 = time.perf_counter()
    try:
        info = await lc.rollback(reason="manual")
    except ValueError as e:
        if state.audit is not None:
            state.audit.record(
                "rollback", name, "rejected",
                duration_ms=(time.perf_counter() - t0) * 1e3, error=str(e))
        return _err(409, str(e))
    if state.audit is not None:
        state.audit.record(
            "rollback", name, "ok",
            duration_ms=(time.perf_counter() - t0) * 1e3,
            version=info.get("version"),
            rolled_back_from=info.get("rolled_back_from"))
    return web.json_response(info)


async def handle_versions(request: web.Request) -> web.Response:
    """GET /admin/models/{name}/versions — live version, retained previous
    version, soak state, and the transition history."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    if name not in state.runtimes:
        return _err(404, f"unknown model {name!r}")
    lc = state.lifecycles[name]
    return web.json_response(lc.describe())


async def handle_warm(request: web.Request) -> web.Response:
    """POST /admin/models/{name}:warm — stage a cold model's weights to
    live through the lifecycle path (integrity gates, variant compile,
    staged canary, atomic publish) and return once it serves. Idempotent
    on a warm model; joins any warm-up already in flight. 409 when the
    fleet scheduler is not enabled."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    if name not in state.runtimes:
        return _err(404, f"unknown model {name!r}")
    if state.scheduler is None:
        return _err(409, "the fleet scheduler ([scheduler] enabled) owns "
                         "warm/cold states; it is not enabled")
    t0 = time.perf_counter()

    def _audit(outcome: str, **fields) -> None:
        if state.audit is not None:
            state.audit.record(
                "warm", name, outcome,
                duration_ms=(time.perf_counter() - t0) * 1e3, **fields)

    try:
        info = await state.scheduler.warm(name)
    except ValueError as e:
        _audit("rejected", error=str(e))
        return _err(409, str(e))
    except Exception as e:  # noqa: BLE001 — a failed warm keeps it cold
        _audit("error", error=str(e))
        return _err(500, f"warm-up failed (model stays cold): {e}")
    _audit("ok", state=info.get("state"))
    return web.json_response(info)


async def handle_demote(request: web.Request) -> web.Response:
    """POST /admin/models/{name}:demote — release a warm cold_start
    model's device params back to cold (the autopilot's warm-budget
    actuator, and an operator's manual page-out). Idempotent: demoting a
    cold (or non-cold_start) model answers 200 with demoted = false.
    409 when the fleet scheduler is not enabled."""
    state: ServerState = request.app[STATE_KEY]
    name = request.match_info["name"]
    if name not in state.runtimes:
        return _err(404, f"unknown model {name!r}")
    if state.scheduler is None:
        return _err(409, "the fleet scheduler ([scheduler] enabled) owns "
                         "warm/cold states; it is not enabled")
    t0 = time.perf_counter()
    try:
        demoted = state.scheduler.demote(name)
    except Exception as e:  # noqa: BLE001 — a failed demote keeps it warm
        if state.audit is not None:
            state.audit.record(
                "demote", name, "error",
                duration_ms=(time.perf_counter() - t0) * 1e3, error=str(e))
        return _err(500, f"demote failed (model stays warm): {e}")
    if state.audit is not None:
        state.audit.record(
            "demote", name, "ok",
            duration_ms=(time.perf_counter() - t0) * 1e3, demoted=demoted)
    return web.json_response({"model": name, "demoted": demoted})


async def handle_tenants(request: web.Request) -> web.Response:
    """GET /tenants — per-tenant containment envelopes + live window
    usage (ISSUE 16). ``?tenant=`` narrows to one tenant's row; any other
    query param is a 400 (the shared validator)."""
    state: ServerState = request.app[STATE_KEY]
    try:
        events_mod.reject_unknown_query(request.query, {"tenant"})
    except ValueError as e:
        return _err(400, str(e))
    if state.tenants is None:
        return _err(409, "[tenants] is disabled; no tenant ledger is kept")
    body = state.tenants.usage()
    if state.tenant_slo is not None:
        body["slo"] = state.tenant_slo.alerts()
    want = request.query.get("tenant")
    if want is not None:
        if want not in body["tenants"]:
            return _err(404, f"unknown tenant {want!r}")
        body["tenants"] = {want: body["tenants"][want]}
    return web.json_response(body)


async def handle_index(request: web.Request) -> web.Response:
    return web.Response(text=_INDEX_HTML, content_type="text/html")


def _err(status: int, message: str,
         retry_after: int | None = None,
         reason: str | None = None,
         trace: "TraceContext | str | None" = None) -> web.Response:
    headers: dict[str, str] = {}
    if retry_after:
        headers["Retry-After"] = str(retry_after)
    body = {"error": message}
    if reason is not None:
        # Machine-readable shed reason (obs.SCHED_SHED_REASONS): the
        # router tier relays it so its own breaker 503s can carry the
        # fleet's live shed cause.
        body["reason"] = reason
    if trace is not None:
        # Trace identity on the ERROR path (ISSUE 12 satellite): the id
        # rides both the X-Trace-Id header and the JSON body, so a user
        # report quoting a shed/504 body joins directly against the
        # flight recorder (/debug/trace?trace_id=...).
        tid = trace if isinstance(trace, str) else trace.trace_id
        body["trace_id"] = tid
        headers["X-Trace-Id"] = tid
    return web.json_response(body, status=status, headers=headers or None)


def _requested_timeout_ms(request: web.Request, body: bytes,
                          ctype: str) -> float | None:
    """Client-supplied per-request deadline: ``timeout_ms`` as a top-level
    JSON body key, a ``?timeout_ms=`` query parameter, or an
    ``X-Timeout-Ms`` header (binary bodies can't carry a JSON key). None
    when absent; ValueError (-> 400) when present but not a positive
    number. The substring guard keeps the extra JSON parse off every
    text/prompt request that doesn't use the feature."""
    raw = request.query.get("timeout_ms") or request.headers.get("X-Timeout-Ms")
    if raw is None and ctype == "application/json" and b"timeout_ms" in body:
        try:
            parsed = json.loads(body)
        except ValueError:
            return None  # model decode owns malformed-body errors
        if isinstance(parsed, dict):
            raw = parsed.get("timeout_ms")
    if raw is None:
        return None
    try:
        val = float(raw)
    except (TypeError, ValueError):
        raise ValueError(f"timeout_ms must be a number, got {raw!r}") from None
    if not math.isfinite(val) or val <= 0:
        raise ValueError(f"timeout_ms must be a positive number, got {val}")
    return val


# -- app wiring --------------------------------------------------------------

def make_app(state: ServerState, loop_index: int = 0,
             primary: bool = True) -> web.Application:
    """Build the aiohttp app for one accept loop.

    ``loop_index`` labels the per-loop ingest counters (0 = main loop).
    ``primary=False`` (a parallel ingest loop, ISSUE 11) skips the
    startup/cleanup hooks — the main app owns the ServerState lifecycle;
    ingest apps only share it. Admin and /stats handlers are wrapped so a
    request landing on an ingest loop executes on the main loop, where
    lifecycles/scheduler/batcher state lives."""
    app = web.Application(client_max_size=64 * 1024 * 1024)
    app[STATE_KEY] = state
    app[INGEST_KEY] = state.ingest_handles(loop_index)
    for verb in _VERBS:
        app.router.add_post(f"/v1/models/{{name}}:{verb}", handle_predict)
    app.router.add_get("/v1/models", handle_models)
    app.router.add_post("/admin/models/{name}:reload",
                        _main_loop_handler(handle_reload))
    app.router.add_post("/admin/models/{name}:rollback",
                        _main_loop_handler(handle_rollback))
    app.router.add_post("/admin/models/{name}:warm",
                        _main_loop_handler(handle_warm))
    app.router.add_post("/admin/models/{name}:demote",
                        _main_loop_handler(handle_demote))
    app.router.add_get("/admin/models/{name}/versions",
                       _main_loop_handler(handle_versions))
    app.router.add_get("/healthz", handle_healthz)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/stats", _main_loop_handler(handle_stats))
    # Telemetry plane (ISSUE 14): history + alerts read the sampler's own
    # locked structures (safe from any loop); profiling arms process-global
    # jax.profiler state and is cheapest kept off the ingest loops.
    app.router.add_get("/stats/history", handle_history)
    app.router.add_get("/alerts", handle_alerts)
    app.router.add_post("/debug/profile", _main_loop_handler(handle_profile))
    app.router.add_get("/debug/trace", handle_trace)
    app.router.add_get("/debug/slow", handle_slow)
    # Event plane (ISSUE 15): all three read locked structures — safe from
    # any accept loop, like /debug/slow.
    app.router.add_get("/debug/events", handle_events)
    app.router.add_get("/debug/postmortems", handle_postmortems)
    app.router.add_get("/debug/audit", handle_audit)
    # Tenant containment (ISSUE 16): the ledger is locked — safe from any
    # accept loop.
    app.router.add_get("/tenants", handle_tenants)
    app.router.add_get("/", handle_index)

    if primary:
        async def on_startup(app: web.Application) -> None:
            await state.start()

        async def on_cleanup(app: web.Application) -> None:
            await state.stop()

        app.on_startup.append(on_startup)
        app.on_cleanup.append(on_cleanup)
    return app


# -- parallel ingest loops (ISSUE 11) -----------------------------------------

class IngestLoop(threading.Thread):
    """One dedicated ingest accept loop: its own thread, its own asyncio
    event loop, its own SO_REUSEPORT listener on the serving port.

    The kernel spreads incoming connections across every listener on the
    port, so HTTP parse, body reads, request decode (frame parse /
    decode_inline), and JSON response encode for this loop's connections
    never serialize on the main loop; handlers hop their submission onto
    the main loop via ``_on_main`` (one hop per request). The thread is a
    daemon: a wedged cleanup can delay exit but never hang the process."""

    def __init__(self, state: ServerState, index: int, host: str,
                 port: int) -> None:
        super().__init__(name=f"tpuserve-ingest-{index}", daemon=True)
        self.state = state
        self.index = index
        self.host = host
        self.port = port
        self.error: BaseException | None = None
        self._ready = threading.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop_ev: asyncio.Event | None = None

    def run(self) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        try:
            loop.run_until_complete(self._serve())
        except BaseException as e:  # noqa: BLE001 — surfaced via wait_ready
            self.error = e
            log.exception("ingest loop %d failed", self.index)
        finally:
            self._ready.set()
            loop.close()

    async def _serve(self) -> None:
        # The witness instruments this loop too: a threading lock held
        # across an await on an ingest loop is just as much a bug here.
        witness.maybe_install()
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            sock.bind((self.host, self.port))
        except OSError:
            sock.close()
            raise
        app = make_app(self.state, loop_index=self.index, primary=False)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.SockSite(runner, sock)
        await site.start()
        self._stop_ev = asyncio.Event()
        self._ready.set()
        try:
            await self._stop_ev.wait()
        finally:
            await runner.cleanup()

    def wait_ready(self, timeout: float = 30.0) -> None:
        """Block (call from an executor) until the listener is up; re-raise
        a bind/startup failure in the caller."""
        self._ready.wait(timeout)
        if self.error is not None:
            raise self.error

    def request_stop(self) -> None:
        """Thread-safe: ask the loop to tear its listener down and exit."""
        loop, ev = self._loop, self._stop_ev
        if loop is not None and ev is not None:
            loop.call_soon_threadsafe(ev.set)


def start_ingest_loops(state: ServerState, host: str,
                       port: int) -> list[IngestLoop]:
    """Spawn the N-1 extra accept loops for ``cfg.ingest_loops = N``.

    Returns the (possibly empty) thread list; the caller must
    ``await stop_ingest_loops`` on shutdown. Degrades to zero extra loops
    with a warning where SO_REUSEPORT is unavailable — correctness never
    depends on the parallel listeners, only ingest throughput does."""
    n = max(1, state.cfg.ingest_loops)
    if n <= 1:
        return []
    if not hasattr(socket, "SO_REUSEPORT"):
        log.warning("ingest_loops = %d requested but SO_REUSEPORT is not "
                    "available on this platform; serving on one loop", n)
        return []
    threads = [IngestLoop(state, i, host, port) for i in range(1, n)]
    for t in threads:
        t.start()
    return threads


async def stop_ingest_loops(threads: list[IngestLoop]) -> None:
    """Stop + join ingest loops without blocking the calling loop."""
    loop = asyncio.get_running_loop()
    for t in threads:
        t.request_stop()
    for t in threads:
        await loop.run_in_executor(None, functools.partial(t.join, 10.0))


class JsonLogFormatter(logging.Formatter):
    """One JSON object per line: ts/level/logger/msg (+ exc when present)."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": round(record.created, 3),
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        if record.exc_info:
            out["exc"] = self.formatException(record.exc_info)
        if record.stack_info:
            out["stack"] = self.formatStack(record.stack_info)
        return json.dumps(out, ensure_ascii=False)


def configure_logging(cfg: ServerConfig) -> None:
    if cfg.log_json:
        handler = logging.StreamHandler()
        handler.setFormatter(JsonLogFormatter())
        logging.basicConfig(level=logging.INFO, handlers=[handler])
    else:
        logging.basicConfig(
            level=logging.INFO,
            format="%(asctime)s %(name)s %(levelname)s %(message)s")


async def serve_async(state: ServerState,
                      ready: asyncio.Event | None = None,
                      stop: asyncio.Event | None = None) -> None:
    """Serve until SIGTERM/SIGINT, then drain gracefully.

    Rolling restarts drop zero accepted requests: on signal the server (1)
    stops admitting — predict answers 503 + Retry-After and /healthz flips
    to "draining" so the load balancer pulls the replica; (2) flushes every
    accepted request within ``drain_timeout_s``; (3) only then tears the
    batchers/pools down (runner cleanup -> state.stop()).

    With ``cfg.ingest_loops = N > 1`` the main loop's listener binds with
    SO_REUSEPORT and N-1 dedicated ingest loops (IngestLoop threads) bind
    sibling listeners on the same port: the kernel spreads connections, so
    one asyncio accept/read loop is no longer the ingest choke point
    (docs/PERFORMANCE.md "The ingest fast path").

    ``ready`` (tests) is set once every listener is up and signal handlers
    are installed; the bound addresses land in ``state.serving_addresses``.
    ``stop`` (tests) substitutes for the signal-driven shutdown event."""
    cfg = state.cfg
    app = make_app(state)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    reuse = cfg.ingest_loops > 1 and hasattr(socket, "SO_REUSEPORT")
    site = web.TCPSite(runner, cfg.host, cfg.port, reuse_port=reuse or None)
    await site.start()
    state.serving_addresses = list(runner.addresses)
    # Parallel ingest loops bind the ACTUAL port (cfg.port may be 0 =
    # ephemeral; every SO_REUSEPORT sibling must name the bound one).
    port = cfg.port or state.serving_addresses[0][1]
    ingest_threads = start_ingest_loops(state, cfg.host, port)
    loop = asyncio.get_running_loop()
    for t in ingest_threads:
        await loop.run_in_executor(None, t.wait_ready)
    log.info("serving on %s (%d accept loop(s))", state.serving_addresses,
             1 + len(ingest_threads))

    if stop is None:
        stop = asyncio.Event()
    installed: list[signal.Signals] = []
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
            installed.append(sig)
        except (NotImplementedError, RuntimeError):
            pass  # non-main thread / platform without signal support
    if ready is not None:
        ready.set()
    try:
        await stop.wait()
        log.info("shutdown signal: draining (budget %.0fs)", cfg.drain_timeout_s)
        drained = await state.drain()
        if not drained:
            log.warning("drain budget expired with requests still in flight")
    finally:
        for sig in installed:
            loop.remove_signal_handler(sig)
        # Ingest listeners go first: no accept loop may outlive the state
        # teardown below (their handlers hop onto this loop's structures).
        await stop_ingest_loops(ingest_threads)
        await runner.cleanup()  # on_cleanup -> state.stop()


def serve(cfg: ServerConfig) -> None:
    """Blocking entry point: build models, compile, serve."""
    configure_logging(cfg)
    # Multi-host: must happen before ServerState.build() touches a device —
    # backend init freezes the process's view of the topology.
    from tpuserve.parallel import init_distributed

    init_distributed(cfg.distributed)
    state = ServerState(cfg)
    state.build()
    asyncio.run(serve_async(state))
