"""Typed configuration for tpuserve (SURVEY.md §2 C9).

The reference's configuration story is unknowable (empty mount, SURVEY.md §0);
per SURVEY.md §5 the build uses typed dataclasses, an optional TOML file, and
CLI dot-path overrides — no global mutable flag framework.

Example TOML::

    port = 8000

    [[model]]
    name = "resnet50"
    family = "resnet50"
    batch_buckets = [1, 4, 8, 16, 32]
    deadline_ms = 5.0
    dtype = "bfloat16"
"""

from __future__ import annotations

import dataclasses
import tomllib
from dataclasses import dataclass, field
from typing import Any


# Fault kinds the chaos injector understands (tpuserve.faults.FaultInjector).
# Each names a call site on the serving path where an armed rule can fire.
FAULT_KINDS = (
    "batch_error",      # raise inside batch dispatch (batcher._execute)
    "slow_dispatch",    # sleep delay_ms inside batch dispatch
    "decode_corrupt",   # fail request decode -> HTTP 400
    "canary_fail",      # fail the per-model canary probe
    "device_error",     # raise inside ModelRuntime.run (below the batcher)
    "slow_compute",     # sleep delay_ms inside ModelRuntime.run
    "kill_group_loop",  # crash the group accumulation task (watchdog food)
    "reload_corrupt",   # fail the reload integrity check (checksum mismatch)
    "reload_nan",       # fail the reload NaN/Inf scan (poisoned checkpoint)
    "reload_regressed", # fail the staged canary (regressed weights)
    "worker_crash",     # os._exit the serving process mid-request (native crash)
    "worker_hang",      # wedge the serving process: the request never answers
    "worker_slow",      # sleep delay_ms in the serving process before decode
    "stream_stall",     # stop writing a started stream (consumer wedged):
                        # the reader sees heartbeats dry up / idle timeout
    "stream_disconnect",  # abruptly close a started stream's transport with
                          # NO terminal event (the torn-stream shape clients
                          # must treat as an error)
)


@dataclass
class FaultRuleConfig:
    """One armed chaos rule (TOML ``[[faults.rule]]``; tpuserve.faults)."""

    # Which call site fires (see FAULT_KINDS).
    kind: str = "batch_error"
    # Model name the rule applies to; "*" matches every model.
    model: str = "*"
    # Per-call-site chance of firing, drawn from a rule-local seeded RNG so
    # runs are reproducible.
    probability: float = 1.0
    # Max times the rule fires; -1 = unlimited.
    count: int = -1
    # Sleep for the slow_* kinds (ignored by the others).
    delay_ms: float = 0.0
    # Rule-local RNG seed; 0 derives one from FaultsConfig.seed + rule index.
    seed: int = 0
    # Arm the rule only after the injector has been alive this long (s):
    # a drill's "fault fires MID-load", reproducibly. 0 = armed from boot.
    after_s: float = 0.0
    # Restrict the rule to one worker process id (router split): -1 = any
    # process. Pinning a slow_* rule to one worker makes the fault a
    # single-host/single-slot event, the autopilot drill's blast shape.
    worker: int = -1

    def __post_init__(self) -> None:
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; known: {list(FAULT_KINDS)}")
        if not 0.0 <= self.probability <= 1.0:
            raise ValueError(f"probability must be in [0, 1], got {self.probability}")
        if self.after_s < 0:
            raise ValueError(f"faults.rule.after_s must be >= 0, got {self.after_s}")
        if self.worker < -1:
            raise ValueError(f"faults.rule.worker must be >= -1, got {self.worker}")


@dataclass
class FaultsConfig:
    """Deterministic fault injection for chaos testing (``[faults]`` TOML).

    Off by default; staging configs arm rules to prove the recovery machinery
    (retry, breaker, watchdog, drain) holds the latency SLO while degraded."""

    enabled: bool = False
    # Base seed rule-local RNGs derive from (reproducible chaos runs).
    seed: int = 0
    rules: list[FaultRuleConfig] = field(default_factory=list)


@dataclass
class LifecycleConfig:
    """Versioned model lifecycle (``[lifecycle]`` TOML; tpuserve.lifecycle).

    Every weight reload is a staged, reversible transition: load off the
    serving path -> verify integrity -> canary the *staged* params -> publish
    as a numbered version with the previous tree retained -> auto-rollback on
    post-publish canary failure or a breaker trip within the soak window."""

    # Verify the sidecar checksum manifest (written by save_orbax /
    # import-model) against the loaded tree when one is present.
    verify_checksum: bool = True
    # Reject reloads of orbax checkpoints that carry NO manifest (strict
    # provenance mode). Off by default: TF/torch imports have no manifest.
    require_manifest: bool = False
    # Scan the candidate tree for NaN/Inf float leaves before staging.
    nan_scan: bool = True
    # Run the canary inference against the STAGED params (via the runtime's
    # params-override hook) before publishing; a failure never publishes.
    staged_canary: bool = True
    # Post-publish soak window (s): if the model's circuit breaker trips or
    # the periodic canary fails within this window, the reload auto-rolls
    # back to the retained last-known-good version. 0 disables soaking.
    soak_s: float = 0.0
    # Soak poll cadence (s).
    soak_poll_s: float = 0.25
    # Version-transition records kept per model (/admin .../versions).
    history_limit: int = 16


@dataclass
class PipelineConfig:
    """Pipelined host execution engine (``[pipeline]`` TOML; tpuserve.hostpipe,
    docs/PERFORMANCE.md).

    The direct-mode hot path runs as a staged pipeline — decode/assemble,
    H2D transfer + dispatch, D2H fetch, postprocess — with a dedicated thread
    pool per stage so consecutive batches occupy different stages
    concurrently, preallocated per-bucket assembly arenas instead of
    per-batch np.stack allocation, and a depth-k staging-slot pool per
    replica bounding batches in the device section ([h2d..fetch])."""

    # Thread-pool size per stage (shared across every direct-mode model).
    assemble_workers: int = 2
    h2d_workers: int = 2
    fetch_workers: int = 2
    postproc_workers: int = 2
    # Batches in flight per replica inside [h2d..fetch] ("staging slots");
    # 0 derives it from each model's max_inflight.
    depth: int = 0
    # The most batches that may be closed (or staged) past depth*replicas.
    # How many are is derived, not set: a batch closes when the device time
    # still queued has fallen to twice its measured staging time, so where
    # staging is small against a launch none is closed ahead (batcher.py).
    assemble_ahead: int = 2
    # Preallocated assembly buffers per (model, bucket); 0 sizes it to
    # depth + assemble_ahead. Acquires beyond this fall back to one-shot
    # allocations counted in arena_overflow_total{model=}.
    arena_slots: int = 0
    # Block the h2d stage until the transfer completes, so the "h2d" phase
    # owns the wire wait and "compute" measures dispatch-to-ready only
    # (roofline attribution, docs/PERFORMANCE.md "Reading the roofline").
    # The block lands on a dedicated h2d stage thread the link serializes
    # anyway, so throughput is unaffected; false restores buffered puts.
    h2d_sync: bool = True

    def __post_init__(self) -> None:
        for f in ("assemble_workers", "h2d_workers", "fetch_workers",
                  "postproc_workers"):
            if getattr(self, f) < 1:
                raise ValueError(f"pipeline.{f} must be >= 1")
        if self.depth < 0 or self.assemble_ahead < 0 or self.arena_slots < 0:
            raise ValueError(
                "pipeline.depth/assemble_ahead/arena_slots must be >= 0")


@dataclass
class CacheConfig:
    """Content-addressed result cache + single-flight coalescing (``[cache]``
    TOML; tpuserve.cache, docs/PERFORMANCE.md "Result cache & coalescing").

    Key = digest(model, live version, preprocessed item); value = the
    postprocessed result. The live model version is part of every key, so a
    lifecycle publish/rollback (tpuserve.lifecycle) atomically invalidates
    all previous entries without a sweep. Hits and coalesced waiters are
    counted separately from misses so cache traffic can never masquerade as
    model throughput in a bench."""

    enabled: bool = False
    # Max cached results per model (LRU beyond it).
    capacity: int = 4096
    # Entry time-to-live in seconds; 0 disables expiry (version churn is the
    # primary invalidation — TTL exists for non-deterministic models).
    ttl_s: float = 0.0
    # Single-flight: N concurrent identical misses occupy ONE batch slot,
    # the result fanning out to every waiter (Clipper P1's prediction-cache
    # trick, which also de-thunders retry storms).
    coalesce: bool = True
    # JSON results at most this big are pre-serialized at population time so
    # a hit's response body is one memcpy, not a per-request json.dumps.
    max_body_bytes: int = 1048576

    def __post_init__(self) -> None:
        if self.capacity < 1:
            raise ValueError(f"cache.capacity must be >= 1, got {self.capacity}")
        if self.ttl_s < 0 or self.max_body_bytes < 0:
            raise ValueError("cache.ttl_s/max_body_bytes must be >= 0")


@dataclass
class AdaptiveConfig:
    """SLO-aware adaptive batching (``[adaptive]`` TOML; tpuserve.batcher,
    docs/PERFORMANCE.md "Adaptive batching").

    Replaces the fixed max-wait flush with an AIMD-adjusted per-group target
    batch size (Clipper P1) plus a deadline-headroom bound from the per-bucket
    batch-duration EWMA (Clockwork P3): under light load the target decays to
    ``min_target`` and batches flush immediately; under sustained load it
    climbs to the largest bucket and batches fill. ``deadline_ms`` stays as
    the max-wait backstop."""

    enabled: bool = True
    # Floor of the AIMD target batch size.
    min_target: int = 1
    # Starting target per group; 0 = the model's largest batch bucket (the
    # pre-adaptive behavior, so cold groups favor throughput).
    initial_target: int = 0
    # Additive increase applied when a batch fills to target with more work
    # still queued (arrivals outpace the target: grow it).
    increase: float = 1.0
    # Multiplicative decrease applied on a timer-driven partial flush
    # (arrivals can't fill the target: shrink it toward min_target).
    decrease: float = 0.5
    # Smoothing factor for the per-bucket batch-duration EWMA.
    ewma_alpha: float = 0.2
    # Safety margin (ms) subtracted with the EWMA from the earliest request
    # deadline when computing the flush headroom bound.
    slack_ms: float = 2.0

    def __post_init__(self) -> None:
        if self.min_target < 1 or self.initial_target < 0:
            raise ValueError(
                "adaptive.min_target must be >= 1 and initial_target >= 0")
        if self.increase <= 0 or not 0.0 < self.decrease <= 1.0:
            raise ValueError(
                "adaptive.increase must be > 0 and decrease in (0, 1]")
        if not 0.0 < self.ewma_alpha <= 1.0 or self.slack_ms < 0:
            raise ValueError(
                "adaptive.ewma_alpha must be in (0, 1] and slack_ms >= 0")


@dataclass
class GenserveConfig:
    """Iteration-level generation engine (``[genserve]`` TOML;
    tpuserve.genserve, docs/PERFORMANCE.md "The generation engine").

    The static-bucket batcher locks a batch for its whole run — correct for
    one-shot classifiers, wrong for multi-step generative work. With this
    block enabled, models whose family implements the generative contract
    (``tpuserve.genserve.GenerativeModel``: textgen, decoder, sd15) serve through an
    iteration-level engine instead (Orca, PAPERS.md P4): the active batch
    re-forms every model iteration, finished sequences retire immediately,
    queued requests fold into free slots mid-flight, and past-deadline
    sequences evict with the fast-504 contract. Non-generative models keep
    the batcher regardless."""

    enabled: bool = False
    # Generative slot capacity per model (the compiled step batch width);
    # 0 = the model's largest batch bucket.
    slots: int = 0
    # Max queued requests folded into free slots per iteration; 0 = fill
    # every free slot (bounding it smooths per-iteration insert cost).
    admit_per_step: int = 0
    # Streaming (ISSUE 17, docs/ROBUSTNESS.md "Streaming failure
    # semantics"): per-request emission queue depth between the step loop
    # and the HTTP writer. A full queue applies the model's stream_policy
    # (drop droppable progress units, or block the slot).
    stream_queue: int = 64
    # SSE heartbeat comments (": hb") across idle emission gaps, so a
    # proxy/client can distinguish "still generating" from a dead stream;
    # 0 disables heartbeats.
    stream_heartbeat_s: float = 5.0
    # Graceful-drain stream budget: on SIGTERM, in-flight STREAMS get this
    # long to finish before the engine terminates stragglers with the
    # well-formed error event (reason "drain" — never a silent
    # truncation); 0 = streams only get the shared drain_timeout_s.
    stream_drain_s: float = 5.0
    # Paged KV cache (ISSUE 18, docs/PERFORMANCE.md "Paged KV & chunked
    # prefill"; PagedAttention/vLLM): families that implement the paged
    # contract (textgen, decoder) allocate KV as fixed-size pages behind a
    # device-resident block table instead of one dense worst-case-ctx slab
    # per slot. Pages are reserved at fold-in (prompt + decode budget) and
    # returned on retire/evict/disconnect; exhaustion sheds 503 with a
    # Retry-After (reason kv_pressure). Default off: dense path stays
    # byte-compatible, and families without paged programs (sd15) keep the
    # dense slab regardless.
    kv_paging: bool = False
    # Tokens per KV page. Smaller pages track real context tighter (less
    # internal fragmentation); larger pages mean fewer gather indices.
    kv_page_tokens: int = 16
    # Total device pages in the pool, INCLUDING the write-sink sentinel
    # (page 0, never allocated). 0 = auto: slots * pages-per-max-ctx + 1,
    # i.e. the same worst-case KV bytes as the dense slab — set it lower
    # to hold memory fixed while raising [genserve] slots, which is the
    # whole point of paging.
    kv_pages: int = 0
    # Chunked prefill (Orca-style iteration-level scheduling applied to
    # the prompt): a paged prompt folds in this many tokens per engine
    # iteration, interleaved with decode steps, so a max-length prompt
    # never stalls in-flight decoders. 0 = whole prompt in one chunk
    # (exactly the dense prefill math). Only meaningful with kv_paging.
    prefill_chunk: int = 0

    def __post_init__(self) -> None:
        if self.slots < 0 or self.admit_per_step < 0:
            raise ValueError(
                "genserve.slots/admit_per_step must be >= 0")
        if self.stream_queue < 1:
            raise ValueError(
                f"genserve.stream_queue must be >= 1, got {self.stream_queue}")
        if self.stream_heartbeat_s < 0 or self.stream_drain_s < 0:
            raise ValueError(
                "genserve.stream_heartbeat_s/stream_drain_s must be >= 0")
        if self.kv_page_tokens < 1:
            raise ValueError(
                f"genserve.kv_page_tokens must be >= 1, got "
                f"{self.kv_page_tokens}")
        if self.kv_pages < 0 or self.prefill_chunk < 0:
            raise ValueError(
                "genserve.kv_pages/prefill_chunk must be >= 0")
        if self.kv_pages == 1:
            raise ValueError(
                "genserve.kv_pages must be 0 (auto) or >= 2 (the pool "
                "includes the sentinel page)")


@dataclass
class TraceConfig:
    """Request-scoped distributed tracing (``[trace]`` TOML; tpuserve.obs,
    docs/OBSERVABILITY.md).

    Every HTTP request gets a 128-bit trace context at ingest (adopted
    from ``X-Trace-Id`` when the router tier already stamped one) and the
    id comes back as an ``X-Trace-Id`` response header on EVERY response,
    errors included — that part is unconditional, the contract clients and
    the router rely on. This block sizes what gets RETAINED: the flight
    recorder's slowest-N-per-model reservoir, the errored-request FIFO,
    and whether /metrics histograms render per-bucket trace-id
    exemplars."""

    # Slowest-N complete span trees retained per model for /debug/slow;
    # 0 disables the slow reservoir (errors still record).
    slow_n: int = 16
    # Record every errored/shed request (HTTP status >= 400) even when
    # fast — a shed 503 or fast 504 is exactly what gets reported.
    always_record_errors: bool = True
    # Errored-request span trees retained (FIFO beyond it).
    error_capacity: int = 256
    # Render per-bucket trace-id exemplars on /metrics histogram bucket
    # lines (OpenMetrics exemplar syntax), so a dashboard p99 bucket names
    # a recorded trace to click through to.
    exemplars: bool = True

    def __post_init__(self) -> None:
        if self.slow_n < 0 or self.error_capacity < 0:
            raise ValueError(
                "trace.slow_n/error_capacity must be >= 0")


@dataclass
class EventsConfig:
    """Structured event plane (``[events]`` TOML; tpuserve.telemetry.events,
    docs/OBSERVABILITY.md "The third pillar").

    On by default: every process owns a bounded ring of structured event
    records (ts_us / level / subsystem / event / model / trace correlation
    ids / free-form fields) fed by explicit emissions AND a stdlib
    ``logging.Handler`` bridge over the existing ``tpuserve.*`` loggers, so
    call sites flow in without rewriting. Queryable at ``GET /debug/events``
    on the server, every worker, and the router. The same block sizes the
    crash-forensics black box (per-worker stderr capture files + periodic
    postmortem snapshots, folded into ``GET /debug/postmortems`` on reap)
    and the admin audit trail (``GET /debug/audit``)."""

    enabled: bool = True
    # Event records retained in the per-process ring (newest kept).
    capacity: int = 4096
    # Optional JSONL file sink: every event appended as one JSON line
    # ("" disables). The ring is the query surface; the file survives the
    # process.
    jsonl_path: str = ""
    # Minimum stdlib-logging level bridged into the event ring
    # (DEBUG/INFO/WARNING/ERROR). Explicit emissions ignore this.
    bridge_level: str = "INFO"
    # Black-box directory for per-slot stderr capture files and postmortem
    # snapshots; "" derives a per-deployment default under the system temp
    # dir (stable across respawns — the supervisor process resolves it
    # once).
    dir: str = ""
    # Per-worker postmortem-snapshot cadence (s): last-N events, flight-
    # recorder summaries, and key counters checkpointed to the slot's
    # snapshot file (one snapshot is also written at startup). 0 disables.
    snapshot_interval_s: float = 2.0
    # Bytes of a dead process's stderr capture folded into its postmortem
    # record.
    stderr_tail_bytes: int = 4096
    # Admin audit records retained (FIFO beyond it).
    audit_capacity: int = 256
    # Postmortem records retained (FIFO beyond it).
    postmortem_capacity: int = 64
    # Derived per worker slot by the supervisor (stderr capture file /
    # snapshot file under `dir`); set explicitly only in tests.
    stderr_path: str = ""
    snapshot_path: str = ""

    def __post_init__(self) -> None:
        if self.capacity < 1 or self.audit_capacity < 1 \
                or self.postmortem_capacity < 1:
            raise ValueError(
                "events.capacity/audit_capacity/postmortem_capacity "
                "must be >= 1")
        if self.snapshot_interval_s < 0 or self.stderr_tail_bytes < 0:
            raise ValueError(
                "events.snapshot_interval_s/stderr_tail_bytes must be >= 0")
        if self.bridge_level.upper() not in ("DEBUG", "INFO", "WARNING",
                                             "ERROR"):
            raise ValueError(
                f"events.bridge_level must be DEBUG/INFO/WARNING/ERROR, "
                f"got {self.bridge_level!r}")


@dataclass
class TelemetryConfig:
    """Fleet telemetry plane (``[telemetry]`` TOML; tpuserve.telemetry,
    docs/OBSERVABILITY.md "The telemetry plane").

    On by default: a background sampler thread snapshots every counter/
    gauge/histogram into bounded per-metric rings at ``sample_interval_s``,
    from which ``GET /stats/history`` serves time-resolved counter rates
    and histogram-delta quantiles, the SLO engine evaluates multi-window
    burn rates (``[model.slo]`` blocks → ``/alerts``), and the sampler
    derives ``device_utilization{model=,replica=}`` from the device-seconds
    ledger. The router tier additionally scrapes every live worker and peer
    router into ``GET /metrics/fleet`` / ``/stats/fleet``."""

    enabled: bool = True
    # Sampler cadence (s): every tick snapshots the whole metric registry
    # into the rings and re-evaluates burn rates + utilization.
    sample_interval_s: float = 1.0
    # History retained per metric (s); ring capacity = history_s /
    # sample_interval_s, hard-capped at 4096 samples per metric.
    history_s: float = 600.0
    # Burn-rate evaluation windows (s), ascending (Google-SRE multi-window
    # style): an alert FIRES when the burn rate exceeds the model's
    # `burn_alert` threshold over BOTH the first two windows, is PENDING on
    # the first alone, and all windows are exported as
    # slo_burn_rate{model=,window=} gauges.
    burn_windows_s: list[float] = field(
        default_factory=lambda: [60.0, 300.0, 1800.0])
    # Sliding window (s) for deriving device_utilization{model=,replica=}
    # from the device_seconds_total counters.
    utilization_window_s: float = 10.0
    # Per-source budget for the router's fleet scrape (/metrics/fleet):
    # a worker/peer slower than this is stale-marked, never a 5xx.
    fleet_timeout_ms: float = 2000.0
    # Upper bound on POST /debug/profile?duration_ms= (one capture at a
    # time; the jax.profiler device trace merges with the span ring).
    profile_max_ms: float = 10000.0

    def __post_init__(self) -> None:
        if self.sample_interval_s <= 0 or self.history_s <= 0:
            raise ValueError(
                "telemetry.sample_interval_s/history_s must be > 0")
        if len(self.burn_windows_s) < 2 \
                or any(w <= 0 for w in self.burn_windows_s) \
                or sorted(self.burn_windows_s) != list(self.burn_windows_s):
            raise ValueError(
                "telemetry.burn_windows_s must be >= 2 ascending positive "
                f"windows, got {self.burn_windows_s}")
        if self.utilization_window_s <= 0 or self.fleet_timeout_ms <= 0 \
                or self.profile_max_ms <= 0:
            raise ValueError(
                "telemetry.utilization_window_s/fleet_timeout_ms/"
                "profile_max_ms must be > 0")


@dataclass
class SloConfig:
    """Per-model service-level objective (``[model.slo]`` TOML;
    tpuserve.telemetry.slo, docs/OBSERVABILITY.md "The telemetry plane").

    A request is "good" when it answers within ``latency_ms``;
    ``availability`` is the target good fraction, so the error budget is
    ``1 - availability`` and the burn rate over a window is
    (bad fraction) / budget — burn 1.0 spends the budget exactly at the
    sustainable pace, burn N spends it N× too fast. Evaluated per
    ``[telemetry] burn_windows_s`` window by the sampler; `latency_ms = 0`
    (the default) disables the SLO for the model."""

    # Latency objective (ms): requests at or under it are "good".
    # 0 disables SLO evaluation for this model.
    latency_ms: float = 0.0
    # Target good fraction; error budget = 1 - availability.
    availability: float = 0.999
    # Burn-rate threshold: FIRING when exceeded over both the short and
    # mid [telemetry] windows, PENDING on the short alone.
    burn_alert: float = 10.0
    # First-token (first-unit) objective for STREAMED generation (ISSUE
    # 17): a stream is "good" when its first emitted unit landed within
    # this many ms (fed by gen_first_unit_ms{model=}). Evaluated by the
    # same burn-rate machinery as latency_ms, surfaced on /alerts as
    # "<model>:first_unit" and in the autopilot's shed-on-burn seam.
    # 0 (default) disables the first-token SLO.
    first_unit_ms: float = 0.0

    def __post_init__(self) -> None:
        if self.latency_ms < 0:
            raise ValueError(
                f"slo.latency_ms must be >= 0, got {self.latency_ms}")
        if self.first_unit_ms < 0:
            raise ValueError(
                f"slo.first_unit_ms must be >= 0, got {self.first_unit_ms}")
        if not 0.0 < self.availability < 1.0:
            raise ValueError(
                f"slo.availability must be in (0, 1), got {self.availability}")
        if self.burn_alert <= 0:
            raise ValueError(
                f"slo.burn_alert must be > 0, got {self.burn_alert}")


@dataclass
class ParallelConfig:
    """Multi-chip serving plan (``[parallel]`` TOML; docs/PERFORMANCE.md
    "Serving on the mesh").

    Server-wide selection of how the serving path uses the device mesh.
    Per-model ``parallelism`` remains the fine-grained knob; this block
    exists so one line flips a whole deployment between the two multi-chip
    modes (AlpaServe, PAPERS.md P5: placement is a throughput/latency
    lever, not a memory trick):

    - ``mode = "replica"`` — N independent single-device runtime replicas,
      params replicated per chip, the batcher keeping every replica's
      depth-k staging slots full via least-loaded dispatch.
    - ``mode = "sharded"`` — ONE executable over the whole mesh, the batch
      sharded on the data axis (``parallel.mesh.batch_sharding``).
    - ``mode = "single"`` — first device only (dev mode).
    - ``mode = ""`` (default) — every model keeps its own ``parallelism``.

    A non-empty mode overrides EVERY configured model (the override is
    deliberate and total, so a drill can flatten a fleet to one layout with
    one override flag)."""

    # "" = respect per-model `parallelism`; "replica" / "sharded" /
    # "single" override every model's mode at build time.
    mode: str = ""
    # Devices the serving path uses; 0 = every visible device. Lets one
    # host carve chips between serving and background work, and makes
    # CPU-CI runs (8 forced host devices) byte-for-byte reproducible.
    n_chips: int = 0
    # Sharded mode: data-axis size; 0 derives it from the device count and
    # the model's tp/sp axes. Setting `data` with n_chips = 0 sizes the
    # mesh to exactly data * tp * sp devices.
    data: int = 0

    def __post_init__(self) -> None:
        if self.mode not in ("", "replica", "sharded", "single"):
            raise ValueError(
                f"parallel.mode must be one of '', 'replica', 'sharded', "
                f"'single'; got {self.mode!r}")
        if self.n_chips < 0 or self.data < 0:
            raise ValueError("parallel.n_chips/data must be >= 0")


@dataclass
class SchedulerConfig:
    """Fleet-level SLO scheduler (``[scheduler]`` TOML; tpuserve.scheduler,
    docs/ROBUSTNESS.md "Fleet isolation & SLO admission").

    Off by default — every model keeps its independent batcher with no
    cross-model arbitration. When enabled, a central scheduler sits between
    admission and the per-model batchers/engines (Clockwork, PAPERS.md P3):
    requests whose stamped deadline provably cannot be met are shed at
    admission with a fast 504 (``deadline_unmeetable``) instead of dying in
    the queue; ``X-Priority: interactive|batch`` requests arbitrate device
    time through a per-model device-seconds ledger (low-priority work sheds
    first under overload, and no model's interactive traffic is starved
    below ``min_share``); and models declared ``cold_start`` boot without
    device params, warming through the lifecycle stage→publish path on
    first request (or ``:warm``) and demoting back to cold after
    ``idle_demote_s`` so more models than fit in HBM serve honestly."""

    enabled: bool = False
    # Sliding window (s) for the per-model device-seconds ledger that
    # backs the priority-share arbitration.
    window_s: float = 10.0
    # The fleet counts as saturated (low-priority sheds, share floors
    # enforce) when the aggregate predicted queue-clear time across warm
    # models exceeds this many seconds.
    overload_clear_s: float = 1.0
    # Interactive floor: under saturation, a model with queued work whose
    # windowed device-time share is below this is "starved", and models
    # consuming more than their allowance (1 - min_share * others) shed
    # until the starved model catches up. 0 disables the floor.
    min_share: float = 0.05
    # Grace (ms) a request gets beyond the predicted completion before the
    # deadline_unmeetable shed fires — raise it to shed less eagerly when
    # duration EWMAs are noisy.
    headroom_ms: float = 0.0
    # > 0: a warm cold_start model idle this long demotes back to cold,
    # freeing its device params (HBM) until the next request re-warms it.
    idle_demote_s: float = 0.0
    # Retry-After hint (s) on warming-window 503s before the first warm-up
    # has been measured (after that, the measured warm duration is used).
    warm_retry_after_s: float = 5.0
    # Idle-demotion sweep cadence (s).
    sweep_interval_s: float = 0.5
    # Chip budget for warm models (ISSUE 20): the scheduler places models
    # by their parallelism DEGREE (chips a warm runtime occupies — every
    # replica mesh, or tp x sp x data for a sharded one). Warming a cold
    # model whose degree would push the warm fleet past this budget first
    # demotes idle cold_start models to make room, and sheds 503
    # ``chip_budget`` when room cannot be made. 0 = unlimited (the
    # pre-budget behavior).
    chip_budget: int = 0

    def __post_init__(self) -> None:
        if self.window_s <= 0 or self.sweep_interval_s <= 0:
            raise ValueError(
                "scheduler.window_s/sweep_interval_s must be > 0")
        if self.chip_budget < 0:
            raise ValueError(
                f"scheduler.chip_budget must be >= 0, got {self.chip_budget}")
        if not 0.0 <= self.min_share < 0.5:
            raise ValueError(
                f"scheduler.min_share must be in [0, 0.5), got {self.min_share}")
        if self.overload_clear_s < 0 or self.headroom_ms < 0 \
                or self.idle_demote_s < 0 or self.warm_retry_after_s < 0:
            raise ValueError(
                "scheduler.overload_clear_s/headroom_ms/idle_demote_s/"
                "warm_retry_after_s must be >= 0")


@dataclass
class TenantConfig:
    """One tenant (``[[tenants.tenant]]`` TOML; tpuserve.scheduler.tenants).

    A tenant is an API key plus its containment envelope: a fairness
    weight, a windowed device-seconds quota, and a request-rate limit.
    Overage is rejected at admission with 429 + Retry-After — one hostile
    tenant's flood must cost itself capacity, never its neighbors'."""

    name: str = ""
    # The key clients present as ``X-Api-Key``. Must be unique and
    # non-empty.
    api_key: str = ""
    # Fairness weight: the tenant's relative share of device time under
    # saturation, and its share of the result-cache capacity partition.
    weight: float = 1.0
    # Device-seconds the tenant may consume per [tenants] window_s window;
    # 0 = unlimited. Enforced from the windowed ledger at admission
    # (tenant_quota_exceeded 429s with a drain-based Retry-After).
    quota_device_s: float = 0.0
    # Request-rate limit (token bucket, requests/s); 0 = unlimited.
    rate_per_s: float = 0.0
    # Token-bucket burst; 0 derives max(1, 2 * rate_per_s).
    burst: float = 0.0

    def __post_init__(self) -> None:
        if not self.name:
            raise ValueError("tenants.tenant.name must be non-empty")
        if not self.api_key:
            raise ValueError(
                f"tenants.tenant {self.name!r}: api_key must be non-empty")
        if self.weight <= 0:
            raise ValueError(
                f"tenants.tenant {self.name!r}: weight must be > 0, "
                f"got {self.weight}")
        if self.quota_device_s < 0 or self.rate_per_s < 0 or self.burst < 0:
            raise ValueError(
                f"tenants.tenant {self.name!r}: quota_device_s/rate_per_s/"
                "burst must be >= 0")


@dataclass
class TenantsConfig:
    """Multi-tenant front door (``[tenants]`` TOML;
    tpuserve.scheduler.tenants, docs/OPERATIONS.md).

    Off by default. When enabled, every predict request must present a
    configured ``X-Api-Key`` (401 otherwise, unless ``allow_anonymous``),
    and admission enforces per-tenant rate, windowed device-seconds quota,
    and — under fleet saturation — weighted fair share, all from one
    sliding-window weighted device-seconds ledger (the PR 10 per-model
    ledger grown one dimension). The result cache partitions its capacity
    by tenant weight so one tenant's churn cannot evict another's hits,
    and each tenant gets its own SLO burn gauges over
    ``tenant_latency_ms{tenant=}``."""

    enabled: bool = False
    # Sliding window (s) for the per-tenant device-seconds ledger.
    window_s: float = 60.0
    # Admit requests with no/unknown API key as the tenant named here
    # ("" = reject them with 401). The anonymous tenant gets weight 1 and
    # no quota/rate unless a [[tenants.tenant]] entry names it explicitly.
    allow_anonymous: str = ""
    # Multiplier of slack over a tenant's weighted fair share before
    # share-based shedding fires under saturation (tenant_share_exceeded);
    # 0 disables fair-share shedding (rate + quota still enforce).
    share_slack: float = 1.25
    # Per-tenant SLO over tenant_latency_ms{tenant=}: latency objective
    # (ms; 0 disables per-tenant burn evaluation), availability target,
    # and burn-alert threshold — same semantics as [model.slo].
    slo_latency_ms: float = 0.0
    slo_availability: float = 0.999
    slo_burn_alert: float = 10.0
    tenants: list[TenantConfig] = field(default_factory=list)

    def __post_init__(self) -> None:
        if self.window_s <= 0:
            raise ValueError(
                f"tenants.window_s must be > 0, got {self.window_s}")
        if self.share_slack < 0:
            raise ValueError(
                f"tenants.share_slack must be >= 0, got {self.share_slack}")
        if self.slo_latency_ms < 0:
            raise ValueError(
                f"tenants.slo_latency_ms must be >= 0, got {self.slo_latency_ms}")
        if not 0.0 < self.slo_availability < 1.0:
            raise ValueError(
                f"tenants.slo_availability must be in (0, 1), "
                f"got {self.slo_availability}")
        if self.slo_burn_alert <= 0:
            raise ValueError(
                f"tenants.slo_burn_alert must be > 0, got {self.slo_burn_alert}")
        names = [t.name for t in self.tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"tenants.tenant names must be unique: {names}")
        keys = [t.api_key for t in self.tenants]
        if len(set(keys)) != len(keys):
            raise ValueError("tenants.tenant api_keys must be unique")


@dataclass
class AutopilotConfig:
    """Self-healing fleet controller (``[autopilot]`` TOML;
    tpuserve.scheduler.autopilot, docs/OPERATIONS.md "Self-operating
    fleet").

    Off by default. When enabled on the primary router, a background
    reconcile loop reads SLO burn state, fleet queue pressure, and
    predicted clear time every ``interval_s`` and acts through the same
    audited verbs an operator would use: scale worker slots per host
    domain up/down, engage/clear shed-on-burn per model, and (with
    ``paging``) warm/demote models under a cross-model budget. Every
    decision is damped by hysteresis (``hysteresis_ticks`` consecutive
    ticks over threshold), a per-(action, target) cooldown, and a bounded
    action budget per window; every action opens a follow-up watch and is
    rolled back when the objective got WORSE. Every decision — rollbacks
    included — lands in the audit trail with its triggering signal
    values."""

    enabled: bool = False
    # Reconcile tick cadence (s).
    interval_s: float = 0.5
    # Consecutive ticks a trigger condition must hold before acting.
    hysteresis_ticks: int = 3
    # Per-(action kind, target) cooldown (s): the same knob is not touched
    # twice within it (rollbacks are exempt — undo must never wait).
    cooldown_s: float = 10.0
    # Action budget: at most this many non-rollback actions per window_s.
    max_actions_per_window: int = 8
    window_s: float = 60.0
    # Follow-up watch: this long after an action the objective is
    # re-measured; if it got worse by more than rollback_tolerance the
    # action is inverted (audited as outcome "rollback"). 0 disables.
    follow_up_s: float = 15.0
    rollback_tolerance: float = 0.5
    # Queue-pressure thresholds (mean in-flight relays per active healthy
    # worker slot): above high -> scale a domain up; below low with no
    # model burning -> scale down. high must exceed low.
    pressure_high: float = 2.0
    pressure_low: float = 0.25
    # Predicted queue-clear time (s) that also triggers scale-up when the
    # signal is available; 0 disables the clear-time trigger.
    clear_high_s: float = 0.0
    # Never scale a domain below this many active slots.
    min_slots: int = 1
    # Allow shed-on-burn actions: a model FIRING its burn alert gets its
    # batch-class traffic shed at the front door until the alert clears.
    burn_shed: bool = True
    # Allow scale actions against host domains.
    scale: bool = True
    # Allow warm/demote paging actions (fan out :warm / :demote to the
    # workers). Off by default: paging needs [scheduler] cold_start models.
    paging: bool = False
    # Cross-model device-memory budget for paging: max concurrently warm
    # models; 0 = unlimited (demote only on idle sweep).
    max_warm: int = 0
    # Decision records retained for GET /debug/autopilot.
    history: int = 256

    def __post_init__(self) -> None:
        if self.interval_s <= 0 or self.window_s <= 0:
            raise ValueError(
                "autopilot.interval_s/window_s must be > 0")
        if self.hysteresis_ticks < 1 or self.max_actions_per_window < 1 \
                or self.min_slots < 1 or self.history < 1:
            raise ValueError(
                "autopilot.hysteresis_ticks/max_actions_per_window/"
                "min_slots/history must be >= 1")
        if self.cooldown_s < 0 or self.follow_up_s < 0 \
                or self.rollback_tolerance < 0 or self.clear_high_s < 0 \
                or self.max_warm < 0:
            raise ValueError(
                "autopilot.cooldown_s/follow_up_s/rollback_tolerance/"
                "clear_high_s/max_warm must be >= 0")
        if not 0.0 <= self.pressure_low < self.pressure_high:
            raise ValueError(
                f"autopilot.pressure_low must be in [0, pressure_high), got "
                f"low={self.pressure_low} high={self.pressure_high}")


@dataclass
class RouterConfig:
    """Router/worker process split (``[router]`` TOML; tpuserve.workerproc,
    docs/ROBUSTNESS.md "Process failure domains").

    Off by default — the single-process server is unchanged. When enabled,
    ``tpuserve serve`` starts a **router** process owning HTTP/JSON, the
    result cache + single-flight coalescing, admission/deadline stamping,
    and per-model circuit breakers, plus ``workers`` isolated worker
    processes each owning batching + the TPU runtime (Clipper's layered
    architecture, PAPERS.md P1). A supervisor health-checks workers, reaps
    dead ones, and respawns them with exponential backoff; the router
    re-dispatches idempotent work to a surviving worker on transport
    failure (never past the request's absolute deadline) and hedges slow
    attempts — one misbehaving or crashed worker costs capacity, never
    availability."""

    enabled: bool = False
    # Worker processes to supervise (each builds every configured model).
    # With hosts > 0 this is the worker count PER HOST.
    workers: int = 2
    # Host failure domains (ISSUE 13, docs/ROBUSTNESS.md "Host failure
    # domains"). 0 = no host layer: workers are direct children of the
    # router (the PR-8 flat supervisor). N >= 1 groups the workers into N
    # named hosts — locally each host is a supervisor subprocess in its own
    # process group owning `workers` worker processes, so one SIGKILL of
    # the group takes out the entire failure domain exactly like a machine
    # dying. The router routes around a dead host (host breaker + health
    # probes), respawns it with the same exponential backoff as workers,
    # and never places a hedge on its primary's host.
    hosts: int = 0
    # Router processes sharing the serving port via SO_REUSEPORT. Router 0
    # (the primary) owns the host/worker supervisor and supervises the
    # N - 1 peer routers; every router shards the result cache by
    # consistent hash, forwarding hits and single-flight leadership to the
    # key's owning router over loopback HTTP and degrading to local-only
    # (counted, never erroring) when the owner is unreachable.
    routers: int = 1
    # Consecutive relay transport failures (connection refused/reset)
    # against one host's workers before the whole host is routed around
    # without waiting for health probes; 0 disables the host breaker.
    host_breaker_threshold: int = 3
    # How long a tripped host breaker sheds picks before half-opening
    # (the next pick is the recovery probe; success closes it).
    host_breaker_cooldown_s: float = 1.0
    # Peer routers poll the primary for topology (worker addresses, ring
    # membership, cache generations) this often.
    peer_sync_interval_s: float = 0.5
    # Primary's peer-listener bind port (the loopback control plane the
    # peer routers sync from and forward cache hops to); 0 = ephemeral.
    peer_port: int = 0
    # Transport-failure re-dispatches per request (connection refused/reset,
    # a worker dying mid-request). Definitive worker answers (any HTTP
    # status from a live worker except 503-not-admitted) are NEVER retried:
    # a 500 means the work already executed and failed — re-running it
    # would double-execute. Retries always honor the admission deadline.
    retry_max: int = 2
    # > 0: an attempt silent for this long gets a duplicate dispatched to a
    # different worker; first definitive answer wins, the loser is
    # cancelled (tail-latency hedging; covers a wedged-but-alive worker).
    hedge_ms: float = 0.0
    # TCP connect budget per attempt.
    connect_timeout_ms: float = 500.0
    # Supervisor HTTP health-probe cadence and per-probe budget.
    health_interval_s: float = 0.5
    health_timeout_ms: float = 1000.0
    # Consecutive failed probes before a live process is routed around.
    unhealthy_after: int = 3
    # Exponential respawn backoff for dead workers:
    # min(max_s, initial_s * multiplier^consecutive_failures).
    respawn_initial_s: float = 0.5
    respawn_max_s: float = 30.0
    respawn_multiplier: float = 2.0
    # Worker boot budget (spawn -> ready handshake), seconds. Generous:
    # a cold worker AOT-compiles every bucket.
    spawn_timeout_s: float = 900.0
    # Initial ACTIVE worker slots per host domain (autopilot scaling seam):
    # slots beyond this boot scaled-down and cost nothing until the
    # controller (or an operator via /admin/hosts/{hid}:scale) activates
    # them. 0 = all `workers` slots active (the pre-autopilot behavior).
    active_workers: int = 0
    # Streaming relay (ISSUE 17): per-stream idle timeout — a STARTED
    # stream whose worker goes silent (no chunk) this long is terminated
    # with the well-formed error event (reason "idle_timeout"), distinct
    # from the absolute request deadline. 0 disables the idle timeout
    # (only the deadline bounds the stream).
    stream_idle_timeout_ms: float = 30000.0
    # Router-side graceful-drain stream budget: on SIGTERM, in-flight
    # streams get this long to finish before the router terminates them
    # with the error event (reason "drain"); 0 = only drain_timeout_s.
    stream_drain_s: float = 5.0

    def __post_init__(self) -> None:
        if self.workers < 1:
            raise ValueError(f"router.workers must be >= 1, got {self.workers}")
        if self.active_workers < 0 or self.active_workers > self.workers:
            raise ValueError(
                f"router.active_workers must be in [0, workers], got "
                f"{self.active_workers}")
        if self.retry_max < 0 or self.hedge_ms < 0:
            raise ValueError("router.retry_max/hedge_ms must be >= 0")
        if self.respawn_initial_s < 0 or self.respawn_max_s <= 0 \
                or self.respawn_multiplier < 1.0:
            raise ValueError(
                "router.respawn_initial_s must be >= 0, respawn_max_s > 0, "
                "respawn_multiplier >= 1")
        if self.health_interval_s <= 0 or self.unhealthy_after < 1:
            raise ValueError(
                "router.health_interval_s must be > 0 and unhealthy_after >= 1")
        if self.hosts < 0:
            raise ValueError(f"router.hosts must be >= 0, got {self.hosts}")
        if self.routers < 1:
            raise ValueError(
                f"router.routers must be >= 1, got {self.routers}")
        if self.host_breaker_threshold < 0 \
                or self.host_breaker_cooldown_s <= 0:
            raise ValueError(
                "router.host_breaker_threshold must be >= 0 and "
                "host_breaker_cooldown_s > 0")
        if self.peer_sync_interval_s <= 0 or self.peer_port < 0:
            raise ValueError(
                "router.peer_sync_interval_s must be > 0 and "
                "peer_port >= 0")
        if self.stream_idle_timeout_ms < 0 or self.stream_drain_s < 0:
            raise ValueError(
                "router.stream_idle_timeout_ms/stream_drain_s must be >= 0")


@dataclass
class WorkerConfig:
    """Worker-process side of the router split (``[worker]`` TOML;
    tpuserve.workerproc.worker). Workers are full single-process servers
    bound to loopback; the router relays to them."""

    # Bind address for worker HTTP listeners (loopback: workers are an
    # internal tier, never exposed).
    host: str = "127.0.0.1"
    # Worker i listens on port_base + i; 0 = ephemeral ports (recommended —
    # the supervisor learns them from the ready handshake).
    port_base: int = 0
    # Per-worker SIGTERM drain budget; 0 = inherit the server's
    # drain_timeout_s.
    drain_timeout_s: float = 0.0

    def __post_init__(self) -> None:
        if self.port_base < 0 or self.drain_timeout_s < 0:
            raise ValueError(
                "worker.port_base/drain_timeout_s must be >= 0")


# Keys retired in PR 57, by (family, options key), with what decides now.
# ``options`` is an open table, so a key that no code reads would be ignored
# in silence: these are refused by name instead.
_RETIRED_OPTIONS = {
    ("bert", "attention"): "ops.fused_attention.attention_path chooses each bucket's attention "
                           "while it is traced, from platform, dtype, length and head width",
    ("bert", "pp_micro"): "parallelism is 'sharded', 'replica' or 'single'",
    ("textgen", "attention"): "prefill attends with the XLA einsum pair",
    ("sd15", "unet_attention"): "every UNet level runs nn.dot_product_attention",
}


def _refuse_retired(cfg: "ModelConfig") -> None:
    if cfg.parallelism == "pipeline":
        raise ValueError(
            f"{cfg.name}: parallelism = 'pipeline' was retired (PR 57): a model is laid "
            "over devices as 'sharded', 'replica' or 'single'")
    for key in cfg.options:
        if (cfg.family, key) in _RETIRED_OPTIONS:
            raise ValueError(
                f"{cfg.name}: options.{key} was retired (PR 57) and is not read: "
                f"{_RETIRED_OPTIONS[cfg.family, key]}; remove the key")


@dataclass
class ModelConfig:
    """Per-model serving configuration."""

    name: str
    # Which implementation in tpuserve.models to build.
    family: str = "resnet50"
    # Optional path to weights: a TF SavedModel dir, a frozen GraphDef .pb,
    # or an orbax checkpoint dir. None => seeded random init (no-network dev).
    weights: str | None = None
    # Optional path to a class-label file (one name per line, in class-index
    # order, e.g. ImageNet synset names). classify/detect responses then
    # carry a human-readable "label" next to each class index.
    labels: str | None = None
    # Static batch-size buckets, ascending. Each (bucket, input-shape) pair is
    # AOT-compiled to its own XLA executable at startup.
    batch_buckets: list[int] = field(default_factory=lambda: [1, 4, 8, 16, 32])
    # Sequence-length buckets for text models (BERT, SD text encoder).
    seq_buckets: list[int] = field(default_factory=lambda: [64, 128, 256, 512])
    # Batcher flush deadline: a request waits at most this long for the batch
    # to fill before a partial (padded) batch is dispatched.
    deadline_ms: float = 5.0
    # Max requests queued before the server sheds load with 429s.
    max_queue: int = 4096
    # Per-request end-to-end deadline -> 504 when exceeded.
    request_timeout_ms: float = 2000.0
    # Compute dtype for params/activations on device.
    dtype: str = "bfloat16"
    # Quantization: "int8" stores large weights as int8 + per-channel scales
    # and dequantizes inside the compiled forward (halves HBM weight
    # streaming and upload bytes); "int8c" additionally COMPUTES the
    # model's opted-in matmul sites int8 x int8 -> int32 on the MXU with
    # dynamic per-token activation scales (families that name native sites
    # only — see tpuserve.quantize). None = full compute-dtype weights.
    quantize: str | None = None
    # Float leaves smaller than this stay unquantized (biases, norms).
    quantize_min_size: int = 4096
    # Image input edge (H == W) for vision models.
    image_size: int = 224
    # Host->device wire shape edge for images: host decodes to (wire, wire, 3)
    # uint8; the device resizes to image_size. Smaller wire = fewer
    # host->device bytes; 256 leaves headroom for crop-style augmentation.
    wire_size: int = 256
    # Wire encoding for images crossing host->device:
    # - "rgb8":   (wire, wire, 3) uint8 — 3 B/px.
    # - "yuv420": raw JPEG planes (full-res Y + 2x2-subsampled Cb/Cr) —
    #   1.5 B/px, half the transfer bytes with no extra fidelity loss (a JPEG
    #   stores exactly these planes); color conversion happens on device
    #   (preproc.device_prepare_images_yuv420). Requires wire_size % 16 == 0.
    wire_format: str = "rgb8"
    # Parallelism mode: "sharded" (one executable, batch sharded over the
    # mesh), "replica" (one executable per device, independent queues),
    # or "single" (first device only). SURVEY.md §2.1.
    parallelism: str = "sharded"
    # Tensor-parallel axis size carved out of the mesh (1 = TP off).
    tp: int = 1
    # The mesh's "seq" axis (1 = off): `textgen` shards its KV pages over it.
    sp: int = 1
    # Model-specific knobs (e.g. SD: num_steps, guidance_scale; detect: score
    # threshold). Kept open-ended on purpose.
    options: dict[str, Any] = field(default_factory=dict)
    # Number of classes / detection size etc. where the family needs it.
    num_classes: int = 1000
    # Device-section pipeline depth per replica (>=1): how many of this
    # model's batches occupy [h2d..fetch] staging slots at once. The
    # server-wide [pipeline] block's `depth` overrides it when nonzero.
    max_inflight: int = 2
    # Default priority class for requests that carry no X-Priority header
    # ("interactive" or "batch"). Only consulted when the fleet scheduler
    # ([scheduler] enabled) arbitrates: under overload, batch-class work
    # sheds first (docs/ROBUSTNESS.md "Fleet isolation & SLO admission").
    priority: str = "interactive"
    # Fleet scheduler weight paging: True boots this model COLD — compiled
    # variants and device params are not built/resident until the first
    # request (or POST .../{name}:warm) stages them through the lifecycle
    # path, and [scheduler] idle_demote_s can demote them back, freeing
    # HBM. Requires [scheduler] enabled.
    cold_start: bool = False
    # Result-cache eligibility: False keeps this model out of every result
    # cache (server-side ModelCache AND the router tier's wire-level cache).
    # Generative families keep every sampling parameter (seed, temperature,
    # max_new_tokens, steps) inside the decoded item, so two requests
    # differing only in seed can never alias a cache key — set this False
    # only for models that are genuinely nondeterministic in their input
    # (e.g. unseeded sampling).
    cacheable: bool = True
    # Streaming slow-consumer policy (ISSUE 17): what the engine does when
    # a stream's bounded emission queue is full because the client reads
    # slowly. "drop" discards DROPPABLE units (progress/preview events —
    # counted in gen_stream_dropped_total; tokens and terminals are never
    # dropped) and blocks only on non-droppable ones; "block" always
    # blocks the step loop (exact delivery, at the cost of backpressuring
    # the whole slot block).
    stream_policy: str = "drop"
    # Service-level objective ([model.slo] sub-table): latency objective +
    # availability target the telemetry plane's burn-rate engine evaluates
    # (docs/OBSERVABILITY.md "The telemetry plane"). Defaults to disabled
    # (latency_ms = 0).
    slo: SloConfig = field(default_factory=SloConfig)
    # -- robustness (docs/ROBUSTNESS.md) ------------------------------------
    # One-shot batch retry: a failed dispatch re-assembles and re-runs the
    # batch once before failing its futures (absorbs transient device/worker
    # faults without the client seeing a 500).
    batch_retry: bool = True
    # When the whole-batch retry also fails, recursively bisect so a single
    # poison item fails only its own future while the other lanes succeed.
    retry_split: bool = True
    # Circuit breaker: consecutive failed dispatches before the model trips
    # to fast 503 + Retry-After (0 disables). Half-opens via the canary path:
    # canary inferences keep riding the batcher while open, and the first
    # success closes the breaker.
    breaker_threshold: int = 5
    # Retry-After hint (s) on breaker 503s when no periodic canary is
    # configured; with canary_interval_s > 0 the hint is the canary interval.
    breaker_retry_after_s: float = 5.0

    def __post_init__(self) -> None:
        _refuse_retired(self)
        if self.tp < 1 or self.sp < 1:
            raise ValueError(
                f"tp and sp must be >= 1, got tp={self.tp} sp={self.sp}")
        if self.priority not in ("interactive", "batch"):
            raise ValueError(
                f"priority must be 'interactive' or 'batch', "
                f"got {self.priority!r}")
        if self.stream_policy not in ("drop", "block"):
            raise ValueError(
                f"stream_policy must be 'drop' or 'block', "
                f"got {self.stream_policy!r}")


@dataclass
class DistributedConfig:
    """Multi-host (multi-process) JAX runtime initialization (SURVEY.md §5
    "Distributed communication backend").

    On TPU pods each host runs one tpuserve process; setting
    ``coordinator_address`` to process 0's ``host:port`` makes startup call
    ``jax.distributed.initialize`` BEFORE any device use, after which
    ``jax.devices()`` is the global device set and the serving mesh spans
    hosts — data-parallel over DCN, tensor/sequence axes within each host's
    ICI domain (see ``tpuserve.parallel.mesh``). Leave empty for single-host.
    """

    # "host:port" of the process-0 coordinator; "" disables distributed init.
    coordinator_address: str = ""
    # Total process (host) count; -1 = take from the TPU/cluster environment.
    num_processes: int = -1
    # This process's rank; -1 = take from the TPU/cluster environment.
    process_id: int = -1


@dataclass
class ServerConfig:
    """Top-level server configuration."""

    host: str = "0.0.0.0"
    port: int = 8000
    # Multi-host runtime init; defaults to single-host (disabled).
    distributed: DistributedConfig = field(default_factory=DistributedConfig)
    # Multi-chip serving plan: replica-per-chip vs sharded-batch over the
    # local mesh (docs/PERFORMANCE.md "Serving on the mesh").
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    # Iteration-level generation engine for generative families
    # (docs/PERFORMANCE.md "The generation engine"). Off by default: the
    # static-bucket batcher serves everything, including generative models
    # as locked batches.
    genserve: GenserveConfig = field(default_factory=GenserveConfig)
    # Fleet-level SLO scheduler: predictive admission, priority classes,
    # warm/cold weight paging (docs/ROBUSTNESS.md "Fleet isolation & SLO
    # admission"). Off by default.
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    # Router/worker process split: multi-process failure domains with
    # supervision + hedged retry (docs/ROBUSTNESS.md). Off by default.
    router: RouterConfig = field(default_factory=RouterConfig)
    # Worker-process knobs for the router split (loopback bind, drain).
    worker: WorkerConfig = field(default_factory=WorkerConfig)
    models: list[ModelConfig] = field(default_factory=list)
    # Parallel ingest (docs/PERFORMANCE.md "The ingest fast path"): total
    # HTTP accept loops on the serving port. 1 = the classic single event
    # loop. N > 1 adds N-1 dedicated ingest event-loop THREADS, each with
    # its own SO_REUSEPORT listener on the same port, so the kernel spreads
    # connections and body read / frame parse / JSON encode stop
    # serializing on one loop — the loop that owns the batchers only runs
    # admission + dispatch (handlers hop to it via a loop-safe entry).
    # Per-loop balance is visible as ingest_requests_total{loop=}.
    ingest_loops: int = 1
    # Host-side decode threadpool size.
    decode_threads: int = 8
    # Decode request bodies inline on the event loop instead of hopping to
    # the threadpool. On a single-core host the executor hop only adds
    # latency; leave False when real CPU parallelism exists.
    decode_inline: bool = False
    # jax.profiler.start_server port; 0 disables.
    profiler_port: int = 0
    # Validate-on-startup canary (tiny inference per model) on/off.
    startup_canary: bool = True
    # > 0: re-run the per-model canary every this many seconds so /healthz
    # reflects live serving health, not the startup snapshot. Canary
    # inferences ride the normal serving path and appear in /metrics like
    # any synthetic probe; a shed canary (queue full) keeps the last status.
    canary_interval_s: float = 0.0
    # Debug mode (SURVEY.md §5): raise on NaN/Inf produced by any jitted
    # computation (sets jax_debug_nans + jax_debug_infs). Expensive —
    # re-checks every output; dev only.
    debug_nans: bool = False
    # Run every compiled executable once at startup so first requests don't
    # pay PJRT program load (runtime.ModelRuntime.prewarm).
    prewarm_executables: bool = True
    # > 0: after prewarm, time each bucket's raw executable with this many
    # back-to-back dispatches (inputs resident, one dependent read) so the
    # /stats "roofline" block can split the serving compute phase into
    # device-time vs host-wait (docs/PERFORMANCE.md "Reading the roofline").
    # 0 disables the startup probe (the bench runs its own in a subprocess).
    roofline_probe_iters: int = 0
    # Observability: max request-trace events kept for /debug/trace.
    trace_capacity: int = 65536
    # Request-scoped distributed tracing: flight-recorder reservoir sizes
    # and metric exemplars (docs/OBSERVABILITY.md).
    trace: TraceConfig = field(default_factory=TraceConfig)
    # Fleet telemetry plane: time-series history sampler, SLO burn-rate
    # engine, device-utilization derivation, fleet scrape + deep profiling
    # (docs/OBSERVABILITY.md "The telemetry plane"). On by default.
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    # Structured event plane + crash-forensics black box + admin audit
    # trail (docs/OBSERVABILITY.md "The third pillar"). On by default.
    events: EventsConfig = field(default_factory=EventsConfig)
    # Multi-tenant front door: per-tenant API keys, weighted device-seconds
    # ledger, quota/rate/fair-share admission, partitioned result cache,
    # per-tenant SLO burn (docs/OPERATIONS.md). Off by default.
    tenants: TenantsConfig = field(default_factory=TenantsConfig)
    # Self-healing fleet controller: reconcile loop acting through audited
    # admin verbs with hysteresis/cooldown/budget/rollback
    # (docs/OPERATIONS.md "Self-operating fleet"). Off by default.
    autopilot: AutopilotConfig = field(default_factory=AutopilotConfig)
    # Emit one JSON object per log line (machine-ingestible) instead of the
    # human-readable default.
    log_json: bool = False
    # Pipelined host execution engine knobs (stage pools, depth, arenas).
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    # Content-addressed result cache + single-flight coalescing (off by
    # default: only correct for models deterministic in their input).
    cache: CacheConfig = field(default_factory=CacheConfig)
    # SLO-aware adaptive batching (AIMD target batch size per group).
    adaptive: AdaptiveConfig = field(default_factory=AdaptiveConfig)
    # Deterministic fault injection (chaos testing); disabled by default.
    faults: FaultsConfig = field(default_factory=FaultsConfig)
    # Versioned reload lifecycle (integrity checks, staged canary, rollback).
    lifecycle: LifecycleConfig = field(default_factory=LifecycleConfig)
    # Watchdog sweep interval: restart dead group-accumulation tasks (and
    # the generation engine's loop) every this many seconds (0 disables).
    watchdog_interval_s: float = 1.0
    # Graceful-drain budget on SIGTERM: new requests 503 immediately while
    # every accepted request gets this long to finish before hard stop.
    drain_timeout_s: float = 30.0
    # Retry-After hint (seconds) on 429 shed and drain 503 responses.
    shed_retry_after_s: float = 1.0

    def __post_init__(self) -> None:
        if self.ingest_loops < 1:
            raise ValueError(
                f"ingest_loops must be >= 1, got {self.ingest_loops}")

    def model(self, name: str) -> ModelConfig:
        for m in self.models:
            if m.name == name:
                return m
        raise KeyError(f"no model named {name!r} configured")


def _build(cls: type, data: dict[str, Any]) -> Any:
    """Construct dataclass ``cls`` from a dict, erroring on unknown keys."""
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - names
    if unknown:
        raise ValueError(f"unknown {cls.__name__} keys: {sorted(unknown)}")
    return cls(**data)


def load_config(path: str | None = None, overrides: list[str] | None = None) -> ServerConfig:
    """Load a ServerConfig from a TOML file plus ``key.path=value`` overrides.

    Overrides use dot paths, e.g. ``port=9000`` or
    ``model.resnet50.deadline_ms=2.5`` (the second path element selects the
    model by name). Values are parsed as TOML scalars/arrays.
    """
    raw: dict[str, Any] = {}
    if path:
        with open(path, "rb") as f:
            raw = tomllib.load(f)

    model_dicts = raw.pop("model", [])
    dist_dict = raw.pop("distributed", None)
    trace_dict = raw.pop("trace", None)
    telemetry_dict = raw.pop("telemetry", None)
    events_dict = raw.pop("events", None)
    parallel_dict = raw.pop("parallel", None)
    genserve_dict = raw.pop("genserve", None)
    scheduler_dict = raw.pop("scheduler", None)
    router_dict = raw.pop("router", None)
    worker_dict = raw.pop("worker", None)
    faults_dict = raw.pop("faults", None)
    tenants_dict = raw.pop("tenants", None)
    autopilot_dict = raw.pop("autopilot", None)
    lifecycle_dict = raw.pop("lifecycle", None)
    pipeline_dict = raw.pop("pipeline", None)
    cache_dict = raw.pop("cache", None)
    adaptive_dict = raw.pop("adaptive", None)
    cfg: ServerConfig = _build(ServerConfig, raw)
    models = []
    for m in model_dicts:
        # [model.slo] is a nested sub-table of its [[model]] entry.
        slo_dict = m.pop("slo", None)
        mc = _build(ModelConfig, m)
        if slo_dict is not None:
            mc.slo = _build(SloConfig, slo_dict)
        models.append(mc)
    cfg.models = models
    if dist_dict is not None:
        cfg.distributed = _build(DistributedConfig, dist_dict)
    if trace_dict is not None:
        cfg.trace = _build(TraceConfig, trace_dict)
    if telemetry_dict is not None:
        cfg.telemetry = _build(TelemetryConfig, telemetry_dict)
    if events_dict is not None:
        cfg.events = _build(EventsConfig, events_dict)
    if parallel_dict is not None:
        cfg.parallel = _build(ParallelConfig, parallel_dict)
    if genserve_dict is not None:
        cfg.genserve = _build(GenserveConfig, genserve_dict)
    if scheduler_dict is not None:
        cfg.scheduler = _build(SchedulerConfig, scheduler_dict)
    if router_dict is not None:
        cfg.router = _build(RouterConfig, router_dict)
    if worker_dict is not None:
        cfg.worker = _build(WorkerConfig, worker_dict)
    if lifecycle_dict is not None:
        cfg.lifecycle = _build(LifecycleConfig, lifecycle_dict)
    if pipeline_dict is not None:
        cfg.pipeline = _build(PipelineConfig, pipeline_dict)
    if cache_dict is not None:
        cfg.cache = _build(CacheConfig, cache_dict)
    if adaptive_dict is not None:
        cfg.adaptive = _build(AdaptiveConfig, adaptive_dict)
    if faults_dict is not None:
        rule_dicts = faults_dict.pop("rule", [])
        cfg.faults = _build(FaultsConfig, faults_dict)
        cfg.faults.rules = [_build(FaultRuleConfig, r) for r in rule_dicts]
    if tenants_dict is not None:
        # [[tenants.tenant]] entries are nested sub-tables of [tenants].
        tenant_dicts = tenants_dict.pop("tenant", [])
        cfg.tenants = _build(TenantsConfig, tenants_dict)
        cfg.tenants.tenants = [_build(TenantConfig, t) for t in tenant_dicts]
        cfg.tenants.__post_init__()  # re-check uniqueness with the list set
    if autopilot_dict is not None:
        cfg.autopilot = _build(AutopilotConfig, autopilot_dict)

    for ov in overrides or []:
        _apply_override(cfg, ov)
    return cfg


def _parse_toml_value(text: str) -> Any:
    try:
        return tomllib.loads(f"v = {text}")["v"]
    except tomllib.TOMLDecodeError:
        return text  # bare string


def _apply_override(cfg: ServerConfig, override: str) -> None:
    if "=" not in override:
        raise ValueError(f"override must look like key.path=value, got {override!r}")
    key, _, text = override.partition("=")
    value = _parse_toml_value(text.strip())
    parts = key.strip().split(".")

    target: Any = cfg
    if parts[0] == "model":
        if len(parts) < 3:
            raise ValueError(f"model override needs model.<name>.<field>: {override!r}")
        target = cfg.model(parts[1])
        parts = parts[2:]
    for p in parts[:-1]:
        target = target[p] if isinstance(target, dict) else getattr(target, p)
    leaf = parts[-1]
    if isinstance(target, dict):  # e.g. model.<name>.options.<key>
        target[leaf] = value
        return
    if dataclasses.is_dataclass(target) and leaf not in {f.name for f in dataclasses.fields(target)}:
        raise ValueError(f"unknown config field {leaf!r} in {type(target).__name__}")
    setattr(target, leaf, value)


def default_config() -> ServerConfig:
    """The out-of-the-box config: ResNet-50 with random weights."""
    return ServerConfig(models=[ModelConfig(name="resnet50", family="resnet50")])
