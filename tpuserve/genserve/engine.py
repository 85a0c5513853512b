"""Iteration-level continuous batching engine (ISSUE 9; Orca, PAPERS.md P4).

The static-bucket batcher (tpuserve.batcher) locks a batch for its whole
run: correct for one-shot ResNet/BERT, wrong for multi-step generative work
where a 2-token completion admitted behind a 200-token one waits for both.
This engine is the second dispatch path, scheduling at MODEL-ITERATION
granularity over a fixed block of generative slots:

- every iteration the active batch RE-FORMS: finished sequences retire
  immediately (``gen_early_exits_total``), queued requests fold into free
  slots mid-flight (``gen_fold_ins_total``), and past-deadline sequences
  evict with PR 2's fast-504 contract (``gen_evictions_total`` +
  ``deadline_exceeded_total``);
- the per-model state block (KV caches, latent slabs, token buffers) is ONE
  device-resident pytree with leading dim = slots, allocated at start and
  threaded through the compiled step — steady-state serving allocates
  nothing, and the host-side :class:`~tpuserve.genserve.arena.SlotArena`
  ledger guarantees no slot is ever double-handed;
- the three device programs (insert / step / extract) register in PR 6's
  VariantKey registry via ``ModelRuntime.register_program``, so
  ``runtime_compiles_total`` covers them and a delta of 0 across sustained
  admit/retire/``:reload`` churn is the zero-recompile proof
  (scripts/genserve_smoke.sh asserts it). Insert and extract take a TRACED
  slot index — one compile serves every slot.

The engine exposes the ModelBatcher surface (submit/start/stop/drain/
revive_group_loops/pipeline_stats), so the existing front door — deadlines,
breakers, result cache + coalescing, canaries, watchdog revival, graceful
drain, the router tier — holds for multi-step requests unchanged. Device
work hops through the server's shared StageExecutors ("h2d" for inserts and
prefill launches, "fetch" for the step's dispatch and every readback,
"postproc" for finalize), so generation shares the pipeline's
stage-granularity scheduling and metrics.

THE LOOP KEEPS ONE STEP QUEUED AHEAD (ISSUE 41). A pass of ``_step_loop``
is: sweep (expire, evict), admit, launch the waiting prefill pieces, then
DISPATCH step k and only then wait for the out-block of step k-1 (the one
place a pass blocks on the device: the chip runs step k meanwhile), account,
emit and retire from out(k-1). Retiring dispatches the finished slots'
``extract`` and frees slot and pages at once; the extract's outputs are read
in the NEXT pass's one wait and finalized in a task of their own. On the chip
the order of programs is step, extract(s), prefill launch(es), step, ...; the
next one is always already queued when one ends. What this asks of a family
is in ``GenerativeModel.step``'s docstring (a lane whose out-block said
``done`` is frozen), and what it costs: the loop learns of ``done`` one step
late, so a finished lane rides one more step and its answer leaves up to one
step after its last token. Because a slot can be re-armed in the pass its
previous occupant retires, an out-block may be OLDER than a lane's arming:
``SlotInfo.since_step`` / ``armed_step`` say which steps are a request's.

All engine state is event-loop-only (the step loop owns every mutation; a
stage thread touches ``_state`` only while the loop awaits it); there is
deliberately no lock to witness.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpuserve.batcher import DeadlineExceeded, QueueFull
from tpuserve.config import GenserveConfig, PipelineConfig
from tpuserve.genserve.arena import SlotArena, SlotInfo
from tpuserve.genserve.model import CachePlan, GenerativeModel, PrefillPiece
from tpuserve.genserve.pages import PageLedger
from tpuserve.hostpipe import StageExecutors
from tpuserve.obs import (GEN_STREAM_REASONS, PRIORITIES, Metrics, trace_call,
                          trace_mark, trace_span)
from tpuserve.utils.locks import new_lock
from tpuserve.utils.retrace import (allow_transfers, host_fetch,
                                    host_fetch_together)

log = logging.getLogger("tpuserve.genserve")

# A prefill launch that is not full may wait this many iterations, counted
# from its oldest piece, for more pieces to fill it while lanes decode
# (ISSUE 31). Chosen by a chip sweep (PERF.md section 6, PR 31: 0, 1, 2 read
# +5%, +16%, +19% requests/s in the generating cell); a request's first
# token comes up to that many iterations later, which no benchmark metric
# sees.
PREFILL_HOLD = 2

# What the step loop is doing, one name for every instant of its life
# (ISSUE 36; docs/OBSERVABILITY.md "The engine's loop by phase"): the labels of
# ``gen_loop_seconds_total{phase=}`` and of the ``tpuserve.gen_loop`` marks.
LOOP_PHASES = ("sweep", "admit", "prefill", "step", "account", "emit",
               "retire", "wait")
# What ``account`` is made of (ISSUE 51), the labels of
# ``gen_account_seconds_total{part=}``: handing the read extracts to their
# tasks, what the request trees cost (ISSUE 52: the step's one record and one
# ``gen_steps`` span a retired request), and the rest (histograms, counters,
# ``_count_step``, the family's ``observe_step``).
ACCOUNT_PARTS = ("finish", "trees", "sums")
# How many of the engine's last steps ``_StepRecord`` holds. A request that
# rode more has the longest of its last ``STEP_RECORD`` in its ``gen_steps``
# span (``held`` says how many), its count exact all the same.
STEP_RECORD = 4096


class KVPressure(QueueFull):
    """Paged-KV admission shed (ISSUE 18): the free-page ledger cannot
    cover this request's prompt + decode reservation on top of demand
    already queued. Subclasses QueueFull so every existing shed plumbing
    (result-cache passthrough, submit re-raise) carries it unchanged; the
    HTTP layer maps it to 503 with a clear-time Retry-After and shed
    reason "kv_pressure" — the same contract queue-full sheds follow."""

    def __init__(self, message: str,
                 retry_after_s: float | None = None) -> None:
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclass
class _GenRequest:
    item: Any
    future: asyncio.Future = field(repr=False)
    enqueued_at: float = 0.0
    deadline_at: float | None = None
    # Paged mode: pages this request will reserve at fold-in (prompt +
    # decode budget); 0 when paging is off. Summed over the queue it is
    # the committed-demand term of the admission pressure check.
    pages_needed: int = 0
    # Priority class resolved at admission (obs.PRIORITIES); None when the
    # fleet scheduler is off.
    priority: str | None = None
    # Request trace context (obs.TraceContext, ISSUE 12); None untraced.
    ctx: Any = None
    # Emission channel for a streamed request (ISSUE 17); None for unary.
    stream: "GenStream | None" = None


@dataclass
class _StepAhead:
    """A step the device holds whose out-block the host has not read."""
    out: Any        # the out-block, still on the device
    seq: int        # the step's number (``SlotInfo.armed_step`` compares to it)
    iter: int       # the pass that dispatched it: its spans' ``iter``
    at: float       # when it was dispatched (the loop's ``step`` stamp)
    ahead: bool     # dispatched while the step before it was still unread


class _StepRecord:
    """What a step leaves behind for the request trees (ISSUE 52): its
    number, when its out-block reached the host (wall clock) and how long it
    took, in three arrays of ``size`` plain numbers written round and round.
    Nothing is allocated a step and nothing here is a container the collector
    walks, however many lanes ride; a request's ``gen_steps`` span is made
    from it once, when the request goes."""

    def __init__(self, size: int = STEP_RECORD) -> None:
        self.size = size
        self._seq = np.full(size, -1, np.int64)
        self._end = np.zeros(size)
        self._step_s = np.zeros(size)

    def put(self, seq: int, end: float, step_s: float) -> None:
        at = seq % self.size
        self._seq[at], self._end[at], self._step_s[at] = seq, end, step_s

    def ridden(self, first: int, steps: int) -> tuple | None:
        """Steps ``first`` .. ``first + steps - 1`` as one span, from those
        of them still held: (the start of the oldest, the end of the newest,
        on the wall clock in seconds, the span's args: the longest's
        ``longest_ms`` and its ``longest_iteration``, counted from 0 at
        ``first``, and ``held`` where fewer than ``steps`` are). None where
        none is held."""
        seqs = np.arange(max(first, first + steps - self.size), first + steps)
        at = seqs % self.size
        ok = self._seq[at] == seqs
        if not ok.any():
            return None
        seqs, at = seqs[ok], at[ok]
        took = self._step_s[at]
        longest = int(took.argmax())
        args = {"longest_ms": round(float(took[longest]) * 1e3, 3),
                "longest_iteration": int(seqs[longest]) - first}
        if len(seqs) < steps:
            args["held"] = len(seqs)
        return float(self._end[at[0]] - took[0]), float(self._end[at[-1]]), args


@dataclass
class _Extract:
    """A dispatched ``extract`` whose outputs the host has not read: a
    retired slot's (``info`` is out of the arena already) or a stream's
    preview (``info`` still rides). Read in the next pass's one wait."""
    slot: int
    info: SlotInfo
    out: Any        # the outputs on the device; an Exception if the dispatch raised
    t0: float       # when the dispatch began
    iter: int
    early: bool = False
    preview: bool = False


def _retrieve_exception(fut: asyncio.Future) -> None:
    """Streamed requests surface failures as error terminal units on the
    stream; the future stays for cancellation + bookkeeping. Retrieve the
    exception so asyncio never logs 'exception was never retrieved'."""
    if not fut.cancelled():
        fut.exception()


class GenStream:
    """Consumer handle for one streamed generation (ISSUE 17): a bounded
    queue of unit dicts the engine produces and the HTTP layer drains.
    Exactly one terminal unit ("done" or "error") always arrives — every
    engine failure path enqueues one — so a client can always distinguish
    a complete stream from a torn transport. ``close()`` is the consumer's
    abandon signal (client disconnect): it stops further emission and
    unblocks a producer waiting on the full queue."""

    __slots__ = ("queue", "policy", "state", "first_unit_at", "terminated",
                 "dropped")

    def __init__(self, maxsize: int, policy: str) -> None:
        self.queue: asyncio.Queue = asyncio.Queue(maxsize=max(1, maxsize))
        self.policy = policy  # ModelConfig.stream_policy: "drop" | "block"
        self.state: dict = {}  # the model's incremental emission state
        self.first_unit_at: float | None = None
        # Terminal enqueued (or consumer gone): emission is over.
        self.terminated = False
        self.dropped = 0

    async def get(self) -> dict:
        return await self.queue.get()

    def close(self) -> None:
        """Consumer gone: stop emission and free any blocked producer."""
        self.terminated = True
        while True:
            try:
                self.queue.get_nowait()
            except asyncio.QueueEmpty:
                return


class GenEngine:
    """One iteration-level generation engine per served generative model."""

    def __init__(self, model: GenerativeModel, runtime: Any,
                 metrics: Metrics, gcfg: "GenserveConfig | None" = None,
                 breaker: "Any | None" = None,
                 injector: "Any | None" = None,
                 stages: "StageExecutors | None" = None,
                 pipeline_cfg: "PipelineConfig | None" = None,
                 replica: int = 0) -> None:
        self.model = model
        self.runtime = runtime
        self.metrics = metrics
        self.cfg = model.cfg
        self.gcfg = gcfg or GenserveConfig()
        self.breaker = breaker
        self.injector = injector
        # Replica identity (ISSUE 20): which runtime mesh this engine's
        # dispatches ride. 0 for single/sharded; a GenEngineGroup builds
        # one engine per replica mesh and sets ``peers`` so model-level
        # gauges publish group-wide sums instead of last-writer-wins.
        self.replica = int(replica)
        self.peers: "list[GenEngine] | None" = None
        # CPU-backend wedge guard (ISSUE 11, batcher device sections):
        # concurrent dispatches from several replica engines' stage threads
        # spin-wait against each other on forced-host-device meshes. The
        # group installs ONE shared lock on the cpu backend; real
        # accelerator backends (and single engines) keep this None — the
        # step loop stays lock-free there.
        self._dispatch_lock = None
        self.slots = self.gcfg.slots or max(self.cfg.batch_buckets)
        self.arena = SlotArena(self.slots)
        # Paged KV cache (ISSUE 18): only families that ship the paged
        # programs answer a plan (WHAT A SLOT KEEPS: the ledger, the state
        # block and /stats ``kv`` are read off it) — with kv_paging on, sd15
        # (no paged contract) keeps the dense slab byte-for-byte.
        self.plan: CachePlan | None = model.kv_plan(
            self.slots, self.gcfg.kv_page_tokens, self.gcfg.kv_pages) \
            if self.gcfg.kv_paging else None
        self.paging = self.plan is not None
        if self.gcfg.kv_paging and not self.paging:
            log.info("%s: [genserve] kv_paging is on but the family has no "
                     "paged programs — dense state slab kept",
                     model.cfg.name)
        self.pages: PageLedger | None = None
        self._prefill_chunk = 0  # static width of the prefill program
        self._prefill_pieces = 1  # prompts' pieces a launch takes (K)
        self._prefill_tile = 0   # rows a tile: a piece takes whole tiles
        # Slots with prompt left to launch, in order of admission.
        self._prefilling: list[int] = []
        if self.paging:
            plan, pt = self.plan, self.plan.page_tokens
            if plan.pages < plan.pages_per_slot + 1:
                raise ValueError(
                    f"{model.cfg.name}: [genserve] kv_pages={plan.pages} cannot "
                    f"cover one max-context request ({plan.pages_per_slot} "
                    "pages + the sentinel)")
            # A family with window layers keeps one ring a slot beside the
            # full pages (ISSUE 28): the same ledger owns both pools.
            self.pages = PageLedger(
                plan.pages, pt, rings=self.slots + 1 if plan.ring_tokens else 0)
            self._prefill_chunk = int(
                model.kv_prefill_chunk(self.gcfg.prefill_chunk))
            self._prefill_pieces = int(
                model.kv_prefill_pieces(self._prefill_chunk, pt))
            if self._prefill_pieces < 1 \
                    or self._prefill_chunk % self._prefill_pieces:
                raise ValueError(
                    f"{model.cfg.name}: {self._prefill_pieces} pieces do not "
                    f"divide a prefill launch of {self._prefill_chunk}")
            self._prefill_tile = self._prefill_chunk // self._prefill_pieces
        # High-water active-slot mark (bench's max_concurrent_slots).
        self.peak_active = 0
        self._own_stages = stages is None
        self.stages = stages if stages is not None \
            else StageExecutors(pipeline_cfg or PipelineConfig(), metrics)
        name = model.cfg.name
        self.name = name
        # Hot-path metric handles, prebound once (the batcher discipline).
        self._c_iterations = metrics.counter(
            f"gen_iterations_total{{model={name}}}")
        # Of those, the steps dispatched while the step before them was
        # still unread (ISSUE 41): the step ahead engaged.
        self._c_steps_ahead = metrics.counter(
            f"gen_steps_ahead_total{{model={name}}}")
        self._c_admitted = metrics.counter(
            f"gen_admitted_total{{model={name}}}")
        self._c_fold_ins = metrics.counter(
            f"gen_fold_ins_total{{model={name}}}")
        self._c_early_exits = metrics.counter(
            f"gen_early_exits_total{{model={name}}}")
        self._c_evictions = metrics.counter(
            f"gen_evictions_total{{model={name}}}")
        self._c_deadline = metrics.counter(
            f"deadline_exceeded_total{{model={name}}}")
        self._c_items = metrics.counter(f"items_total{{model={name}}}")
        self._c_units = metrics.counter(f"gen_units_total{{model={name}}}")
        self._c_batch_errors = metrics.counter(
            f"batch_errors_total{{model={name}}}")
        self._c_shed = metrics.counter(f"shed_total{{model={name}}}")
        self._g_queue_depth = metrics.gauge(f"queue_depth{{model={name}}}")
        self._g_active = metrics.gauge(f"gen_active_slots{{model={name}}}")
        self._h_step = metrics.histogram(f"gen_step_ms{{model={name}}}")
        self._h_insert = metrics.histogram(f"gen_insert_ms{{model={name}}}")
        self._h_extract = metrics.histogram(f"gen_extract_ms{{model={name}}}")
        self._h_queue = metrics.histogram(
            f"latency_ms{{model={name},phase=queue}}")
        # Streaming (ISSUE 17): first-unit latency feeds the first-token
        # SLO; the terminated counter is per-reason (created on demand).
        self._h_first_unit = metrics.histogram(
            f"gen_first_unit_ms{{model={name}}}")
        self._h_token_gap = metrics.histogram(
            f"gen_token_gap_ms{{model={name}}}")
        # The loop's wall time by phase (ISSUE 36): the eight sum to it. Its
        # thread's CPU time beside it (ISSUE 51): in a phase that holds no
        # await, wall less CPU is time the thread wanted to run and did not.
        self._c_loop = {p: metrics.counter(
            f"gen_loop_seconds_total{{model={name},phase={p}}}")
            for p in LOOP_PHASES}
        self._c_loop_cpu = {p: metrics.counter(
            f"gen_loop_cpu_seconds_total{{model={name},phase={p}}}")
            for p in LOOP_PHASES}
        self._c_account = {p: metrics.counter(
            f"gen_account_seconds_total{{model={name},part={p}}}")
            for p in ACCOUNT_PARTS}
        self._c_streams = metrics.counter(f"gen_streams_total{{model={name}}}")
        self._c_disconnects = metrics.counter(
            f"gen_client_disconnects_total{{model={name}}}")
        self._c_stream_dropped = metrics.counter(
            f"gen_stream_dropped_total{{model={name}}}")
        # Paged-KV observability (ISSUE 18), prebound like everything else
        # so the telemetry sampler sees the rows from the first scrape.
        self._g_kv_pages_total = metrics.gauge(
            f"gen_kv_pages_total{{model={name}}}")
        self._g_kv_pages_free = metrics.gauge(
            f"gen_kv_pages_free{{model={name}}}")
        self._g_kv_util = metrics.gauge(
            f"gen_kv_page_utilization{{model={name}}}")
        self._c_prefill_chunks = metrics.counter(
            f"gen_prefill_chunks_total{{model={name}}}")
        # A launch of the prefill program carries one or more prompts'
        # pieces (ISSUE 31): chunks counts launches, pieces what they carried.
        self._c_prefill_pieces = metrics.counter(
            f"gen_prefill_pieces_total{{model={name}}}")
        self._c_prefill_held = metrics.counter(
            f"gen_prefill_held_total{{model={name}}}")
        self._c_kv_shed = metrics.sched_shed_counter(name, "kv_pressure")
        # What the two phases processed, and what the caches held while they
        # did (ISSUE 28): per-step sums, so a window's mean is a ratio of
        # two deltas (lanes = decode tokens / iterations).
        self._c_prefill_tokens = metrics.counter(
            f"gen_prefill_tokens_total{{model={name}}}")
        self._c_decode_tokens = metrics.counter(
            f"gen_decode_tokens_total{{model={name}}}")
        self._c_pages_held = metrics.counter(
            f"gen_kv_page_steps_total{{model={name}}}")
        self._c_rings_held = metrics.counter(
            f"gen_kv_ring_steps_total{{model={name}}}")
        self._g_kv_rings_free = metrics.gauge(
            f"gen_kv_rings_free{{model={name}}}")
        self._g_state_bytes = metrics.gauge(
            f"gen_state_bytes{{model={name}}}")
        self._g_kv_row_bytes = metrics.gauge(
            f"gen_kv_row_bytes{{model={name}}}")
        self._default_priority = getattr(model.cfg, "priority", "interactive")
        self._h_qwait = {p: metrics.queue_wait_histogram(name, p)
                         for p in PRIORITIES}
        # Fleet device-time ledger hook (tpuserve.scheduler): called with
        # each compiled step's seconds when a scheduler is attached.
        self.device_time_cb = None
        # Device-seconds ledger (ISSUE 14): step time lands on THIS
        # engine's replica row; the telemetry sampler derives
        # device_utilization{model=,replica=} from its rate.
        self._c_device_seconds = metrics.device_seconds_counter(
            name, self.replica)
        # Per-replica engine ledger (ISSUE 20): steps/units/occupancy rows
        # keyed {model=,replica=} — prebound so the telemetry sampler
        # captures them into /stats/history from the first scrape.
        self._c_replica_steps = metrics.gen_replica_steps_counter(
            name, self.replica)
        self._c_replica_units = metrics.gen_replica_units_counter(
            name, self.replica)
        self._g_replica_active = metrics.gen_replica_active_gauge(
            name, self.replica)
        self._g_replica_kv_free = metrics.gen_replica_kv_free_gauge(
            name, self.replica)
        self._pending: collections.deque[_GenRequest] = collections.deque()
        self._state: Any = None
        self._state_struct: Any = None
        self._loop_task: asyncio.Task | None = None
        self._work_event: asyncio.Event | None = None
        self._idle_event: asyncio.Event | None = None
        self._running = False
        # Serving-rate model for estimate_clear_s (429 Retry-After).
        self._ewma_step_ms: float | None = None
        self._ewma_iters: float | None = None
        # Pages-per-request EWMA (paged mode): the "typical admission" the
        # kv_clear_s pressure signal prices.
        self._ewma_pages: float | None = None
        # Runaway guard: a slot that somehow never reports done is failed
        # (and freed) past this bound instead of pinning its slot forever.
        self._max_steps_guard = 2 * max(1, model.gen_max_steps())
        # Drain's bounded stream budget: once set (perf_counter clock),
        # still-open streams past it terminate with the "drain" error
        # event instead of holding the drain hostage.
        self._stream_kill_at: float | None = None
        # The loop's own clock (``_stamp``): the phase it is in, since when,
        # the pass of the loop it belongs to, and when a decoding iteration's
        # out-block last reached the host (None across a wait).
        self._phase = "sweep"
        self._phase_t = 0.0
        self._phase_cpu = 0.0
        self._iter = 0
        self._last_decode_at: float | None = None
        # The step ahead (ISSUE 41): how many steps were dispatched (the next
        # one's number), the one whose out-block is unread, when the last
        # out-block reached the host, the extracts dispatched and unread, and
        # the tasks that finalize and answer what was read.
        self._n_steps = 0
        self._steps = _StepRecord()
        self._ahead: _StepAhead | None = None
        self._last_out_at = 0.0
        self._extracts: list[_Extract] = []
        self._finishing: dict[asyncio.Task, SlotInfo] = {}

    # -- compilation ----------------------------------------------------------
    def compile(self) -> None:
        """Register the insert/step/extract programs in the runtime's
        specialized-variant registry and execute each once (prewarm: PJRT
        program load off the first request's latency). Blocking; call from
        ServerState.build."""
        model, rt = self.model, self.runtime
        t0 = time.perf_counter()
        if self.paging:
            # Paged state block: global page pool + per-slot block table.
            # Page indices are TRACED (like slot indices), so this one
            # registration serves every page assignment the ledger ever
            # makes — the zero-recompile obligation extends to page churn.
            self._state_struct = self.plan.state
            self._g_state_bytes.set(float(self.plan.slot_bytes))
            self._g_kv_row_bytes.set(float(self.plan.row_bytes))
        else:
            self._state_struct = model.state_signature(self.slots)
        geometry = {"kv_paging": self.paging, "slots": self.slots,
                    "pages": self.pages.pages if self.paging else 0,
                    "page_tokens": self.pages.page_tokens
                    if self.paging else 0,
                    "prefill_chunk": self._prefill_chunk}
        if "step" in rt.gen_programs:
            # Programs already registered on this runtime (a second engine
            # over the same runtime — tests, restarts). Reuse requires the
            # same slot width AND the same paging geometry: the compiled
            # state block is shape-frozen.
            step_key = next(k for k in rt.variants
                            if k.bucket and k.bucket[0] == "step")
            if step_key.bucket[1] != self.slots:
                raise ValueError(
                    f"{self.name}: runtime programs were compiled for "
                    f"{step_key.bucket[1]} slots, engine wants {self.slots}")
            prior = getattr(rt, "gen_meta", None)
            if prior and prior != geometry:
                raise ValueError(
                    f"{self.name}: runtime programs were compiled for "
                    f"geometry {prior}, engine wants {geometry}")
            return
        slot_struct = jax.ShapeDtypeStruct((), np.int32)
        item = model.canary_item()
        # Sharded decode (ISSUE 20): on a sharded mesh the family may pin
        # state-block dims to mesh axes (textgen: KV heads on "model").
        # The SAME spec tree goes in as the state arg's sharding and out
        # as the state output's sharding — the state feeds back through
        # the AOT executable, and Compiled demands exact input shardings.
        from jax.sharding import PartitionSpec as P
        sspecs = None
        if getattr(rt, "mode", "single") == "sharded":
            sspecs = model.state_partition_specs(self._state_struct,
                                                 rt.meshes[0])

        def _specs(n_extra: int) -> dict:
            """register_program spec kwargs for (state, *n_extra args)."""
            if sspecs is None:
                return {}
            return {"arg_specs": (sspecs,) + (None,) * n_extra,
                    "out_specs": sspecs}

        if self.paging:
            # ONE prefill program: a launch's shapes are what the family
            # packs, whatever pieces it carries.
            launch_struct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               np.asarray(a).dtype),
                self._canary_launches(item)[0])
            chunk = self._prefill_chunk

            def prefill_fn(params, state, launch):
                return model.prefill_chunk(params, state, launch, chunk=chunk)

            rt.register_program("prefill", prefill_fn,
                                (self._state_struct, launch_struct),
                                width=self.slots, donate_argnums=(0,),
                                **_specs(1))
        else:
            def insert_fn(params, state, slot, item):
                fresh = model.init_state(params, item)
                return jax.tree_util.tree_map(
                    lambda s, u: jax.lax.dynamic_update_index_in_dim(
                        s, u.astype(s.dtype), slot, 0),
                    state, fresh)

            rt.register_program("insert", insert_fn,
                                (self._state_struct, slot_struct,
                                 model.gen_item_signature()),
                                width=self.slots, donate_argnums=(0,),
                                **_specs(2))
        step_specs = {} if sspecs is None else {
            "arg_specs": (sspecs,), "out_specs": (sspecs, P())}
        rt.register_program("step", model.step, (self._state_struct,),
                            width=self.slots, donate_argnums=(0,),
                            **step_specs)
        rt.register_program("extract", model.extract,
                            (self._state_struct, slot_struct),
                            width=self.slots,
                            **({} if sspecs is None
                               else {"arg_specs": (sspecs, None)}))
        rt.gen_meta = geometry
        # Prewarm: one full fold-in + step + extract on a zero state block,
        # with a dependent read per program (the only honest completion
        # signal). Paged mode walks every prefill chunk of the canary so
        # the chunked program loads too. EVERY replica mesh prewarms —
        # PJRT program load must come off replica k's first request too,
        # not just replica 0's.
        for r in range(getattr(rt, "n_replicas", 1)):
            state = self._zero_state(r)
            with self._dispatch_guard():
                if self.paging:
                    for launch in self._canary_launches(item):
                        state = rt.run_program("prefill", state, launch,
                                               replica=r)
                else:
                    state = rt.run_program("insert", state, np.int32(0),
                                           item, replica=r)
                state, out = rt.run_program("step", state, replica=r)
                jax.tree_util.tree_map(np.asarray, out)
                jax.tree_util.tree_map(
                    np.asarray,
                    rt.run_program("extract", state, np.int32(0), replica=r))
        log.info("%s: generation engine compiled+prewarmed %d slots in %.1fs",
                 self.name, self.slots, time.perf_counter() - t0)

    @staticmethod
    def _host_zeros(struct: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda s: np.zeros(tuple(s.shape), s.dtype), struct)

    def _zero_state(self, replica: "int | None" = None) -> Any:
        """The state block as zeros, where a program will take it. A mesh of
        ONE device gets them made there: host zeros cross to the device when
        the first program takes them, and a block of 8.75 GiB took 30 s to
        cross, twice a start-up (prewarm, then the serving block: my chip
        run, PR 55). A mesh of several takes host zeros, which the program
        places by its own specs."""
        mesh = self.runtime.meshes[self.replica if replica is None else replica]
        if mesh.size != 1:
            return self._host_zeros(self._state_struct)
        with jax.default_device(mesh.devices.flat[0]):
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(tuple(s.shape), s.dtype), self._state_struct)

    # -- lifecycle ------------------------------------------------------------
    async def start(self) -> None:
        self._state = self._zero_state()
        if self.pages is not None:
            peers = [e for e in (self.peers or [self])
                     if e.pages is not None]
            self._g_kv_pages_total.set(
                float(sum(e.pages.usable for e in peers)))
            self._update_kv_gauges()
        self._work_event = asyncio.Event()
        self._idle_event = asyncio.Event()
        self._idle_event.set()
        self._running = True
        self._loop_task = asyncio.get_running_loop().create_task(
            self._step_loop())

    async def stop(self) -> None:
        """Cancel the step loop, fail queued and mid-flight requests."""
        self._running = False
        t = self._loop_task
        if t is not None:
            t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass
            except Exception:
                log.exception("step loop for %s failed during stop", self.name)
            self._loop_task = None
        err = RuntimeError(f"server shutting down; {self.name} not served")
        while self._pending:
            req = self._pending.popleft()
            self._terminate_stream(req.stream, "shutdown", str(err))
            if not req.future.done():
                req.future.set_exception(err)
        # The step ahead's out-block is dropped unread; a retired request
        # whose extract was not read, or whose answer a task was still
        # finalizing, is failed like one that still held its slot.
        self._ahead = None
        finishing, self._finishing = self._finishing, {}
        for t in finishing:
            t.cancel()
        await asyncio.gather(*finishing, return_exceptions=True)
        retired = [x.info for x in self._extracts if not x.preview] \
            + list(finishing.values())
        self._extracts = []
        for info in retired + self.arena.release_all():
            self._terminate_stream(info.stream, "shutdown", str(err))
            if not info.future.done():
                info.future.set_exception(err)
        self._prefilling.clear()
        if self.pages is not None:
            self.pages.release_all()
            self._update_kv_gauges()
        self._publish_queue_depth()
        self._publish_active()
        self._maybe_idle()
        if self._own_stages:
            self.stages.shutdown()

    def revive_group_loops(self) -> int:
        """Watchdog hook (same name as the batcher's so server registration
        is uniform): restart the step loop if it died. Mid-flight slots are
        still in the arena, so a revived loop resumes stepping them."""
        if not self._running:
            return 0
        t = self._loop_task
        if t is not None and not t.done():
            return 0
        if t is not None and not t.cancelled() and t.exception() is not None:
            log.error("step loop for %s died: %r — restarting", self.name,
                      t.exception())
        self._loop_task = asyncio.get_running_loop().create_task(
            self._step_loop())
        return 1

    async def drain(self, deadline: float) -> bool:
        """Graceful drain: wait until every accepted request (queued or
        mid-generation) resolved, bounded by ``deadline`` (event-loop
        clock). Same idle-event discipline as the batcher. Streams get
        their own bounded budget inside the window (gcfg.stream_drain_s):
        past it the scheduling passes terminate stragglers with the
        "drain" error event — a well-formed torn-stream signal, never a
        silent truncation or an unbounded drain."""
        loop = asyncio.get_running_loop()
        self._stream_kill_at = time.perf_counter() + self.gcfg.stream_drain_s
        try:
            while self._busy():
                timeout = deadline - loop.time()
                if timeout <= 0:
                    break
                self._idle_event.clear()
                if not self._busy():
                    break
                try:
                    await asyncio.wait_for(self._idle_event.wait(), timeout)
                except asyncio.TimeoutError:
                    break
        finally:
            self._stream_kill_at = None
        self._maybe_idle()
        return not self._busy()

    # -- submission (event loop) ----------------------------------------------
    def submit(self, item: Any, group: Any = None,
               deadline_at: float | None = None,
               priority: str | None = None,
               ctx: Any = None) -> asyncio.Future:
        """Enqueue one decoded request; returns a Future of its result.
        ``group`` is accepted for batcher-API parity and ignored — the
        engine has one slot block, not per-group queues. ``priority``
        labels the queue-wait histogram (arbitration happened upstream).
        ``ctx`` (obs.TraceContext) collects the request's queue/fold-in/
        step/evict/retire spans, tagged with its slot (ISSUE 12)."""
        return self._enqueue(item, deadline_at, priority, ctx, None)

    def submit_stream(self, item: Any, deadline_at: float | None = None,
                      priority: str | None = None,
                      ctx: Any = None) -> "tuple[asyncio.Future, GenStream]":
        """Enqueue one streamed generation -> (future, stream). The HTTP
        layer consumes ONLY the stream (units ending in one terminal —
        every failure path pushes an error terminal, so the queue is the
        single channel); the future exists for disconnect cancellation.
        Raises QueueFull exactly like submit (a shed stream was never
        started — plain 429, no stream semantics involved)."""
        stream = GenStream(self.gcfg.stream_queue,
                           getattr(self.cfg, "stream_policy", "drop"))
        fut = self._enqueue(item, deadline_at, priority, ctx, stream)
        fut.add_done_callback(_retrieve_exception)
        self._c_streams.inc()
        return fut, stream

    def _enqueue(self, item: Any, deadline_at: float | None,
                 priority: str | None, ctx: Any,
                 stream: "GenStream | None") -> asyncio.Future:
        if not self._running or self._work_event is None:
            raise RuntimeError(f"engine for {self.name} not started")
        if len(self._pending) >= self.cfg.max_queue:
            self._c_shed.inc()
            raise QueueFull(self.name)
        need = 0
        if self.pages is not None:
            # Page-pressure admission (ISSUE 18; budgeted admission,
            # Clockwork P3).  An admitted request never hits mid-decode
            # page exhaustion (its FULL reservation — prompt + decode
            # budget — is taken at fold-in), so queued demand only costs
            # latency, not correctness.  We therefore allow one pool
            # turnover of backlog (pages recycle as sequences retire,
            # exactly like the dense queue draining) and shed with a
            # clear-time hint once projected demand exceeds that: at
            # that point the page pool, not compute, is the bottleneck.
            need = self.plan.pages_for(self.model.context_tokens(item))
            projected = self.pages.n_reserved + self._queued_pages() + need
            if projected > 2 * self.pages.usable:
                self._c_shed.inc()
                self._c_kv_shed.inc()
                raise KVPressure(
                    f"{self.name}: kv page pool exhausted (need {need} "
                    f"pages, {self.pages.n_free} free, "
                    f"{self._queued_pages()} queued demand)",
                    retry_after_s=self.kv_clear_s())
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending.append(_GenRequest(
            item=item, future=fut, enqueued_at=time.perf_counter(),
            deadline_at=deadline_at, priority=priority, ctx=ctx,
            stream=stream, pages_needed=need))
        self._publish_queue_depth()
        self._idle_event.clear()
        self._work_event.set()
        return fut

    # -- stream emission (event loop; ISSUE 17) -------------------------------
    def _count_termination(self, reason: str) -> None:
        if reason not in GEN_STREAM_REASONS:
            # Off-vocabulary labels would fragment the metric and dodge
            # the docs/tests contract (TPS404): fail loudly in dev.
            raise ValueError(f"unknown stream-termination reason {reason!r} "
                             f"(add it to obs.GEN_STREAM_REASONS)")
        self.metrics.counter(
            f"gen_stream_terminated_total{{model={self.name},"
            f"reason={reason}}}").inc()

    def _terminate_stream(self, stream: "GenStream | None", reason: str,
                          message: str | None = None,
                          unit: dict | None = None) -> None:
        """Enqueue the terminal unit (sync-safe: callable from scheduling
        passes and stop()). The terminal is never dropped — on a full
        queue the oldest buffered unit makes room; the terminal outranks
        any backlog because the stream is ending either way."""
        if stream is None or stream.terminated:
            return
        stream.terminated = True
        if unit is None:
            unit = {"type": "error", "error": reason,
                    "message": message or reason}
        q = stream.queue
        while True:
            try:
                q.put_nowait(unit)
                break
            except asyncio.QueueFull:
                try:
                    q.get_nowait()
                except asyncio.QueueEmpty:
                    break
        self._count_termination(reason)

    async def _emit_unit(self, stream: "GenStream", unit: dict) -> None:
        """Policy-aware in-flight emission. A droppable unit under policy
        "drop" is discarded when the consumer lags (gen_stream_dropped_
        total); everything else blocks the step loop until the consumer
        drains — re-checking the terminated flag every 50 ms so an
        abandoned stream can never wedge the engine."""
        if stream.terminated:
            return
        if unit.get("droppable") and stream.policy == "drop":
            if stream.queue.full():
                stream.dropped += 1
                self._c_stream_dropped.inc()
                return
            stream.queue.put_nowait(unit)
            return
        while not stream.terminated:
            if not self._running:
                # stop() is tearing the engine down; it sends the
                # "shutdown" terminal itself once the loop exits.
                return
            kill_at = self._stream_kill_at
            if kill_at is not None and time.perf_counter() >= kill_at:
                # Draining and the stream budget is spent: a wedged
                # consumer must not hold the step loop (and the drain)
                # open — it gets the "drain" terminal instead.
                self._terminate_stream(stream, "drain",
                                       "server draining; stream budget spent")
                return
            try:
                await asyncio.wait_for(stream.queue.put(unit), 0.05)
                return
            except asyncio.TimeoutError:
                continue

    async def _emit_step_units(self, out: dict, seq: int) -> None:
        """Flush each streaming slot's newly produced units for step ``seq``
        (the per-iteration flushing Orca's frame makes natural), plus the
        family's optional preview extract — which reuses the compiled extract
        program, so previews never add a compile. A slot armed after step
        ``seq`` was dispatched is passed over: what the out-block says of its
        lane is its previous occupant's. A preview's extract is dispatched
        here and read in the next pass's wait, like a retired slot's."""
        model = self.model
        previews = []
        for slot in self.arena.active_slots():
            info = self.arena.peek(slot)
            stream = info.stream
            if stream is None or stream.terminated or info.future.done() \
                    or not self._rides(info, seq):
                continue
            try:
                units = model.stream_units(out, slot, stream.state)
            except Exception:  # noqa: BLE001 — emission must not kill a slot
                log.exception("stream_units failed for %s slot %d",
                              self.name, slot)
                continue
            if units and stream.first_unit_at is None:
                now = time.perf_counter()
                stream.first_unit_at = now
                ms = (now - info.enqueued_at) * 1e3
                tid = info.ctx.trace_id if info.ctx is not None else None
                self._h_first_unit.observe(ms, trace_id=tid)
                if info.ctx is not None:
                    wall = time.time()
                    info.ctx.span("first_unit", wall - ms / 1e3, wall,
                                  tid=self.name, slot=slot)
            for u in units:
                await self._emit_unit(stream, u)
            if "preview_unread" not in info.meta \
                    and model.stream_wants_preview(out, slot, stream.state):
                previews.append(slot)
        for x in await self._dispatch_extracts(previews):
            x.preview = x.info.meta["preview_unread"] = True

    def _busy(self) -> bool:
        """Is any accepted request unanswered: queued, holding a slot, or
        retired with its extract unread or its answer still being finalized
        (the slot of such a request is free already)?"""
        return bool(self._pending or self.arena.n_active or self._extracts
                    or self._finishing)

    def _maybe_idle(self) -> None:
        if self._idle_event is not None and not self._busy():
            self._idle_event.set()

    # -- gauge publication (event loop) ---------------------------------------
    # Metrics are name-keyed singletons: every engine in a replica group
    # binds the SAME gen_active_slots{model=} handle, so model-level
    # gauges must publish the group-wide value (peers sum) — last-writer-
    # wins would make the gauge flap with whichever replica updated last.
    # Per-replica truth lives on the {model=,replica=} rows. All engines
    # of a group share one event loop, so the sums are consistent.
    def _publish_active(self) -> None:
        n = self.arena.n_active
        self._g_replica_active.set(float(n))
        peers = self.peers
        self._g_active.set(float(n) if peers is None
                           else float(sum(e.arena.n_active for e in peers)))

    def _publish_queue_depth(self) -> None:
        peers = self.peers
        n = (len(self._pending) if peers is None
             else sum(len(e._pending) for e in peers))
        self._g_queue_depth.set(float(n))

    # -- page ledger plumbing (event loop; ISSUE 18) --------------------------
    def _release_slot(self, slot: int) -> SlotInfo:
        """EVERY slot-release path funnels through here so the slot's KV
        pages return to the free list the same instant the slot frees —
        retire, evict, disconnect, runaway guard, insert failure alike.
        ``holds`` guards the page half: a slot can fail admission before
        its page-acquire lands (arena.release's SlotCorrupted tripwire
        still catches double-release through this funnel)."""
        if self.pages is not None and self.pages.holds(slot):
            self.pages.release(slot)
            self._update_kv_gauges()
        if slot in self._prefilling:  # its waiting piece leaves the launch
            self._prefilling.remove(slot)
        return self.arena.release(slot)

    def _update_kv_gauges(self) -> None:
        self._g_replica_kv_free.set(float(self.pages.n_free))
        self._g_kv_rings_free.set(float(self.pages.n_free_rings))
        peers = [e for e in (self.peers or [self]) if e.pages is not None]
        usable = sum(e.pages.usable for e in peers)
        self._g_kv_pages_free.set(float(sum(e.pages.n_free for e in peers)))
        self._g_kv_util.set(
            sum(e.pages.n_reserved for e in peers) / usable if usable
            else 0.0)

    def _queued_pages(self) -> int:
        """Pages the already-accepted queue will reserve once admitted
        (the committed-demand term of the admission pressure check)."""
        return sum(r.pages_needed for r in self._pending)

    def _cache_row(self, page_list: "list[int]", ring: int) -> Any:
        """What the prefill program is told of one slot's caches: its
        block-table row (its pages in position order, padded with the
        sentinel, page 0, past its reservation) and, for a family with
        window layers, its ring beside it."""
        row = np.zeros((self.plan.pages_per_slot,), np.int32)
        row[:len(page_list)] = page_list
        if not self.plan.ring_tokens:
            return row
        return {"pages": row, "ring": np.int32(ring)}

    def _canary_launches(self, item: Any) -> list:
        """One request's whole prompt as the launches of a lone slot 0 over
        the first pages and ring (compile's shapes and prewarm, the staged
        canary): a launch a chunk."""
        row = self._cache_row(list(range(1, self.plan.pages_per_slot + 1)), 1)
        n, chunk = self.model.prompt_tokens(item), self._prefill_chunk
        return [self.model.pack_prefill(
            [PrefillPiece(0, item, s, min(chunk, n - s), row)], chunk,
            self._prefill_pieces) for s in range(0, max(n, 1), chunk)]

    def _observe_pages(self, need: int) -> None:
        prev = self._ewma_pages
        self._ewma_pages = (float(need) if prev is None
                            else prev + 0.2 * (need - prev))

    # -- step loop (event loop) -----------------------------------------------
    def _stamp(self, phase: str) -> float:
        """The loop's ONE clock reading at a boundary (ISSUE 36). Entering
        another phase ends the one the loop was in: its length goes to
        ``gen_loop_seconds_total{phase=}``, the CPU time its thread took in
        it to ``gen_loop_cpu_seconds_total{phase=}`` (ISSUE 51: whatever else
        the event loop ran during a phase's awaits is in it), and, while a
        profiler session is on, into the trace as a ``tpuserve.gen_loop``
        mark. Within a phase it only reads the clock, so that whatever
        reports a piece of the loop's time (``gen_step_ms``,
        ``gen_insert_ms``, ``gen_extract_ms``, the request trees' events)
        takes it from these readings."""
        now = time.perf_counter()
        if phase != self._phase:
            cpu = time.thread_time()
            self._c_loop[self._phase].inc(now - self._phase_t)
            self._c_loop_cpu[self._phase].inc(cpu - self._phase_cpu)
            trace_mark("tpuserve.gen_loop", self._phase_t, now,
                       model=self.name, phase=self._phase, iter=self._iter)
            self._phase, self._phase_t, self._phase_cpu = phase, now, cpu
        return now

    async def _step_loop(self) -> None:
        self._phase, self._phase_t = "sweep", time.perf_counter()
        self._phase_cpu = time.thread_time()
        # The loop condition (not just task cancellation) gates each
        # iteration: asyncio.wait_for can swallow a cancel that lands the
        # same tick its inner future completes, and a step loop that
        # survived its own cancellation would leave stop() awaiting it
        # forever. _running goes False before stop() cancels, so either
        # path exits.
        while self._running:
            if await self._pass():
                continue
            self._stamp("wait")
            self._last_decode_at = None  # no token gap across a wait
            self._maybe_idle()
            self._work_event.clear()
            if not self._pending and not self.arena.n_active:
                await self._work_event.wait()

    async def _pass(self) -> bool:
        """One pass of the loop; False when it found nothing to do and
        nothing of the device's unread (the loop then waits for work).

        The pass blocks on the device in ONE place: ``_step_sync`` dispatches
        step k and then waits for the out-block of step k-1, which the chip
        finished (or is finishing) while the host did the rest of the last
        pass. Everything else a pass does for the device is a dispatch: the
        prefill launches and a retired slot's extract queue behind the step
        that runs."""
        name = self.name
        self._stamp("sweep")
        self._iter += 1
        if self.injector is not None:
            # Chaos: an escaped exception kills this task — exactly the
            # failure revive_group_loops exists to repair.
            self.injector.check("kill_group_loop", name)
        self._expire_pending()
        self._evict_expired()
        if self.arena.n_active or self._pending:
            self._stamp("admit")
            await self._admit()
            self._stamp("prefill")
            await self._advance_prefills()
        elif self._ahead is None and not self._extracts:
            return False
        try:
            t0 = self._stamp("step")
            if self.injector is not None and self.arena.n_active:
                delay = self.injector.delay_s("slow_dispatch", name)
                if delay > 0:
                    await asyncio.sleep(delay)
                    t0 = self._stamp("step")  # the phase's, not the step's
                self.injector.check("batch_error", name)
            # A pass that finds no lane to step dispatches nothing ahead,
            # and the out-block of a step that no request rides any more
            # (every slot went while it was queued) is dropped unread: it
            # counts among neither the iterations nor the steps ahead.
            go = self.arena.n_active > 0
            prev = self._ahead if go else None
            self._ahead = None
            if not go and not self._extracts:
                return True
            queued, out, fetched = await self.stages.run(
                name, "fetch", self._step_sync, go, prev,
                list(self._extracts))
            del self._extracts[:len(fetched)]  # read: no longer the device's
            if go:
                self._ahead = _StepAhead(queued, self._n_steps, self._iter,
                                         t0, prev is not None)
                self._n_steps += 1
            t1 = self._stamp("account")
            wall = time.time()
            # The request trees (ISSUE 52). A step leaves ONE record, of plain
            # numbers, whatever rides it (a span a riding lane a step was the
            # loop's pass and the collector's walk), and a request's tree gets
            # its steps as one span when it goes (``_steps_span``): here for
            # those whose extracts were read, before the tasks that answer
            # them exist. The histogram's exemplar samples one rider.
            for x, _ in fetched:
                if not x.preview:
                    self._steps_span(x.info, x.slot)
            if prev is not None:
                # The host's step time (``gen_step_ms``,
                # ``device_seconds_total``, ``predicted_service_s``): from one
                # out-block's arrival to the next, or from the step's dispatch
                # where nothing was ahead of it. Whatever the chip ran between
                # two steps (a retired slot's extract, the prefill launches)
                # is inside it, as it was inside the blocking loop's
                # dispatch-to-fetch.
                step_s = t1 - max(prev.at, self._last_out_at)
                self._last_out_at = t1
                self._steps.put(prev.seq, wall, step_s)
                rider = self.arena.oldest()  # rides the step if any request does
                ex_tid = rider.ctx.trace_id if (
                    rider is not None and rider.ctx is not None
                    and prev.seq >= rider.since_step) else None
            t_trees = time.perf_counter()
            self._c_account["trees"].inc(t_trees - t1)
            for x, got in fetched:
                self._h_extract.observe((t1 - x.t0) * 1e3)
                if x.preview:
                    await self._emit_preview(x, got)
                else:
                    self._track(self._finish(x, got), x.info)
            t_finish = time.perf_counter()
            self._c_account["finish"].inc(t_finish - t_trees)
            if prev is None:
                return True
            self._h_step.observe(step_s * 1e3, trace_id=ex_tid)
            self._observe_step(step_s * 1e3)
            self._c_device_seconds.inc(step_s)
            if self.device_time_cb is not None:
                self.device_time_cb(step_s)
            self._c_iterations.inc()
            if prev.ahead:
                self._c_steps_ahead.inc()
            self._c_replica_steps.inc()
            self._count_step(out, prev.seq, t1)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — contained per batch
            await self._fail_active(e)
            return True
        self._c_account["sums"].inc(self._stamp("emit") - t_finish)
        await self._emit_step_units(out, prev.seq)
        self._stamp("retire")
        await self._retire(out, prev.seq)
        return True

    def _steps_span(self, info: SlotInfo, slot: int) -> None:
        """The steps a request rode as ONE span of its tree (ISSUE 52),
        written when it goes (retired, evicted, failed with the block): from
        the start of the first to the end of the last, ``steps`` of them, and
        the longest by its length and its place among them, so that a
        stretched step still shows in every rider's tree. A request that rode
        none gets none."""
        if info.ctx is None or not info.iterations:
            return
        span = self._steps.ridden(info.since_step, info.iterations)
        if span is not None:
            start, end, args = span
            info.ctx.span("gen_steps", start, end, tid=self.name, slot=slot,
                          steps=info.iterations, **args)

    @staticmethod
    def _rides(info: SlotInfo, seq: int) -> bool:
        """Does step ``seq``'s out-block speak of THIS request's lane? Not
        while its prompt is in prefill (the lane's device done-flag is its
        freeze, not completion), and not when the step was dispatched before
        the lane was armed: the loop reads out(k) a pass after it dispatched
        step k, and a slot released in that pass and armed again in it shows
        in out(k) as its previous occupant left it (``done`` included)."""
        return info.armed_step is not None and seq >= info.armed_step

    def _count_step(self, out: dict, seq: int, at: float) -> None:
        """Step ``seq``'s part of the per-step sums: the lanes that decoded a
        token in it, the pages and rings reserved when its out-block was
        read, and whatever the family sums on the device (``observe_step``).
        ``at`` is when the step's out-block reached the host (the ``step``
        phase's end): a lane of a paged family that decodes for the first time
        has its first token there (``gen_first_unit_ms``, streamed or not: a
        streamed request is observed where its unit is emitted), and two such
        moments in a row are one token gap (``gen_token_gap_ms``). A lane
        armed after the step was dispatched did not decode in it."""
        self.model.observe_step(out)
        decoded = 0
        for s in self.arena.active_slots():
            info = self.arena.peek(s)
            if not self._rides(info, seq):
                continue
            decoded += 1
            if info.first_unit_at is None:
                info.first_unit_at = at
                if self.paging and info.stream is None:
                    self._h_first_unit.observe(
                        (at - info.enqueued_at) * 1e3,
                        trace_id=info.ctx.trace_id if info.ctx is not None
                        else None)
        self._c_decode_tokens.inc(decoded)
        if decoded:
            if self._last_decode_at is not None:
                self._h_token_gap.observe((at - self._last_decode_at) * 1e3)
            self._last_decode_at = at
        if self.pages is not None:
            self._c_pages_held.inc(self.pages.n_reserved)
            self._c_rings_held.inc(self.pages.n_reserved_rings)

    def _dispatch_guard(self):
        """Context for one device-dispatch section: the group's shared
        CPU-backend lock when installed (see __init__), else a no-op.
        Sync-only sections — no await ever runs under it, so the lock
        witness has nothing to flag."""
        lock = self._dispatch_lock
        return lock if lock is not None else nullcontext()

    def _dispatched(self, outputs: Any) -> Any:
        """The end of a dispatch section. Where the group's CPU-backend lock
        is installed the section lasts until the program has run, as it did
        when every dispatch was followed by its fetch: forced host devices
        share the host's cores, and programs of several replicas in flight at
        once spin-wait against each other. On an accelerator (no lock) this
        is nothing, and the program runs on while the host goes on."""
        if self._dispatch_lock is not None:
            jax.block_until_ready(outputs)
        return outputs

    def _step_sync(self, go: bool, prev: "_StepAhead | None",
                   extracts: "list[_Extract]") -> tuple:
        """The pass's one wait, on the fetch stage executor: dispatch the
        next step if there is a lane to step (``go``; the call returns once
        the chip has it queued), THEN block until the out-block of the step
        before it is on the host, and the outputs of the extracts dispatched
        in the last pass, which the chip ran right behind that step. Returns
        (the new step's out-block on the device or None, the older one's on
        the host or None, [(extract, outputs or the exception its read
        raised)]).
        ``tpuserve.gen_fetch`` carries the ``iter`` of the step whose
        out-block it waits for, not the pass's."""
        queued = out = None
        if go:
            with self._dispatch_guard():
                with trace_span("tpuserve.gen_step", model=self.name,
                                lanes=self.arena.n_active, iter=self._iter):
                    self._state, queued = self._dispatched(
                        self.runtime.run_program("step", self._state,
                                                 replica=self.replica))
        if prev is not None:
            with trace_span("tpuserve.gen_fetch", model=self.name,
                            iter=prev.iter):
                out = host_fetch_together(prev.out)
        fetched = []
        for x in extracts:
            got = x.out
            if not isinstance(got, Exception):
                try:
                    got = host_fetch_together(got)
                except Exception as e:  # noqa: BLE001 — contained to this slot
                    got = e
            fetched.append((x, got))
        return queued, out, fetched

    def _insert_sync(self, slot: int, item: Any) -> None:
        with self._dispatch_guard():
            self._state = self.runtime.run_program(
                "insert", self._state, np.int32(slot), item,
                replica=self.replica)

    def _prefill_sync(self, pieces: "list[PrefillPiece]") -> None:
        with trace_span("tpuserve.gen_pack", model=self.name,
                        pieces=len(pieces), iter=self._iter):
            launch = self.model.pack_prefill(pieces, self._prefill_chunk,
                                             self._prefill_pieces)
        with self._dispatch_guard(), trace_span(
                "tpuserve.gen_prefill", model=self.name, slot=pieces[0].slot,
                start=pieces[0].start, pieces=len(pieces),
                tokens=sum(p.length for p in pieces), iter=self._iter):
            self._state = self.runtime.run_program(
                "prefill", self._state, launch, replica=self.replica)

    def _pack_launches(self, pieces: "list[PrefillPiece]"
                       ) -> "list[list[PrefillPiece]]":
        """Waiting pieces, in order, into as few launches as hold them: a
        launch has K tiles, a piece takes whole tiles, and a piece that does
        not fit what is left of a launch is cut at a tile's edge. Only the
        last launch can be short of full."""
        k, tile = self._prefill_pieces, self._prefill_tile
        launches: list[list[PrefillPiece]] = [[]]
        room = k
        for p in pieces:
            start, left = p.start, p.length
            while left > 0:
                if room == 0:
                    launches.append([])
                    room = k
                take = min(left, room * tile)
                launches[-1].append(
                    PrefillPiece(p.slot, p.item, start, take, p.cache))
                room -= -(-take // tile)
                start, left = start + take, left - take
        return launches if launches[-1] else launches[:-1]

    def _decoding(self) -> bool:
        """Is any lane past its prefill (the step has live work to do)?"""
        return any("prefill_next" not in self.arena.peek(s).meta
                   for s in self.arena.active_slots())

    async def _advance_prefills(self) -> None:
        """Once an iteration: every prefilling slot's next piece (at most a
        launch's width of its prompt, so a long prompt advances as it did
        alone and a short one is not starved behind it), in order of
        admission, packed into launches. A full launch goes at once. The
        last, if it is not full, goes too when no lane is decoding or its
        oldest piece has waited ``PREFILL_HOLD`` iterations; else it waits
        for the next iteration's pieces while the lanes step. Interleaved
        with decode steps (Orca's iteration-level scheduling applied to
        prefill), in-flight decoders see a bounded per-iteration stall."""
        if not self._prefilling:
            return
        pieces = []
        for slot in self._prefilling:
            info = self.arena.peek(slot)
            if info.future.done():  # abandoned: _evict_expired frees it
                continue
            start = info.meta["prefill_next"]
            pieces.append(PrefillPiece(
                slot, info.item, start,
                min(self._prefill_chunk, info.meta["prefill_n"] - start),
                info.meta["pages_row"]))
        for launch in self._pack_launches(pieces):
            metas = [self.arena.peek(p.slot).meta for p in launch]
            waited = max(m["prefill_held"] for m in metas)
            tiles = sum(-(-p.length // self._prefill_tile) for p in launch)
            if tiles < self._prefill_pieces and waited < PREFILL_HOLD \
                    and self._decoding():
                for m in metas:
                    m["prefill_held"] += 1
                return
            t0 = self._stamp("prefill")
            try:
                await self.stages.run(self.name, "h2d", self._prefill_sync,
                                      launch)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — the block may be
                # half-written: the blast radius of a failed insert.
                await self._fail_active(e)
                return
            self._h_insert.observe((self._stamp("prefill") - t0) * 1e3)
            self._c_prefill_chunks.inc()
            self._c_prefill_pieces.inc(len(launch))
            self._c_prefill_tokens.inc(sum(p.length for p in launch))
            if waited:
                self._c_prefill_held.inc()
            for p, m in zip(launch, metas):
                m["prefill_held"] = 0
                m["prefill_next"] = p.start + p.length
                if m["prefill_next"] >= m["prefill_n"]:
                    # The program armed the lane: decode owns it from the
                    # next step dispatched on (an out-block of an earlier
                    # step, still unread, is not this request's).
                    del m["prefill_next"]
                    self._prefilling.remove(p.slot)
                    self.arena.peek(p.slot).armed_step = self._n_steps

    def _extract_dispatch_sync(self, slots: "list[int]") -> list:
        """Dispatch ``extract`` for each slot, in order, and wait for none:
        the programs queue behind the step the chip is running, and read the
        state block as that step leaves it (a finished lane is frozen, so
        the rows are the ones its ``done`` was said of). One slot's dispatch
        that raises is that slot's failure: its place holds the exception."""
        outs = []
        for slot in slots:
            try:
                with self._dispatch_guard(), trace_span(
                        "tpuserve.gen_extract", model=self.name, slot=slot,
                        iter=self._iter):
                    outs.append(self._dispatched(self.runtime.run_program(
                        "extract", self._state, np.int32(slot),
                        replica=self.replica)))
            except Exception as e:  # noqa: BLE001 — contained to this slot
                outs.append(e)
        return outs

    async def _dispatch_extracts(self, slots: "list[int]") -> "list[_Extract]":
        """Queue the slots' extracts on the device (one hop to the fetch
        stage for all of them) and note them unread: the next pass's one
        wait reads them behind its out-block."""
        if not slots:
            return []
        t0 = self._stamp(self._phase)
        outs = await self.stages.run(self.name, "fetch",
                                     self._extract_dispatch_sync, slots)
        new = [_Extract(slot, self.arena.peek(slot), out, t0, self._iter)
               for slot, out in zip(slots, outs)]
        self._extracts += new
        return new

    def _track(self, coro: Any, info: SlotInfo) -> None:
        """Run ``coro``, which answers ``info``'s request, as a task off the
        loop's path; ``drain`` and ``_maybe_idle`` count it until it ends,
        and ``stop`` answers for it if it has not."""
        task = asyncio.get_running_loop().create_task(coro)
        self._finishing[task] = info

        def done(t: asyncio.Task) -> None:
            self._finishing.pop(t, None)
            if not t.cancelled() and t.exception() is not None:
                log.error("finishing a retired slot of %s failed: %r",
                          self.name, t.exception())
            self._maybe_idle()
        task.add_done_callback(done)

    async def _emit_preview(self, x: _Extract, extracted: Any) -> None:
        """A stream's preview unit from its extract's outputs, read a pass
        after they were asked for. Best-effort and droppable."""
        info = x.info
        info.meta.pop("preview_unread", None)
        stream = info.stream
        if stream is None or stream.terminated:
            return
        if isinstance(extracted, Exception):
            log.error("preview extract failed for %s slot %d: %r",
                      self.name, x.slot, extracted)
            return
        try:
            u = self.model.stream_preview_unit(extracted, stream.state)
        except Exception:  # noqa: BLE001 — a preview is best-effort
            log.exception("preview unit failed for %s slot %d",
                          self.name, x.slot)
        else:
            await self._emit_unit(stream, u)

    async def _finish(self, x: _Extract, extracted: Any) -> None:
        """The rest of a retirement, OFF the loop's path (a task a slot):
        ``finalize`` on the postproc stage, the stream's terminal burst, the
        future's result and the request's counters. The slot and its pages
        went back when the extract was dispatched; ``extracted`` is what the
        next pass's wait read of it, or the exception that raised."""
        info, slot = x.info, x.slot
        trace_id = info.ctx.trace_id if info.ctx is not None else None
        try:
            if isinstance(extracted, Exception):
                raise extracted
            result = await self.stages.run(
                self.name, "postproc", trace_call, "tpuserve.gen_finalize",
                {"model": self.name, "slot": slot, "iter": x.iter},
                self.model.finalize, extracted, info.item)
            if info.stream is not None and not info.stream.terminated:
                # Terminal burst: the family's final units (sd15's
                # image, then done with finish reason + usage). The
                # done unit goes through _terminate_stream so its
                # delivery is unconditional and the per-reason
                # counter sees a "done".
                finals = self.model.stream_final_units(extracted, result)
                for u in finals[:-1]:
                    await self._emit_unit(info.stream, u)
                self._terminate_stream(
                    info.stream, "done",
                    unit=finals[-1] if finals else {"type": "done"})
        except asyncio.CancelledError:  # stop(), which answers for this one
            raise
        except Exception as e:  # noqa: BLE001 — contained to this slot
            log.error("retire failed for %s slot %d: %r", self.name, slot, e)
            self._c_batch_errors.inc()
            if self.breaker is not None:
                self.breaker.record_failure()
            self._terminate_stream(info.stream, "engine_error", str(e))
            if not info.future.done():
                info.future.set_exception(e)
            return
        if not info.future.done():
            info.future.set_result(result)
        self._c_items.inc()
        units = self.model.result_units(result)
        self._c_units.inc(units)
        self._c_replica_units.inc(units)
        self._observe_retire(info.iterations)
        if x.early:
            self._c_early_exits.inc()
        if self.breaker is not None:
            self.breaker.record_success()
        t1 = time.perf_counter()
        wall1 = time.time()
        trace_mark("tpuserve.gen_retire", x.t0, t1, model=self.name,
                   slot=slot)
        if info.ctx is not None:
            # Retire event: extract + finalize for this slot, behind the
            # request's ``gen_steps`` span.
            info.ctx.span("retire", wall1 - (t1 - x.t0), wall1,
                          tid=self.name, slot=slot,
                          iterations=info.iterations)
        self.metrics.tracer.add(
            f"gen[{info.iterations}it]",
            wall1 - (t1 - info.enqueued_at), wall1,
            tid=self.name, trace_id=trace_id, slot=slot,
            iterations=info.iterations)

    # -- scheduling passes ----------------------------------------------------
    def _expire_pending(self) -> None:
        """Fail queued requests whose deadline passed and drop cancelled
        ones — rejected in microseconds, never admitted (fast-504)."""
        if not self._pending:
            return
        now = time.perf_counter()
        kill_at = self._stream_kill_at
        live: collections.deque[_GenRequest] = collections.deque()
        n_expired = 0
        for req in self._pending:
            if req.future.done():
                if req.stream is not None:
                    req.stream.close()  # consumer already gone
                continue
            if req.deadline_at is not None and now >= req.deadline_at:
                msg = ("deadline expired after "
                       f"{(now - req.enqueued_at) * 1e3:.0f} ms in queue")
                self._terminate_stream(req.stream, "deadline_exceeded", msg)
                req.future.set_exception(DeadlineExceeded(msg))
                n_expired += 1
                continue
            if req.stream is not None and kill_at is not None \
                    and now >= kill_at:
                # Drain's stream budget spent before this one ever started.
                self._terminate_stream(req.stream, "drain",
                                       "server draining; stream budget spent")
                req.future.set_exception(RuntimeError(
                    f"{self.name}: draining; stream budget spent"))
                continue
            live.append(req)
        if n_expired:
            self._c_deadline.inc(n_expired)
        if len(live) != len(self._pending):
            self._pending = live
            self._publish_queue_depth()

    def _evict_expired(self) -> None:
        """Mid-generation deadline eviction: a slot whose request deadline
        passed (or whose client went away) frees NOW — its remaining
        iterations are never computed for nobody (Clockwork P3). The
        freed slot's device lanes hold stale state until the next insert
        overwrites them; their own done-flag freezes them within the
        model's step bound, so the garbage compute is bounded and the
        ledger stays exact. A step that still holds the lane live may be
        queued on the chip when the slot goes (the step ahead, ISSUE 41):
        its out-block is read for the lanes that remain, and a request that
        takes the slot meanwhile is passed over in it (``_rides``: the step
        was dispatched before that request's arming)."""
        now = time.perf_counter()
        kill_at = self._stream_kill_at
        for slot in self.arena.active_slots():
            info = self.arena.peek(slot)
            if info.future.done():  # client disconnected mid-generation
                if info.stream is not None:
                    self._c_disconnects.inc()
                    self._count_termination("disconnect")
                    info.stream.close()
                self._release_slot(slot)
                continue
            if info.deadline_at is not None and now >= info.deadline_at:
                msg = (f"deadline expired after {info.iterations} "
                       "iteration(s) "
                       f"({(now - info.enqueued_at) * 1e3:.0f} ms total)")
                # Deadline-contract split (ISSUE 17): before the first unit
                # the HTTP layer still answers a plain fast 504; after it,
                # this terminal becomes the in-stream error event naming
                # deadline_exceeded — either way, never a silent cut.
                self._terminate_stream(info.stream, "deadline_exceeded", msg)
                info.future.set_exception(DeadlineExceeded(msg))
                self._c_deadline.inc()
                self._c_evictions.inc()
                if info.ctx is not None:
                    self._steps_span(info, slot)
                    wall = time.time()
                    info.ctx.span("evict", wall, wall, tid=self.name,
                                  slot=slot, iterations=info.iterations)
                self._release_slot(slot)
                continue
            if info.stream is not None and kill_at is not None \
                    and now >= kill_at:
                self._terminate_stream(info.stream, "drain",
                                       "server draining; stream budget spent")
                info.future.set_exception(RuntimeError(
                    f"{self.name}: draining; stream terminated after "
                    f"{info.iterations} iteration(s)"))
                self._c_evictions.inc()
                if info.ctx is not None:
                    self._steps_span(info, slot)
                    wall = time.time()
                    info.ctx.span("evict", wall, wall, tid=self.name,
                                  slot=slot, iterations=info.iterations,
                                  reason="drain")
                self._release_slot(slot)
        self._publish_active()

    async def _admit(self) -> None:
        """Fold queued requests into free slots — mid-flight when the block
        is already generating (the continuous-batching property)."""
        cap = self.gcfg.admit_per_step or self.slots
        admitted = 0
        while self.arena.n_free and self._pending and admitted < cap:
            req = self._pending.popleft()
            self._publish_queue_depth()
            if req.future.done():
                continue
            now = time.perf_counter()
            if req.deadline_at is not None and now >= req.deadline_at:
                msg = ("deadline expired after "
                       f"{(now - req.enqueued_at) * 1e3:.0f} ms in queue")
                self._terminate_stream(req.stream, "deadline_exceeded", msg)
                req.future.set_exception(DeadlineExceeded(msg))
                self._c_deadline.inc()
                continue
            if self.pages is not None \
                    and not self.pages.can_cover(req.pages_needed):
                # Head-of-line waits for pages to free (strict FIFO —
                # skipping ahead would starve long-context requests); the
                # admission-time pressure check bounds how long.
                self._pending.appendleft(req)
                self._publish_queue_depth()
                break
            fold = any(self.arena.peek(s).iterations > 0
                       for s in self.arena.active_slots())
            info = SlotInfo(item=req.item, future=req.future,
                            deadline_at=req.deadline_at,
                            enqueued_at=req.enqueued_at, admitted_at=now,
                            ctx=req.ctx, stream=req.stream,
                            since_step=self._n_steps)
            slot = self.arena.acquire(info)
            t0 = now
            try:
                # One protecting try covers the whole held window — page
                # acquire, host bookkeeping, and the compiled insert — so
                # no exception path can leak the slot or its pages
                # (TPS601: ledger escape analysis gates on this).
                if self.pages is not None:
                    page_list = self.pages.acquire(slot, req.pages_needed)
                    self._update_kv_gauges()
                    self._observe_pages(req.pages_needed)
                    n_prompt = self.model.prompt_tokens(req.item)
                    info.meta["pages_row"] = self._cache_row(
                        page_list, self.pages.ring_of(slot))
                    info.meta["prefill_n"] = n_prompt
                    info.meta["prefill_next"] = 0   # the launched cursor
                    info.meta["prefill_held"] = 0   # iterations waited
                    # Iterations its prefill can take: a tile at least every
                    # iteration that does not hold it.
                    info.meta["prefill_chunks"] = \
                        -(-n_prompt // self._prefill_tile) \
                        * (PREFILL_HOLD + 1)
                if self.arena.n_active > self.peak_active:
                    self.peak_active = self.arena.n_active
                wait_ms = (now - req.enqueued_at) * 1e3
                trace_id = req.ctx.trace_id if req.ctx is not None else None
                self._h_queue.observe(wait_ms, trace_id=trace_id)
                self._h_qwait[req.priority or self._default_priority].observe(
                    wait_ms, trace_id=trace_id)
                if req.ctx is not None:
                    wall = time.time()
                    req.ctx.span("queue", wall - wait_ms / 1e3, wall,
                                 tid=self.name)
                t0 = time.perf_counter()
                trace_mark("tpuserve.gen_admit", now, t0, model=self.name,
                           slot=slot)
                if self.pages is not None:
                    # Paged fold-in is the host's part only: the prompt's
                    # pieces go to the device in _advance_prefills, packed
                    # with whatever else waits and interleaved with decode
                    # steps, so a long prompt never stalls the block for
                    # one monolithic prefill.
                    self._prefilling.append(slot)
                else:
                    await self.stages.run(self.name, "h2d",
                                          self._insert_sync, slot, req.item)
                    # The insert armed the lane: the next step dispatched
                    # is the first that is this request's.
                    info.armed_step = self._n_steps
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                # The state block may be half-written (and donated buffers
                # consumed on TPU): hard-reset like a step failure. The
                # admitting request fails with the cause too.
                self._release_slot(slot)
                self._terminate_stream(req.stream, "engine_error", str(e))
                if not req.future.done():
                    req.future.set_exception(e)
                await self._fail_active(e)
                return
            insert_s = time.perf_counter() - t0
            if self.pages is None:  # paged: a launch's time, where it goes
                self._h_insert.observe(insert_s * 1e3, trace_id=trace_id)
            if req.ctx is not None:
                # "fold_in" = admitted into an ALREADY-generating block
                # (the continuous-batching property); "admit" = joined a
                # fresh one. Span covers the compiled insert program (the
                # paged path launches later: its span is the fold-in's
                # moment).
                wall = time.time()
                req.ctx.span("fold_in" if fold else "admit",
                             wall - insert_s, wall, tid=self.name,
                             slot=slot)
            self._c_admitted.inc()
            admitted += 1
            if fold:
                self._c_fold_ins.inc()
        self._publish_active()

    async def _retire(self, out: dict, seq: int) -> None:
        """Account step ``seq``'s iteration and retire every slot its
        out-block reports finished — a short sequence exits as soon as the
        host learns its own work is done, regardless of what the rest of the
        block still owes. Retiring awaits no device read: the finished slots'
        extracts are DISPATCHED (they queue behind the step ahead, in which
        the finished lane rode frozen) and slot and pages go back at once, so
        the pass's admit can hand them on; the device's own order puts the
        extract's read of the slot's rows before a new occupant's prefill
        writes them. The outputs are read in the next pass's wait and
        answered by ``_finish``."""
        done: list[int] = []
        for slot in self.arena.active_slots():
            info = self.arena.peek(slot)
            if seq >= info.since_step:  # an older step is not this request's
                info.iterations += 1
            if info.future.done():
                if info.stream is not None:
                    self._c_disconnects.inc()
                    self._count_termination("disconnect")
                    info.stream.close()
                self._release_slot(slot)
                continue
            # Prefill chunks ride the same iteration counter, so a paged
            # slot's guard stretches by its chunk count.
            guard = self._max_steps_guard + info.meta.get("prefill_chunks", 0)
            if info.iterations > guard:
                msg = (f"{self.name}: slot {slot} exceeded the "
                       f"{guard}-iteration guard without "
                       "reporting done")
                self._terminate_stream(info.stream, "engine_error", msg)
                info.future.set_exception(RuntimeError(msg))
                self._c_batch_errors.inc()
                self._release_slot(slot)
                continue
            if self._rides(info, seq) and self.model.is_finished(out, slot):
                done.append(slot)
        left = self.arena.n_active
        for i, x in enumerate(await self._dispatch_extracts(done)):
            # An early exit leaves others at work: all but the block's last.
            x.early = left - i > 1 or bool(self._pending)
            self._release_slot(x.slot)
        self._publish_active()
        self._maybe_idle()

    async def _fail_active(self, e: Exception) -> None:
        """A step/insert failure poisons the whole state block: fail every
        mid-flight request with the cause, free all slots, and reinitialize
        the block to zeros. The step loop and queued requests survive —
        failure is contained to the in-flight generation set."""
        log.exception("generation step failed for %s", self.name)
        self._c_batch_errors.inc()
        if self.breaker is not None:
            self.breaker.record_failure()
        wall = time.time()
        for slot in self.arena.active_slots():
            info = self.arena.release(slot)
            self._terminate_stream(info.stream, "engine_error", str(e))
            if not info.future.done():
                info.future.set_exception(e)
            if info.ctx is not None:
                self._steps_span(info, slot)
                info.ctx.span("engine_failure", wall, wall, tid=self.name,
                              iterations=info.iterations,
                              error=type(e).__name__)
        self._prefilling.clear()
        if self.pages is not None:
            self.pages.release_all()
            self._update_kv_gauges()
        self._state = self._zero_state()
        # The step ahead was a step of the failed block: its out-block is
        # dropped, never read beside the new one (nor its ``acc`` into
        # ``observe_step``). An extract dispatched before the failure keeps
        # its place: its outputs are its own buffers, and if the failure
        # reached them its read raises for that request alone. A preview's
        # slot is gone with the rest.
        self._ahead = None
        self._extracts = [x for x in self._extracts if not x.preview]
        self._last_decode_at = None  # no lane is left to feel a gap
        self._publish_active()
        self._maybe_idle()

    # -- staged canary (lifecycle hook; runs in an executor thread) -----------
    def staged_canary_sync(self, staged: list[Any]) -> None:
        """Run a SHORT generation end-to-end against a staged candidate
        tree (params_override) through the real compiled programs, on a
        scratch state block — the live block and the serving loop are
        untouched. Any non-finite output, empty result, or failure to
        finish within the model's step bound rejects the candidate
        (tpuserve.lifecycle wires this in place of the one-shot
        staged-canary path for engine-served models)."""
        model, rt = self.model, self.runtime
        r = self.replica
        item = model.canary_item()
        state = self._zero_state()
        with self._dispatch_guard():
            if self.paging:
                for launch in self._canary_launches(item):
                    state = rt.run_program("prefill", state, launch,
                                           params_override=staged, replica=r)
            else:
                state = rt.run_program("insert", state, np.int32(0), item,
                                       params_override=staged, replica=r)
            for _ in range(self._max_steps_guard):
                state, out = rt.run_program("step", state,
                                            params_override=staged, replica=r)
                with allow_transfers():  # deliberate: canary progress read
                    done = bool(np.asarray(out["done"])[0])
                if done:
                    break
            else:
                raise ValueError(
                    f"staged canary did not finish a generation within "
                    f"{self._max_steps_guard} iterations")
            extracted = host_fetch(
                rt.run_program("extract", state, np.int32(0),
                               params_override=staged, replica=r))
        for path, leaf in jax.tree_util.tree_flatten_with_path(extracted)[0]:
            arr = np.asarray(leaf)
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                raise ValueError(
                    "staged canary produced non-finite outputs in "
                    f"{jax.tree_util.keystr(path)}")
        if model.finalize(extracted, item) is None:
            raise ValueError("staged canary produced no result")

    # -- introspection --------------------------------------------------------
    def _observe_step(self, ms: float) -> None:
        prev = self._ewma_step_ms
        self._ewma_step_ms = ms if prev is None else prev + 0.2 * (ms - prev)

    def _observe_retire(self, iters: int) -> None:
        prev = self._ewma_iters
        self._ewma_iters = (float(iters) if prev is None
                            else prev + 0.2 * (iters - prev))

    @property
    def pending(self) -> int:
        """Requests accepted but not yet admitted into a slot (the fleet
        scheduler's demand signal)."""
        return len(self._pending)

    def predicted_service_s(self, n_items: int = 1) -> float | None:
        """Predicted seconds for one full generation once admitted:
        iterations-per-request EWMA priced at the step EWMA (the engine's
        counterpart of the batcher's per-bucket duration model). None
        before any retirement."""
        if not self._ewma_step_ms or not self._ewma_iters:
            return None
        return max(1, n_items) * self._ewma_iters * self._ewma_step_ms / 1e3

    def kv_clear_s(self) -> float | None:
        """Page-pressure term (paged mode only): estimated seconds until
        enough pages free for a typical admission — the Retry-After hint
        on a kv_pressure shed and a term FleetScheduler.predict_completion_s
        adds so deadline_unmeetable fires before enqueue. None when paging
        is off or the ledger already covers a typical request with nothing
        queued ahead. The soonest page return is the most-advanced active
        request finishing: one request's EWMA span over the active count
        (uniform-progress assumption, same modeling posture as
        estimate_clear_s)."""
        if self.pages is None:
            return None
        need = self._ewma_pages or 1.0
        if self.pages.n_free >= need and not self._pending:
            return None
        if not self._ewma_step_ms or not self._ewma_iters:
            return None
        per_req_s = self._ewma_iters * self._ewma_step_ms / 1e3
        return per_req_s / max(1, self.arena.n_active)

    def estimate_clear_s(self) -> float | None:
        """Queue-clear estimate (raw, unclamped — same split as the
        batcher's: ``clamp_retry_after_s`` owns the 429 Retry-After hint):
        pending requests
        times the observed iterations-per-request, priced at the step EWMA,
        amortized over the slot width, plus the page-pressure term when
        paging is on. None before any retirement."""
        if not self._pending:
            return None
        if not self._ewma_step_ms or not self._ewma_iters:
            return None
        per_req_s = self._ewma_iters * self._ewma_step_ms / 1e3
        base = len(self._pending) * per_req_s / max(1, self.slots)
        return base + (self.kv_clear_s() or 0.0)

    def pipeline_stats(self) -> dict:
        """The /stats "pipeline" block entry for this model (the engine's
        counterpart of the batcher's; mode "genserve" tells them apart)."""
        per_slot = [
            {"slot": s, "iterations": self.arena.peek(s).iterations}
            for s in self.arena.active_slots()]
        stats = {
            "mode": "genserve",
            "slots": self.slots,
            "active": self.arena.n_active,
            "free": self.arena.n_free,
            "peak_active": self.peak_active,
            "pending": len(self._pending),
            "admitted_total": self.arena.acquires_total,
            "iterations_total": self._c_iterations.value,
            "fold_ins_total": self._c_fold_ins.value,
            "early_exits_total": self._c_early_exits.value,
            "evictions_total": self._c_evictions.value,
            "step_ewma_ms": round(self._ewma_step_ms, 3)
            if self._ewma_step_ms else None,
            "iters_per_request_ewma": round(self._ewma_iters, 2)
            if self._ewma_iters else None,
            "per_slot": per_slot,
            "loop": self._loop_stats(),
        }
        if self.pages is not None:
            plan = self.plan
            stats["kv"] = {
                **self.pages.stats(),
                **self._prefill_stats(),
                "queued_pages": self._queued_pages(),
                "kv_bytes": plan.pool_bytes,
                "row_bytes_per_token": plan.row_bytes,
                # Positions a page stands for (its rows, unless a row sums
                # several positions up) and, where the rings lie in the
                # pools, what of ``kv_bytes`` is rings and what pages.
                "page_positions": plan.page_positions,
                **({"ring_bytes": plan.ring_bytes,
                    "page_bytes": plan.pages * plan.page_bytes}
                   if plan.ring_pages else {}),
                # The third kind (ISSUE 32): a fixed block a slot beside the pages,
                # the leaves the family's recurrent mixer states and no other.
                "state_bytes_per_slot": plan.slot_bytes // self.slots,
                "state_bytes": plan.slot_bytes,
            }
        share = self.model.share_stats()
        if share is not None:
            stats["share"] = share
        # Per-replica rows (ISSUE 20): one row for a single engine, one per
        # member for a GenEngineGroup (which overrides the aggregate keys
        # above and composes these) — uniform shape either way.
        stats["per_replica"] = [self.replica_row()]
        return stats

    def _loop_stats(self) -> dict:
        """Where the step loop's time went since start, in milliseconds an
        iteration by phase (``gen_loop_seconds_total`` over
        ``gen_iterations_total``: the model's counters, which a group's
        members share)."""
        iters = self._c_iterations.value

        def per_iteration(counters: dict) -> dict | None:
            return {p: round(c.value * 1e3 / iters, 3)
                    for p, c in counters.items()} if iters else None

        return {"iterations": iters,
                "ms_per_iteration": per_iteration(self._c_loop),
                "cpu_ms_per_iteration": per_iteration(self._c_loop_cpu),
                "account_ms_per_iteration": per_iteration(self._c_account)}

    def _prefill_stats(self) -> dict:
        """Launches of the prefill program and what they carried (the
        counters are the model's: a group's members share them)."""
        launches = self._c_prefill_chunks.value
        return {
            "prefill_chunk": self._prefill_chunk,
            "prefill_pieces": self._prefill_pieces,
            "prefill_hold": PREFILL_HOLD,
            "prefill_chunks_total": launches,
            "prefill_pieces_total": self._c_prefill_pieces.value,
            "prefill_held_total": self._c_prefill_held.value,
            "pieces_per_launch": round(
                self._c_prefill_pieces.value / launches, 3)
            if launches else None,
            "tokens_per_launch": round(
                self._c_prefill_tokens.value / launches, 1)
            if launches else None,
        }

    def replica_row(self) -> dict:
        """One engine's row of the /stats genserve ``per_replica`` block:
        slots in use, steps, units, and page-pool occupancy."""
        row = {
            "replica": self.replica,
            "slots": self.slots,
            "active": self.arena.n_active,
            "free": self.arena.n_free,
            "pending": len(self._pending),
            "steps_total": self._c_replica_steps.value,
            "units_total": self._c_replica_units.value,
        }
        if self.pages is not None:
            row["kv"] = self.pages.snapshot()
        return row

    def kv_cache_bytes(self) -> int:
        """Device bytes of the plan's page pools (paged engines only)."""
        return self.plan.pool_bytes

    def kv_row_bytes(self) -> int:
        """Device bytes ONE position of context takes in them, all layers."""
        return self.plan.row_bytes


class GenEngineGroup:
    """Replica-per-chip generation engines over one replica-mode runtime
    (ISSUE 20; AlpaServe P5's parallelism-as-serving-lever applied to the
    generation pillar).

    One :class:`GenEngine` per replica mesh, each owning its own slot
    arena, page ledger, and device state block on its own chip, all
    sharing the runtime's compiled program registry (register_program
    compiles each program once per replica mesh, so `runtime_compiles_
    total` counts chips x programs at startup and 0 forever after — the
    same zero-recompile obligation, now per chip). The group exposes the
    full engine surface (submit/submit_stream/start/stop/drain/
    revive_group_loops/pipeline_stats/staged_canary_sync/scheduler
    predictors), so every downstream consumer — HTTP layer, watchdog,
    lifecycle, fleet scheduler, /stats — composes unchanged.

    Placement is least-loaded: a request goes to the engine with the
    fewest committed items (active slots + queued), ties rotating, so a
    replica pinned by long generations never starves the others. Model-
    level counters are name-keyed singletons shared by every member;
    per-replica truth lives on the {model=,replica=} rows and the
    ``per_replica`` stats block."""

    def __init__(self, model: GenerativeModel, runtime: Any,
                 metrics: Metrics, gcfg: "GenserveConfig | None" = None,
                 breaker: "Any | None" = None,
                 injector: "Any | None" = None,
                 stages: "StageExecutors | None" = None,
                 pipeline_cfg: "PipelineConfig | None" = None) -> None:
        n = int(getattr(runtime, "n_replicas", 1))
        self.model = model
        self.runtime = runtime
        self.metrics = metrics
        self.cfg = model.cfg
        self.gcfg = gcfg or GenserveConfig()
        self.name = model.cfg.name
        self._own_stages = stages is None
        self.stages = stages if stages is not None \
            else StageExecutors(pipeline_cfg or PipelineConfig(), metrics)
        self.engines = [
            GenEngine(model, runtime, metrics, gcfg=self.gcfg,
                      breaker=breaker, injector=injector, stages=self.stages,
                      pipeline_cfg=pipeline_cfg, replica=i)
            for i in range(n)]
        for e in self.engines:
            e.peers = self.engines
        if n > 1 and jax.default_backend() == "cpu":
            # Shared dispatch lock: see GenEngine.__init__ (ISSUE 11's
            # forced-host-device wedge, the replica-engine form).
            lock = new_lock("genserve.cpu_dispatch")
            for e in self.engines:
                e._dispatch_lock = lock
        self._rr = 0

    # -- pass-through configuration (server wiring sets these post-build) -----
    @property
    def injector(self) -> Any:
        return self.engines[0].injector

    @injector.setter
    def injector(self, inj: Any) -> None:
        for e in self.engines:
            e.injector = inj

    @property
    def breaker(self) -> Any:
        return self.engines[0].breaker

    @breaker.setter
    def breaker(self, br: Any) -> None:
        for e in self.engines:
            e.breaker = br

    @property
    def device_time_cb(self) -> Any:
        return self.engines[0].device_time_cb

    @device_time_cb.setter
    def device_time_cb(self, cb: Any) -> None:
        # Every engine feeds the same fleet ledger: the model's device
        # seconds are the sum of its replicas' step time.
        for e in self.engines:
            e.device_time_cb = cb

    # -- aggregates -----------------------------------------------------------
    @property
    def slots(self) -> int:
        return sum(e.slots for e in self.engines)

    @property
    def peak_active(self) -> int:
        return sum(e.peak_active for e in self.engines)

    @property
    def pending(self) -> int:
        return sum(e.pending for e in self.engines)

    @property
    def paging(self) -> bool:
        return self.engines[0].paging

    # -- lifecycle ------------------------------------------------------------
    def compile(self) -> None:
        """First engine registers the programs (compiled once per replica
        mesh) and prewarms every replica; the rest validate geometry
        against the registry and reuse."""
        for e in self.engines:
            e.compile()

    async def start(self) -> None:
        for e in self.engines:
            await e.start()

    async def stop(self) -> None:
        for e in self.engines:
            await e.stop()
        if self._own_stages:
            self.stages.shutdown()

    async def drain(self, deadline: float) -> bool:
        results = await asyncio.gather(
            *(e.drain(deadline) for e in self.engines))
        return all(results)

    def revive_group_loops(self) -> int:
        return sum(e.revive_group_loops() for e in self.engines)

    # -- submission (event loop) ----------------------------------------------
    def _pick(self) -> GenEngine:
        """Least-loaded engine by committed work (active + queued); ties
        rotate a cursor so idle replicas share cold traffic — the engine
        twin of ModelRuntime.pick_replica."""
        n = len(self.engines)
        best, best_load = self.engines[self._rr % n], None
        for k in range(n):
            e = self.engines[(self._rr + k) % n]
            load = e.arena.n_active + len(e._pending)
            if best_load is None or load < best_load:
                best, best_load = e, load
        self._rr = (self._rr + 1) % n
        return best

    def submit(self, item: Any, group: Any = None,
               deadline_at: float | None = None,
               priority: str | None = None,
               ctx: Any = None) -> asyncio.Future:
        return self._pick().submit(item, group=group, deadline_at=deadline_at,
                                   priority=priority, ctx=ctx)

    def submit_stream(self, item: Any, deadline_at: float | None = None,
                      priority: str | None = None,
                      ctx: Any = None) -> "tuple[asyncio.Future, GenStream]":
        return self._pick().submit_stream(item, deadline_at=deadline_at,
                                          priority=priority, ctx=ctx)

    # -- staged canary (lifecycle hook; executor thread) ----------------------
    def staged_canary_sync(self, staged: list[Any]) -> None:
        """Fan the staged canary to EVERY replica engine — each runs the
        short real generation against ITS mesh's staged tree, so a
        candidate that loads clean on replica 0 but broken on replica 3
        is rejected before publish. Failure names the replica (the
        lifecycle surfaces the message through /admin reload errors)."""
        for i, e in enumerate(self.engines):
            try:
                e.staged_canary_sync(staged)
            except Exception as err:
                raise ValueError(
                    f"staged canary failed on replica {i}: {err}") from err

    # -- scheduler surface ----------------------------------------------------
    def predicted_service_s(self, n_items: int = 1) -> float | None:
        vals = [v for e in self.engines
                if (v := e.predicted_service_s(n_items)) is not None]
        return (sum(vals) / len(vals)) if vals else None

    def kv_clear_s(self) -> float | None:
        vals = [v for e in self.engines
                if (v := e.kv_clear_s()) is not None]
        return max(vals) if vals else None

    def estimate_clear_s(self) -> float | None:
        # Replicas drain in parallel: the group clears when its slowest
        # member does.
        vals = [v for e in self.engines
                if (v := e.estimate_clear_s()) is not None]
        return max(vals) if vals else None

    # -- introspection --------------------------------------------------------
    def pipeline_stats(self) -> dict:
        e0 = self.engines[0]
        # Model-level counters are singletons — e0's handles already carry
        # group totals; only the occupancy fields need summing.
        stats = e0.pipeline_stats()
        stats.update(
            replicas=len(self.engines),
            slots=self.slots,
            active=sum(e.arena.n_active for e in self.engines),
            free=sum(e.arena.n_free for e in self.engines),
            peak_active=self.peak_active,
            pending=self.pending,
            admitted_total=sum(e.arena.acquires_total for e in self.engines),
        )
        ewmas = [e._ewma_step_ms for e in self.engines if e._ewma_step_ms]
        stats["step_ewma_ms"] = (round(sum(ewmas) / len(ewmas), 3)
                                 if ewmas else None)
        iters = [e._ewma_iters for e in self.engines if e._ewma_iters]
        stats["iters_per_request_ewma"] = (round(sum(iters) / len(iters), 2)
                                           if iters else None)
        stats["per_slot"] = [
            {"replica": e.replica, "slot": s,
             "iterations": e.arena.peek(s).iterations}
            for e in self.engines for s in e.arena.active_slots()]
        if e0.pages is not None:
            paged = [e for e in self.engines if e.pages is not None]
            usable = sum(e.pages.usable for e in paged)
            reserved = sum(e.pages.n_reserved for e in paged)
            stats["kv"] = {
                "pages": sum(e.pages.pages for e in paged),
                "usable": usable,
                "free": sum(e.pages.n_free for e in paged),
                "reserved": reserved,
                "page_tokens": e0.pages.page_tokens,
                "utilization": round(reserved / usable, 4) if usable else 0.0,
                "acquires_total": sum(e.pages.acquires_total for e in paged),
                **e0._prefill_stats(),
                "queued_pages": sum(e._queued_pages() for e in paged),
                "kv_bytes": sum(e.plan.pool_bytes for e in paged),
                "row_bytes_per_token": e0.plan.row_bytes,
            }
        stats["per_replica"] = [e.replica_row() for e in self.engines]
        return stats
