"""Fault injection + recovery machinery (ISSUE 1; docs/ROBUSTNESS.md).

At serving scale the common case is partial failure — a poisoned batch, a
wedged worker process, a dead group loop — not a clean crash. This module
holds both sides of that story:

- **FaultInjector**: a deterministic, config-driven chaos layer (replacing
  the ad-hoc ``fault_hook`` the batcher used to carry). Rules
  (``[[faults.rule]]`` in TOML, ``FaultRuleConfig``) name a *kind* — a call
  site on the serving path — plus model / probability / count, and draw from
  rule-local seeded RNGs so a chaos run replays exactly. Call sites live in
  the batcher (batch_error, slow_dispatch, kill_group_loop), the runtime
  (device_error, slow_compute), the server (decode_corrupt, canary_fail,
  plus the process-boundary kinds worker_slow / worker_hang / worker_crash
  that degrade, wedge, or os._exit the serving process — behind the router split
  (tpuserve.workerproc) they prove hedging/retry/supervision, drilled by
  ``tpuserve chaos --drill worker_kill``), and the reload lifecycle
  (reload_corrupt / reload_nan at the staging gates in
  ModelRuntime.stage_params, reload_regressed at the staged canary in
  tpuserve.lifecycle — drill them with ``tpuserve chaos --drill reload``).

- **CircuitBreaker**: per-model, trips to fast 503 + ``Retry-After`` after N
  consecutive failed dispatches; half-opens via the existing canary path
  (canaries keep riding the batcher while open; the first success closes).
  The fleet isolation drill (``tpuserve chaos --drill fleet``,
  tpuserve.scheduler.drill) poisons one model's dispatches with
  ``device_error`` at 100% under multi-model load and asserts this breaker
  contains the blast radius: the victim trips while every other model
  holds its SLO.

- **Watchdog**: periodic sweep that restarts dead group-accumulation tasks
  and engine loops (and, behind the router, reaps dead worker processes and
  schedules their respawn), with restart counters in
  ``/metrics`` (``watchdog_restarts_total{model=...,component=...}``).

- **run_chaos**: the ``python -m tpuserve chaos`` backend — serve a
  fault-injected config on an ephemeral port, drive the load generator at
  it, and report availability + injection counts.

The batch-retry policy itself lives in ``tpuserve.batcher`` (it owns the
dispatch path); graceful drain lives in ``tpuserve.server`` (it owns the
accept path). Both are exercised by tests/test_faults.py.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import Callable

from tpuserve.config import FaultRuleConfig, FaultsConfig
from tpuserve.obs import BREAKER_STATES, Metrics
from tpuserve.utils.locks import new_lock

log = logging.getLogger("tpuserve.faults")


class FaultInjected(RuntimeError):
    """An injected chaos fault, not a real serving failure."""


class _ArmedRule:
    """One rule plus its mutable firing state (RNG, remaining budget)."""

    def __init__(self, cfg: FaultRuleConfig, derived_seed: int) -> None:
        self.cfg = cfg
        self.rng = random.Random(cfg.seed if cfg.seed else derived_seed)
        self.remaining = cfg.count  # -1 = unlimited
        self.fired = 0

    def matches(self, kind: str, model: str) -> bool:
        return self.cfg.kind == kind and self.cfg.model in ("*", model)

    def draw(self) -> bool:
        if self.remaining == 0:
            return False
        if self.cfg.probability < 1.0 and self.rng.random() >= self.cfg.probability:
            return False
        if self.remaining > 0:
            self.remaining -= 1
        self.fired += 1
        return True


class FaultInjector:
    """Deterministic config-driven fault injection for the serving path.

    Thread-safe: call sites run on the event loop, in the decode/fetch
    threadpool (runtime.run)."""

    def __init__(self, cfg: FaultsConfig, metrics: Metrics | None = None) -> None:
        self.cfg = cfg
        self.metrics = metrics
        self._lock = new_lock("faults.FaultInjector")
        # Epoch for rule.after_s gating: rules with after_s > 0 stay cold
        # until the injector has been alive that long, so a drill can arm a
        # fault that reproducibly fires MID-load rather than from boot.
        self._born = time.monotonic()
        # Worker-process id for rule.worker pinning (set by the serving
        # process under the router split); None/-1 rules match any process.
        self.worker_id: int | None = None
        # Derived seeds keep distinct rules decorrelated even when the
        # operator leaves every rule.seed at 0.
        self._rules = [_ArmedRule(r, cfg.seed * 1000003 + i + 1)
                       for i, r in enumerate(cfg.rules)]

    @classmethod
    def single(cls, kind: str, model: str = "*", probability: float = 1.0,
               count: int = -1, delay_ms: float = 0.0, seed: int = 0,
               metrics: Metrics | None = None) -> "FaultInjector":
        """One-rule injector (test/REPL convenience)."""
        rule = FaultRuleConfig(kind=kind, model=model, probability=probability,
                               count=count, delay_ms=delay_ms, seed=seed)
        return cls(FaultsConfig(enabled=True, seed=seed, rules=[rule]), metrics)

    def set_enabled(self, enabled: bool) -> None:
        """Flip injection live (chaos tests stop injecting mid-run)."""
        self.cfg.enabled = enabled

    def fire(self, kind: str, model: str) -> FaultRuleConfig | None:
        """First matching armed rule that draws true, or None."""
        if not self.cfg.enabled:
            return None
        with self._lock:
            alive_s = time.monotonic() - self._born
            for rule in self._rules:
                if rule.cfg.after_s > 0 and alive_s < rule.cfg.after_s:
                    continue
                if rule.cfg.worker >= 0 and rule.cfg.worker != self.worker_id:
                    continue
                if rule.matches(kind, model) and rule.draw():
                    if self.metrics is not None:
                        self.metrics.counter(
                            f"faults_injected_total{{model={model},kind={kind}}}").inc()
                    return rule.cfg
        return None

    def check(self, kind: str, model: str) -> None:
        """Raise FaultInjected when an armed rule fires at this call site."""
        if self.fire(kind, model) is not None:
            raise FaultInjected(f"injected fault: {kind} ({model})")

    def delay_s(self, kind: str, model: str) -> float:
        """Injected sleep for the slow_* kinds; 0.0 when nothing fires."""
        rule = self.fire(kind, model)
        return rule.delay_ms / 1e3 if rule is not None else 0.0

    def snapshot(self) -> list[dict]:
        """Per-rule firing state for /stats and chaos-run reports."""
        with self._lock:
            return [{
                "kind": r.cfg.kind,
                "model": r.cfg.model,
                "probability": r.cfg.probability,
                "fired": r.fired,
                "remaining": r.remaining,
            } for r in self._rules]


class CircuitBreaker:
    """Per-model breaker over consecutive failed dispatches.

    closed --(threshold consecutive failures)--> open
    open   --(canary probe admitted)-----------> half_open
    open/half_open --(any recorded success)----> closed

    While open/half-open the server sheds that model's traffic with a fast
    503 + ``Retry-After`` *before* reading the request body, so a tripped
    model costs microseconds, not a doomed dispatch. Recovery is driven by
    the canary path: ``run_canary`` keeps submitting through the batcher
    regardless of breaker state, and the first successful dispatch closes
    the breaker (within 2 canary intervals of the fault clearing)."""

    def __init__(self, model: str, threshold: int,
                 metrics: Metrics | None = None,
                 retry_after_s: float = 5.0) -> None:
        self.model = model
        self.threshold = threshold
        self.metrics = metrics
        self.retry_after_s = retry_after_s
        self._lock = new_lock("faults.CircuitBreaker")
        self.state = "closed"
        self.consecutive_errors = 0
        self.opened_total = 0
        self.shed_total = 0
        self._set_gauge()

    def allow(self) -> bool:
        """May normal (non-canary) traffic reach this model's batcher?"""
        if self.threshold <= 0:
            return True
        return self.state == "closed"

    def record_success(self) -> None:
        with self._lock:
            self.consecutive_errors = 0
            changed = self.state != "closed"
            self.state = "closed"
        if changed:
            log.info("breaker for %s closed (recovered)", self.model)
            self._set_gauge()

    def record_failure(self) -> None:
        if self.threshold <= 0:
            return
        with self._lock:
            self.consecutive_errors += 1
            was = self.state
            if was == "half_open":
                self.state = "open"  # failed probe: back to shedding
            elif was == "closed" and self.consecutive_errors >= self.threshold:
                self.state = "open"
                self.opened_total += 1
        if was != self.state:
            log.warning("breaker for %s opened after %d consecutive failures",
                        self.model, self.consecutive_errors)
            self._set_gauge()
        elif was == "half_open":
            self._set_gauge()

    def probe(self) -> None:
        """A canary was admitted while tripped: open -> half_open."""
        with self._lock:
            changed = self.state == "open"
            if changed:
                self.state = "half_open"
        if changed:
            self._set_gauge()

    def on_shed(self) -> None:
        """One request answered 503 because the breaker is not closed."""
        with self._lock:
            self.shed_total += 1
        if self.metrics is not None:
            self.metrics.counter(
                f"breaker_shed_total{{model={self.model}}}").inc()

    def _set_gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.gauge(
                f"breaker_state{{model={self.model}}}").set(BREAKER_STATES[self.state])

    def describe(self) -> dict:
        with self._lock:
            return {
                "state": self.state,
                "threshold": self.threshold,
                "consecutive_errors": self.consecutive_errors,
                "opened_total": self.opened_total,
                "shed_total": self.shed_total,
            }


class Watchdog:
    """Periodic sweep restarting dead serving machinery.

    Components register a sweep callable returning how many restarts it
    performed; non-zero sweeps land in
    ``watchdog_restarts_total{model=...,component=...}``. Registered
    sweeps run on the event loop and must be non-blocking."""

    def __init__(self, interval_s: float, metrics: Metrics) -> None:
        self.interval_s = interval_s
        self.metrics = metrics
        self._targets: list[tuple[str, str, Callable[[], int]]] = []
        self._task: asyncio.Task | None = None

    def register(self, model: str, component: str, sweep: Callable[[], int]) -> None:
        self._targets.append((model, component, sweep))

    def start(self) -> None:
        if self.interval_s > 0 and self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _loop(self) -> None:
        while True:
            await asyncio.sleep(self.interval_s)
            try:
                self.sweep()
            except asyncio.CancelledError:
                raise
            except Exception:  # one bad sweep must not end the watchdog
                log.exception("watchdog sweep failed")

    def sweep(self) -> int:
        """Run every registered sweep once; returns total restarts."""
        total = 0
        for model, component, fn in self._targets:
            try:
                n = fn()
            except Exception:
                log.exception("watchdog sweep for %s/%s failed", model, component)
                continue
            if n:
                log.warning("watchdog restarted %d %s for %s", n, component, model)
                self.metrics.counter(
                    f"watchdog_restarts_total{{model={model},component={component}}}").inc(n)
                total += n
        return total


# ---------------------------------------------------------------------------
# Chaos-run harness (python -m tpuserve chaos)
# ---------------------------------------------------------------------------

async def run_chaos(state, model_name: str, duration_s: float = 10.0,
                    warmup_s: float = 1.0, concurrency: int = 16,
                    rate_per_s: float | None = None, verb: str = "predict",
                    edge: int = 256, drill: str | None = None,
                    drill_interval_s: float = 0.5) -> dict:
    """Serve ``state`` on an ephemeral local port, drive the load generator
    at one model, and report availability + per-rule injection counts.

    The server must be built (``state.build()``) but not started; this owns
    its lifecycle. Intended for staging chaos drills: arm ``[faults]`` rules
    in the config and assert the availability number here, not in prod.

    ``drill="reload"`` additionally hammers ``:reload`` every
    ``drill_interval_s`` throughout the run — with ``reload_corrupt`` /
    ``reload_nan`` / ``reload_regressed`` rules armed this proves the
    lifecycle gates hold availability while every reload is failing; the
    summary carries the reload outcomes and final lifecycle state."""
    import aiohttp
    from aiohttp import web

    from tpuserve.bench.loadgen import run_load, run_load_open, synthetic_image_npy
    from tpuserve.server import make_app

    app = make_app(state)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    drill_task = None
    reload_stats = {"attempts": 0, "ok": 0, "rejected": 0, "rolled_back": 0,
                    "errors": 0}

    async def reload_driller(base: str) -> None:
        async with aiohttp.ClientSession() as session:
            while True:
                await asyncio.sleep(drill_interval_s)
                reload_stats["attempts"] += 1
                try:
                    async with session.post(
                            f"{base}/admin/models/{model_name}:reload") as r:
                        body = await r.json()
                        if r.status == 200:
                            reload_stats["ok"] += 1
                        elif body.get("rolled_back"):
                            reload_stats["rolled_back"] += 1
                        else:
                            reload_stats["rejected"] += 1
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — drill races teardown
                    reload_stats["errors"] += 1

    try:
        port = runner.addresses[0][1]
        base = f"http://127.0.0.1:{port}"
        url = f"{base}/v1/models/{model_name}:{verb}"
        payload = synthetic_image_npy(edge=edge)
        if drill == "reload":
            drill_task = asyncio.get_running_loop().create_task(
                reload_driller(base))
        if rate_per_s:
            result = await run_load_open(url, payload, "application/x-npy",
                                         rate_per_s, duration_s, warmup_s)
        else:
            result = await run_load(url, payload, "application/x-npy",
                                    duration_s, concurrency, warmup_s)
    finally:
        if drill_task is not None:
            drill_task.cancel()
            try:
                await drill_task
            except asyncio.CancelledError:
                pass
        # Snapshot lifecycle state BEFORE cleanup tears the server down.
        lifecycle_out = {n: lc.describe()
                         for n, lc in state.lifecycles.items()}
        await runner.cleanup()
    out = result.summary()
    total = result.n_ok + result.n_err
    out["availability"] = round(result.n_ok / total, 5) if total else 0.0
    if state.injector is not None:
        out["faults"] = state.injector.snapshot()
    out["breakers"] = {n: br.describe() for n, br in state.breakers.items()}
    if lifecycle_out:
        out["lifecycle"] = lifecycle_out
    if drill is not None:
        out["reload_drill"] = reload_stats
    return out
