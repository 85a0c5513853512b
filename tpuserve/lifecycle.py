"""Versioned model lifecycle: staged, reversible weight transitions (ISSUE 2).

PR 1 made the request path survive partial failure; this module is the state
path's counterpart. TF-Serving's servable lifecycle (PAPERS.md P2) treats
version transitions as the central reliability problem — a new version must
prove itself before serving and must never drop accepted traffic — and the
old ``reload_params``-then-canary flow violated both: unvalidated weights
were published first and a failed canary left them serving.

``ModelLifecycle`` turns `POST /admin/models/{name}:reload` into a gated
pipeline, every step of which keeps the old version serving on failure:

1. **stage** — load the candidate OFF the serving path; verify the sidecar
   checksum manifest (``savedmodel.write_manifest``), scan for NaN/Inf, and
   match shapes/dtypes/structure against the compiled executables
   (``ModelRuntime.stage_params``).
2. **staged canary** — run the model's canary item through the real compiled
   executable *against the staged tree* via the ``params_override`` hook in
   ``ModelRuntime.run``. A regressed candidate never serves one request.
3. **publish** — one reference assignment under the runtime's reload lock;
   the tree becomes numbered version N and version N-1 is retained in
   memory as last-known-good.
4. **post-publish canary + soak** — the canary re-runs on the live serving
   path; failure (or the model's CircuitBreaker tripping within
   ``lifecycle.soak_s``) auto-rolls back to the retained tree.

`POST .../{name}:rollback` exposes the same rollback manually and
`GET .../{name}/versions` the transition history. Behind the router split
(tpuserve.workerproc) each worker process owns one of these lifecycles and
the router fans ``:reload`` out to EVERY live worker atomically: any gate
failure rolls the workers that published back, so the fleet never serves
mixed versions, and a success bumps the router's cache generation so
stale cached answers invalidate fleet-wide. Metrics: ``model_version``
gauge, ``reloads_total`` / ``reload_rejected_total{stage=}`` /
``rollbacks_total{reason=}`` counters (tpuserve.obs). Chaos kinds
``reload_corrupt`` / ``reload_nan`` / ``reload_regressed`` fire at gates 1-2
so ``tpuserve chaos --drill reload`` proves availability holds while every
reload is failing (tests/test_lifecycle.py, scripts/reload_drill.sh).
"""

from __future__ import annotations

import asyncio
import logging
import time
from functools import partial
from typing import Any, Awaitable, Callable

import numpy as np

from tpuserve.analysis import witness
from tpuserve.config import LifecycleConfig
from tpuserve.obs import Metrics
from tpuserve.runtime import NaNDetected
from tpuserve.savedmodel import IntegrityError
from tpuserve.telemetry import events as events_mod
from tpuserve.utils.locks import new_async_lock

log = logging.getLogger("tpuserve.lifecycle")


class ReloadRejected(Exception):
    """A reload did not end with the candidate serving.

    ``stage`` names the gate that failed (``integrity``, ``nan_scan``,
    ``structure``, ``load``, ``staged_canary``, ``post_canary``);
    ``rolled_back`` is True when the candidate HAD published and the
    lifecycle reverted it (post-publish canary failure)."""

    def __init__(self, message: str, stage: str,
                 rolled_back: bool = False) -> None:
        super().__init__(message)
        self.stage = stage
        self.rolled_back = rolled_back


class ModelLifecycle:
    """Per-model version lifecycle manager.

    Owns the reload/rollback state machine for one served model. The server
    constructs one per runtime at start() and routes the admin endpoints
    through it."""

    def __init__(self, name: str, runtime: Any, model: Any,
                 cfg: LifecycleConfig, metrics: Metrics,
                 breaker: Any | None = None,
                 canary: Callable[[], Awaitable[bool]] | None = None,
                 canary_status: Callable[[], bool | None] | None = None,
                 injector: Any | None = None,
                 staged_canary_fn: Callable[[list], None] | None = None) -> None:
        self.name = name
        self.runtime = runtime
        self.model = model
        self.cfg = cfg
        self.metrics = metrics
        self.breaker = breaker
        # Coroutine fn re-running the model's live canary (rides the batcher;
        # feeds /healthz and the breaker's half-open path). None in tests
        # that drive the lifecycle without a server.
        self._canary = canary
        # Cheap read of the latest periodic-canary verdict (state.canary_ok);
        # the soak monitor watches it without submitting extra probes.
        self._canary_status = canary_status
        # Replacement staged-canary body (blocking; runs in the executor):
        # engine-served generative models pass GenEngine.staged_canary_sync
        # so the candidate proves itself on a SHORT end-to-end generation
        # through the real compiled insert/step/extract programs, instead
        # of the one-shot forward path they no longer compile.
        self._staged_canary_fn = staged_canary_fn
        self.injector = injector
        self._lock = new_async_lock("lifecycle.ModelLifecycle")
        self._soak_task: asyncio.Task | None = None
        # Version-transition records, newest last: {version, at, status,
        # ...detail}. status: live | superseded | rolled_back | rejected.
        self.history: list[dict] = []
        self._record(version=runtime.version, status="live", source="startup")
        self.metrics.set_model_version(name, runtime.version)

    # -- public API ----------------------------------------------------------

    async def reload(self) -> dict:
        """Staged, reversible reload from cfg.weights. Returns the publish
        info dict on success; raises ReloadRejected with the failing gate
        (and whether a rollback happened) otherwise."""
        async with self._lock:
            self._cancel_soak()
            t0 = time.perf_counter()
            loop = asyncio.get_running_loop()
            # Default executor, NOT the server's decode pool: a slow
            # checkpoint load must not occupy a decode/fetch thread the
            # batcher depends on.
            try:
                staged = await loop.run_in_executor(None, partial(
                    self.runtime.stage_params,
                    verify_integrity=self.cfg.verify_checksum,
                    nan_scan=self.cfg.nan_scan,
                    require_manifest=self.cfg.require_manifest))
            except IntegrityError as e:
                self._reject("integrity", e)
            except NaNDetected as e:
                self._reject("nan_scan", e)
            except ValueError as e:
                self._reject("structure", e)
            except Exception as e:  # noqa: BLE001 — e.g. unreadable ckpt
                self._reject("load", e)

            # Variant completeness gate (ISSUE 6): every configured bucket's
            # specialized executable must be resident BEFORE the staged
            # canary runs, so neither the canary nor the first post-publish
            # request ever pays a first-compile. Steady state (shapes
            # unchanged across versions) this compiles nothing — the
            # runtime_compiles_total delta stays 0 across reload churn.
            if hasattr(self.runtime, "ensure_compiled"):
                try:
                    # The staged tree supplies the param shardings when the
                    # live tree is absent (a cold-booted model's first
                    # warm-up, tpuserve.scheduler); steady state this is
                    # the same no-op it always was.
                    # Sanctioned for the retrace witness: demand-compiling
                    # a cold-booted model's missing variants is the
                    # feature; steady state this window sees 0 compiles.
                    with witness.sanctioned_compiles():
                        n_new = await loop.run_in_executor(
                            None,
                            partial(self.runtime.ensure_compiled, staged))
                    if n_new:
                        log.info("%s: compiled %d missing variant(s) at "
                                 "stage time", self.name, n_new)
                except Exception as e:  # noqa: BLE001 — XLA compile failure
                    self._reject("load", e)

            if self.cfg.staged_canary:
                try:
                    if self.injector is not None:
                        self.injector.check("reload_regressed", self.name)
                    await loop.run_in_executor(
                        None, self._staged_canary_sync, staged)
                except Exception as e:  # noqa: BLE001
                    self._reject("staged_canary", e)

            info = self.runtime.publish(staged)
            self.metrics.counter(
                f"reloads_total{{model={self.name}}}").inc()
            self.metrics.set_model_version(self.name, self.runtime.version)
            if self.history and self.history[-1]["status"] == "live":
                self.history[-1]["status"] = "superseded"
            self._record(version=self.runtime.version, status="live",
                         source=self.model.cfg.weights or "init")
            log.info("%s: published version %d", self.name, self.runtime.version)
            # Structured twin of the log line (ISSUE 15): version fields a
            # postmortem/audit reader can machine-match, where the bridge
            # only carries the rendered message.
            events_mod.emit("info", "lifecycle", "published",
                            model=self.name, version=self.runtime.version)

            canary_ok = True
            if self._canary is not None:
                canary_ok = await self._canary()
            if not canary_ok:
                rb = await self._rollback_locked("post_publish_canary")
                raise ReloadRejected(
                    f"post-publish canary failed for {self.name}; rolled "
                    f"back to version {rb['version']}",
                    stage="post_canary", rolled_back=True)

            if self.cfg.soak_s > 0:
                self._soak_task = asyncio.get_running_loop().create_task(
                    self._soak(self.runtime.version))
            info["reload_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
            info["canary_ok"] = canary_ok
            info["soak_s"] = self.cfg.soak_s
            return info

    async def rollback(self, reason: str = "manual") -> dict:
        """Restore the retained last-known-good version (N-1). Raises
        ValueError when nothing is retained."""
        async with self._lock:
            return await self._rollback_locked(reason)

    def describe(self) -> dict:
        return {
            "model": self.name,
            "live_version": self.runtime.version,
            "previous_version": self.runtime._prev_version,
            "soaking": self._soak_task is not None
                       and not self._soak_task.done(),
            "history": list(self.history),
        }

    def close(self) -> None:
        """Server shutdown: stop the soak monitor."""
        self._cancel_soak()

    # -- internals -----------------------------------------------------------

    def _record(self, **fields) -> None:
        fields.setdefault("at", round(time.time(), 3))
        self.history.append(fields)
        del self.history[: -self.cfg.history_limit]

    def _reject(self, stage: str, err: Exception) -> None:
        self.metrics.counter(
            f"reload_rejected_total{{model={self.name},stage={stage}}}").inc()
        self._record(version=self.runtime.version, status="rejected",
                     stage=stage, error=str(err))
        log.warning("%s: reload rejected at %s gate: %s; version %d keeps "
                    "serving", self.name, stage, err, self.runtime.version)
        events_mod.emit("warning", "lifecycle", "reload_rejected",
                        model=self.name, stage=stage, error=str(err),
                        version=self.runtime.version)
        raise ReloadRejected(
            f"reload rejected at {stage} gate: {err}", stage=stage) from err

    def _staged_canary_sync(self, staged: list[Any]) -> None:
        """Run the model's canary item through the real compiled executables
        against the STAGED tree (params_override): the candidate proves
        itself on device before one request can reach it. Blocking D2H —
        runs in the default executor.

        Multi-chip (ISSUE 7): the canary runs on EVERY replica — staging
        device_puts one candidate copy per mesh, and a copy corrupted on
        replica 5 alone must fail the gate, not serve an eighth of the
        traffic. Dispatches go out async first so the replica loads
        overlap; one fetch per replica then proves each. Sharded mode has
        one mesh, so this degenerates to the single canary it always was."""
        if self._staged_canary_fn is not None:
            self._staged_canary_fn(staged)
            return
        item = self.model.canary_item()
        bucket = self.model.bucket_for(1, group=self.model.group_key(item))
        host_batch = self.model.assemble([item], bucket)
        n = max(1, int(getattr(self.runtime, "n_replicas", 1)))
        pending = [self.runtime.run(bucket, host_batch, replica=i,
                                    params_override=staged)
                   for i in range(n)]
        for i, dev_out in enumerate(pending):
            out = self.runtime.fetch(dev_out)
            bad = [k for k, a in _np_leaves(out)
                   if a.dtype.kind == "f" and not np.isfinite(a).all()]
            if bad:
                raise ValueError("staged canary produced non-finite outputs "
                                 f"in {bad} on replica {i}")
            results = self.model.host_postprocess(out, 1)
            if not results:
                raise ValueError(
                    f"staged canary produced no result on replica {i}")

    async def _rollback_locked(self, reason: str) -> dict:
        self._cancel_soak()
        info = self.runtime.rollback()  # ValueError if nothing retained
        self.metrics.counter(
            f"rollbacks_total{{model={self.name},reason={reason}}}").inc()
        self.metrics.set_model_version(self.name, self.runtime.version)
        for rec in reversed(self.history):
            if rec["version"] == info["rolled_back_from"]\
                    and rec["status"] in ("live", "superseded"):
                rec["status"] = "rolled_back"
                rec["reason"] = reason
                break
        self._record(version=info["version"], status="live",
                     source=f"rollback({reason})")
        log.warning("%s: rolled back version %d -> %d (%s)", self.name,
                    info["rolled_back_from"], info["version"], reason)
        events_mod.emit("warning", "lifecycle", "rolled_back",
                        model=self.name, reason=reason,
                        version=info["version"],
                        rolled_back_from=info["rolled_back_from"])
        # Re-canary so /healthz reflects the restored weights and the
        # breaker's recovery path sees a live probe.
        if self._canary is not None:
            await self._canary()
        return info

    async def _soak(self, version: int) -> None:
        """Post-publish soak monitor: a breaker trip or canary failure
        within the window rolls the just-published version back."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.cfg.soak_s
        try:
            while loop.time() < deadline:
                await asyncio.sleep(self.cfg.soak_poll_s)
                if self.runtime.version != version:
                    return  # superseded or manually rolled back
                reason = None
                if self.breaker is not None and self.breaker.state != "closed":
                    reason = "soak_breaker"
                elif (self._canary_status is not None
                      and self._canary_status() is False):
                    reason = "soak_canary"
                if reason is not None:
                    # Clear our own handle first: _rollback_locked cancels
                    # the registered soak task, which would be this one.
                    self._soak_task = None
                    try:
                        await self.rollback(reason=reason)
                    except ValueError:
                        log.warning("%s: soak wanted rollback but no "
                                    "previous version retained", self.name)
                    return
            log.info("%s: version %d passed its %.1fs soak window",
                     self.name, version, self.cfg.soak_s)
        except asyncio.CancelledError:
            raise

    def _cancel_soak(self) -> None:
        try:
            current = asyncio.current_task()
        except RuntimeError:  # close() outside a running loop
            current = None
        t = self._soak_task
        if t is None or t is current:
            return  # the soak task rolling back clears its own handle
        if not t.done():
            t.cancel()
        self._soak_task = None


def _np_leaves(tree: Any) -> list[tuple[str, np.ndarray]]:
    import jax

    from tpuserve.utils.retrace import allow_transfers

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    with allow_transfers():  # deliberate: canary/guard comparison readback
        return [(jax.tree_util.keystr(p), np.asarray(x)) for p, x in flat]
