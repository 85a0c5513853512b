"""On-demand deep profiling (ISSUE 14 tentpole part 5).

``POST /debug/profile?duration_ms=`` arms a ``jax.profiler`` device trace
for the window, then merges whatever the profiler produced (the perfetto
trace JSON when the backend emits one) with the span ring's events from
the same window into ONE Chrome-trace artifact. The workflow this closes:
``/debug/slow`` names a slow request → its span tree says *which phase*
(queue/h2d/compute) — but not which kernel; arming a capture during a
repro answers at device-op granularity, device lanes and serving-path
spans on one timeline.

Degradation contract: profiling is best-effort by construction — a
backend that emits only an xplane (no perfetto JSON), or a profiler that
refuses to start, still yields the span-ring half with
``device_trace: "unavailable"`` in the metadata, and never a 5xx for the
capture having less to say than hoped. One capture at a time (409 while
armed): the profiler is process-global state.

Blocking profiler calls run in an executor; the duration wait is an
``asyncio.sleep`` — nothing here may stall the serving loop.
"""

from __future__ import annotations

import asyncio
import glob
import gzip
import json
import logging
import os
import shutil
import tempfile
import time

from tpuserve.obs import Metrics

log = logging.getLogger("tpuserve.telemetry")


class CaptureBusy(Exception):
    """A capture is already armed (-> 409): jax.profiler is one-at-a-time
    process-global state."""


def _find_device_events(log_dir: str) -> "list | None":
    """Pull Chrome/perfetto trace events out of a finished profiler dir.

    jax writes ``plugins/profile/<run>/*.trace.json.gz`` (and, when asked,
    ``perfetto_trace.json.gz``); both are Chrome-trace JSON. None when the
    backend emitted nothing parseable (xplane-only captures)."""
    patterns = [
        os.path.join(log_dir, "**", "*.trace.json.gz"),
        os.path.join(log_dir, "**", "*trace.json"),
    ]
    for pattern in patterns:
        for path in sorted(glob.glob(pattern, recursive=True)):
            try:
                if path.endswith(".gz"):
                    with gzip.open(path, "rt", encoding="utf-8") as f:
                        data = json.load(f)
                else:
                    with open(path, encoding="utf-8") as f:
                        data = json.load(f)
            except (OSError, ValueError):
                continue
            events = data.get("traceEvents")
            if isinstance(events, list) and events:
                return events
    return None


class ProfileCapture:
    """One process's profiling endpoint state."""

    # Device lanes are re-based onto pids >= this so they never collide
    # with the serving tiers' span lanes (0 router, worker id + 1 workers).
    DEVICE_PID_BASE = 1000

    def __init__(self, metrics: Metrics) -> None:
        self.metrics = metrics
        self._armed = False
        self.captures = metrics.counter("profile_captures_total")
        self.last_capture: dict | None = None

    @property
    def armed(self) -> bool:
        return self._armed

    async def capture(self, duration_ms: float) -> dict:
        """Run one capture; returns the merged Chrome-trace dict. Raises
        CaptureBusy when one is already in flight."""
        if self._armed:
            raise CaptureBusy()
        self._armed = True
        loop = asyncio.get_running_loop()
        tmpdir = tempfile.mkdtemp(prefix="tpuserve_profile_")
        t0_us = time.time() * 1e6
        device_note = "ok"
        device_events: "list | None" = None
        try:
            started = await loop.run_in_executor(
                None, self._start_trace, tmpdir)
            await asyncio.sleep(duration_ms / 1e3)
            if started:
                await loop.run_in_executor(None, self._stop_trace)
                device_events = await loop.run_in_executor(
                    None, _find_device_events, tmpdir)
                if device_events is None:
                    device_note = ("unavailable: profiler emitted no "
                                   "parseable trace JSON (xplane-only "
                                   "backend output)")
            else:
                device_note = "unavailable: jax.profiler failed to start"
        finally:
            self._armed = False
            shutil.rmtree(tmpdir, ignore_errors=True)

        # The span ring's slice of the SAME window: serving-path batch /
        # generation spans beside the device lanes.
        ring = json.loads(self.metrics.tracer.chrome_trace(
            limit=None, since_us=t0_us))["traceEvents"]
        merged = list(ring)
        if device_events:
            for ev in device_events:
                ev = dict(ev)
                if isinstance(ev.get("pid"), int):
                    ev["pid"] = self.DEVICE_PID_BASE + ev["pid"]
                else:
                    ev["pid"] = self.DEVICE_PID_BASE
                merged.append(ev)
        self.captures.inc()
        meta = {
            "duration_ms": duration_ms,
            "device_trace": device_note,
            "ring_events": len(ring),
            "device_events": len(device_events or []),
            "captured_at": round(t0_us / 1e6, 3),
        }
        self.last_capture = meta
        return {"traceEvents": merged, "tpuserve_profile": meta}

    @staticmethod
    def _start_trace(log_dir: str) -> bool:
        try:
            import jax

            jax.profiler.start_trace(log_dir, create_perfetto_trace=True)
            return True
        except Exception:  # noqa: BLE001 — best-effort by contract
            log.exception("jax.profiler.start_trace failed")
            return False

    @staticmethod
    def _stop_trace() -> None:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            log.exception("jax.profiler.stop_trace failed")

    def stats(self) -> dict:
        return {"armed": self._armed,
                "captures_total": int(self.captures.value),
                "last_capture": self.last_capture}
