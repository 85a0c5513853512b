"""On-demand deep profiling (ISSUE 14 tentpole part 5; repaired for what
jax 0.9 writes by ISSUE 25).

``POST /debug/profile?duration_ms=`` arms a ``jax.profiler`` trace for the
window, reads the capture's ``*.xplane.pb`` with ``jax.profiler.ProfileData``
and answers ONE Chrome trace: every chip's ``XLA Modules`` and ``XLA Ops``
lines beside the program's own ``tpuserve.*`` spans (obs.trace_span /
obs.trace_mark, written into the same trace from the threads that do the
work), all on the profiler's clock. The workflow this closes:
``/debug/slow`` names a slow request -> its span tree says *which phase*
(queue/h2d/compute) — but not which kernel, nor what the host was doing
while the device sat idle; a capture during a repro answers both on one
timeline. The span ring (``/debug/trace``) is on ``time.time()`` and is NOT
merged in: two clocks on one timeline is how gaps get misattributed.

Degradation contract: profiling is best-effort by construction — a
profiler that refuses to start, or a capture with no device plane (the CPU
backend), still answers 200 with what it has and
``device_trace: "unavailable: ..."`` in the metadata, never a 5xx for the
capture having less to say than hoped. One capture at a time (409 while
armed): the profiler is process-global state.

Blocking profiler calls run in an executor; the duration wait is an
``asyncio.sleep`` — nothing here may stall the serving loop.
"""

from __future__ import annotations

import asyncio
import glob
import logging
import os
import re
import shutil
import tempfile
import time

from tpuserve.obs import Metrics

log = logging.getLogger("tpuserve.telemetry")

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_DEVICE_LINES = ("XLA Modules", "XLA Ops")
SPAN_PREFIX = "tpuserve."
# Device lanes get pids from here up, apart from the host spans' pid 0.
DEVICE_PID_BASE = 1000


class CaptureBusy(Exception):
    """A capture is already armed (-> 409): jax.profiler is one-at-a-time
    process-global state."""


def read_capture(log_dir: str) -> "tuple[list, list] | None":
    """(device events, host spans) of a finished profiler directory as
    Chrome ``ph: "X"`` events, microseconds on the profiler's clock. Device
    events: one lane per (chip, line) for ``XLA Modules`` and ``XLA Ops``.
    Host spans: the ``tpuserve.*`` annotations of every host plane, one
    lane per thread (lines are keyed by id: threads share names), their
    keyword arguments as ``args``. A zero-length span that carries
    ``dur_us``/``ago_us`` (obs.trace_mark) is drawn where it was measured.
    None when the profiler wrote no xplane."""
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        return None
    from jax.profiler import ProfileData

    device: list = []
    host: list = []
    for plane in ProfileData.from_file(hits[-1]).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            pid = DEVICE_PID_BASE + int(m.group(2))
            for line in plane.lines:
                if line.name in _DEVICE_LINES:
                    device.extend(
                        {"name": ev.name[:160], "ph": "X",
                         "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                         "pid": pid, "tid": line.name}
                        for ev in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                args = dict(ev.stats)
                ts, dur = ev.start_ns / 1e3, ev.duration_ns / 1e3
                if "dur_us" in args:  # measured after the fact
                    dur = float(args["dur_us"])
                    ts -= float(args.get("ago_us", 0)) + dur
                host.append({"name": ev.name, "ph": "X", "ts": ts, "dur": dur,
                             "pid": 0, "tid": f"{line.name}#{n}", "args": args})
    return device, host


class ProfileCapture:
    """One process's profiling endpoint state."""

    def __init__(self, metrics: Metrics) -> None:
        self.metrics = metrics
        self._armed = False
        self.captures = metrics.counter("profile_captures_total")
        self.last_capture: dict | None = None

    @property
    def armed(self) -> bool:
        return self._armed

    async def capture(self, duration_ms: float) -> dict:
        """Run one capture; returns the Chrome-trace dict. Raises
        CaptureBusy when one is already in flight."""
        if self._armed:
            raise CaptureBusy()
        self._armed = True
        loop = asyncio.get_running_loop()
        tmpdir = tempfile.mkdtemp(prefix="tpuserve_profile_")
        t0 = time.time()
        device_note = "ok"
        device: list = []
        host: list = []
        try:
            started = await loop.run_in_executor(
                None, self._start_trace, tmpdir)
            await asyncio.sleep(duration_ms / 1e3)
            if started:
                await loop.run_in_executor(None, self._stop_trace)
                read = await loop.run_in_executor(None, self._read, tmpdir)
                if read is None:
                    device_note = ("unavailable: the profiler wrote no "
                                   "readable xplane")
                else:
                    device, host = read
                    if not device:
                        device_note = ("unavailable: the capture holds no "
                                       "device plane (CPU backend, or "
                                       "nothing ran on a chip)")
            else:
                device_note = "unavailable: jax.profiler failed to start"
        finally:
            self._armed = False
            shutil.rmtree(tmpdir, ignore_errors=True)

        self.captures.inc()
        spans: dict[str, int] = {}
        for ev in host:
            spans[ev["name"]] = spans.get(ev["name"], 0) + 1
        meta = {
            "duration_ms": duration_ms,
            "device_trace": device_note,
            "clock": "profiler",
            "device_events": len(device),
            "host_spans": len(host),
            "span_names": spans,
            "captured_at": round(t0, 3),
        }
        self.last_capture = meta
        merged = sorted(device + host, key=lambda e: e["ts"])
        return {"traceEvents": merged, "tpuserve_profile": meta}

    @staticmethod
    def _read(log_dir: str) -> "tuple[list, list] | None":
        try:
            return read_capture(log_dir)
        except Exception:  # noqa: BLE001 — best-effort by contract
            log.exception("reading the profiler's xplane failed")
            return None

    @staticmethod
    def _start_trace(log_dir: str) -> bool:
        try:
            import jax

            # Host spans at the level TraceAnnotation writes at; no Python
            # call tracer (it multiplies the capture's size and cost).
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
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
