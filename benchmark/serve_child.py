"""The server child: the program's own entry, `tpuserve.cli.main(["serve",
...])` (what `python -m tpuserve serve` runs), plus the two things only the
process that holds the chip can give:

- on SIGUSR1, a profiler trace of `--trace-ms` of whatever is running, written
  to `--out`/trace, with the traced window's length by the host clock in
  `--out`/trace_done.json;
- after the server has drained and returned, `--out`/device.json: platform,
  kind, device count and the peak bytes in use on the fullest device.

It changes nothing of the program and passes it no option it does not have.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def _write(path: str, obj: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _trace(out: str, trace_ms: float) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # device planes are what the reduction reads
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(os.path.join(out, "trace"), profiler_options=opts)
        t0 = time.perf_counter()
        time.sleep(trace_ms / 1e3)
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        _write(os.path.join(out, "trace_done.json"),
               {"ok": True, "window_s": t1 - t0,
                "stop_s": time.perf_counter() - t1})
    except Exception as e:  # the parent reports it; the server keeps serving
        _write(os.path.join(out, "trace_done.json"),
               {"ok": False, "error": f"{type(e).__name__}: {e}"})


def _committed(stats: dict) -> int:
    """Peak bytes a chip had committed: its live buffers at their peak plus
    what the runtime reserved for the loaded programs' scratch. On the TPU
    `peak_bytes_in_use` counts buffers alone (parameters, inputs, outputs); a
    program's temporaries are `peak_bytes_reserved` (seen on the chip, PR 24:
    a program whose compiler analysis says 3,221,257,728 bytes of temp moved
    `peak_bytes_reserved` by 3,221,241,856 and `peak_bytes_in_use` by 2 MB)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace-ms", type=float, default=0.0)
    args = ap.parse_args()

    if args.trace_ms > 0:
        signal.signal(signal.SIGUSR1, lambda *_: threading.Thread(
            target=_trace, args=(args.out, args.trace_ms), daemon=True).start())

    from tpuserve.cli import main as tpuserve_main

    rc = tpuserve_main(["serve", "--config", args.config])

    import jax

    devs = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devs]
    fullest = max(stats, key=_committed)
    _write(os.path.join(args.out, "device.json"),
           {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": _committed(fullest),
            "memory_stats": fullest})
    return int(rc or 0)


if __name__ == "__main__":
    sys.exit(main())
