"""The server child: the program's own entry, `tpuserve.cli.main(["serve",
...])` (what `python -m tpuserve serve` runs), plus the one thing only the
process that holds the chip can give: on SIGUSR1, a profiler trace of
`--trace-ms` of whatever is running, written to `--out`/trace, with the traced
window's length by the host clock in `--out`/trace_done.json.

It changes nothing of the program and passes it no option it does not have.
The device's kind, count and peak memory are read from the program's own
`/stats` (run.py `read_device`, `read_memory_peak`): the exit-time
`device.json` this file wrote until PR 27 held the same bytes (chip, PR 27).
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

    return int(tpuserve_main(["serve", "--config", args.config]) or 0)


if __name__ == "__main__":
    sys.exit(main())
