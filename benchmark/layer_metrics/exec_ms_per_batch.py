"""Device time of one launch of the program the device spent most of the
traced window in, from the device trace: the median device time of that
program's launches (the trace's edges cut a launch short, so not the mean). In the closed-loop cells that is the (256, 512) program;
the run prints which bucket it was (read from the shapes of the program's own
operations in the trace). The program's buckets share one module name and
differ in the fingerprint that follows it."""

from benchmark.trace_reduce import bucket_of


def read(run: dict):
    top = (run["trace"] or {}).get("top_module")
    if not top:
        return None
    run["notes"].append(
        f"exec_ms_per_batch: program {top['name']}, bucket "
        f"{bucket_of(top, run['sizes']['d_model'])}, {top['launches']} launches in the traced window")
    return top["launch_s"] * 1e3
