"""Device time of one launch of the program the device spent most of the
traced window in, from the device trace: the median device time of that
program's launches that lie whole inside the traced window (the trace's edges
cut the first and the last launch on the chip's line short: a median over all
of four launches read 617-786 ms for a program of 847, PR 26). In the
closed-loop cells that is the (256, 512) program;
the run prints which bucket it was (read from the shapes of the program's own
operations in the trace). The program's buckets share one module name and
differ in the fingerprint that follows it."""

from benchmark.trace_reduce import bucket_of


def read(run: dict):
    top = (run.get("trace") or {}).get("top_module")
    if not top:
        return None
    whole = top["whole_launches"]
    run.setdefault("notes", []).append(
        f"exec_ms_per_batch: program {top['name']}, bucket "
        f"{bucket_of(top, run['sizes']['d_model'])}, {top['launches']} launches in the traced "
        f"window, " + (f"{whole} of them whole inside it (the median is over those)" if whole else
                       "NONE known to lie whole inside it: the median is over cut launches"))
    return top["launch_s"] * 1e3
