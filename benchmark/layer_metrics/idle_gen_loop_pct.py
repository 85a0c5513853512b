"""Idle device time, in percent of the traced window, that lies inside one of
the generation engine's own spans (`tpuserve.gen_admit`, `gen_prefill`,
`gen_step`, `gen_fetch`, `gen_retire`, on the profiler's clock): the device
waits while the engine's loop hands it the next program, fetches a step's
out-block or retires a request. What is left of `device_idle_share` lies
between the spans: the event loop, the stage executors' hand-offs, no request
to run. The two planes of one trace were seen 0 to 3 ms apart (PERF.md, PR
25); no offset is removed here, so read differences under a few percent as
none."""

from benchmark import host_spans
from benchmark import gen_window
from benchmark.trace_reduce import gaps_of


def read(run: dict):
    trace, path = run.get("trace"), run.get("xplane")
    if not trace or not path:
        return None
    from jax.profiler import ProfileData

    data = host_spans.read_profile(ProfileData.from_file(path))
    spans = sorted((s["t0"], s["t1"]) for s in data["spans"] if s["name"] in gen_window.GEN_SPANS)
    ops = data["ops"]
    if not spans or not ops:
        return None
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    window_ns = max(int(trace["window_s"] * 1e9), hi - lo)
    pad = (window_ns - (hi - lo)) // 2
    merged: list[list[int]] = []
    for s, e in spans:  # the union of the spans, so that nested ones count once
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    covered = 0
    for g0, g1 in gaps_of(ops, lo - pad, hi + pad):
        covered += sum(max(0, min(g1, e) - max(g0, s)) for s, e in merged)
    by_name: dict[str, int] = {}
    for s in data["spans"]:
        if s["name"] in gen_window.GEN_SPANS:
            by_name[s["name"]] = by_name.get(s["name"], 0) + 1
    run.setdefault("notes", []).append(f"idle_gen_loop_pct: spans in the trace {by_name}")
    return 100.0 * covered / window_ns
