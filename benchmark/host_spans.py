"""Name the device's idle gaps: the program's own `tpuserve.*` spans, which it
writes into the profiler's trace on the profiler's clock
(`jax.profiler.TraceAnnotation`, recorded at `host_tracer_level = 1`, the level
`serve_child.py` records at), read beside the chip's operation intervals.

This file depends on span NAMES and ARGUMENTS only, never on `tpuserve/` code:

    tpuserve.tokenize                      a decode thread turning texts into ids
    tpuserve.accumulate  batch n reason    first item's arrival -> the flush decision
    tpuserve.slot_wait   batch             flush decision -> admitted into the pipeline
    tpuserve.assemble    batch bucket n    the assemble stage's thread
    tpuserve.staging_wait batch replica    assembled -> a device-section slot
    tpuserve.h2d         batch bucket n    device_put and launch, one stage thread
    tpuserve.launch      bucket replica    the compiled call, nested in tpuserve.h2d
    tpuserve.fetch       batch bucket n    blocks until the outputs are on the host

The three `*_wait`/`accumulate` intervals are measured after the fact on the
event loop and written as ZERO-LENGTH annotations that carry `dur_us` (the
interval's length) and `ago_us` (how long before the write it ended): such an
event at t stands for [t - ago - dur, t - ago). A program without these spans
(the parent of the PR that added them) gives every reader here None.

Seen on the chip (PR 25, `fixtures/recorded_v5e_spans.md`): the annotations
are on the plane `/host:CPU`, one line per thread (threads share names, so a
line is keyed by its position), and that plane shares the time base of
`/device:TPU:0`.

THE ATTRIBUTION RULE. Gaps are those of the chip's `XLA Ops` union inside the
traced window, as `trace_reduce` computes them (same window, same padding).
Gaps under 1 ms are summed as `unknown`. Each longer gap is charged to the
batch whose launch ends it: the first `XLA Modules` event that starts in the
gap, matched to its `tpuserve.launch` in order of time; the batch is that of
the `tpuserve.h2d` span the launch is nested in. The gap's interval is then
split over where THAT batch was: `h2d`, `assemble`, `staging_wait`,
`slot_wait`, `accumulate` (its own spans, earlier names win an overlap); before
its `accumulate` began its items had not reached the batcher: `tokenize` while
any `tpuserve.tokenize` is running, else `no_request` (the server holds nothing
it could run: callers are waiting for answers or have not sent). What no span
covers is `unknown`. A gap that no launch ends (the window's tail) is
`tokenize` or `no_request` by the same step; a gap whose launch has no span in
the trace (launched before the tracer started) is `unknown`.

THE CLOCK CHECK. Launches and module events are both in order of time, so the
i-th launch belongs to the (i + shift)-th module for one small shift (launches
before the tracer started, modules after it stopped). Physics bounds the
clocks' offset c (host + c = device) for the right shift: every launch BEGINS
before its module starts, and every batch's fetch ends after its module ends,
so max(module.end - fetch.end) <= c <= min(module.start - launch.start). (A
launch need not END before its module starts: when the device is idle it
begins the program while the call is still returning, a millisecond or, when
the thread then waits for the GIL, tens of milliseconds later. The run prints
that lag too.) The reader takes the shift whose bounds hold for the most
launches (then the offset nearest 0; an offset over 10 ms is a wrong pairing,
not a clock), uses 0 when 0 is inside the bounds (the clocks agree), else the
bound nearest 0 (a constant disagreement, removed and printed). No shift with
bounds that hold: the clocks disagree without pattern and NOTHING is
attributed (all idle time is `unknown`, and the note says so).
"""

from __future__ import annotations

import bisect
import statistics

from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, gaps_of

PREFIX = "tpuserve."
LONG_GAP_NS = 1_000_000  # shorter gaps are not named
BATCH_STATES = ("h2d", "assemble", "staging_wait", "slot_wait", "accumulate")
STATES = BATCH_STATES + ("tokenize", "no_request", "unknown")
MAX_SHIFT = 4            # launches or modules cut off by the tracer's edges
MATCH_SLACK_NS = 50_000  # rounding of the two planes' timestamps
MAX_OFFSET_NS = 10_000_000  # the planes of one session were seen 0.8 to 2.9 ms apart


# -- reading -------------------------------------------------------------------

def read_profile(profile) -> dict:
    """The chip's intervals and the program's spans from a ProfileData.
    Times in nanoseconds as ProfileData gives them."""
    ops: list[tuple[int, int]] = []
    modules: list[tuple[int, int, str]] = []
    spans: list[dict] = []
    planes: dict[str, int] = {}
    seen_device = False
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            if seen_device:  # the gaps are those of the first chip, as in trace_reduce
                continue
            here_ops, here_mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    here_ops = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    here_mods = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
                                 for e in line.events]
            if not here_ops and here_mods:
                here_ops = [(s, e) for s, e, _n in here_mods]
            if here_ops:
                seen_device = True
                ops, modules = sorted(here_ops), sorted(here_mods)
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                args = dict(e.stats)
                t0 = int(e.start_ns)
                t1 = t0 + int(e.duration_ns)
                if "dur_us" in args:  # measured after the fact: see the header
                    t1 = t0 - int(float(args.get("ago_us", 0)) * 1e3)
                    t0 = t1 - int(float(args["dur_us"]) * 1e3)
                spans.append({"name": e.name[len(PREFIX):], "line": (plane.name, n),
                              "t0": t0, "t1": t1, "args": args})
                planes[plane.name] = planes.get(plane.name, 0) + 1
    return {"ops": ops, "modules": modules, "spans": spans, "span_planes": planes}


# -- launches, batches, clocks ---------------------------------------------------

def _launches(spans: list[dict]) -> list[dict]:
    """`launch` spans in order of their starts (the call hands the program
    to the runtime early and may return late), each with the batch of the
    `h2d` span it is nested in (same line, inside it), or None."""
    h2d_by_line: dict[tuple, list[dict]] = {}
    for s in spans:
        if s["name"] == "h2d":
            h2d_by_line.setdefault(s["line"], []).append(s)
    out = []
    for s in sorted((s for s in spans if s["name"] == "launch"), key=lambda s: s["t0"]):
        batch, h2d = None, None
        for h in h2d_by_line.get(s["line"], ()):
            if h["t0"] <= s["t0"] and s["t1"] <= h["t1"]:
                batch, h2d = h["args"].get("batch"), h
                break
        out.append({**s, "batch": batch, "h2d": h2d})
    return out


def align(launches: list[dict], modules: list[tuple], fetch_end: dict) -> dict | None:
    """The shift and the clock offset of the header's clock check; None when
    no shift satisfies the physical bounds."""
    best = None
    for shift in range(-MAX_SHIFT, MAX_SHIFT + 1):
        pairs = [(launches[i], modules[i + shift]) for i in range(len(launches))
                 if 0 <= i + shift < len(modules)]
        # A shift may drop launches at an edge only: at most MAX_SHIFT of them.
        if not pairs or len(launches) - len(pairs) > MAX_SHIFT:
            continue
        hi = min(m[0] - la["t0"] for la, m in pairs)  # c <= module.start - launch.start
        lows = [m[1] - fetch_end[la["batch"]] for la, m in pairs
                if la["batch"] in fetch_end]          # c >= module.end - fetch.end
        lo = max(lows) if lows else None
        if lo is not None and lo > hi + MATCH_SLACK_NS:
            continue
        if lo is None:  # no fetch seen: only the upper bound holds
            offset = 0 if hi >= -MATCH_SLACK_NS else hi
        elif lo - MATCH_SLACK_NS <= 0 <= hi + MATCH_SLACK_NS:
            offset = 0
        else:
            offset = hi if abs(hi) < abs(lo) else lo
        if abs(offset) > MAX_OFFSET_NS:  # a wrong pairing, not a clock
            continue
        cand = {"shift": shift, "offset_ns": offset, "pairs": pairs,
                "bounds_ns": (lo, hi), "dropped": len(launches) - len(pairs)}
        # The shift that pairs the most launches: a wrong shift rarely
        # satisfies the bounds of more pairs than the right one.
        key = (cand["dropped"], abs(offset), abs(shift))
        if best is None or key < best[0]:
            best = (key, cand)
    return best[1] if best else None


# -- attribution -----------------------------------------------------------------

def _overlap(a0: int, a1: int, b0: int, b1: int) -> tuple[int, int] | None:
    s, e = max(a0, b0), min(a1, b1)
    return (s, e) if e > s else None


def _subtract(pieces: list[tuple[int, int]], cut: tuple[int, int]) -> list[tuple[int, int]]:
    out = []
    for s, e in pieces:
        if cut[1] <= s or cut[0] >= e:
            out.append((s, e))
            continue
        if s < cut[0]:
            out.append((s, cut[0]))
        if cut[1] < e:
            out.append((cut[1], e))
    return out


def _charge(free: list[tuple[int, int]], intervals: list[tuple[int, int]]):
    """Charge the parts of `free` that `intervals` cover: (ns charged, what
    is left of `free`)."""
    charged = 0
    for iv in sorted(intervals):
        for s, e in list(free):
            ov = _overlap(s, e, *iv)
            if ov:
                charged += ov[1] - ov[0]
                free = _subtract(free, ov)
    return charged, free


def attribute(data: dict, window_s: float) -> dict | None:
    """Split the idle time of the traced window over STATES (seconds), by the
    header's rule. None where the trace has no operation on a chip or no
    `tpuserve.launch` (a program without the spans)."""
    ops, modules, spans = data["ops"], data["modules"], data["spans"]
    launches = _launches(spans)
    if not ops or not launches:
        return None
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    window_ns = max(int(window_s * 1e9), hi - lo)
    pad = (window_ns - (hi - lo)) // 2
    gaps = gaps_of(ops, lo - pad, hi + pad)
    by_batch: dict = {}
    for s in spans:
        b = s["args"].get("batch")
        if b is not None and s["name"] in BATCH_STATES:
            by_batch.setdefault(b, {}).setdefault(s["name"], []).append((s["t0"], s["t1"]))
    fetch_end = {s["args"]["batch"]: s["t1"] for s in spans
                 if s["name"] == "fetch" and "batch" in s["args"]}
    tokenize = [(s["t0"], s["t1"]) for s in spans if s["name"] == "tokenize"]
    totals = dict.fromkeys(STATES, 0)
    named: list[dict] = []
    gap_lags: list[float] = []  # launch.start -> module.start where the device was idle
    al = align(launches, modules, fetch_end)
    if al is None:  # clocks disagree without pattern: attribute nothing
        totals["unknown"] = sum(e - s for s, e in gaps)
        return {"totals_s": {k: v / 1e9 for k, v in totals.items()}, "gaps": [],
                "window_s": window_ns / 1e9, "clock": None, "n_launches": len(launches)}
    c = al["offset_ns"]
    launch_of = {m: la for la, m in al["pairs"]}  # module event -> its launch span
    mod_starts = [m[0] for m in modules]

    def shifted(intervals):  # host intervals on the device's clock
        return [(s + c, e + c) for s, e in intervals]

    tokenize_dev = shifted(tokenize)
    for g0, g1 in gaps:
        if g1 - g0 < LONG_GAP_NS:
            totals["unknown"] += g1 - g0
            continue
        parts = dict.fromkeys(STATES, 0)
        free = [(g0, g1)]
        i = bisect.bisect_left(mod_starts, g0 - MATCH_SLACK_NS)
        module = modules[i] if i < len(modules) and modules[i][0] <= g1 + MATCH_SLACK_NS else None
        la = launch_of.get(module) if module else None
        batch = la["batch"] if la else None
        tail = module is None and i >= len(modules)
        if la is not None:
            gap_lags.append((module[0] - (la["t0"] + c)) / 1e6)
        if la is not None and batch is not None:
            states = by_batch.get(batch, {})
            for st in BATCH_STATES:
                parts[st], free = _charge(free, shifted(states.get(st, [])))
            acc = states.get("accumulate")
            if acc:  # before its accumulate began: not in the batcher yet
                before = min(s for s, _e in acc) + c
                early = [p for p in (_overlap(s, e, g0, before) for s, e in free) if p]
                for p in early:
                    free = _subtract(free, p)
                parts["tokenize"], rest = _charge(early, tokenize_dev)
                parts["no_request"] = sum(e - s for s, e in rest)
        elif tail:  # no launch ends it
            parts["tokenize"], free = _charge(free, tokenize_dev)
            parts["no_request"], free = sum(e - s for s, e in free), []
        parts["unknown"] = sum(e - s for s, e in free)
        for k, v in parts.items():
            totals[k] += v
        named.append({"start_ms": (g0 - (lo - pad)) / 1e6, "ms": (g1 - g0) / 1e6,
                      "batch": batch, "bucket": la["args"].get("bucket") if la else None,
                      "ended_by": "launch" if la else ("nothing" if tail else "unmatched"),
                      "parts_ms": {k: v / 1e6 for k, v in parts.items() if v}})
    lags = [(m[0] - (la["t0"] + c)) / 1e6 for la, m in al["pairs"]]
    end_lags = [(m[0] - (la["t1"] + c)) / 1e6 for la, m in al["pairs"]]
    fetch_lags = [(fetch_end[la["batch"]] + c - m[1]) / 1e6 for la, m in al["pairs"]
                  if la["batch"] in fetch_end]
    return {
        "totals_s": {k: v / 1e9 for k, v in totals.items()},
        "gaps": sorted(named, key=lambda g: -g["ms"]),
        "window_s": window_ns / 1e9,
        "n_launches": len(launches),
        "clock": {"offset_ms": c / 1e6, "shift": al["shift"], "pairs": len(al["pairs"]),
                  "launch_to_module_ms": lags, "launch_end_to_module_ms": end_lags,
                  "fetch_after_module_ms": fetch_lags,
                  "gap_ending_launch_to_module_ms": gap_lags,
                  "bounds_ms": tuple(None if b is None else b / 1e6 for b in al["bounds_ns"])},
    }


def h2d_less_launch_ms(spans: list[dict]) -> list[float]:
    """Per `tpuserve.h2d` span that holds a launch: its length less the
    launch's (the transfer and its wait, without the compiled call)."""
    return [(la["h2d"]["t1"] - la["h2d"]["t0"] - (la["t1"] - la["t0"])) / 1e6
            for la in _launches(spans) if la["h2d"] is not None]


# -- for the readers in layer_metrics/ --------------------------------------------

def analyse(profile, window_s: float) -> dict | None:
    """attribute() over a ProfileData, with what else the readers take from
    the spans. None as attribute() gives it."""
    data = read_profile(profile)
    att = attribute(data, window_s)
    if att is None:
        return None
    return {**att, "h2d_ms": h2d_less_launch_ms(data["spans"]),
            "span_planes": data["span_planes"]}


def for_run(run: dict) -> dict | None:
    """What the readers of one run share, computed once: None where the run
    has no device trace (`run["trace"]` is None on the CPU rehearsal, so no
    device metric comes from a CPU run), no `run["xplane"]` (the trace's
    file, from run.py), or the program wrote no spans."""
    if "host_spans" not in run:
        trace = run.get("trace")
        path = run.get("xplane") if trace else None
        if path:
            from jax.profiler import ProfileData

            run["host_spans"] = analyse(ProfileData.from_file(path), trace["window_s"])
        else:
            run["host_spans"] = None
    return run["host_spans"]


def idle_pct(run: dict, states: tuple[str, ...]) -> float | None:
    """Idle time charged to `states`, in percent of the traced window."""
    hs = for_run(run)
    if hs is None:
        return None
    return 100.0 * sum(hs["totals_s"][s] for s in states) / hs["window_s"]


def notes(hs: dict, top: int = 10) -> list[str]:
    """The lines a traced run prints: the clock check and the longest gaps
    with their names."""
    out = []
    ck = hs["clock"]
    if ck is None:
        return ["host_spans: NO shift of launches against module events satisfies "
                "launch.start <= module.start and fetch.end >= module.end within 10 ms: the "
                "host's and the chip's clocks disagree without pattern; nothing is attributed"]
    lags, ends, fl = (ck["launch_to_module_ms"], ck["launch_end_to_module_ms"],
                      ck["fetch_after_module_ms"])
    lo, hi = ck["bounds_ms"]
    line = (f"host_spans: clock check over {ck['pairs']} launches (shift {ck['shift']}; offset "
            f"{ck['offset_ms']:.3f} ms removed, bounds [{lo if lo is None else round(lo, 3)}, "
            f"{hi:.3f}]): launch.start -> module.start least {min(lags):.3f} ms, median "
            f"{statistics.median(lags):.3f} ms; launch.end -> module.start least {min(ends):.3f} ms")
    if ck["gap_ending_launch_to_module_ms"]:
        g = ck["gap_ending_launch_to_module_ms"]
        line += (f"; the {len(g)} launches that end a gap begin a median "
                 f"{statistics.median(g):.3f} ms, at most {max(g):.3f} ms, before their modules")
    if fl:
        line += (f"; module.end -> fetch.end least {min(fl):.3f} ms, median "
                 f"{statistics.median(fl):.3f} ms, most {max(fl):.3f} ms")
    ok = min(lags) >= -MATCH_SLACK_NS / 1e6 and (not fl or min(fl) >= -MATCH_SLACK_NS / 1e6)
    out.append(line + (" -> holds" if ok else " -> DOES NOT HOLD"))
    tot = hs["totals_s"]
    out.append("host_spans: idle by state, ms: " + ", ".join(
        f"{k}={v * 1e3:.1f}" for k, v in tot.items() if v))
    for g in hs["gaps"][:top]:
        parts = ", ".join(f"{k} {v:.1f}" for k, v in sorted(
            g["parts_ms"].items(), key=lambda kv: -kv[1]))
        out.append(f"host_spans: gap {g['ms']:.1f} ms at +{g['start_ms']:.0f} ms, ended by "
                   f"{g['ended_by']} (batch {g['batch']}, bucket {g['bucket']}): {parts}")
    return out
