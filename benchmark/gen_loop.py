"""Name the device's idle gaps in a generating cell, and read the generation
engine's loop counters: what the `idle_gen_*_pct` and `gen_loop_*` /
`gen_first_token_*` / `gen_token_gap_*` readers of `layer_metrics/` share.

The engine (`tpuserve/genserve/engine.py`) runs ONE loop, so its time is a
sequence: every instant has one `phase`. This file depends on span NAMES and
ARGUMENTS and on counter NAMES only, never on `tpuserve/` code:

    tpuserve.gen_loop     phase iter    one phase of one pass of the loop, measured after the fact on
                                        the event loop (a zero-length annotation with `dur_us`, `ago_us`:
                                        host_spans.py's header says how such a mark is placed)
    tpuserve.gen_pack     iter pieces   `pack_prefill`, in the worker thread, before the compiled call
    tpuserve.gen_prefill  iter ...      a prefill launch's compiled call
    tpuserve.gen_step     iter lanes    a decode step's compiled call
    tpuserve.gen_fetch    iter          blocks until the step's out-block is on the host
    tpuserve.gen_extract  iter slot     a finished slot's extract program and its fetch
    tpuserve.gen_finalize iter slot     `model.finalize`, in the postproc thread
    gen_loop_seconds_total{model=,phase=}   the loop's wall time by phase: the eight sum to it
    gen_first_unit_ms, gen_token_gap_ms     histograms, one observation a request / a decoding iteration

A program that writes no `tpuserve.gen_loop` (the parent of the PR that added
it) gives every reader here None; so does a run without a device trace (the
CPU rehearsal: no device number comes from a CPU run).

THE ATTRIBUTION RULE. Gaps are those of the chip's `XLA Ops` union over the
traced window with `trace_reduce`'s padding, as `idle_gen_loop_pct` and
`host_spans.py` take them, so the parts sum to `device_idle_share`. A gap under
1 ms is `unknown`, and so are the window's edges beyond the device's events. A
longer gap's interval is split over what covers it, a worker's span before the
loop's phase, in this order:

    fetch    inside `gen_fetch`: the step has ended on the chip, the host does not hold its out-block yet
    launch   inside `gen_step` or `gen_prefill`: in the compiled call, before the chip begins
    host     inside `gen_pack`; then phases `sweep`, `admit`, `account`, `emit`: the loop's own Python
    hop      phase `prefill` or `step` outside every worker's span: the hand-over to the executor
             thread and back (executor queue, the GIL, a late event loop)
    retire   phase `retire`, whatever is inside it (the notes split it: extract, finalize, hand-over)
    no_work  phase `wait`
    unknown  what no span covers

THE CLOCK CHECK. Calls and module events are both in order of time, so the
i-th `gen_step` belongs to the (i + shift)-th `jit_step` module event, and the
i-th `gen_prefill` to the (i + shift')-th `jit_prefill_fn`, for two small
shifts (calls before the tracer started, modules after it stopped). Physics
bounds the planes' offset c (host + c = device): every compiled call BEGINS
before its module starts, every `gen_fetch` ENDS after its `jit_step` module
ends: max(module.end - fetch.end) <= c <= min(module.start - call.start). The
engine hands the device one thing at a time, so at most calls the device is
idle and the two bounds are a dispatch and a copy apart. The reader takes the
shifts whose bounds hold for the most calls (then the offset nearest 0; over
10 ms is a wrong pairing, not a clock), uses 0 when 0 lies inside the bounds,
else the bound nearest 0 (removed and printed). No shifts with bounds that
hold: NOTHING is attributed (all idle time is `unknown`, and the note says so).
"""

from __future__ import annotations

import statistics

from benchmark import gen_window, host_spans, prom
from benchmark.trace_reduce import gaps_of

STATES = ("fetch", "launch", "hop", "retire", "host", "no_work", "unknown")
PHASES = ("sweep", "admit", "prefill", "step", "account", "emit", "retire", "wait")
# The loop's time outside its two awaits of the device, nothing queued on the chip.
SERIAL_PHASES = ("sweep", "admit", "account", "emit", "retire")
PHASE_STATE = {"prefill": "hop", "step": "hop", "retire": "retire", "wait": "no_work",
               "sweep": "host", "admit": "host", "account": "host", "emit": "host"}
WORKERS = ("gen_pack", "gen_prefill", "gen_step", "gen_fetch")
LONG_GAP_NS = host_spans.LONG_GAP_NS
MAX_SHIFT = host_spans.MAX_SHIFT
SLACK_NS = host_spans.MATCH_SLACK_NS
MAX_OFFSET_NS = host_spans.MAX_OFFSET_NS


# -- the clock -------------------------------------------------------------------

def _shifts(calls: list[dict], mods: list[tuple]) -> list[tuple]:
    """(shift, pairs, calls dropped) for every shift that drops calls at an
    edge only; one empty pairing where there is nothing to pair."""
    if not calls or not mods:
        return [(0, [], 0)]
    out = []
    for shift in range(-MAX_SHIFT, MAX_SHIFT + 1):
        pairs = [(calls[i], mods[i + shift]) for i in range(len(calls))
                 if 0 <= i + shift < len(mods)]
        if pairs and len(calls) - len(pairs) <= MAX_SHIFT:
            out.append((shift, pairs, len(calls) - len(pairs)))
    return out


def align(spans: list[dict], modules: list[tuple]) -> dict | None:
    """The two shifts and the clock offset of the header's check; None when
    no pairing satisfies the physical bounds."""
    def named(name):
        return sorted((s for s in spans if s["name"] == name), key=lambda s: s["t0"])

    def mods(prefix):
        return [m for m in modules if m[2] == prefix or m[2].startswith(prefix + "(")]

    fetch_end = {s["args"].get("iter"): s["t1"] for s in named("gen_fetch")}
    best = None
    for s_shift, s_pairs, s_drop in _shifts(named("gen_step"), mods(gen_window.STEP_MODULE)):
        for p_shift, p_pairs, p_drop in _shifts(named("gen_prefill"),
                                                mods(gen_window.PREFILL_MODULE)):
            pairs = s_pairs + p_pairs
            if not pairs:
                continue
            hi = min(m[0] - call["t0"] for call, m in pairs)
            lows = [m[1] - fetch_end[call["args"].get("iter")] for call, m in s_pairs
                    if call["args"].get("iter") in fetch_end]
            lo = max(lows) if lows else None
            if lo is not None and lo > hi + SLACK_NS:
                continue
            if lo is None:
                offset = 0 if hi >= -SLACK_NS else hi
            elif lo - SLACK_NS <= 0 <= hi + SLACK_NS:
                offset = 0
            else:
                offset = hi if abs(hi) < abs(lo) else lo
            if abs(offset) > MAX_OFFSET_NS:
                continue
            key = (s_drop + p_drop, abs(offset), abs(s_shift) + abs(p_shift))
            if best is None or key < best[0]:
                best = (key, {"offset_ns": offset, "bounds_ns": (lo, hi),
                              "shifts": (s_shift, p_shift), "step_pairs": s_pairs,
                              "prefill_pairs": p_pairs, "fetch_end": fetch_end})
    return best[1] if best else None


# -- attribution -----------------------------------------------------------------

def _cover(free: list[tuple[int, int]], iv: tuple[int, int]):
    """The pieces of `free` that `iv` covers, and what is left of `free`."""
    got, left = [], []
    for s, e in free:
        a, b = max(s, iv[0]), min(e, iv[1])
        if b <= a:
            left.append((s, e))
            continue
        got.append((a, b))
        if s < a:
            left.append((s, a))
        if b < e:
            left.append((b, e))
    return got, left


def _ns(pieces) -> int:
    return sum(e - s for s, e in pieces)


def attribute(data: dict, window_s: float) -> dict | None:
    """Split the idle time of the traced window over STATES by the header's
    rule. None where the trace has no operation on a chip or no
    `tpuserve.gen_loop` (a program without the phases)."""
    ops, spans = data["ops"], data["spans"]
    phases = sorted((s for s in spans if s["name"] == "gen_loop"), key=lambda s: s["t0"])
    if not ops or not phases:
        return None
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    window_ns = max(int(window_s * 1e9), hi - lo)
    pad = (window_ns - (hi - lo)) // 2
    gaps = gaps_of(ops, lo - pad, hi + pad)
    totals = dict.fromkeys(STATES, 0)
    detail: dict[str, int] = {}
    base = {"window_s": window_ns / 1e9,
            "span_counts": {n: sum(1 for s in spans if s["name"] == n)
                            for n in ("gen_loop",) + WORKERS + ("gen_extract", "gen_finalize")}}
    al = align(spans, data["modules"])
    if al is None:  # clocks disagree without pattern: attribute nothing
        totals["unknown"] = _ns(gaps)
        return {**base, "totals_s": {k: v / 1e9 for k, v in totals.items()}, "detail_s": {},
                "gaps": [], "clock": None}
    c = al["offset_ns"]

    def on_chip(names):  # host intervals on the device's clock, in order
        return sorted(((s["t0"] + c, s["t1"] + c, s) for s in spans if s["name"] in names),
                      key=lambda x: x[:2])

    layers = [("fetch", on_chip(("gen_fetch",))), ("launch", on_chip(("gen_step", "gen_prefill"))),
              ("host", on_chip(("gen_pack",)))]
    workers = on_chip(WORKERS)
    inside_retire = on_chip(("gen_extract", "gen_finalize"))
    loop = on_chip(("gen_loop",))
    named = []
    for g0, g1 in gaps:
        if g1 - g0 < LONG_GAP_NS or g1 <= lo or g0 >= hi:  # short, or the window's edge
            totals["unknown"] += g1 - g0
            if g1 - g0 >= LONG_GAP_NS:
                detail["unknown:window_edge"] = detail.get("unknown:window_edge", 0) + g1 - g0
            continue
        parts: dict[str, int] = {}
        free = [(g0, g1)]

        def charge(state, what, pieces):
            if pieces:
                parts[f"{state}:{what}"] = parts.get(f"{state}:{what}", 0) + _ns(pieces)

        for state, layer in layers:
            for t0, t1, s in layer:
                if t1 > g0 and t0 < g1:
                    got, free = _cover(free, (t0, t1))
                    charge(state, s["name"], got)
        iters = []
        for p0, p1, s in loop:
            if p1 <= g0 or p0 >= g1:
                continue
            got, free = _cover(free, (p0, p1))
            phase = str(s["args"].get("phase"))
            state = PHASE_STATE.get(phase, "unknown")
            iters.append(s["args"].get("iter"))
            if state == "retire":
                for t0, t1, w in inside_retire:
                    if t1 > p0 and t0 < p1:
                        for piece in list(got):
                            inside, rest = _cover([piece], (t0, t1))
                            if inside:
                                got.remove(piece)
                                got += rest
                                charge(state, w["name"], inside)
                charge(state, "hand_over", got)
            elif state == "hop":  # by the workers' spans of this phase on either side
                mine = [(t0, t1, w["name"]) for t0, t1, w in workers
                        if t0 >= p0 - SLACK_NS and t1 <= p1 + SLACK_NS]
                for a, b in got:
                    before = [n for _t0, t1, n in mine if t1 <= a + SLACK_NS]
                    after = [n for t0, _t1, n in mine if t0 >= b - SLACK_NS]
                    charge(state, f"{before[-1] if before else 'loop'}>"
                                  f"{after[0] if after else 'loop'}", [(a, b)])
            else:
                charge(state, phase, got)
        charge("unknown", "no_span", free)
        by_state = dict.fromkeys(STATES, 0)
        for k, v in parts.items():
            by_state[k.split(":", 1)[0]] += v
            detail[k] = detail.get(k, 0) + v
        for k, v in by_state.items():
            totals[k] += v
        named.append({"start_ms": (g0 - (lo - pad)) / 1e6, "ms": (g1 - g0) / 1e6,
                      "iter": next((i for i in iters if i is not None), None),
                      "parts_ms": {k: v / 1e6 for k, v in by_state.items() if v},
                      "detail_ms": {k: v / 1e6 for k, v in parts.items()}})
    fe = al["fetch_end"]
    return {
        **base,
        "totals_s": {k: v / 1e9 for k, v in totals.items()},
        "detail_s": {k: v / 1e9 for k, v in detail.items()},
        "gaps": sorted(named, key=lambda g: -g["ms"]),
        "clock": {
            "offset_ms": c / 1e6, "shifts": al["shifts"],
            "pairs": (len(al["step_pairs"]), len(al["prefill_pairs"])),
            "bounds_ms": tuple(None if b is None else b / 1e6 for b in al["bounds_ns"]),
            "call_to_module_ms": [(m[0] - call["t0"] - c) / 1e6
                                  for call, m in al["step_pairs"] + al["prefill_pairs"]],
            "fetch_after_module_ms": [(fe[call["args"].get("iter")] + c - m[1]) / 1e6
                                      for call, m in al["step_pairs"]
                                      if call["args"].get("iter") in fe]},
    }


def notes(gl: dict, top: int = 10) -> list[str]:
    """The lines a traced run prints: the clock check, idle by state and by
    what it lay in, and the longest gaps with their parts."""
    ck = gl["clock"]
    if ck is None:
        return ["gen_loop: NO shift of gen_step / gen_prefill calls against jit_step / jit_prefill_fn "
                "module events satisfies call.start <= module.start and fetch.end >= module.end "
                "within 10 ms: the host's and the chip's clocks disagree without pattern; nothing "
                "is attributed (all idle time is unknown)"]
    lo, hi = ck["bounds_ms"]
    lags, fl = ck["call_to_module_ms"], ck["fetch_after_module_ms"]
    line = (f"gen_loop: clock check over {ck['pairs'][0]} steps and {ck['pairs'][1]} prefill launches "
            f"(shifts {ck['shifts'][0]}, {ck['shifts'][1]}; offset {ck['offset_ms']:.3f} ms removed, "
            f"bounds [{'none' if lo is None else f'{lo:.3f}'}, {hi:.3f}]"
            + ("" if lo is None else f", {hi - lo:.3f} ms apart")
            + f"): call.start -> module.start least {min(lags):.3f} ms, median "
            f"{statistics.median(lags):.3f} ms")
    if fl:
        line += (f"; module.end -> fetch.end least {min(fl):.3f} ms, median "
                 f"{statistics.median(fl):.3f} ms, most {max(fl):.3f} ms")
    out = [line, "gen_loop: spans in the trace " + str(gl["span_counts"])]
    out.append("gen_loop: idle by state, ms: " + ", ".join(
        f"{k}={v * 1e3:.1f}" for k, v in gl["totals_s"].items() if v))
    out.append("gen_loop: idle by what it lay in, ms: " + ", ".join(
        f"{k}={v * 1e3:.1f}" for k, v in sorted(gl["detail_s"].items(), key=lambda kv: -kv[1])))
    for g in gl["gaps"][:top]:
        parts = ", ".join(f"{k} {v:.2f}" for k, v in sorted(
            g["detail_ms"].items(), key=lambda kv: -kv[1]))
        out.append(f"gen_loop: gap {g['ms']:.1f} ms at +{g['start_ms']:.0f} ms (iter {g['iter']}): {parts}")
    return out


# -- for the readers in layer_metrics/ --------------------------------------------

def for_run(run: dict) -> dict | None:
    """What the `idle_gen_*_pct` readers of one run share, computed once and
    its notes printed once. None where the run has no device trace, no
    `run["xplane"]`, or the program wrote no `tpuserve.gen_loop`."""
    if "gen_loop" not in run:
        trace = run.get("trace")
        path = run.get("xplane") if trace else None
        run["gen_loop"] = None
        if path:
            from jax.profiler import ProfileData

            run["gen_loop"] = attribute(
                host_spans.read_profile(ProfileData.from_file(path)), trace["window_s"])
        if run["gen_loop"]:
            run.setdefault("notes", []).extend(notes(run["gen_loop"]))
    return run["gen_loop"]


def idle_pct(run: dict, state: str) -> float | None:
    """Idle time charged to `state`, in percent of the traced window;
    `unknown` is what is left of `device_idle_share` after the other six, so
    that the seven sum to it."""
    gl = for_run(run)
    trace = run.get("trace")
    if gl is None or not trace or not trace["window_s"]:
        return None
    if state != "unknown":
        return 100.0 * gl["totals_s"][state] / gl["window_s"]
    idle = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    return idle - sum(idle_pct(run, s) for s in STATES if s != "unknown")


def loop_seconds(run: dict) -> dict | None:
    """The window's `gen_loop_seconds_total` by phase, from the two scrapes;
    None where the program has no such counter."""
    d, model = run.get("metrics_delta") or {}, run.get("model_name")
    by_phase = {p: sum(prom.select(d, "gen_loop_seconds_total", model=model, phase=p).values())
                for p in PHASES}
    return by_phase if sum(by_phase.values()) > 0 else None
