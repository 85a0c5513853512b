"""The host's own time, as the program counts it (PR 51): what the eight
`layer_metrics/` readers of the collector's pauses, the loop's CPU beside its
wall time and the server's CPU by thread share.

This file depends on counter NAMES and LABELS and on one span's NAME and
ARGUMENTS only, never on `tpuserve/` code:

    host_gc_seconds_total{generation=}        seconds inside the collector, by generation (0, 1, 2)
    host_gc_collections_total{generation=}    collections, by generation
    tpuserve.gc  generation collected         one collection of 1 ms or more, written after the fact from the
                                              thread that collected (a zero-length annotation with `dur_us`,
                                              `ago_us`: host_spans.py's header says how such a mark is placed)
    gen_loop_cpu_seconds_total{model=,phase=} CPU time of the loop's thread by phase, beside
                                              gen_loop_seconds_total (wall); in `sweep` and `account`, which
                                              hold no await in the benchmark's traffic, wall less CPU is time
                                              the thread wanted to run and did not
    gen_account_seconds_total{model=,part=}   the `account` phase in three parts: finish, trees, sums
    host_thread_cpu_seconds_total{role=}      user + system CPU of the process's threads by role: event_loop,
                                              decode, stage, compile, runtime, other

A program without a family (the parent of the PR that added them) gives its
reader None, and so does a run without the scrapes; nothing here raises on it.

`idle_host_gc_pct` is a VIEW of `device_idle_share`, not one more part of it:
the device's idle gaps of 1 ms or more (the gaps both attribution rules name:
`trace_reduce.gaps_of` over the chip's `XLA Ops`, the traced window with its
padding) that lie under a `tpuserve.gc` mark, with the planes' offset removed
that `gen_loop.py` or `host_spans.py` finds for the run. Whatever state those
rules charge such a gap to (`fetch`, `host`, `slot_wait`, ...) they charge it
still.
"""

from __future__ import annotations

import bisect

from benchmark import gen_loop, gen_window, host_spans, prom
from benchmark.trace_reduce import gaps_of

GENERATIONS = ("0", "1", "2")
ROLES = ("event_loop", "decode", "stage", "compile", "runtime", "other")
ACCOUNT_PARTS = ("finish", "trees", "sums")
NO_AWAIT_PHASES = ("sweep", "account")  # in the benchmark's traffic
LONG_GAP_NS = host_spans.LONG_GAP_NS
LISTED_MS = 20.0  # pauses and gaps at least this long are listed one by one


def _note(run: dict, line: str) -> None:
    run.setdefault("notes", []).append(line)


def _by_label(run: dict, family: str, label: str, values: tuple, **labels) -> dict | None:
    """The window's `family` summed by each of `values` of `label`; None
    where the scrapes hold no such family (the parent's program)."""
    d = run.get("metrics_delta") or {}
    if not prom.select(d, family, **labels):
        return None
    return {v: sum(prom.select(d, family, **labels, **{label: v}).values()) for v in values}


# -- the collector, from the scrapes -----------------------------------------------

def gc_by_generation(run: dict) -> dict | None:
    """{generation: (collections, seconds)} of the window; None where the
    program has no such counter."""
    seconds = _by_label(run, "host_gc_seconds_total", "generation", GENERATIONS)
    counts = _by_label(run, "host_gc_collections_total", "generation", GENERATIONS)
    if seconds is None or counts is None:
        return None
    return {g: (counts[g], seconds[g]) for g in GENERATIONS}


def gc_pause_ms_per_s(run: dict) -> float | None:
    by_gen = gc_by_generation(run)
    seconds = getattr(run.get("load"), "seconds", None)
    if by_gen is None or not seconds:
        return None
    _note(run, f"host_gc_pause_ms_per_s: over {seconds:.1f} s; by generation: " + "; ".join(
        f"gen {g}: {n:.0f} collections, {s * 1e3:.1f} ms"
        + (f", mean {s * 1e3 / n:.3f} ms" if n else "") for g, (n, s) in by_gen.items()))
    return 1e3 * sum(s for _n, s in by_gen.values()) / seconds


# -- the collector, in the trace ---------------------------------------------------

def _clock_offset_ns(run: dict, data: dict, window_s: float) -> int:
    """host + offset = chip, as the run's own attribution found it: the
    generating cells' rule first, then the batched path's; 0 where neither
    pairs calls with modules."""
    for key, mod in (("gen_loop", gen_loop), ("host_spans", host_spans)):
        att = run[key] if key in run else mod.attribute(data, window_s)
        if att and att.get("clock"):
            return int(round(att["clock"]["offset_ms"] * 1e6))
    return 0


def _most_of(mark: tuple[int, int], spans: list[dict]) -> dict | None:
    """The span that covers most of `mark`, None where none touches it."""
    best, best_ns = None, 0
    for s in spans:
        ns = min(s["t1"], mark[1]) - max(s["t0"], mark[0])
        if ns > best_ns:
            best, best_ns = s, ns
    return best


def gc_idle(data: dict, window_s: float, offset_ns: int = 0) -> dict | None:
    """Idle time of the traced window under `tpuserve.gc` marks, from what
    `host_spans.read_profile` gives. None where no operation ran on a chip.
    `pauses` lists the marks, `gaps` the device's gaps of LISTED_MS or more
    with the part of each that a mark covers."""
    ops, spans = data["ops"], data["spans"]
    if not ops:
        return None
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    window_ns = max(int(window_s * 1e9), hi - lo)
    pad = (window_ns - (hi - lo)) // 2
    gaps = [g for g in gaps_of(ops, lo - pad, hi + pad) if g[1] - g[0] >= LONG_GAP_NS]
    starts = [g[0] for g in gaps]
    marks = sorted((s for s in spans if s["name"] == "gc"), key=lambda s: s["t0"])
    phases = [s for s in spans if s["name"] == "gen_loop"]
    others = [s for s in spans if s["name"] not in ("gc", "gen_loop")]
    on_line: dict[tuple, dict[str, int]] = {}
    for s in spans:
        if s["name"] != "gc":
            names = on_line.setdefault(s["line"], {})
            names[s["name"]] = names.get(s["name"], 0) + 1
    under = [0] * len(gaps)
    pauses = []
    for m in marks:
        m0, m1 = m["t0"] + offset_ns, m["t1"] + offset_ns
        idle = 0
        for i in range(max(0, bisect.bisect_right(starts, m0) - 1), len(gaps)):
            g0, g1 = gaps[i]
            if g0 >= m1:
                break
            ns = max(0, min(g1, m1) - max(g0, m0))
            idle += ns
            under[i] += ns
        phase, worker = _most_of((m["t0"], m["t1"]), phases), _most_of((m["t0"], m["t1"]), others)
        names = on_line.get(m["line"], {})
        pauses.append({
            "start_ms": (m0 - (lo - pad)) / 1e6, "ms": (m["t1"] - m["t0"]) / 1e6, "idle_ms": idle / 1e6,
            "generation": m["args"].get("generation"), "collected": m["args"].get("collected"),
            "line": m["line"],
            "line_writes": sorted(names, key=names.get, reverse=True)[:3],
            "phase": None if phase is None else f"{phase['args'].get('phase')} (iter {phase['args'].get('iter')})",
            "span": None if worker is None else worker["name"]})
    return {"window_s": window_ns / 1e9, "idle_s": sum(under) / 1e9, "pauses": pauses,
            "gaps": [{"start_ms": (g0 - (lo - pad)) / 1e6, "ms": (g1 - g0) / 1e6, "under_gc_ms": under[i] / 1e6}
                     for i, (g0, g1) in enumerate(gaps) if g1 - g0 >= LISTED_MS * 1e6]}


def gc_idle_notes(gi: dict, offset_ns: int) -> list[str]:
    longest = max((p["ms"] for p in gi["pauses"]), default=0.0)
    out = [f"idle_host_gc_pct: {len(gi['pauses'])} tpuserve.gc marks (collections of 1 ms or more) in the "
           f"traced {gi['window_s']:.3f} s, {sum(p['ms'] for p in gi['pauses']):.1f} ms in all, the longest "
           f"{longest:.1f} ms; {gi['idle_s'] * 1e3:.1f} ms of the device's idle gaps lie under them "
           f"(offset {offset_ns / 1e6:.3f} ms removed)"]
    for p in gi["pauses"]:
        if p["ms"] >= LISTED_MS:
            out.append(
                f"idle_host_gc_pct: pause {p['ms']:.1f} ms at +{p['start_ms']:.0f} ms, generation "
                f"{p['generation']}, collected {p['collected']}, on line {p['line'][0]}#{p['line'][1]} (which "
                f"writes {', '.join(p['line_writes']) or 'no other span'}), in phase {p['phase']}, span "
                f"{p['span']}; {p['idle_ms']:.1f} ms of it with the device idle")
    for g in gi["gaps"]:
        out.append(f"idle_host_gc_pct: device gap {g['ms']:.1f} ms at +{g['start_ms']:.0f} ms: "
                   f"{g['under_gc_ms']:.1f} ms of it under a tpuserve.gc mark")
    return out


def read_trace(path: str) -> dict:
    """The chip's intervals and the program's spans of a trace's file."""
    from jax.profiler import ProfileData

    return host_spans.read_profile(ProfileData.from_file(path))


def idle_host_gc_pct(run: dict) -> float | None:
    """None where the program has no `host_gc_seconds_total` (a trace of the
    parent holds no mark, and neither does a window of the change in which no
    collection took 1 ms: the scrapes tell the two apart) or the run no
    device trace."""
    trace = run.get("trace")
    path = run.get("xplane") if trace else None
    if not path or not trace.get("window_s") or gc_by_generation(run) is None:
        return None
    data = read_trace(path)
    offset = _clock_offset_ns(run, data, trace["window_s"])
    gi = gc_idle(data, trace["window_s"], offset)
    if gi is None:
        return None
    run.setdefault("notes", []).extend(gc_idle_notes(gi, offset))
    return 100.0 * gi["idle_s"] / gi["window_s"]


# -- the loop's CPU beside its wall time ---------------------------------------------

def loop_cpu_seconds(run: dict) -> dict | None:
    """The window's `gen_loop_cpu_seconds_total` by phase; None where the
    program has no such counter."""
    return _by_label(run, "gen_loop_cpu_seconds_total", "phase", gen_loop.PHASES,
                     model=run.get("model_name"))


def loop_cpu_share_pct(run: dict) -> float | None:
    cpu, wall = loop_cpu_seconds(run), gen_loop.loop_seconds(run)
    iters = gen_window.total(run, "gen_iterations_total")
    if cpu is None or wall is None or iters <= 0:
        return None
    wall_s = sum(wall[p] for p in NO_AWAIT_PHASES)
    if wall_s <= 0:
        return None
    _note(run, "gen_loop_cpu_share_pct: CPU / wall ms an iteration by phase: " + ", ".join(
        f"{p}={1e3 * cpu[p] / iters:.3f}/{1e3 * wall[p] / iters:.3f}" for p in gen_loop.PHASES)
        + f"; over {iters:.0f} iterations the loop's thread had the CPU for {sum(cpu.values()):.3f} s of "
        f"{sum(wall.values()):.3f} s")
    return 100.0 * sum(cpu[p] for p in NO_AWAIT_PHASES) / wall_s


def account_parts(run: dict) -> dict | None:
    return _by_label(run, "gen_account_seconds_total", "part", ACCOUNT_PARTS,
                     model=run.get("model_name"))


def account_trees_pct(run: dict) -> float | None:
    parts = account_parts(run)
    iters = gen_window.total(run, "gen_iterations_total")
    if parts is None or iters <= 0 or sum(parts.values()) <= 0:
        return None
    _note(run, "gen_account_trees_pct: account by part, ms an iteration: " + ", ".join(
        f"{p}={1e3 * s / iters:.3f}" for p, s in parts.items()))
    return 100.0 * parts["trees"] / sum(parts.values())


# -- the server's CPU by thread -------------------------------------------------------

def role_cpu_seconds(run: dict) -> dict | None:
    return _by_label(run, "host_thread_cpu_seconds_total", "role", ROLES)


def role_cpu_ms_per_item(run: dict, role: str, with_note: bool = False) -> float | None:
    by_role = role_cpu_seconds(run)
    items = getattr(run.get("load"), "items_in_window", 0)
    if by_role is None or not items:
        return None
    if with_note:
        outside = run.get("server_cpu_s")
        _note(run, "host_thread_cpu_seconds_total, ms an item by role: " + ", ".join(
            f"{r}={1e3 * s / items:.4f}" for r, s in by_role.items())
            + f"; the six sum to {1e3 * sum(by_role.values()) / items:.4f}"
            + ("" if outside is None else
               f" beside server_cpu_ms_per_item {1e3 * outside / items:.4f} (/proc/<pid>/stat, from outside)"))
    return 1e3 * by_role[role] / items
