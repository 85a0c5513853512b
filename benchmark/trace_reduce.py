"""From a profiler trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`,
which needs nothing but JAX and no chip) to the numbers the per-layer metrics
read. Only the device planes' operation and module lines are walked: the host
planes of a serving window are most of the file and none of the answer.

What a TPU trace looks like (looked at by hand, PR 24, TPU v5 lite; PERF.md
section 3 has the notes): one plane per chip named `/device:TPU:<n>`; on it a
line `XLA Modules` with one event per launch of a compiled program, named
`<module>(<fingerprint>)`, and a line `XLA Ops` with one event per HLO
operation. Launches of different programs of one module name differ in that
fingerprint, so a launch is keyed by the event's whole name; which bucket a
program is for is read from its operations' own shapes (an operation's event is
named by its HLO text, `%fusion.1 = bf16[256,512,768]{...} fusion(...)`): each
module's three-dimensional result shapes are listed, most device time first, and
`bucket_of` picks (batch, sequence) from the one whose last dimension is the
model's width ((batch, heads, sequence) shapes are there too).

`busy_s` is the union of the operation intervals on a chip, clipped to the
window, averaged over the chips that ran anything. The window is the span the
tracer was on by the host clock, or the extent of the device events where
that is longer (the tracer keeps events of launches that straddle its edges).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def union_s(intervals: list[tuple[int, int]]) -> float:
    """Seconds covered by the union of [start_ns, end_ns) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps_of(intervals: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle stretches of [lo, hi) that the intervals leave."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


_SHAPE3 = re.compile(r" = \(?[a-z0-9]+\[(\d+),(\d+),(\d+)\]")


def op_shape3(event_name: str) -> tuple | None:
    """The result shape of an operation whose result has three dimensions,
    `%x = bf16[256,512,768]{...} fusion(...)` -> (256, 512, 768): in a
    transformer's program those are (batch, sequence, width)."""
    m = _SHAPE3.search(event_name)
    return tuple(int(g) for g in m.groups()) if m else None


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO text, `%fusion.12 = bf16[...]
    fusion(...)`: keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


MIN_GAP_S = 1e-6  # shorter gaps are the spacing between two operations


def reduce_profile(profile, window_s: float | None = None, top: int = 10) -> dict | None:
    """`profile` is a jax.profiler.ProfileData. None where no operation ran
    on any device."""
    per_device = []
    op_time: dict[str, float] = {}
    modules: dict[str, dict] = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, shaped, mods = [], [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    ops.append((s, e))
                    name = op_name(ev.name)
                    op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
                    shape = op_shape3(ev.name)
                    if shape:
                        shaped.append((s, e - s, shape))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    mods.append((int(ev.start_ns), int(ev.duration_ns), ev.name))
        if not ops and mods:  # a trace without an op line: launches stand in
            ops = [(s, s + d) for s, d, _ in mods]
        if ops:
            per_device.append(ops)
        mods.sort()
        starts = [s for s, _d, _n in mods]
        for i, (s, d, name) in enumerate(mods):
            m = modules.setdefault(name, {"launches": 0, "device_s": 0.0, "durations": [],
                                          "whole": [], "shapes": {}})
            m["launches"] += 1
            m["device_s"] += d / 1e9
            m["durations"].append(d / 1e9)
            # A chip runs one program at a time, so the tracer's edges can cut
            # only the first and the last launch on its line: a launch with
            # another before it and another after it lies whole in the window.
            if 0 < i < len(mods) - 1:
                m["whole"].append(d / 1e9)
        for s, d, shape in shaped:  # time per 3-d result shape, by the launch it ran in
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][0] + mods[i][1]:
                shapes = modules[mods[i][2]]["shapes"]
                shapes[shape] = shapes.get(shape, 0) + d
    for m in modules.values():  # the shapes most time went to, longest first
        shapes = m["shapes"]
        m["shapes"] = [list(k) for k in sorted(shapes, key=shapes.get, reverse=True)[:8]]
        # The median launch, over the launches that lie whole inside the
        # window: the tracer's edges cut the first and the last launch short,
        # and with four launches in the window a median over all of them is
        # half made of the pieces (PERF.md, Findings, PR 27). Where no launch
        # is known to be whole the median is over all, and
        # `whole_launches` = 0 says so.
        durations, whole = m.pop("durations"), m.pop("whole")
        m["whole_launches"] = len(whole)
        m["launch_s"] = statistics.median(whole or durations)
    if not per_device:
        return None
    lo = min(s for ops in per_device for s, _ in ops)
    hi = max(e for ops in per_device for _, e in ops)
    extent_s = (hi - lo) / 1e9
    window = max(window_s or 0.0, extent_s)
    # Centre the window on the events' extent: the tracer's own edges are not
    # on the device clock, so the slack is split between the two ends.
    pad = int((window - extent_s) * 1e9 / 2)
    busy = sum(union_s(ops) for ops in per_device) / len(per_device)
    gaps = sorted((g for g in ((e - s) / 1e9 for s, e in gaps_of(
        per_device[0], lo - pad, hi + pad)) if g >= MIN_GAP_S), reverse=True)
    top_module = None
    if modules:  # the program the device spent most of the window in
        name = max(modules, key=lambda n: modules[n]["device_s"])
        top_module = {"name": name, **modules[name]}
    return {
        "n_devices": len(per_device),
        "window_s": window,
        "busy_s": busy,
        "modules": modules,
        "top_module": top_module,
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        # Unnamed here: run.py names them from the program's spans where the
        # trace holds any (host_spans.py), and keeps these where it does not.
        "idle_gaps": [["host:unknown", g] for g in gaps[:top]],
    }


def bucket_of(module: dict, width: int) -> tuple[int, int] | None:
    """(batch, sequence) of a module from reduce_profile, for a model of the
    given width: its first (batch, sequence, width) result shape."""
    for shape in module.get("shapes", []):
        if shape[2] == width:
            return shape[0], shape[1]
    return None


def reduce_file(path: str, window_s: float | None = None) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window_s)
