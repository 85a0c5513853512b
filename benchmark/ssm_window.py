"""What the `ssm_*` per-layer readers share: the device time, a launch, of the
operations that belong to a `jax.named_scope` of the program (`ssm_update` in
the step, `ssm_scan` in a prefill launch).

What identifies them (looked at on the chip, PR 32, `scripts/bench_hybrid.py`;
PERF.md section 3). An operation's event on a device plane's `XLA Ops` line is
named by its HLO text and carries three statistics of time and nothing else:
no `op_name`, no scope. But the SAME trace file holds, on its `/host:metadata`
plane, one event-metadata entry a compiled program (`jit_step(<id>)`) whose
statistic `Hlo Proto` is the program's whole `HloProto`: every instruction's
name with its `metadata.op_name`, `jit(step)/.../ssm_update/mul`. `jax.profiler`'s
`ProfileData` does not show that plane's metadata, so `scope_map` reads the
file's protobuf wire format itself (forty lines, no dependency), and an
operation's event is under a scope when the instruction it names is. A fusion
carries the `op_name` of the instruction it was built around, so an operation
the compiler fused ACROSS a scope's edge counts on one side of it only: the
reading is the scope's to within the fusions at its two edges.

Operations nest on that line (a `while` and its body): a launch's time under a
scope is the UNION of its tagged intervals. The number a launch is the median
over the launches of the module that lie whole inside the traced window, as
`trace_reduce.py` takes it. Everything returns None, and never raises, where
the trace has no such module, no program text or no operation under the scope
(the parent of the PR that added the scopes, a CPU run).
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import gen_window
from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, op_name, union_s

METADATA_PLANE = b"/host:metadata"
HLO_STAT = "Hlo Proto"


# -- the trace file's wire format, as far as the programs' text -------------------

def _varint(b, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one protobuf message: a varint's int, the
    bytes of a length-delimited field (a string or a nested message), the raw
    bytes of a fixed one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, v


def _first(b, field: int):
    return next((v for f, v in _fields(b) if f == field), None)


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace") if b is not None else ""


def scope_map(path: str) -> dict[str, dict[str, str]]:
    """{program's base name (`jit_step`): {instruction name: its op_name}} from
    the `Hlo Proto` statistics of the trace's `/host:metadata` plane. XSpace
    .planes = 1; XPlane .name = 2, .event_metadata = 4 (a map: value = 2),
    .stat_metadata = 5; XEventMetadata .name = 2, .stats = 5; XStat
    .metadata_id = 1, .bytes_value = 6; XStatMetadata .id = 1, .name = 2;
    HloProto .hlo_module = 1; HloModuleProto .computations = 3;
    HloComputationProto .instructions = 2; HloInstructionProto .name = 1,
    .metadata = 7; OpMetadata .op_name = 2."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for field, plane in _fields(data):
        if field != 1 or bytes(_first(plane, 2) or b"") != METADATA_PLANE:
            continue
        stat_names = {}
        for f2, v in _fields(plane):
            if f2 == 5:
                md = _first(v, 2)
                stat_names[_first(md, 1)] = _text(_first(md, 2))
        for f2, v in _fields(plane):
            if f2 != 4:
                continue
            md = _first(v, 2)
            names = out.setdefault(_text(_first(md, 2)).split("(")[0], {})
            for f3, st in _fields(md):
                if f3 != 5 or stat_names.get(_first(st, 1)) != HLO_STAT:
                    continue
                module = _first(_first(st, 6) or b"", 1) or b""
                for f4, comp in _fields(module):
                    if f4 != 3:
                        continue
                    for f5, inst in _fields(comp):
                        meta = _first(inst, 7) if f5 == 2 else None
                        if meta is not None:
                            names[_text(_first(inst, 1))] = _text(_first(meta, 2))
    return out


def scoped_launch_s(run: dict, module_prefix: str, scope: str) -> dict | None:
    """{"launch_s": median seconds a whole launch spends under `scope`,
    "launches", "whole_launches", "names": the tagged operations by time}."""
    cache = run.setdefault("_scoped", {})
    if (module_prefix, scope) in cache:
        return cache[(module_prefix, scope)]
    out = cache[(module_prefix, scope)] = _read(run, module_prefix, scope)
    return out


def _read(run: dict, module_prefix: str, scope: str) -> dict | None:
    path = run.get("xplane")
    if not path or not run.get("trace"):
        return None
    try:
        from jax.profiler import ProfileData

        under = scope_map(path).get(module_prefix) or {}
        planes = [p for p in ProfileData.from_file(path).planes if DEVICE_PLANE.match(p.name)]
    except Exception:  # a file that is no trace: nothing to read
        return None
    if not any(scope in v for v in under.values()):
        return None
    per_launch, whole, names = [], [], {}
    for plane in planes:
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((int(e.start_ns), int(e.duration_ns), e.name) for e in line.events)
            elif line.name == OPS_LINE:
                for e in line.events:
                    n = op_name(e.name)
                    if scope in under.get(n, ""):
                        ops.append((int(e.start_ns), int(e.start_ns) + int(e.duration_ns), n))
        starts = [s for s, _d, _n in mods]
        mine = [name.startswith(module_prefix + "(") or name == module_prefix
                for _s, _d, name in mods]
        by_launch: dict[int, list] = {}
        for s, e, n in ops:  # another program may have an instruction of the same name
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mine[i] and s < mods[i][0] + mods[i][1]:
                by_launch.setdefault(i, []).append((s, e))
                names[n] = names.get(n, 0.0) + (e - s) / 1e9
        for i in range(len(mods)):
            if not mine[i]:
                continue
            t = union_s(by_launch.get(i, []))
            per_launch.append(t)
            if 0 < i < len(mods) - 1:
                whole.append(t)
    if not per_launch or not any(per_launch):
        return None
    top = sorted(names.items(), key=lambda kv: -kv[1])[:6]
    run.setdefault("notes", []).append(
        f"{scope}: {len(per_launch)} launches of {module_prefix} in the traced window, "
        f"{len(whole)} whole; {len(names)} operations carry the scope, most time in "
        + ", ".join(f"{n} ({t * 1e3:.2f} ms)" for n, t in top))
    return {"launch_s": statistics.median(whole or per_launch), "launches": len(per_launch),
            "whole_launches": len(whole), "names": names}


def roofline_share(run: dict, what: str, ops_bytes: tuple, launch_s: float | None):
    peaks = run.get("peaks")
    if not peaks or not launch_s:
        return None
    ops, nbytes = ops_bytes
    t_ops, t_bytes = ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"{what} roofline: bound by {'compute' if t_ops >= t_bytes else 'memory'} (ops {ops:.4g} -> "
        f"{t_ops * 1e3:.3f} ms, bytes {nbytes:.4g} -> {t_bytes * 1e3:.3f} ms) against "
        f"{launch_s * 1e3:.3f} ms a launch")
    return 100.0 * max(t_ops, t_bytes) / launch_s


def tokens_per_launch(run: dict, phase: str) -> float | None:
    """Live tokens a launch through ONE scan layer, from the window's
    `ssm_tokens_total{phase=}` over the layers and the launches."""
    n = (run.get("sizes") or {}).get("n_mamba")
    launches = gen_window.total(
        run, "gen_iterations_total" if phase == "decode" else "gen_prefill_chunks_total")
    tokens = gen_window.total(run, "ssm_tokens_total", phase=phase)
    if not n or launches <= 0 or tokens <= 0:
        return None
    return tokens / n / launches
