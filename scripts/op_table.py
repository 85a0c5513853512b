"""One launch of a program by operation, from a device trace: the table of
PERF.md section 5 ("a launch's N ms by operation") in one call.

An operation's event on a chip's `XLA Ops` line is named by its HLO text; the
trace's own copy of the program (`benchmark/ssm_window.py` `scope_map`) gives
each instruction the `op_name` it was traced under, `jit(step)/.../moe_dispatch/
sort`. A row of the table is a `jax.named_scope` of the program, the innermost
on the instruction's path, with the kind of instruction under it, or, outside
every scope, the kind alone. What is a scope is told by the path's shape, by
`benchmark/launch_scopes.py`'s rule (no list here: a scope a PR adds to the
program is a row with no edit), and a traced run of a cell prints that file's
table by CHAIN of scopes for both programs; this one is by operation, of any
program of any trace. Operations nest on that line (a `while` or a
`conditional` and what runs inside it), so the containers are listed apart and
left out of the sum.

Used by `scripts/bench_hybrid.py` and `scripts/bench_prefill.py`; reads any
`*.xplane.pb`:  python scripts/op_table.py [--longest] <trace.xplane.pb> [module prefix ...]
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.launch_scopes import CONTAINERS, chain, kind, scopes_of  # noqa: E402
from benchmark.ssm_window import scope_map  # noqa: E402
from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, op_name  # noqa: E402


def dispatch_of(model, tokens: int, acc_delta) -> dict:
    """What the expert layers' dispatch carried in the launches that moved one
    phase's row of ``acc`` by ``acc_delta``: the picks of a launch of ``tokens``
    rows, the rows its compact branch carries (``ops/moe.py`` ``_row_bound``),
    and the layers that ran each branch (the row's fourth column is held
    experts x expert layers run, ``COMPACT_COLUMN`` the layers that ran compact)."""
    from tpuserve.models.paged_lm import COMPACT_COLUMN
    from tpuserve.ops.moe import _row_bound

    picks = tokens * model.top_k
    ran = int(acc_delta[3]) // max(1, model.e_count)
    compact = int(acc_delta[model.COLUMNS.index(COMPACT_COLUMN)])
    return {"picks": picks, "rows_carried_compact": _row_bound(picks, model.e_count,
                                                               model.n_experts),
            "expert_layers_run": ran, "compact": compact, "wide": ran - compact}


def last_launches(path: str, longest: bool = False) -> dict[str, dict]:
    """{module's base name: {"name", "ns", "ops": [(instruction, ns, op_name)]}}
    for the LAST launch of each program on the first chip that ran any
    (``longest``: the longest instead: of a cell's traced window, whose launches
    differ in their live rows and whose last the trace's end may cut)."""
    from jax.profiler import ProfileData

    names = scope_map(path)
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if not DEVICE_PLANE.match(plane.name) or OPS_LINE not in lines \
                or MODULES_LINE not in lines:
            continue
        out = {}
        for ev in lines[MODULES_LINE].events:
            base = ev.name.split("(")[0]
            if longest and base in out and out[base]["ns"] >= int(ev.duration_ns):
                continue
            out[base] = {"name": ev.name, "lo": int(ev.start_ns), "ns": int(ev.duration_ns),
                         "ops": []}
        for ev in lines[OPS_LINE].events:
            for base, m in out.items():
                if m["lo"] <= ev.start_ns < m["lo"] + m["ns"]:
                    inst = op_name(ev.name)
                    m["ops"].append((inst, int(ev.duration_ns), names.get(base, {}).get(inst, "")))
        return out
    return {}


def table(ops: list[tuple[str, int, str]]) -> tuple[list[tuple[str, float, int]], float]:
    """[(row, ms, operations)] most time first, and the containers' ms."""
    rows: dict[str, list] = {}
    inside = 0
    known = scopes_of(traced_as for _inst, _ns, traced_as in ops)
    for inst, ns, traced_as in ops:
        what = kind(inst)
        if what in CONTAINERS:
            inside += ns
            continue
        scopes = chain(traced_as, known)
        prim = traced_as.rsplit("/", 1)[-1] if traced_as else ""
        row = f"{scopes[-1]}: {what}" if scopes else f"{what} ({prim})" if prim else what
        got = rows.setdefault(row, [0, 0])
        got[0] += ns
        got[1] += 1
    return sorted(((r, ns / 1e6, n) for r, (ns, n) in rows.items()), key=lambda x: -x[1]), \
        inside / 1e6


def print_tables(path: str, prefixes: tuple[str, ...] = (), top: int = 28, out=print,
                 longest: bool = False) -> None:
    for base, m in last_launches(path, longest).items():
        if prefixes and not base.startswith(prefixes) or m["ns"] < 1e6:
            continue   # not asked for, or a program of under a millisecond
        rows, inside = table(m["ops"])
        total = sum(ms for _r, ms, _n in rows)
        out(f"-- {m['name']}: {m['ns'] / 1e6:.3f} ms a launch, {total:.3f} ms in "
            f"{sum(n for _r, _ms, n in rows)} operations ({inside:.3f} ms of containers apart)")
        by_scope: dict[str, float] = {}
        for r, ms, _n in rows:
            scope = r.split(":")[0] if ":" in r else "outside every scope"
            by_scope[scope] = by_scope.get(scope, 0.0) + ms
        out("   " + ", ".join(f"{s} {ms:.3f}" for s, ms in sorted(by_scope.items(),
                                                                 key=lambda kv: -kv[1])))
        for r, ms, n in rows[:top]:
            out(f"   {ms:8.3f} ms  {n:4d}  {r}")


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--longest"]
    print_tables(args[0], tuple(args[1:]), longest="--longest" in sys.argv)
