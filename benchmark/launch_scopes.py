"""The two generation programs BY SCOPE, from one pass over a traced run's file
(ISSUE 66): what `scripts/op_table.py` printed from a kept trace by hand, in
every traced run of every generating cell, and the table the `gen_*_unscoped_pct`,
`gen_proj_*`, `gen_ffn_*`, `gen_glue_*` and `gen_head_step_ms` readers take their
numbers from.

The program names its parts with `jax.named_scope` (`tpuserve/models/paged_lm.py`
has the vocabulary), and the trace's own copy of each program gives every
instruction the `op_name` it was traced under (`ssm_window.scope_map`):
`jit(step)/moe_layer/cond/branch_1_fun/moe_experts/jit(gmm)/pallas_call`. An
operation's event goes under the CHAIN of the program's scopes in that path:
`moe_layer>moe_experts`, `ssm_update>norm`, or the empty chain.

A scope is recognised by the path's SHAPE, not by a list, so one that a later PR
adds to the program shows up here with no edit: a component that is a plain
identifier, is none of the tracer's own words (`TRACER`: `while`, `body`, `cond`,
`branch_N_fun`, `pallas_call`, ...; `jit(...)`, `vmap(...)` and an einsum's
`td,dhk->thk` are no identifiers) and is not the last component, which is the
primitive. Some instructions' paths END in their scope (`jit(step)/moe_experts`:
no primitive follows), so within one program a last component counts where the
same word stands as a scope elsewhere in that program (`scopes_of`). A scope
entered from inside itself (`proj/proj`) is that scope once.

Per chain, a launch's SELF time is the union of the intervals of the operations
whose chain is exactly that one (`choosing-metrics` section 4: a span's duration
less what its children cover), the median over the launches that lie whole
inside the traced window, as `trace_reduce.py` takes a launch. Containers
(`while`, `conditional`, `call`) are left out, as `op_table.py` leaves them out:
what runs inside them is on the line too. A scope's inclusive time is the sum
over the chains that pass through it (`inclusive_ms`).

The file is parsed ONCE a run for both programs (`for_run`; `ssm_window._read`
parses it again for every scope it is asked for) and the table kept on `run`.
One note a program goes to `run["notes"]`: every chain with its self ms, its
operations a launch and its share, most time first, the unnamed remainder last
with its five costliest operations and its kinds of instruction (`copy-done`,
`copy`, `fusion`: what the compiler added has a kind and no `op_name`).

THE SHARE WITH NO NAME is read only for a program that names its kinds of work,
which is known by the scope every such launch has (`MARKER`): a program from
before ISSUE 66, or one the compile cache served from such a tree's entry (JAX
leaves `op_name` out of the cache's key), reads None, not a share under names
it does not have. Everything returns None, and never raises, where the trace
has no such program or no program text.
"""

from __future__ import annotations

import bisect
import re
import statistics
import time

from benchmark.ssm_window import scope_map
from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, op_name, union_s

IDENTIFIER = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
# Plain identifiers that the tracer puts on the path itself.
TRACER = re.compile(r"^(while|body|cond|branch_\d+_fun|pallas_call|scan|closed_call|core_call|"
                    r"checkpoint|remat\d*|rematted_computation|custom_jvp_call|custom_vjp_call|"
                    r"custom_vjp_call_jaxpr|custom_lin|jit|pjit|xla_call|shard_map|run_scoped)$")
CONTAINERS = ("while", "conditional", "call")
MARKER = "plan"   # the scope every launch of a program that names its kinds of work has
UNNAMED_LISTED = 5


def is_scope(component: str) -> bool:
    return bool(IDENTIFIER.match(component)) and not TRACER.match(component)


def scopes_of(op_names) -> frozenset:
    """The scopes of one program: every component but the last of any of its
    `op_name`s that has a scope's shape."""
    found = set()
    for name in op_names:
        found.update(c for c in name.split("/")[:-1] if is_scope(c))
    return frozenset(found)


def chain(traced_as: str, known: frozenset = frozenset()) -> tuple[str, ...]:
    """The scopes on the path `traced_as`, outermost first. `known`: the
    program's scopes, by which a path's last component is told from a
    primitive."""
    parts = traced_as.split("/")
    out: list[str] = []
    for i, c in enumerate(parts):
        if (c in known or is_scope(c) and i < len(parts) - 1) and (not out or out[-1] != c):
            out.append(c)
    return tuple(out)


def kind(instruction: str) -> str:
    """`fusion.12` -> `fusion`; `gather_fusion.3` -> `gather_fusion`."""
    return re.sub(r"[.\d]+$", "", instruction) or instruction


def for_run(run: dict) -> dict:
    """{program's base name: its table} for the run's trace, parsed once and
    kept on `run`; empty where there is nothing to read."""
    if "_launch_scopes" not in run:
        run["_launch_scopes"] = {}
        path = run.get("xplane")
        if path and run.get("trace"):
            t0 = time.monotonic()
            try:
                run["_launch_scopes"] = tables(path)
            except Exception:  # a file that is no trace: nothing to read
                pass
            for base, t in run["_launch_scopes"].items():
                run.setdefault("notes", []).append(note(base, t))
            run.setdefault("notes", []).append(
                f"launch_scopes: the trace read once for {len(run['_launch_scopes'])} program(s) "
                f"in {time.monotonic() - t0:.1f} s")
    return run["_launch_scopes"]


def tables(path: str, only: tuple[str, ...] = ("jit_step", "jit_prefill_fn")) -> dict:
    """`by_chain` of the trace file at `path`: its own copy of the programs'
    text and its device planes, each parsed once."""
    from jax.profiler import ProfileData

    programs = scope_map(path)
    if not any(names and (not only or base in only) for base, names in programs.items()):
        return {}
    return by_chain(programs, ProfileData.from_file(path), only)


def by_chain(programs: dict, profile, only: tuple[str, ...] = ()) -> dict:
    """{program's base name: table} of the programs of `only` (every program
    where empty) that `programs` ({base: {instruction: op_name}}) has the text of
    and `profile` (a `ProfileData`) at least one launch of. A table: `chains`
    {chain: {"ms", "ops"}} (self time and operations of a median whole launch),
    `total_ms` their sum, `scopes`, `launches`, `whole_launches`, `unnamed`
    [(operation, ms a launch)] and `unnamed_kinds` [(kind, ms a launch)], both
    summed over the whole launches and most time first."""
    wanted = {base: names for base, names in programs.items()
              if names and (not only or base in only)}
    known = {base: scopes_of(names.values()) for base, names in wanted.items()}
    chains = {base: {inst: chain(traced, known[base]) for inst, traced in names.items()}
              for base, names in wanted.items()}
    # by program: {launch: {chain: [(start, end)]}}, whether a launch is whole, the unnamed by name
    spans: dict[str, dict] = {base: {} for base in wanted}
    whole: dict[str, dict] = {base: {} for base in wanted}
    unnamed: dict[str, dict] = {base: {} for base in wanted}
    for p, plane in enumerate(profile.planes):
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        mods = sorted((int(e.start_ns), int(e.duration_ns), e.name.split("(")[0])
                      for e in lines[MODULES_LINE].events)
        starts = [s for s, _d, _b in mods]
        for i, (_s, _d, base) in enumerate(mods):
            if base in wanted:
                spans[base][p, i] = {}
                # the tracer's edges can cut only a line's first and last launch
                whole[base][p, i] = 0 < i < len(mods) - 1
        for ev in lines[OPS_LINE].events:
            s = int(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][0] + mods[i][1] or mods[i][2] not in wanted:
                continue
            base, inst = mods[i][2], op_name(ev.name)
            if kind(inst) in CONTAINERS:
                continue
            c = chains[base].get(inst, ())
            e = s + int(ev.duration_ns)
            spans[base][p, i].setdefault(c, []).append((s, e))
            if not c and whole[base][p, i]:
                unnamed[base][inst] = unnamed[base].get(inst, 0) + e - s
    out = {}
    for base, launches in spans.items():
        if not launches:
            continue
        kept = [k for k in launches if whole[base][k]] or list(launches)
        n_whole = sum(whole[base].values())
        rows = {}
        for c in {c for k in kept for c in launches[k]}:
            per = [launches[k].get(c, []) for k in kept]
            rows[c] = {"ms": statistics.median(union_s(iv) for iv in per) * 1e3,
                       "ops": statistics.median(len(iv) for iv in per)}
        traced = wanted[base]
        top = sorted(unnamed[base].items(), key=lambda kv: -kv[1])[:UNNAMED_LISTED]
        kinds: dict[str, int] = {}
        for inst, ns in unnamed[base].items():
            kinds[kind(inst)] = kinds.get(kind(inst), 0) + ns
        per_launch = 1e6 * max(1, n_whole)
        out[base] = {
            "chains": rows, "total_ms": sum(r["ms"] for r in rows.values()),
            "scopes": known[base], "launches": len(launches), "whole_launches": n_whole,
            "unnamed": [(f"{inst} ({traced.get(inst, '').rsplit('/', 1)[-1]})", ns / per_launch)
                        for inst, ns in top],
            "unnamed_kinds": [(k, ns / per_launch) for k, ns in
                              sorted(kinds.items(), key=lambda kv: -kv[1])[:UNNAMED_LISTED]]}
    return out


def note(base: str, t: dict) -> str:
    """One program's table on one line, most time first, the unnamed last."""
    def row(c):
        r = t["chains"][c]
        return (f"{'>'.join(c) or 'unnamed'} {r['ms']:.3f} ms {r['ops']:g} "
                f"{100.0 * r['ms'] / t['total_ms']:.1f}%")

    named = sorted((c for c in t["chains"] if c), key=lambda c: -t["chains"][c]["ms"])
    line = (f"launch_scopes {base}: {t['whole_launches']} whole launches of {t['launches']} in the "
            f"traced window, {t['total_ms']:.3f} ms in "
            f"{sum(r['ops'] for r in t['chains'].values()):g} operations a launch (containers "
            f"apart), by chain of scopes (self ms, operations, share): "
            + "; ".join(row(c) for c in named))
    if () in t["chains"]:
        line += "; " + row(()) + ", most in " + ", ".join(
            f"{name} {ms:.3f}" for name, ms in t["unnamed"]) + "; by kind " + ", ".join(
            f"{name} {ms:.3f}" for name, ms in t["unnamed_kinds"])
    return line


def _named(run: dict, base: str) -> dict | None:
    """The program's table where it names its kinds of work (`MARKER`)."""
    t = for_run(run).get(base)
    return t if t and MARKER in t["scopes"] and t["total_ms"] > 0 else None


def unscoped_pct(run: dict, base: str) -> float | None:
    """Self time of the empty chain over the sum of all chains, in percent."""
    t = _named(run, base)
    if t is None:
        return None
    return 100.0 * t["chains"].get((), {"ms": 0.0})["ms"] / t["total_ms"]


def ends_in_ms(run: dict, base: str, names: tuple[str, ...]) -> float | None:
    """Self ms a launch of every chain whose INNERMOST scope is one of
    `names`; None where the program has no such chain."""
    t = _named(run, base)
    if t is None:
        return None
    found = [r["ms"] for c, r in t["chains"].items() if c and c[-1] in names]
    return sum(found) if found else None


def inclusive_ms(t: dict, scope: str) -> float:
    """A scope's inclusive ms a launch: the chains that pass through it."""
    return sum(r["ms"] for c, r in t["chains"].items() if scope in c)
