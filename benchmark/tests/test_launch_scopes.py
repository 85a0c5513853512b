"""`benchmark/launch_scopes.py` (ISSUE 66): the chain rule on hand-written
`op_name`s, self against inclusive time on a hand-written trace whose answers are
worked out in the comment above `EVENTS`, the ten readers' entries in `BENCHMARK.json`,
None on a recorded trace that holds no such program, and ONE parse of the file a
run however many readers ask. A file of its own because a PR that is not a
`benchmark` PR may add to the benchmark's files and edit none."""

import os

import pytest
from jax.profiler import ProfileData

from benchmark import gen_window, launch_scopes, spec, trace_reduce

BENCH = spec.load_benchmark()
GEN = ("gen_step_unscoped_pct", "gen_prefill_unscoped_pct", "gen_proj_step_ms",
       "gen_proj_prefill_ms", "gen_ffn_step_ms", "gen_ffn_prefill_ms", "gen_glue_step_ms",
       "gen_glue_prefill_ms", "gen_head_step_ms")

# -- the chain rule -------------------------------------------------------------------------------

STEP = {
    "fusion.1": "jit(step)/mla_decode/proj/td,dhk->thk/dot_general",
    "lane_walk.2": "jit(step)/mla_decode/jit(lane_walk)/pallas_call",
    "gmm.3": "jit(step)/moe_layer/cond/branch_1_fun/moe_experts/jit(gmm)/pallas_call",
    "fusion.4": "jit(step)/moe_layer/moe_experts",          # a path that ENDS in its scope
    "fusion.5": "jit(step)/head/norm/reduce_sum",
    "fusion.6": "jit(step)/a_scope_of_2031/mul",            # one this file has never heard of
    "fusion.7": "jit(step)/plan/jit(_where)/select_n",
    "fusion.8": "jit(step)/mla_decode/proj/proj/norm/rsqrt",  # entered from inside itself
    "fusion.9": "jit(step)/sample/cond/branch_0_fun/vmap()/PagedLM._sample.<locals>.one",
    "copy.10": "",
    "fusion.11": "jit(step)/add",
    "while.12": "jit(step)/mla_decode/while",
    "fusion.13": "jit(step)/mla_decode/while/body/closed_call/mul",
}


def test_a_scope_is_told_by_the_shape_of_the_path():
    known = launch_scopes.scopes_of(STEP.values())
    assert known == {"mla_decode", "proj", "moe_layer", "moe_experts", "head", "norm",
                     "a_scope_of_2031", "plan", "sample"}
    chains = {inst: launch_scopes.chain(op, known) for inst, op in STEP.items()}
    assert chains == {
        "fusion.1": ("mla_decode", "proj"), "lane_walk.2": ("mla_decode",),
        "gmm.3": ("moe_layer", "moe_experts"), "fusion.4": ("moe_layer", "moe_experts"),
        "fusion.5": ("head", "norm"), "fusion.6": ("a_scope_of_2031",), "fusion.7": ("plan",),
        "fusion.8": ("mla_decode", "proj", "norm"), "fusion.9": ("sample",), "copy.10": (),
        "fusion.11": (), "while.12": ("mla_decode",), "fusion.13": ("mla_decode",)}
    # without the program's scopes a last component is the primitive
    assert launch_scopes.chain("jit(step)/moe_layer/moe_experts") == ("moe_layer",)
    assert launch_scopes.chain("jit(step)/moe_layer/moe_experts/mul") == \
        ("moe_layer", "moe_experts")
    for word in ("while", "body", "cond", "branch_12_fun", "pallas_call", "closed_call", "scan",
                 "jit(step)", "vmap()", "td,dhk->thk", "PagedLM._sample.<locals>.one", ""):
        assert not launch_scopes.is_scope(word), word
    assert launch_scopes.kind("gather_fusion.3") == "gather_fusion"
    assert launch_scopes.kind("while.12") in launch_scopes.CONTAINERS


# -- self and inclusive time, by hand ---------------------------------------------------------------

# One chip, times in ms. Launches: step [0, 1) (the line's first: cut), step [2, 7), prefill
# [8, 10), step [12, 17), step [18, 19) (the last: cut). The two whole steps, by operation:
#   step [2, 7):   while.12 [2, 7) (a container: left out)   fusion.1 [2, 3)   lane_walk.2 [3, 5)
#                  fusion.13 [4, 4.5) (inside the walk's interval, the same chain: the union is 2)
#                  gmm.3 [5, 5.6)   fusion.4 [5.4, 5.8) (overlaps gmm.3: their union is 0.8)
#                  fusion.5 [5.8, 6)   copy.10 [6, 6.5)   fusion.11 [6.5, 6.6)   fusion.7 [6.6, 7)
#   step [12, 17): the same, each 10 ms later, but copy.10 [16, 16.9) and no fusion.11
# By hand, the median of two launches being their mean:
#   mla_decode>proj 1.0   mla_decode 2.0   moe_layer>moe_experts 0.8   head>norm 0.2   plan 0.4 / 0.1
#   -> 0.25   unnamed 0.6 / 0.9 -> 0.75 (3 operations in the two launches: 1.5 a launch; copy.10
#   0.7 ms a launch, fusion.11 0.05)   total 5.0 ms   mla_decode inclusive 3.0   unnamed 15%
# The prefill launch [8, 10) is the line's only launch of its program and whole: fusion.1 [8, 9)
# under `jit(prefill_fn)/attn_prefill/proj/dot_general`, fusion.2 [9, 10) under
# `jit(prefill_fn)/plan/iota`.
PREFILL = {"fusion.1": "jit(prefill_fn)/attn_prefill/proj/dot_general",
           "fusion.2": "jit(prefill_fn)/plan/iota"}
EVENTS = {  # line: [(name, start ms, ms)]
    "XLA Modules": [("jit_step(1)", 0, 1), ("jit_step(1)", 2, 5), ("jit_prefill_fn(2)", 8, 2),
                    ("jit_step(1)", 12, 5), ("jit_step(1)", 18, 1)],
    "XLA Ops": [("fusion.1", 0.2, 0.5),
                ("while.12", 2, 5), ("fusion.1", 2, 1), ("lane_walk.2", 3, 2), ("fusion.13", 4, 0.5),
                ("gmm.3", 5, 0.6), ("fusion.4", 5.4, 0.4), ("fusion.5", 5.8, 0.2),
                ("copy.10", 6, 0.5), ("fusion.11", 6.5, 0.1), ("fusion.7", 6.6, 0.4),
                ("fusion.1", 8, 1), ("fusion.2", 9, 1),
                ("while.12", 12, 5), ("fusion.1", 12, 1), ("lane_walk.2", 13, 2),
                ("fusion.13", 14, 0.5), ("gmm.3", 15, 0.6), ("fusion.4", 15.4, 0.4),
                ("fusion.5", 15.8, 0.2), ("copy.10", 16, 0.9), ("fusion.7", 16.9, 0.1),
                ("fusion.1", 18.1, 0.5)],
}


def xspace_text() -> str:
    names = sorted({n for evs in EVENTS.values() for n, _s, _d in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    lines = []
    for k, (line, evs) in enumerate(EVENTS.items()):
        body = "\n".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {round(s * 1e9)} "
                         f"duration_ps: {round(d * 1e9)} }}" for n, s, d in evs)
        lines.append(f'  lines {{\n    id: {k + 1}\n    name: "{line}"\n{body}\n  }}')
    meta = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "%{n} = f32[8]{{0}} fusion()" }} }}'
        if "(" not in n else f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in ids.items())
    return ('planes {\n  id: 1\n  name: "/device:TPU:0"\n' + "\n".join(lines) + "\n" + meta
            + '\n}\nplanes {\n  id: 2\n  name: "/host:CPU"\n}\n')


@pytest.fixture(scope="module")
def by_hand():
    profile = ProfileData.from_text_proto(xspace_text())
    return launch_scopes.by_chain({"jit_step": STEP, "jit_prefill_fn": PREFILL,
                                   "jit_other": {"x": "jit(other)/mul"}}, profile)


def test_self_time_is_the_union_of_a_chains_own_operations(by_hand):
    t = by_hand["jit_step"]
    assert (t["launches"], t["whole_launches"]) == (4, 2)
    ms = {c: r["ms"] for c, r in t["chains"].items()}
    assert ms == {("mla_decode", "proj"): pytest.approx(1.0), ("mla_decode",): pytest.approx(2.0),
                  ("moe_layer", "moe_experts"): pytest.approx(0.8),
                  ("head", "norm"): pytest.approx(0.2), ("plan",): pytest.approx(0.25),
                  (): pytest.approx(0.75)}
    assert t["total_ms"] == pytest.approx(5.0)
    assert t["chains"][("mla_decode",)]["ops"] == 2 and t["chains"][()]["ops"] == 1.5
    # inclusive: the chains that pass through the scope
    assert launch_scopes.inclusive_ms(t, "mla_decode") == pytest.approx(3.0)
    assert launch_scopes.inclusive_ms(t, "moe_experts") == pytest.approx(0.8)
    assert [(n, pytest.approx(v)) for n, v in t["unnamed"]] == [("copy.10 ()", 0.7),
                                                               ("fusion.11 (add)", 0.05)]
    assert "jit_other" not in by_hand          # no launch of it on the line
    line = launch_scopes.note("jit_step", t)
    assert line.startswith("launch_scopes jit_step: 2 whole launches of 4")
    assert "mla_decode 2.000 ms 2 40.0%; mla_decode>proj 1.000 ms 1 20.0%" in line
    assert line.endswith("unnamed 0.750 ms 1.5 15.0%, most in copy.10 () 0.700, "
                         "fusion.11 (add) 0.050; by kind copy 0.700, fusion 0.050")
    assert [(k, pytest.approx(v)) for k, v in t["unnamed_kinds"]] == [("copy", 0.7),
                                                                      ("fusion", 0.05)]


def test_the_readers_take_their_numbers_from_the_table(by_hand):
    run = {"_launch_scopes": by_hand}
    read = {name: spec.load_module("layer_metrics", name).read(run) for name in GEN}
    assert read == {
        "gen_step_unscoped_pct": pytest.approx(15.0), "gen_prefill_unscoped_pct": pytest.approx(0.0),
        "gen_proj_step_ms": pytest.approx(1.0), "gen_proj_prefill_ms": pytest.approx(1.0),
        # `head>norm` is glue, with `plan`; no chain ends in `head`, `ffn_dense` or `moe_shared`
        "gen_glue_step_ms": pytest.approx(0.45), "gen_glue_prefill_ms": pytest.approx(1.0),
        "gen_ffn_step_ms": None, "gen_ffn_prefill_ms": None, "gen_head_step_ms": None}
    # a program that does not name its kinds of work (it has no `plan`) reads nothing: the
    # parent's, or one the compile cache served from the parent's entry
    old = {k: v for k, v in STEP.items() if "/plan/" not in v}
    profile = ProfileData.from_text_proto(xspace_text())
    run = {"_launch_scopes": launch_scopes.by_chain({"jit_step": old}, profile)}
    assert run["_launch_scopes"]["jit_step"]["chains"][("mla_decode", "proj")]["ms"] == \
        pytest.approx(1.0)
    for name in GEN:
        assert spec.load_module("layer_metrics", name).read(run) is None, name


# -- the entries, and runs with nothing to read -------------------------------------------------------

def test_the_ten_metrics_are_listed_as_the_issue_says():
    generating = spec.find(BENCH["per_layer"], "gen_sample_ms", "metric")["workloads"]
    assert len(generating) == 12
    assert [m["name"] for m in BENCH["per_layer"][-10:]] == [*GEN, "host_stall_ms_per_s"]
    # Granite-micro's launch is the one program of the twenty-four with no Pallas kernel in
    # it: its compile-cache key is the parent's, and a run served from the parent's entry
    # has the parent's names and nothing for the launch's four readers to read.
    kernel_less_launch = "granite-4.0-h-micro.shortchat-closed"
    for name in GEN:
        m = spec.find(BENCH["per_layer"], name, "metric")
        cells = [c for c in generating if "prefill" not in name or c != kernel_less_launch]
        assert m == {"name": name, "unit": "%" if name.endswith("_pct") else "ms",
                     "better": "lower", "source": "device_trace", "layer": "models",
                     "moves": "items_per_s", "workloads": cells}
    gc = spec.find(BENCH["per_layer"], "host_gc_pause_ms_per_s", "metric")
    assert spec.find(BENCH["per_layer"], "host_stall_ms_per_s", "metric") == \
        {**gc, "name": "host_stall_ms_per_s"}
    for m in BENCH["per_layer"][-10:]:
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{m['name']}.py"))


def test_the_stall_reader_reads_the_counters_difference():
    read = spec.load_module("layer_metrics", "host_stall_ms_per_s").read

    class Load:
        seconds = 45.0

    assert read({}) is None and read({"metrics_delta": {"x_total": 1.0}, "load": Load}) is None
    assert read({"metrics_delta": {"host_stall_seconds_total": 0.0}, "load": Load}) == 0.0
    assert read({"metrics_delta": {"host_stall_seconds_total": 2.25}, "load": Load}) == \
        pytest.approx(50.0)


def test_nothing_is_read_where_there_is_no_such_program(tmp_path):
    path = os.path.join(spec.HERE, "fixtures", "recorded_v5e.xplane.pb")
    run = {"xplane": path, "trace": trace_reduce.reduce_file(path, 0.6)}
    assert run["trace"] is not None
    for name in GEN:
        assert spec.load_module("layer_metrics", name).read(run) is None, name
    assert launch_scopes.for_run(run) == {}
    assert launch_scopes.tables(path, only=()) == {}   # a trace from before it kept the text
    bad = tmp_path / "no.xplane.pb"
    bad.write_bytes(b"not a trace")
    for run in ({}, {"trace": None, "xplane": None},
                {"trace": {"window_s": 1.0}, "xplane": str(bad)}):
        for name in GEN:
            assert spec.load_module("layer_metrics", name).read(run) is None, name


def test_the_file_is_parsed_once_a_run_however_many_readers_ask(by_hand, monkeypatch):
    calls = []

    def tables(path):
        calls.append(path)
        return by_hand

    monkeypatch.setattr(launch_scopes, "tables", tables)
    run = {"xplane": "some.xplane.pb", "trace": {"window_s": 1.0}, "notes": []}
    for _ in range(2):
        for name in GEN:
            spec.load_module("layer_metrics", name).read(run)
    assert calls == ["some.xplane.pb"]
    assert launch_scopes.unscoped_pct(run, gen_window.STEP_MODULE) == pytest.approx(15.0)
    # one note a program, and the seconds the one parse took
    assert [n.split(":")[0] for n in run["notes"]] == [
        "launch_scopes jit_step", "launch_scopes jit_prefill_fn", "launch_scopes"]
