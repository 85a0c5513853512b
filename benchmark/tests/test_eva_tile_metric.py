"""`eva_tile_kernel_pct` (ISSUE 58), the one thing that PR added to the
benchmark: its entry in `BENCHMARK.json` (the EvaByte cell alone, a program
counter of the kernels' layer that should move `items_per_s`), and its reader on
a run that has nothing, on a program of another family (no counter of this
family's), on the parent of the PR (this family's launches ran and the series is
not there: 0) and on a window's counters. A file of its own because a PR that
claims a gain may add to the benchmark's files and edit none (`test_eva_cell.py`
and `test_eva_walk_metric.py` have the cell's other readers)."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "evabyte-6.5b-l8.bytedoc-closed-24"
NAME = "eva_tile_kernel_pct"


def test_the_metric_is_listed_for_the_evabyte_cell_alone():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "items_per_s"
    assert (m["source"], m["layer"], m["unit"], m["better"]) == \
        ("program_counter", "kernels", "%", "higher")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert BENCH["per_layer"][-1] is m                     # appended: nothing before it moved
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    assert NAME in {x["name"] for x in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", CELL)}


def test_the_reader_is_none_without_this_familys_launches_zero_before_the_kernel_and_its_share():
    read = spec.load_module("layer_metrics", NAME).read
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None}
    assert read({}) is None and read(dict(run)) is None
    # another family's program: launches ran, this family's counters are not there
    run["metrics_delta"] = {'gen_prefill_chunks_total{model="model"}': 721.0}
    assert read(dict(run)) is None
    # this family's program and no launch in the window
    rows = 'eva_rows_attended_total{model="model",phase="prefill",kind="%s"}'
    tiles = 'eva_prefill_tiles_total{model="model",phase="prefill",path="%s"}'
    run["metrics_delta"].update({rows % "exact": 0.0, rows % "summary": 0.0,
                                 tiles % "tile_kernel": 0.0, tiles % "xla": 0.0})
    assert read(dict(run)) is None
    # the parent of the PR that gave the counter: launches attended rows, no tile is counted
    run["metrics_delta"] = {rows % "exact": 6.1e8, rows % "summary": 2.2e8}
    assert read(dict(run)) == 0.0
    run["metrics_delta"].update({tiles % "tile_kernel": 44000.0, tiles % "xla": 0.0})
    assert read(dict(run)) == 100.0
    run["metrics_delta"][tiles % "xla"] = 132000.0
    assert read(dict(run)) == 25.0
    # a step's rows and another model's tiles on the same server are not this cell's launches
    run["metrics_delta"]['eva_prefill_tiles_total{model="other",phase="prefill",path="xla"}'] = 9e9
    assert read(dict(run)) == 25.0
    decode = {'eva_rows_attended_total{model="model",phase="decode",kind="exact"}': 5e7}
    assert read({**run, "metrics_delta": decode}) is None
