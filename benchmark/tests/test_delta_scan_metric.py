"""`delta_scan_kernel_pct` (ISSUE 54), the one thing that PR added to the
benchmark: its entry in `BENCHMARK.json` (the Solar cell alone, a program
counter of the models' layer that should move `items_per_s`), and its reader on
a run that has nothing, on a program without the counter (the parent) and on a
window's counters. A file of its own because a PR that claims a gain may add to
the benchmark's files and edit none (`test_hybrid_delta_cell.py` has the cell's
other readers)."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "solar-open2-250b-e8-l4.docchat-closed-192"
NAME = "delta_scan_kernel_pct"


def test_the_metric_is_listed_for_the_solar_cell_alone():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "items_per_s"
    assert (m["source"], m["layer"], m["unit"], m["better"]) == \
        ("program_counter", "models", "%", "higher")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    assert NAME in {x["name"] for x in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", CELL)}


def test_the_reader_returns_nothing_where_the_program_has_no_counter_and_the_share_where_it_has():
    read = spec.load_module("layer_metrics", NAME).read
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None}
    assert read({}) is None and read(dict(run)) is None
    # the parent of the PR that added the counter: the step's counter moves, this one is not there
    run["metrics_delta"] = {'delta_steps_total{model="model",phase="decode",path="kernel"}': 5700.0}
    assert read(dict(run)) is None
    run["metrics_delta"].update({
        'delta_scans_total{model="model",phase="prefill",path="kernel"}': 1875.0,
        'delta_scans_total{model="model",phase="prefill",path="xla"}': 0.0})
    assert read(dict(run)) == 100.0
    run["metrics_delta"]['delta_scans_total{model="model",phase="prefill",path="xla"}'] = 625.0
    assert read(dict(run)) == 75.0
