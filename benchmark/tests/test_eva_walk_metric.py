"""`eva_walk_kernel_pct` (ISSUE 56), the one thing that PR added to the
benchmark: its entry in `BENCHMARK.json` (the EvaByte cell alone, a program
counter of the kernels' layer that should move `items_per_s`), and its reader on
a run that has nothing, on a program of another family (no such counter), on the
parent of the PR (the counter there under `path=walk`, jax's kernel: 0) and on a
window's counters. A file of its own because a PR that claims a gain may add to
the benchmark's files and edit none (`test_eva_cell.py` has the cell's other
readers)."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "evabyte-6.5b-l8.bytedoc-closed-24"
NAME = "eva_walk_kernel_pct"


def test_the_metric_is_listed_for_the_evabyte_cell_alone():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "items_per_s"
    assert (m["source"], m["layer"], m["unit"], m["better"]) == \
        ("program_counter", "kernels", "%", "higher")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    assert NAME in {x["name"] for x in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", CELL)}


def test_the_reader_returns_nothing_without_the_counter_or_steps_and_the_kernels_share_with_them():
    read = spec.load_module("layer_metrics", NAME).read
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None}
    assert read({}) is None and read(dict(run)) is None
    # another family's program: steps ran, this counter is not there
    run["metrics_delta"] = {'gen_iterations_total{model="model"}': 1164.0}
    assert read(dict(run)) is None
    # the counter there and no step in the window
    steps = 'eva_decode_steps_total{model="model",phase="decode",path="%s"}'
    run["metrics_delta"].update({steps % "head_walk": 0.0, steps % "gather": 0.0})
    assert read(dict(run)) is None
    # the parent of the PR that gave the label: every step under jax's kernel
    run["metrics_delta"] = {steps % "walk": 214000.0, steps % "gather": 0.0}
    assert read(dict(run)) == 0.0
    run["metrics_delta"] = {steps % "head_walk": 214000.0, steps % "gather": 0.0}
    assert read(dict(run)) == 100.0
    run["metrics_delta"][steps % "gather"] = 642000.0
    assert read(dict(run)) == 25.0
    # another model's steps on the same server are not this cell's
    run["metrics_delta"]['eva_decode_steps_total{model="other",phase="decode",path="gather"}'] = 9e9
    assert read(dict(run)) == 25.0
