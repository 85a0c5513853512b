"""`blk_select_kernel_pct` (ISSUE 69), the one thing that PR added to the
benchmark: its entry in `BENCHMARK.json` (the `hybrid_blk` cell alone, a program
counter of the kernels' layer that should move `items_per_s`), and its reader on
a run that has nothing, on a program without the counter (the parent) and on a
window's counters. A file of its own because a PR that claims a gain may add to
the benchmark's files and edit none (`test_ssm_scan_metric.py` is the Mamba-2
scan's)."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
CELL = "minicpm-sala-l4.longsel-closed-16"
NAME = "blk_select_kernel_pct"


def test_the_metric_is_listed_for_the_block_selecting_cell_alone():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    assert m["workloads"] == [CELL] and m["moves"] == "items_per_s"
    assert (m["source"], m["layer"], m["unit"], m["better"]) == \
        ("program_counter", "kernels", "%", "higher")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    config = spec.load_config(BENCH, spec.find(BENCH["workloads"], CELL, "cell")["config"])
    assert config["family"] == "hybrid_blk"
    assert NAME in {x["name"] for x in spec.cell_metrics(BENCH, "per_layer", CELL)}
    assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", CELL)}
    with open(os.path.join(spec.REPO, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_reader_returns_nothing_where_the_program_has_no_counter_and_the_share_where_it_has():
    read = spec.load_module("layer_metrics", NAME).read
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None}
    assert read({}) is None and read(dict(run)) is None
    # the parent of the PR that added the counter: the picks' older counters move, not this one
    run["metrics_delta"] = {'blk_queries_total{model="model",phase="prefill",path="picked"}': 9e5,
                            'blk_blocks_scored_total{model="model",phase="prefill"}': 4e8}
    assert read(dict(run)) is None
    # a step's lanes are the plain form's by design: the decode phase does not enter
    run["metrics_delta"].update({
        'blk_selects_total{model="model",phase="prefill",path="kernel"}': 430.0,
        'blk_selects_total{model="model",phase="prefill",path="xla"}': 0.0,
        'blk_selects_total{model="model",phase="decode",path="xla"}': 12000.0})
    assert read(dict(run)) == 100.0
    run["metrics_delta"]['blk_selects_total{model="model",phase="prefill",path="xla"}'] = 430.0
    assert read(dict(run)) == 50.0
