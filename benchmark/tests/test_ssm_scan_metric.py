"""`ssm_scan_kernel_pct` (ISSUE 67), the one thing that PR added to the
benchmark: its entry in `BENCHMARK.json` (the three Mamba-2 cells, a program
counter of the kernels' layer that should move `items_per_s`), and its reader on
a run that has nothing, on a program without the counter (the parent) and on a
window's counters. A file of its own because a PR that claims a gain may add to
the benchmark's files and edit none (`test_delta_scan_metric.py` is the delta
rule's)."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
CELLS = ["nemotron-3-super-q4-l11.chat-closed-256", "granite-4.0-h-micro.shortchat-closed",
         "granite-4.0-h-small-e2-l10.support-closed-96"]
NAME = "ssm_scan_kernel_pct"


def test_the_metric_is_listed_for_the_three_mamba2_cells_alone():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    assert m["workloads"] == CELLS and m["moves"] == "items_per_s"
    assert (m["source"], m["layer"], m["unit"], m["better"]) == \
        ("program_counter", "kernels", "%", "higher")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert BENCH["per_layer"][-1] is m     # appended: nothing the benchmark had moved
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    families = {spec.load_config(BENCH, spec.find(BENCH["workloads"], c, "cell")["config"])
                ["family"] for c in CELLS}
    assert families == {"hybrid", "hybrid_ffn", "hybrid_ffn_moe"}
    for cell in CELLS:
        assert NAME in {x["name"] for x in spec.cell_metrics(BENCH, "per_layer", cell)}
        assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", cell)}


def test_the_reader_returns_nothing_where_the_program_has_no_counter_and_the_share_where_it_has():
    read = spec.load_module("layer_metrics", NAME).read
    run = {"metrics_delta": {}, "model_name": "model", "trace": None, "xplane": None}
    assert read({}) is None and read(dict(run)) is None
    # the parent of the PR that added the counter: the scans' older counters move, not this one
    run["metrics_delta"] = {'ssm_tokens_total{model="model",phase="prefill"}': 53000.0,
                            'ssm_pieces_total{model="model",start="zero"}': 300.0}
    assert read(dict(run)) is None
    run["metrics_delta"].update({
        'ssm_scans_total{model="model",phase="prefill",path="kernel"}': 6624.0,
        'ssm_scans_total{model="model",phase="prefill",path="xla"}': 0.0})
    assert read(dict(run)) == 100.0
    run["metrics_delta"]['ssm_scans_total{model="model",phase="prefill",path="xla"}'] = 2208.0
    assert read(dict(run)) == 75.0
