"""`gen_sample_ms` (ISSUE 60), the one thing that PR added to the benchmark: its
entry in `BENCHMARK.json` (the ten generating cells, a device time of the models'
layer that should move `items_per_s`; the two BERT cells have no sampler), and
its reader on a run that has nothing to read: no trace, a file that is no trace,
which is also what the parent of that PR gives it (no `sample` scope in its
programs). A file of its own because a PR that claims a gain may add to the
benchmark's files and edit none."""

import os

from benchmark import spec

BENCH = spec.load_benchmark()
NAME = "gen_sample_ms"


def test_the_metric_is_listed_for_every_generating_cell():
    m = spec.find(BENCH["per_layer"], NAME, "metric")
    generating = [w["name"] for w in BENCH["workloads"]
                  if "gen_step_ms" in {x["name"] for x in
                                       spec.cell_metrics(BENCH, "per_layer", w["name"])}]
    assert m["workloads"] == generating and len(generating) == 10
    assert (m["source"], m["layer"], m["unit"], m["better"], m["moves"]) == \
        ("device_trace", "models", "ms", "lower", "items_per_s")
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{NAME}.py"))
    for cell in generating:
        assert "items_per_s" in {x["name"] for x in spec.cell_metrics(BENCH, "end_to_end", cell)}


def test_the_reader_is_none_where_there_is_nothing_to_read(tmp_path):
    read = spec.load_module("layer_metrics", NAME).read
    assert read({}) is None and read({"trace": None, "xplane": None}) is None
    path = tmp_path / "no.xplane.pb"
    path.write_bytes(b"not a trace")
    assert read({"trace": {"window_s": 1.0}, "xplane": str(path)}) is None
