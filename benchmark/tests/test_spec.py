"""BENCHMARK.json meets the contract's shape, and a later PR adds a
configuration, a mix, a cell and a per-layer metric as files and entries of
its own: the harness finds them by name with no edit."""

import json
import os
import re
import shutil

import pytest

from benchmark import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_shape():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(spec.REPO, c["file"]))
    pairs = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert {w["config"] for w in b["workloads"]} == configs
    assert os.path.getsize(os.path.join(spec.REPO, "BENCHMARK.json")) < 64 * 1024


def test_every_named_file_exists():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        cfg = spec.load_config(b, w["config"])
        mix = spec.load_mix(w["traffic"])
        for kind, name in (("reference", cfg["family"]), ("flops", cfg["family"]),
                           ("traffic", mix["traffic"])):
            assert os.path.exists(os.path.join(spec.HERE, kind, f"{name}.py"))
        for m in spec.cell_metrics(b, "per_layer", w["name"]):
            assert callable(spec.load_module("layer_metrics", m["name"]).read)
        assert cfg["reduced"] == [], "no width and no depth is cut in the first cells"


def test_configs_hold_the_published_sizes():
    b = spec.load_benchmark()
    base = spec.load_config(b, "bert-base-s512")
    large = spec.load_config(b, "bert-large-s512")
    pick = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "intermediate_size", "vocab_size", "max_position_embeddings")
    assert [base[k] for k in pick] == [12, 768, 12, 3072, 30522, 512]
    assert [large[k] for k in pick] == [24, 1024, 16, 4096, 30522, 512]


def test_control_config_is_its_base_with_the_lower_precision():
    b = spec.load_benchmark()
    ctl = spec.load_config(b, "bert-base-s512-int8c")
    base = spec.load_config(b, "bert-base-s512")
    assert ctl["serve"]["model"]["quantize"] == "int8c" and not ctl["cell"]
    assert "quantize" not in base["serve"]["model"]
    assert ctl["hidden_size"] == base["hidden_size"]


@pytest.fixture
def scratch_tree(tmp_path, monkeypatch):
    """A copy of the benchmark's data directories that a 'later PR' may add
    to, with the harness pointed at it."""
    for d in ("configs", "mixes", "layer_metrics"):
        shutil.copytree(os.path.join(spec.HERE, d), tmp_path / d)
    monkeypatch.setattr(spec, "HERE", str(tmp_path))
    return tmp_path


def test_a_later_pr_adds_only_files_and_entries(scratch_tree):
    b = spec.load_benchmark()
    before = json.dumps(b, sort_keys=True)
    new_cfg = dict(spec.load_config(b, "bert-base-s512"), name="bert-new")
    (scratch_tree / "configs" / "bert-new.json").write_text(json.dumps(new_cfg))
    new_mix = dict(spec.load_mix("docs-closed"), clients=8)
    (scratch_tree / "mixes" / "docs-few.json").write_text(json.dumps(new_mix))
    (scratch_tree / "layer_metrics" / "answer.py").write_text(
        "def read(run):\n    return 42.0\n")
    b["configs"].append({"name": "bert-new", "source": "x", "reduced": [], "why": "y",
                         "file": os.path.relpath(scratch_tree / "configs" / "bert-new.json", spec.REPO)})
    b["workloads"].append({"name": "bert-new.docs-few", "config": "bert-new",
                           "traffic": "docs-few", "chips": 1, "why": "z"})
    b["per_layer"].append({"name": "answer", "unit": "count", "better": "higher",
                           "source": "program_counter", "layer": "runtime",
                           "moves": "items_per_s", "workloads": ["bert-new.docs-few"]})
    cell = spec.find(b["workloads"], "bert-new.docs-few", "workload")
    assert spec.load_config(b, cell["config"])["name"] == "bert-new"
    assert spec.load_mix(cell["traffic"])["clients"] == 8
    mine = spec.cell_metrics(b, "per_layer", cell["name"])
    assert "answer" in [m["name"] for m in mine]
    assert spec.load_module("layer_metrics", "answer").read({}) == 42.0
    # the new metric lists its cell, so the old cells do not report it
    assert "answer" not in [m["name"] for m in spec.cell_metrics(
        b, "per_layer", "bert-base-s512.docs-closed-64")]
    assert before == json.dumps(spec.load_benchmark(), sort_keys=True)


def test_a_metric_that_lists_its_cells_is_left_out_elsewhere():
    b = spec.load_benchmark()
    names = lambda cell: [m["name"] for m in spec.cell_metrics(b, "end_to_end", cell)]  # noqa: E731
    assert "latency_p50_ms" in names("bert-base-s512.docs-closed-64")
    assert "latency_p50_ms" not in names("a-cell-it-does-not-list")
    assert "latency_p50_ms" in names(None), "a run that is no cell reports every metric"
    for cell in (w["name"] for w in b["workloads"]):
        assert {"setup_s", "items_per_s", "latency_p50_ms"} <= set(names(cell))
        per_layer = spec.cell_metrics(b, "per_layer", cell)
        assert len(per_layer) >= 10 and all(m["moves"] in names(cell) for m in per_layer)
        assert "request_p95_ms" in [m["name"] for m in per_layer], "the tail, per layer: it has no bound"
    assert "latency_p95_ms" not in names(None), "no tail is held to a bound (PERF.md, section 2)"
