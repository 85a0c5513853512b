import time

import pytest

from benchmark import budget, prom

TEXT = """# TYPE items_total counter
items_total{model="model"} 10
runtime_variant_batches_total{model="model",variant="256x512/bfloat16/fp/single"} 2
latency_ms_bucket{model="model",phase="parse",le="1"} 0 # {trace_id="ab"} 0.5 1.0
latency_ms_bucket{model="model",phase="parse",le="2"} 10
latency_ms_bucket{model="model",phase="parse",le="4"} 20
latency_ms_bucket{model="model",phase="parse",le="+Inf"} 20
latency_ms_count{model="model",phase="parse"} 20
# EOF
"""


def test_parse_delta_select_quantile():
    a = prom.parse(TEXT)
    assert a['items_total{model="model"}'] == 10
    b = {k: v * 2 for k, v in a.items()}
    d = prom.delta(b, a)
    assert d == a
    assert len(prom.select(a, "latency_ms_bucket", phase="parse")) == 4
    assert prom.select(a, "latency_ms_bucket", phase="queue") == {}
    assert prom.histogram_quantile(a, "latency_ms", 0.5, phase="parse") == pytest.approx(2.0)
    assert prom.histogram_quantile(a, "latency_ms", 0.75, phase="parse") == pytest.approx(3.0)
    assert prom.histogram_quantile(a, "latency_ms", 0.5, phase="queue") is None


def test_batch_fill_reader_uses_the_variant_batch_size():
    from benchmark import spec

    read = spec.load_module("layer_metrics", "batch_fill_ratio").read
    run = {"metrics_delta": prom.parse(TEXT), "model_name": "model"}
    assert read(run) == pytest.approx(100.0 * 10 / 512)


def test_budget_is_two_thirds_of_the_drivers_limit_and_refuses_past_it():
    assert budget.RUN_BUDGET_S == pytest.approx(240.0)
    b = budget.Budget(10.0, start=time.monotonic() - 9.0)
    assert 0.5 < b.left() < 1.1
    assert b.wait_s("x", at_most=0.2) == pytest.approx(0.2)
    with pytest.raises(budget.OverBudget):
        b.need(5.0, "the window")
    with pytest.raises(budget.OverBudget):
        b.wait_s("a wait", reserve_s=2.0)
    with pytest.raises(budget.OverBudget):
        budget.Budget(1.0, start=time.monotonic() - 2.0).wait_s("anything")
