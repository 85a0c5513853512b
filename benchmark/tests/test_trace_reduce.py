"""The reduction from a trace to numbers, against a hand-written trace whose
answers are worked out by hand in the fixture's header, and against a small
trace recorded on the chip (TPU v5 lite, PR 24)."""

import os

import pytest
from jax.profiler import ProfileData

from benchmark import spec, trace_reduce

FIXTURES = os.path.join(spec.HERE, "fixtures")


@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(FIXTURES, "hand.xspace.txt"), encoding="utf-8") as f:
        return trace_reduce.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_busy_idle_and_window_by_hand(hand):
    assert hand["n_devices"] == 1
    assert hand["busy_s"] == pytest.approx(9e-3)
    assert hand["window_s"] == pytest.approx(12e-3)
    assert [g for _n, g in hand["idle_gaps"]] == pytest.approx([2e-3, 1e-3])
    assert {n for n, _g in hand["idle_gaps"]} == {"host:unknown"}


def test_a_longer_host_window_widens_the_window_not_the_busy_time():
    with open(os.path.join(FIXTURES, "hand.xspace.txt"), encoding="utf-8") as f:
        r = trace_reduce.reduce_profile(ProfileData.from_text_proto(f.read()), window_s=20e-3)
    assert r["window_s"] == pytest.approx(20e-3) and r["busy_s"] == pytest.approx(9e-3)
    # the 8 ms of slack is split between the two ends: 4 ms idle at each
    assert sorted(g for _n, g in r["idle_gaps"]) == pytest.approx([1e-3, 2e-3, 4e-3, 4e-3])


def test_modules_and_top_operations_by_hand(hand):
    assert hand["modules"] == {
        # A's launches are the first and the last on the line, so neither is
        # known to lie whole in the window and its median is over both.
        "jit_forward(111)": {"launches": 2, "whole_launches": 0, "device_s": pytest.approx(8e-3),
                             "launch_s": pytest.approx(4e-3), "shapes": [[8, 128, 64]]},
        "jit_forward(222)": {"launches": 1, "whole_launches": 1, "device_s": pytest.approx(1e-3),
                             "launch_s": pytest.approx(1e-3), "shapes": []}}
    assert hand["top_module"]["name"] == "jit_forward(111)"
    assert hand["device_ops"] == [["convolution.2", pytest.approx(5e-3)],
                                  ["fusion.1", pytest.approx(4e-3)],
                                  ["copy-done.3", pytest.approx(1e-3)]]


def test_layer_metric_readers_on_the_hand_trace(hand):
    run = {"trace": hand, "peaks": None, "notes": [], "sizes": {"d_model": 64}}
    assert spec.load_module("layer_metrics", "exec_ms_per_batch").read(run) == pytest.approx(4.0)
    assert spec.load_module("layer_metrics", "device_idle_share").read(run) == pytest.approx(25.0)
    assert spec.load_module("layer_metrics", "exec_roofline_share").read(run) is None
    flops = spec.load_module("flops", "bert")
    sizes = {"layers": 1, "d_model": 64, "heads": 2, "d_ff": 512, "vocab_size": 100,
             "positions": 128, "num_classes": 5}
    run.update(peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}, flops=flops,
               sizes=sizes)
    ops, nbytes = flops.ops_and_bytes(sizes, 8, 128)
    share = spec.load_module("layer_metrics", "exec_roofline_share").read(run)
    assert share == pytest.approx(100.0 * max(ops, nbytes) / 1e12 / 4e-3)
    assert "bucket (8, 128) bound by compute" in run["notes"][-1]


def test_no_device_plane_gives_nothing():
    text = 'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" } }'
    assert trace_reduce.reduce_profile(ProfileData.from_text_proto(text)) is None
    run = {"trace": None, "peaks": None, "notes": []}
    assert spec.load_module("layer_metrics", "device_idle_share").read(run) is None


def test_union_and_gaps():
    assert trace_reduce.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace_reduce.gaps_of([(5, 10), (8, 12), (20, 30)], 0, 40) == [(0, 5), (12, 20), (30, 40)]
    assert trace_reduce.op_name("%fusion.7 = bf16[2]{0} fusion(%x)") == "fusion.7"
    assert trace_reduce.op_shape3("%f = bf16[256,512,768]{2,1,0:T(8,128)(2,1)} fusion(%x)") == (256, 512, 768)
    assert trace_reduce.op_shape3("%f = (f32[256,512]{1,0}, bf16[256,512,768]{2,1,0}) fusion(%x)") is None
    assert trace_reduce.op_shape3("%c = bf16[768]{0} copy-done(%s)") is None


RECORDED = os.path.join(FIXTURES, "recorded_v5e.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    """benchmark/fixtures/recorded_v5e.md says how it was recorded and lists
    what was read from it by hand (planes, lines, the events' times)."""
    import json

    with open(os.path.join(FIXTURES, "recorded_v5e.json"), encoding="utf-8") as f:
        want = json.load(f)
    r = trace_reduce.reduce_file(RECORDED)
    assert r["n_devices"] == want["n_devices"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert {k: v["launches"] for k, v in r["modules"].items()} == want["launches"]
    assert trace_reduce.bucket_of(r["top_module"], 768) == (256, 512), "BERT-base's largest program"
    assert [256, 12, 512] in r["top_module"]["shapes"], "(batch, heads, sequence) shapes are there too"
    assert r["device_ops"][0][0] == want["top_op"]
