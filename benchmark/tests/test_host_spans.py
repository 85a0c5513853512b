"""Naming the device's idle gaps (benchmark/host_spans.py) against a
hand-written trace whose answers are worked out in its header, the same trace
with the host's clock off by a constant, and a cut-down recording from the chip
(TPU v5 lite, PR 25) whose facts were computed apart from the reader."""

import json
import os
import re

import pytest
from jax.profiler import ProfileData

from benchmark import host_spans, spec, trace_reduce

FIXTURES = os.path.join(spec.HERE, "fixtures")
WINDOW_S = 0.055
BY_HAND_MS = {"unknown": 6.6, "tokenize": 5.5, "no_request": 5.5, "accumulate": 4.0,
              "slot_wait": 1.0, "staging_wait": 0.5, "assemble": 2.5, "h2d": 4.9}
IDLE = ("idle_batcher_pct", "idle_host_stage_pct", "idle_no_request_pct", "idle_unknown_pct")


def hand_text(host_shift_ms: float = 0.0) -> str:
    """The hand-written trace; `host_shift_ms` moves every event of the host
    plane (a host clock that disagrees with the chip's by a constant)."""
    with open(os.path.join(FIXTURES, "hand_spans.xspace.txt"), encoding="utf-8") as f:
        text = f.read()
    head, host = text.split('name: "/host:CPU"')
    host = re.sub(r"offset_ps: (\d+)",
                  lambda m: f"offset_ps: {int(m.group(1)) + int(host_shift_ms * 1e9)}", host)
    return head + 'name: "/host:CPU"' + host


def analyse(text: str):
    profile = ProfileData.from_text_proto(text)
    return (host_spans.attribute(host_spans.read_profile(profile), WINDOW_S),
            trace_reduce.reduce_profile(profile, WINDOW_S))


@pytest.fixture(scope="module")
def hand():
    return analyse(hand_text())


def test_idle_by_state_by_hand(hand):
    att, reduced = hand
    assert {k: v * 1e3 for k, v in att["totals_s"].items()} == pytest.approx(BY_HAND_MS)
    # every idle nanosecond is charged once: the states sum to what trace_reduce calls idle
    assert sum(att["totals_s"].values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert att["window_s"] == pytest.approx(reduced["window_s"]) == pytest.approx(WINDOW_S)


def test_a_gap_split_over_the_states_of_the_batch_that_ended_it(hand):
    gap = hand[0]["gaps"][0]
    assert gap["ms"] == pytest.approx(20.0) and gap["batch"] == 2 and gap["ended_by"] == "launch"
    assert gap["bucket"] == "8x128"
    assert gap["parts_ms"] == pytest.approx({
        "no_request": 2.0, "tokenize": 4.0, "accumulate": 4.0, "slot_wait": 1.0, "assemble": 2.5,
        "staging_wait": 0.5, "h2d": 4.9, "unknown": 1.1})


def test_a_gap_no_launch_ends_a_gap_with_no_span_and_a_short_gap(hand):
    gaps = {round(g["start_ms"]): g for g in hand[0]["gaps"]}
    tail = gaps[50]  # [45, 50): no launch after it
    assert tail["ended_by"] == "nothing" and tail["batch"] is None
    assert tail["parts_ms"] == pytest.approx({"tokenize": 1.5, "no_request": 3.5})
    head = gaps[0]   # [-5, 0): ended by a launch made before the tracer started
    assert head["ended_by"] == "unmatched" and head["parts_ms"] == pytest.approx({"unknown": 5.0})
    # the 0.5 ms gap inside the first launch is summed as unknown and not listed
    assert len(hand[0]["gaps"]) == 3


def test_the_clock_check_by_hand(hand):
    ck = hand[0]["clock"]
    assert ck["shift"] == 1 and ck["offset_ms"] == 0.0 and ck["pairs"] == 2
    assert ck["launch_to_module_ms"] == pytest.approx([1.0, 7.0])
    assert ck["launch_end_to_module_ms"] == pytest.approx([0.2, 6.5])
    assert ck["gap_ending_launch_to_module_ms"] == pytest.approx([1.0])
    assert ck["fetch_after_module_ms"] == pytest.approx([1.0, 0.0])
    assert ck["bounds_ms"] == pytest.approx((0.0, 1.0))
    assert any("-> holds" in line for line in host_spans.notes(hand[0]))


def test_a_constant_clock_offset_is_removed():
    """Every host event 2 ms earlier than it was (host + 2 ms = chip): the
    reader finds the offset from launches against module events, the bounds
    are [2, 3] as the fixture's header works out, and the answers stay."""
    att, _ = analyse(hand_text(-2.0))
    assert att["clock"]["offset_ms"] == pytest.approx(2.0)
    assert att["clock"]["shift"] == 1
    assert att["clock"]["bounds_ms"] == pytest.approx((2.0, 3.0))
    assert {k: v * 1e3 for k, v in att["totals_s"].items()} == pytest.approx(BY_HAND_MS)
    assert "offset 2.000 ms removed" in host_spans.notes(att)[0]


def test_an_offset_is_known_only_as_well_as_its_bounds():
    """Every host event 3 ms later (as on the chip, where the host plane runs
    ahead): the bounds are [-3, -2] and the reader removes the one nearer 0,
    so it is 1 ms off and so is every boundary between two states."""
    att, _ = analyse(hand_text(3.0))
    assert att["clock"]["offset_ms"] == pytest.approx(-2.0) and att["clock"]["shift"] == 1
    got = {k: v * 1e3 for k, v in att["totals_s"].items()}
    assert got == pytest.approx(BY_HAND_MS, abs=1.01)
    assert sum(got.values()) == pytest.approx(30.5)


def test_clocks_that_disagree_without_pattern_attribute_nothing():
    """Both fetches return long before any module they could belong to has
    ended, whatever the pairing and whatever offset puts the launches before
    their modules: no offset fits, so all idle time is unknown and the note
    says why."""
    text = hand_text().replace("offset_ps: 29950000000 duration_ps: 11050000000",
                               "offset_ps: 29950000000 duration_ps: 1050000000")
    text = text.replace("offset_ps: 41000000000 duration_ps: 4000000000",
                        "offset_ps: 41000000000 duration_ps: 1000000000")
    att, reduced = analyse(text)
    assert att["clock"] is None and att["gaps"] == []
    assert att["totals_s"]["unknown"] == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert sum(v for k, v in att["totals_s"].items() if k != "unknown") == 0
    assert "without pattern" in host_spans.notes(att)[0]


def test_a_program_without_spans_gives_none():
    with open(os.path.join(FIXTURES, "hand.xspace.txt"), encoding="utf-8") as f:
        profile = ProfileData.from_text_proto(f.read())
    assert host_spans.attribute(host_spans.read_profile(profile), 0.012) is None


def _run(text: str) -> dict:
    """A reader's `run` dict over a hand-written trace: what run.py hands a
    reader, with the spans' analysis already in its place (`for_run` reads
    it from the run's xplane file; the recording's test goes that way)."""
    profile = ProfileData.from_text_proto(text)
    reduced = trace_reduce.reduce_profile(profile, WINDOW_S)
    return {"trace": reduced, "notes": [],
            "host_spans": host_spans.analyse(profile, reduced["window_s"])}


def test_the_four_idle_metrics_sum_to_device_idle_share():
    run = _run(hand_text())
    read = {n: spec.load_module("layer_metrics", n).read(run) for n in IDLE}
    assert read == pytest.approx({"idle_batcher_pct": 10.0, "idle_host_stage_pct": 100 * 12.9 / 55,
                                  "idle_no_request_pct": 10.0, "idle_unknown_pct": 12.0})
    share = spec.load_module("layer_metrics", "device_idle_share").read(run)
    assert sum(read.values()) == pytest.approx(share) == pytest.approx(100 * 30.5 / 55)
    assert spec.load_module("layer_metrics", "h2d_ms_per_batch").read(run) == pytest.approx(2.6)
    # the reader of idle_unknown_pct prints the clock check and the gaps with their names
    assert any("clock check" in n for n in run["notes"])
    assert any("gap 20.0 ms" in n and "batch 2" in n for n in run["notes"])


def test_device_trace_readers_return_none_without_a_device_trace():
    """The CPU rehearsal: run["trace"] is None, so no device metric comes
    from a CPU run, whatever xplane lies about."""
    run = {"trace": None, "notes": []}
    for name in IDLE + ("h2d_ms_per_batch",):
        assert spec.load_module("layer_metrics", name).read(run) is None
    assert run["notes"] == []


def test_device_trace_readers_return_none_for_a_program_without_spans():
    """The parent of the PR that added the spans: a device trace, no
    tpuserve.* event in it."""
    with open(os.path.join(FIXTURES, "hand.xspace.txt"), encoding="utf-8") as f:
        run = _run(f.read())
    assert run["host_spans"] is None
    for name in IDLE + ("h2d_ms_per_batch",):
        assert spec.load_module("layer_metrics", name).read(run) is None


def test_counter_readers():
    from benchmark import prom

    text = "\n".join([
        'latency_ms_bucket{model="model",phase="tokenize",le="10"} 0',
        'latency_ms_bucket{model="model",phase="tokenize",le="20"} 4',
        'latency_ms_bucket{model="model",phase="tokenize",le="+Inf"} 4',
        'latency_ms_bucket{model="model",phase="slot_wait",le="40"} 0',
        'latency_ms_bucket{model="model",phase="slot_wait",le="50"} 2',
        'latency_ms_bucket{model="model",phase="slot_wait",le="+Inf"} 2',
        'ingest_tokenize_cpu_seconds_total{model="model"} 0.128',
        'ingest_tokens_total{model="model"} 9600',
        'items_total{model="model"} 64',
        'batcher_flushes_total{model="model",reason="target"} 1',
        'batcher_flushes_total{model="model",reason="timer"} 3',
        'runtime_variant_batches_total{model="model",variant="32x128/bf16/none/single"} 2',
        'runtime_variant_batches_total{model="model",variant="256x512/bf16/none/single"} 0',
        'runtime_variant_batches_total{model="model",variant="16x512/bf16/none/single"} 1'])
    run = {"metrics_delta": prom.parse(text), "model_name": "model"}
    read = lambda name: spec.load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("tokenize_ms_p50") == pytest.approx(15.0)
    assert read("slot_wait_ms_p50") == pytest.approx(45.0)
    assert read("tokenize_cpu_ms_per_item") == pytest.approx(2.0)
    assert read("timer_flush_share") == pytest.approx(75.0)
    assert read("token_fill_ratio") == pytest.approx(100 * 9600 / (2 * 32 * 128 + 16 * 512))
    # a program without the counters (the parent): nothing to read, nothing raised
    bare = {"metrics_delta": prom.parse('items_total{model="model"} 64'), "model_name": "model"}
    for name in ("tokenize_ms_p50", "slot_wait_ms_p50", "tokenize_cpu_ms_per_item",
                 "timer_flush_share", "token_fill_ratio"):
        assert spec.load_module("layer_metrics", name).read(bare) is None


def test_the_recording_from_the_chip():
    """The cut-down recording (fixtures/recorded_v5e_spans.md) against facts
    computed apart from the reader (recorded_v5e_spans.json)."""
    path = os.path.join(FIXTURES, "recorded_v5e_spans.xplane.pb")
    with open(os.path.join(FIXTURES, "recorded_v5e_spans.json"), encoding="utf-8") as f:
        facts = json.load(f)
    data = host_spans.read_profile(ProfileData.from_file(path))
    assert data["span_planes"] == {"/host:CPU": len(data["spans"])}
    assert len(data["modules"]) == facts["modules"] and len(data["ops"]) == facts["operations"]
    att = host_spans.attribute(data, facts["extent_ms"] / 1e3)
    assert att["n_launches"] == facts["launch_spans"]
    # ProfileData rounds picoseconds to whole nanoseconds: 4,589 operations, microseconds
    assert sum(att["totals_s"].values()) * 1e3 == pytest.approx(
        facts["extent_ms"] - facts["busy_ms"], abs=0.01)
    ck = att["clock"]
    assert ck["shift"] == 2 and ck["pairs"] == 4
    assert ck["bounds_ms"] == pytest.approx(facts["offset_bounds_ms"])
    assert ck["offset_ms"] == pytest.approx(facts["offset_bounds_ms"][1])  # the bound nearer 0
    assert min(ck["launch_to_module_ms"]) >= 0 and min(ck["fetch_after_module_ms"]) > 0
    assert max(ck["gap_ending_launch_to_module_ms"]) < 1.0  # within a millisecond
    # the compiled call returns AFTER an idle device has begun the program
    assert -1.0 < min(ck["launch_end_to_module_ms"]) < 0
    gap = att["gaps"][0]
    assert gap["ms"] == pytest.approx(facts["longest_gap_ms"][1] - facts["longest_gap_ms"][0])
    assert gap["batch"] == facts["longest_gap_batch"] and gap["bucket"] == "256x512"
    assert gap["parts_ms"]["staging_wait"] == pytest.approx(facts["longest_gap_staging_wait_ms"])
    assert gap["parts_ms"]["h2d"] == pytest.approx(facts["longest_gap_h2d_ms"])
    assert set(gap["parts_ms"]) == {"staging_wait", "h2d", "unknown"}
    # through the readers, as a run would: run.py hands them the trace's file
    run = {"trace": trace_reduce.reduce_profile(ProfileData.from_file(path)), "xplane": path,
           "notes": []}
    read = {n: spec.load_module("layer_metrics", n).read(run) for n in IDLE}
    share = spec.load_module("layer_metrics", "device_idle_share").read(run)
    assert sum(read.values()) == pytest.approx(share)
    assert read["idle_batcher_pct"] > 0.9 * share and read["idle_unknown_pct"] < 0.01 * share
    assert 1.0 < spec.load_module("layer_metrics", "h2d_ms_per_batch").read(run) < 3.0
