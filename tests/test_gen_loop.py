"""The generation engine's loop accounted for from inside (ISSUE 36): its
phases' counters against the wall clock, the first token and the token gap
through the toy paged decoder of tests/test_decoder.py, and the benchmark's
reader (benchmark/gen_loop.py) against a hand-written trace whose answers are
worked out below, the same trace with the host's clock off by a constant, the
parent's spans alone, and clocks that fit no pairing."""

import asyncio
import os
import time

import pytest
from jax.profiler import ProfileData

from benchmark import gen_loop, prom, spec, trace_reduce
from tests.test_genserve_paged import (ByHand, dec_item, dec_rt,  # noqa: F401 — dec_rt is a fixture
                                       make_engine, paged_over)
from tpuserve.genserve.engine import LOOP_PHASES

IDLE = tuple(f"idle_gen_{s}_pct" for s in gen_loop.STATES)
COUNTER_READERS = ("gen_loop_serial_ms_per_iter", "gen_first_token_ms_p50",
                   "gen_token_gap_ms_p50", "gen_token_gap_ms_p95")


def reader(name):
    return spec.load_module("layer_metrics", name).read


# -- the hand-written trace --------------------------------------------------------------------------
# Times in ms. Read with window_s = 0.100: the chip's events extend over [0, 90), so the window is
# padded by 5 ms at each end: [-5, 95). host + 0 = chip unless a test shifts the host plane.
#
# Chip  P0 jit_prefill_fn [0, 10)   launched before the tracer started: no gen_prefill span
#       S1 jit_step [10.5, 20)      gen_step(iter 1) [9, 9.4), gen_fetch(1) [9.4, 20): ends WITH its module
#       P1 jit_prefill_fn [32, 42)  gen_pack(2) [27, 29), gen_prefill(2) [29.2, 33)
#       S2 jit_step [42, 50)        gen_step(2) [34, 34.5), gen_fetch(2) [34.5, 51)
#       X1 jit_extract [54, 55)     gen_extract(2) [53, 55.5), then gen_finalize(2) [56, 58.5)
#       S3 jit_step [70, 80)        gen_step(4) [70, 70.6): begins WITH its module; gen_fetch(4) [70.6, 81)
#       X2 jit_extract [88, 90)     gen_extract(4) [84.5, 90.5)
# Loop  iter 1: step [8.5, 22) account [22, 23) emit [23, 23.5) retire [23.5, 24)
#       iter 2: sweep [24, 24.5) admit [24.5, 25.5) prefill [25.5, 33.5) step [33.5, 51.5)
#               account [51.5, 52) emit [52, 52.5) retire [52.5, 60)
#       iter 3: sweep [60, 60.5) wait [60.5, 66)
#       iter 4: sweep [66, 66.4) admit [66.4, 67) prefill [67, 67.5) (a held launch: no worker)
#               step [67.5, 81.5) account [81.5, 82) (no emit mark: [82, 84) has no span) retire [84, 89)
#
# Busy 10 + 9.5 + 10 + 8 + 1 + 10 + 2 = 50.5 ms of 100: device_idle_share 49.5 %. The gaps, by the rule:
#   [-5, 0) and [90, 95)  the window's edges                                         -> unknown 10
#   [10, 10.5)  under 1 ms                                                           -> unknown 0.5
#   A [20, 32)  step(1) after gen_fetch: hop gen_fetch>loop 2; account 1, emit .5, sweep .5, admit 1: host 3;
#               retire(1) .5 (hand-over); prefill(2): hop loop>gen_pack 1.5, gen_pack: host 2,
#               hop gen_pack>gen_prefill .2, gen_prefill before P1 begins: launch 2.8              = 12
#   B1 [50, 54) gen_fetch(2): fetch 1; hop gen_fetch>loop .5; account .5 + emit .5: host 1;
#               retire(2): hand-over .5, gen_extract 1                                              = 4
#   B2 [55, 70) retire(2): gen_extract .5, hand-over .5, gen_finalize 2.5, hand-over 1.5 = 5; sweep(3) .5:
#               host; wait 5.5: no_work; sweep(4) .4 + admit .6: host; prefill(4): hop loop>loop .5;
#               step(4) before gen_step: hop loop>gen_step 2.5; gen_step begins with S3: launch 0  = 15
#   C [80, 88)  gen_fetch(4): fetch 1; hop gen_fetch>loop .5; account .5: host; [82, 84) unknown 2;
#               retire(4): hand-over .5, gen_extract 3.5                                            = 8
BY_HAND_MS = {"fetch": 2.0, "launch": 2.8, "hop": 7.7, "retire": 11.0, "host": 8.0,
              "no_work": 5.5, "unknown": 12.5}
DETAIL_MS = {"hop:gen_fetch>loop": 3.0, "hop:loop>gen_pack": 1.5, "hop:gen_pack>gen_prefill": 0.2,
             "hop:loop>loop": 0.5, "hop:loop>gen_step": 2.5, "retire:hand_over": 3.5,
             "retire:gen_extract": 5.0, "retire:gen_finalize": 2.5, "host:gen_pack": 2.0,
             "host:account": 2.0, "host:emit": 1.0, "host:sweep": 1.4, "host:admit": 1.6,
             "no_work:wait": 5.5, "launch:gen_prefill": 2.8, "fetch:gen_fetch": 2.0,
             "unknown:no_span": 2.0, "unknown:window_edge": 10.0}
WINDOW_S = 0.100
CHIP = [("jit_prefill_fn(9)", 0, 10), ("jit_step(7)", 10.5, 9.5), ("jit_prefill_fn(9)", 32, 10),
        ("jit_step(7)", 42, 8), ("jit_extract(5)", 54, 1), ("jit_step(7)", 70, 10),
        ("jit_extract(5)", 88, 2)]
PHASES = [(1, "step", 8.5, 22), (1, "account", 22, 23), (1, "emit", 23, 23.5), (1, "retire", 23.5, 24),
          (2, "sweep", 24, 24.5), (2, "admit", 24.5, 25.5), (2, "prefill", 25.5, 33.5),
          (2, "step", 33.5, 51.5), (2, "account", 51.5, 52), (2, "emit", 52, 52.5),
          (2, "retire", 52.5, 60), (3, "sweep", 60, 60.5), (3, "wait", 60.5, 66),
          (4, "sweep", 66, 66.4), (4, "admit", 66.4, 67), (4, "prefill", 67, 67.5),
          (4, "step", 67.5, 81.5), (4, "account", 81.5, 82), (4, "retire", 84, 89)]
WORKERS = [("h2d", "gen_pack", 2, 27, 29), ("h2d", "gen_prefill", 2, 29.2, 33),
           ("fetch", "gen_step", 1, 9, 9.4), ("fetch", "gen_fetch", 1, 9.4, 20),
           ("fetch", "gen_step", 2, 34, 34.5), ("fetch", "gen_fetch", 2, 34.5, 51),
           ("fetch", "gen_extract", 2, 53, 55.5), ("postproc", "gen_finalize", 2, 56, 58.5),
           ("fetch", "gen_step", 4, 70, 70.6), ("fetch", "gen_fetch", 4, 70.6, 81),
           ("fetch", "gen_extract", 4, 84.5, 90.5)]


def xspace(phases=PHASES, workers=WORKERS, host_shift_ms=0.0, with_iter=True) -> str:
    """The trace above in XSpace text format; `host_shift_ms` moves every
    event of the host plane (a host clock off by a constant); `with_iter`
    False writes the workers' spans as the parent's program does."""
    names, stats = {}, {}

    def ident(table, key):
        return table.setdefault(key, len(table) + 1)

    def ps(ms):
        return int(round(ms * 1e9))

    def event(name, start, dur, args):
        st = " ".join(
            f"stats {{ metadata_id: {ident(stats, k)} "
            + (f'str_value: "{v}"' if isinstance(v, str) else f"int64_value: {v}") + " }"
            for k, v in args.items())
        return (f"events {{ metadata_id: {ident(names, name)} offset_ps: {ps(start + host_shift_ms)} "
                f"duration_ps: {ps(dur)} {st} }}")

    lines = {"loop": [event("tpuserve.gen_loop", t1 + 0.002, 0.001, {
        "dur_us": int(round((t1 - t0) * 1e3)), "ago_us": 2, "model": "m", "phase": ph, "iter": it})
        for it, ph, t0, t1 in phases]}
    for thread, name, it, t0, t1 in workers:
        args = {"model": "m", **({"iter": it} if with_iter else {})}
        lines.setdefault(thread, []).append(event("tpuserve." + name, t0, t1 - t0, args))
    lines["loop"].append(event("handle_generate", 0, 90, {}))  # not the program's: never read
    host = "\n".join(f'lines {{ id: {i + 1} name: "python3"\n' + "\n".join(evs) + "\n}"
                     for i, evs in enumerate(lines.values()))
    host += "\n" + "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                             for n, i in names.items())
    host += "\n" + "\n".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                             for n, i in stats.items())
    mods = {n: i + 1 for i, n in enumerate(dict.fromkeys(n for n, _s, _d in CHIP))}
    chip = ('lines { id: 1 name: "XLA Modules"\n' + "\n".join(
        f"events {{ metadata_id: {mods[n]} offset_ps: {ps(s)} duration_ps: {ps(d)} }}"
        for n, s, d in CHIP) + '\n}\nlines { id: 2 name: "XLA Ops"\n' + "\n".join(
        f"events {{ metadata_id: 9 offset_ps: {ps(s)} duration_ps: {ps(d)} }}"
        for _n, s, d in CHIP) + "\n}\n" + "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in mods.items())
        + '\nevent_metadata { key: 9 value { id: 9 name: "%fusion.1 = bf16[8,128,64]{2,1,0} fusion()" } }')
    return (f'planes {{ id: 1 name: "/device:TPU:0"\n{chip}\n}}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n{host}\n}}\n')


def analyse(text: str):
    profile = ProfileData.from_text_proto(text)
    from benchmark import host_spans
    return (gen_loop.attribute(host_spans.read_profile(profile), WINDOW_S),
            trace_reduce.reduce_profile(profile, WINDOW_S))


def run_of(text: str) -> dict:
    """What run.py hands a reader, with the analysis already in its place
    (`for_run` reads it from the run's xplane file: the recording's test
    below goes that way)."""
    att, reduced = analyse(text)
    return {"trace": reduced, "notes": [], "gen_loop": att}


@pytest.fixture(scope="module")
def hand():
    return analyse(xspace())


def test_idle_by_state_by_hand(hand):
    att, reduced = hand
    assert {k: v * 1e3 for k, v in att["totals_s"].items()} == pytest.approx(BY_HAND_MS, abs=0.01)
    # every idle nanosecond is charged once: the states sum to what trace_reduce calls idle
    assert sum(att["totals_s"].values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert att["window_s"] == pytest.approx(reduced["window_s"]) == pytest.approx(WINDOW_S)
    assert {k: v * 1e3 for k, v in att["detail_s"].items()} == pytest.approx(DETAIL_MS, abs=0.01)


def test_a_gap_is_split_over_the_workers_spans_before_the_loops_phases(hand):
    gaps = {round(g["start_ms"]): g for g in hand[0]["gaps"]}
    assert sorted(gaps) == [25, 55, 60, 85]  # the edges and the 0.5 ms gap are not listed
    a = gaps[25]  # A = [20, 32) in a window that begins at -5
    assert a["ms"] == pytest.approx(12.0) and a["iter"] == 1
    assert a["parts_ms"] == pytest.approx(
        {"hop": 3.7, "host": 5.0, "retire": 0.5, "launch": 2.8}, abs=0.01)
    assert a["detail_ms"]["host:gen_pack"] == pytest.approx(2.0, abs=0.01)  # not the phase's hop
    b2 = hand[0]["gaps"][0]
    assert b2["ms"] == pytest.approx(15.0) and b2["parts_ms"]["no_work"] == pytest.approx(5.5, abs=0.01)
    assert gaps[85]["parts_ms"]["unknown"] == pytest.approx(2.0, abs=0.01)  # what no span covers


def test_the_clock_check_by_hand(hand):
    ck = hand[0]["clock"]
    assert ck["shifts"] == (0, 1) and ck["pairs"] == (3, 1) and ck["offset_ms"] == 0.0
    # a call that begins with its module and a fetch that ends with its module: [0, 0]
    assert ck["bounds_ms"] == pytest.approx((0.0, 0.0))
    assert sorted(ck["call_to_module_ms"]) == pytest.approx([0.0, 1.5, 2.8, 8.0])
    assert ck["fetch_after_module_ms"] == pytest.approx([0.0, 1.0, 1.0])
    lines = gen_loop.notes(hand[0])
    assert "offset 0.000 ms removed" in lines[0] and "bounds [0.000, 0.000]" in lines[0]
    assert sum(line.startswith("gen_loop: gap ") for line in lines) == 4
    assert "gen_finalize 2.50" in next(line for line in lines if "gap 15.0 ms" in line)


@pytest.mark.parametrize("shift_ms", [2.0, -3.0])
def test_a_constant_clock_offset_is_removed_and_printed(shift_ms):
    att, _ = analyse(xspace(host_shift_ms=shift_ms))
    assert att["clock"]["offset_ms"] == pytest.approx(-shift_ms)
    assert att["clock"]["shifts"] == (0, 1)
    assert {k: v * 1e3 for k, v in att["totals_s"].items()} == pytest.approx(BY_HAND_MS, abs=0.01)
    assert f"offset {-shift_ms:.3f} ms removed" in gen_loop.notes(att)[0]


def test_the_seven_parts_sum_to_device_idle_share():
    run = run_of(xspace())
    got = {n: reader(n)(run) for n in IDLE}
    assert got == pytest.approx({f"idle_gen_{k}_pct": v for k, v in BY_HAND_MS.items()}, abs=0.01)
    assert sum(got.values()) == pytest.approx(reader("device_idle_share")(run)) == pytest.approx(49.5)


def test_clocks_that_fit_no_pairing_attribute_nothing():
    """Every fetch returns 8 ms before its step's module has ended, and the
    last step's call begins with its module: max(module.end - fetch.end) = 8 >
    0 = min(module.start - call.start) for the pairing in order, and every
    other pairing is tens of ms off: nothing is attributed, the note says why,
    and the seven still sum to the idle share."""
    workers = [(th, n, it, t0, t1 - 8 if n == "gen_fetch" else t1) for th, n, it, t0, t1 in WORKERS]
    att, reduced = analyse(xspace(workers=workers))
    assert att["clock"] is None and att["gaps"] == []
    assert att["totals_s"]["unknown"] == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert sum(v for k, v in att["totals_s"].items() if k != "unknown") == 0
    assert "without pattern" in gen_loop.notes(att)[0] and "unknown" in gen_loop.notes(att)[0]
    run = {"trace": reduced, "notes": [], "gen_loop": att}
    assert reader("idle_gen_unknown_pct")(run) == pytest.approx(49.5)
    assert reader("idle_gen_hop_pct")(run) == 0.0


@pytest.mark.parametrize("name", IDLE)
def test_the_parents_spans_alone_give_none(name):
    """The five spans of before ISSUE 36, without `iter` and without a
    `tpuserve.gen_loop`: every new reader leaves its metric out."""
    text = xspace(phases=[], workers=[w for w in WORKERS if w[1] in ("gen_prefill", "gen_step", "gen_fetch")],
                  with_iter=False)
    att, reduced = analyse(text)
    assert att is None
    assert reader(name)({"trace": reduced, "notes": [], "gen_loop": att}) is None


@pytest.mark.parametrize("name", IDLE + COUNTER_READERS)
def test_a_run_with_nothing_to_read_gives_none_and_raises_nothing(name):
    """The CPU rehearsal's traced run (no device plane: `trace` None), an
    untraced one, and a BERT cell's trace from the chip (no `gen_loop` span,
    no engine counter) read through the run's xplane file as run.py does."""
    assert reader(name)({}) is None
    assert reader(name)({"trace": None, "xplane": None, "metrics_delta": {}, "model_name": "m"}) is None
    path = os.path.join(spec.HERE, "fixtures", "recorded_v5e_spans.xplane.pb")
    run = {"trace": trace_reduce.reduce_file(path, 3.0), "xplane": path, "notes": [],
           "metrics_delta": {'items_total{model="m"}': 5.0}, "model_name": "m"}
    assert reader(name)(run) is None and run["notes"] == []


# -- the loop's counters, the first token and the token gap, through the engine ------------------

def loop_seconds(metrics, name):
    return {p: metrics.counter(f"gen_loop_seconds_total{{model={name},phase={p}}}").value
            for p in LOOP_PHASES}


def run_engine(fix, go, **over):
    eng, metrics = make_engine(fix, slots=6, **paged_over(kv_page_tokens=4, prefill_chunk=16, **over))

    async def main():
        await eng.start()
        try:
            return await go(eng, metrics)
        finally:
            await eng.stop()
    return asyncio.run(main())


def test_the_eight_phases_sum_to_the_loops_wall_time_and_wait_takes_an_idle_engines(dec_rt):  # noqa: F811
    model, _ = dec_rt

    async def go(eng, metrics):
        def read():  # the counters, the loop's last boundary and the clock, with no await between
            return loop_seconds(metrics, eng.name), eng._phase_t, time.perf_counter()

        await asyncio.gather(*[eng.submit(dec_item(model, 5 + i, first=1 + 10 * i)) for i in range(2)])
        await asyncio.sleep(0.02)  # the loop reaches its wait
        before = read()
        await asyncio.sleep(0.25)  # idle: all of it is the wait's, counted when the wait ends
        await asyncio.gather(*[eng.submit(dec_item(model, 4 + i, max_new=10, first=1 + 9 * i))
                               for i in range(5)])
        await asyncio.sleep(0.02)
        return before, read(), eng.pipeline_stats()["loop"]

    (c0, edge0, t0), (c1, edge1, t1), stats = run_engine(dec_rt, go)
    delta = {p: c1[p] - c0[p] for p in LOOP_PHASES}
    assert all(v >= 0 for v in delta.values()) and set(delta) == set(LOOP_PHASES)
    # between two boundaries of the loop the eight sum to its wall time exactly ...
    assert sum(delta.values()) == pytest.approx(edge1 - edge0, abs=1e-6)
    # ... and a read falls short of the clock by the phase that runs then (here: a wait just begun)
    assert t0 >= edge0 and t1 >= edge1
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.05, abs=(t0 - edge0) + (t1 - edge1))
    assert 0.24 <= delta["wait"] <= (edge1 - edge0) - delta["step"]
    for busy in ("sweep", "admit", "prefill", "step", "account", "emit", "retire"):
        assert delta[busy] > 0, busy
    # /stats: the same counters, in ms an iteration since start
    assert stats["iterations"] > 0 and set(stats["ms_per_iteration"]) == set(LOOP_PHASES)
    assert stats["ms_per_iteration"]["step"] > 0


@pytest.mark.parametrize("streamed", [False, True])
def test_every_request_gets_exactly_one_first_token_observation(dec_rt, streamed):  # noqa: F811
    model, _ = dec_rt

    async def one(eng, item):
        if not streamed:
            t0 = time.perf_counter()
            await eng.submit(item)
            return (time.perf_counter() - t0) * 1e3
        _fut, stream = eng.submit_stream(item)
        t0, first = time.perf_counter(), None
        while True:
            unit = await stream.get()
            if first is None:
                first = (time.perf_counter() - t0) * 1e3
            if unit["type"] in ("done", "error"):
                assert unit["type"] == "done"
                return first

    async def go(eng, metrics):
        h = metrics.histogram(f"gen_first_unit_ms{{model={eng.name}}}")
        # a prompt of two launches among one-launch ones: the first token comes iterations after admission
        waits = await asyncio.gather(*[one(eng, dec_item(model, n, max_new=6, first=1 + 25 * i))
                                       for i, n in enumerate((5, 24, 7))])
        return h.n, h.total, waits

    n, total_ms, waits = run_engine(dec_rt, go)
    assert n == 3
    # from the request's arrival to its first token: not after the caller had it
    # (a streamed caller's first unit; an unstreamed one's whole answer)
    assert 0 < total_ms <= sum(waits)


def test_the_token_gap_counts_decoding_iterations_less_one_a_busy_stretch(dec_rt):  # noqa: F811
    model, _ = dec_rt

    async def go(eng, metrics):
        gap = metrics.histogram(f"gen_token_gap_ms{{model={eng.name}}}")
        iters = metrics.counter(f"gen_iterations_total{{model={eng.name}}}")
        # one prompt of one launch: its lane decodes in every iteration of its stretch
        await eng.submit(dec_item(model, 5, max_new=7))
        await asyncio.sleep(0.05)  # the loop reaches its wait: the stretch is over
        first = (iters.value, gap.n)
        await eng.submit(dec_item(model, 6, max_new=4, first=30))
        await asyncio.sleep(0.05)
        return first, (iters.value, gap.n), gap.total

    (i1, g1), (i2, g2), total_ms = run_engine(dec_rt, go)
    assert i1 >= 6 and g1 == i1 - 1           # no gap before a stretch's first token
    assert g2 - g1 == (i2 - i1) - 1           # and none across the wait between two stretches
    assert total_ms > 0


def test_an_iteration_in_which_no_lane_decoded_is_inside_a_gap_not_one_of_its_own(dec_rt, monkeypatch):  # noqa: F811
    """By hand, a pass at a time: a prompt of two launches has one pass in
    which its lane is still in prefill (a step runs, nothing decodes); each
    later pass is one gap more."""
    model, _ = dec_rt

    async def go():
        async with ByHand(dec_rt, 2, monkeypatch, **paged_over(kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            gap = h.metrics.histogram(f"gen_token_gap_ms{{model={eng.name}}}")
            first = h.metrics.histogram(f"gen_first_unit_ms{{model={eng.name}}}")
            fut = eng.submit(dec_item(model, 24, max_new=5))
            decoding = 0
            for _ in range(7):
                tokens = h.count("gen_decode_tokens_total")
                await h.iterate()
                decoding += h.count("gen_decode_tokens_total") > tokens
                assert gap.n == max(0, decoding - 1)
                assert first.n == (1 if decoding else 0)
            assert (await fut)["n_tokens"] == 5 and len(h.launches) == 2 and decoding == 4
            assert h.count("gen_iterations_total") > decoding
    asyncio.run(go())


def test_the_counter_readers_read_the_programs_scrapes(dec_rt):  # noqa: F811
    """Two scrapes of the program's own /metrics text around a busy stretch,
    as run.py takes them: the serial phases an iteration, the first token and
    the gap, each a number; the eight phases sum to the time between the
    scrapes."""
    model, _ = dec_rt

    async def go(eng, metrics):
        await eng.submit(dec_item(model, 5))  # warm: the first scrape finds every family
        await asyncio.sleep(0.02)
        start, t0 = prom.parse(metrics.render_prometheus()), eng._phase_t
        await asyncio.gather(*[eng.submit(dec_item(model, 4 + i, max_new=8, first=1 + 9 * i))
                               for i in range(6)])
        await asyncio.sleep(0.02)
        return prom.delta(prom.parse(metrics.render_prometheus()), start), eng._phase_t - t0, eng.name

    delta, wall, name = run_engine(dec_rt, go)  # wall: between the loop's last boundaries at the scrapes
    run = {"metrics_delta": delta, "model_name": name, "notes": []}
    by_phase = gen_loop.loop_seconds(run)
    assert sum(by_phase.values()) == pytest.approx(wall, abs=1e-4)
    iters = sum(prom.select(delta, "gen_iterations_total", model=name).values())
    serial = reader("gen_loop_serial_ms_per_iter")(run)
    assert serial == pytest.approx(1e3 * sum(by_phase[p] for p in gen_loop.SERIAL_PHASES) / iters)
    assert 0 < serial < 1e3 * wall / iters
    assert "the eight phases sum to" in run["notes"][0]
    first = reader("gen_first_token_ms_p50")(run)
    p50, p95 = reader("gen_token_gap_ms_p50")(run), reader("gen_token_gap_ms_p95")(run)
    assert 0 < first < 1e3 * wall and 0 < p50 <= p95 < 1e3 * wall
    assert sum(v for k, v in delta.items() if k.startswith("gen_first_unit_ms_count")) == 6


# -- one step queued ahead (ISSUE 41) -----------------------------------------------------------

def test_steps_ahead_reads_none_for_a_lone_one_step_request_and_all_but_the_first_of_a_busy_stretch(dec_rt):  # noqa: F811
    """`gen_steps_ahead_total` counts, beside `gen_iterations_total`, the steps
    dispatched while the step before them was unread. A request of one token
    is done at its arming: out(0) says so, and step 1, dispatched ahead of that
    reading, is dropped unread once no lane is left (it counts in neither).
    A busy stretch of n steps accounted for has n - 1 of them ahead."""
    model, _ = dec_rt

    async def go(eng, metrics):
        iters = metrics.counter(f"gen_iterations_total{{model={eng.name}}}")
        ahead = metrics.counter(f"gen_steps_ahead_total{{model={eng.name}}}")
        assert (await eng.submit(dec_item(model, 5, max_new=1)))["n_tokens"] == 1
        await asyncio.sleep(0.05)  # the loop reaches its wait
        lone = (iters.value, ahead.value, eng._ahead)
        await asyncio.gather(eng.submit(dec_item(model, 6, max_new=7, first=30)),
                             eng.submit(dec_item(model, 4, max_new=3, first=50)))
        await asyncio.sleep(0.05)
        return lone, (iters.value, ahead.value, eng._ahead)

    (i1, a1, left1), (i2, a2, left2) = run_engine(dec_rt, go)
    assert (i1, a1, left1) == (1, 0, None)
    assert i2 - i1 >= 6 and a2 - a1 == (i2 - i1) - 1 and left2 is None


def test_the_step_ahead_reader_reads_the_programs_scrapes_and_none_from_the_parents(dec_rt):  # noqa: F811
    model, _ = dec_rt

    async def go(eng, metrics):
        await eng.submit(dec_item(model, 5))  # warm: the first scrape finds every family
        await asyncio.sleep(0.02)
        start = prom.parse(metrics.render_prometheus())
        await asyncio.gather(*[eng.submit(dec_item(model, 4 + i, max_new=8, first=1 + 9 * i))
                               for i in range(6)])
        await asyncio.sleep(0.02)
        return prom.delta(prom.parse(metrics.render_prometheus()), start), eng.name

    delta, name = run_engine(dec_rt, go)
    run = {"metrics_delta": delta, "model_name": name, "notes": []}
    iters = sum(prom.select(delta, "gen_iterations_total", model=name).values())
    got = reader("gen_step_ahead_pct")(run)
    assert got == pytest.approx(100.0 * (iters - 1) / iters) and 80 < got < 100
    # the parent's program has no such counter: the same scrapes without it
    parent = {k: v for k, v in delta.items() if not k.startswith("gen_steps_ahead_total")}
    assert len(parent) == len(delta) - 1
    assert reader("gen_step_ahead_pct")({**run, "metrics_delta": parent}) is None
    assert reader("gen_step_ahead_pct")({}) is None
    # and it stands in BENCHMARK.json as the generation engine's, for the generating cells
    entry = next(m for m in spec.load_benchmark()["per_layer"] if m["name"] == "gen_step_ahead_pct")
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"], entry["better"]) == (
        "generation engine", "items_per_s", "program_counter", "%", "higher")
    assert len(entry["workloads"]) == 13


# The loop of ISSUE 41 in the reader's eyes. Times in ms, window_s = 0.100, the chip busy from 0 to 92:
# Chip  S1 jit_step [0, 20)  S2 [20, 40)  X jit_extract [40, 41)  P jit_prefill_fn [41, 51)  S3 [51, 71)
#       S4 [71, 91)  X [91, 92)
# Pass 2: gen_step(2) [1, 1.4) queues S2 behind S1, which runs; gen_fetch [1.4, 20.5) waits for S1's
#         out-block: it carries iter 1, its STEP's; account, emit, retire with gen_extract(2) [24, 24.3)
# Pass 3: gen_pack(3), gen_prefill(3) [27.5, 28) queue behind S2; gen_step(3) [29.5, 30) -> S3 at 51;
#         gen_fetch(2) [30, 40.6); pass 4: gen_step(4) [44, 44.4) -> S4 at 71; gen_fetch(3) [44.4, 71.5);
#         gen_extract(4) [74, 74.3); pass 5: gen_fetch(4) [76, 91.4)
AHEAD_CHIP = [("jit_step(7)", 0, 20), ("jit_step(7)", 20, 20), ("jit_extract(5)", 40, 1),
              ("jit_prefill_fn(9)", 41, 10), ("jit_step(7)", 51, 20), ("jit_step(7)", 71, 20),
              ("jit_extract(5)", 91, 1)]
AHEAD_PHASES = [(2, "step", 0.5, 21), (2, "account", 21, 23), (2, "emit", 23, 23.5), (2, "retire", 23.5, 25),
                (3, "sweep", 25, 25.5), (3, "admit", 25.5, 26), (3, "prefill", 26, 29), (3, "step", 29, 41),
                (3, "account", 41, 43), (3, "emit", 43, 43.2), (3, "retire", 43.2, 43.5),
                (4, "sweep", 43.5, 43.8), (4, "step", 43.8, 72), (4, "account", 72, 73.5),
                (4, "retire", 73.5, 75), (5, "sweep", 75, 75.5), (5, "step", 75.5, 92)]


def ahead_workers(fetch_iter_is_the_steps: bool):
    f = 0 if fetch_iter_is_the_steps else 1   # what a fetch would carry were it its pass's number
    return [("fetch", "gen_step", 2, 1, 1.4), ("fetch", "gen_fetch", 1 + f, 1.4, 20.5),
            ("fetch", "gen_extract", 2, 24, 24.3), ("h2d", "gen_pack", 3, 26.5, 27.5),
            ("h2d", "gen_prefill", 3, 27.5, 28), ("fetch", "gen_step", 3, 29.5, 30),
            ("fetch", "gen_fetch", 2 + f, 30, 40.6), ("fetch", "gen_step", 4, 44, 44.4),
            ("fetch", "gen_fetch", 3 + f, 44.4, 71.5), ("fetch", "gen_extract", 4, 74, 74.3),
            ("fetch", "gen_fetch", 4 + f, 76, 91.4)]


def test_the_clock_check_pairs_calls_that_queue_a_whole_step_before_their_module_starts(monkeypatch):
    """With a step queued ahead every compiled call returns long before its
    module begins, so the check's upper bound (call.start <= module.start) is a
    step slack and the lower one (a fetch ends after ITS step's module) decides:
    it holds because `tpuserve.gen_fetch` carries its step's `iter`. The chip is
    never idle between programs, so nothing but the window's edges is idle."""
    monkeypatch.setitem(globals(), "CHIP", AHEAD_CHIP)
    att, reduced = analyse(xspace(phases=AHEAD_PHASES, workers=ahead_workers(True)))
    ck = att["clock"]
    assert ck["offset_ms"] == 0.0 and ck["shifts"] == (1, 0) and ck["pairs"] == (3, 1)
    assert ck["bounds_ms"] == pytest.approx((-0.4, 13.5))
    assert sorted(ck["call_to_module_ms"]) == pytest.approx([13.5, 19.0, 21.5, 27.0])
    assert sorted(ck["fetch_after_module_ms"]) == pytest.approx([0.4, 0.5, 0.6])
    idle_ms = {k: v * 1e3 for k, v in att["totals_s"].items()}
    assert idle_ms["unknown"] == pytest.approx(8.0) and sum(idle_ms.values()) == pytest.approx(8.0)
    assert sum(idle_ms.values()) == pytest.approx((reduced["window_s"] - reduced["busy_s"]) * 1e3)
    # were the fetch to carry its PASS's number, each would be held against the module of the step the
    # same hop dispatched, which ends a step later: no pairing within 10 ms, nothing attributed
    att, _ = analyse(xspace(phases=AHEAD_PHASES, workers=ahead_workers(False)))
    assert att["clock"] is None
