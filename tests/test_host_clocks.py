"""The host's own time (ISSUE 51): the collector's pauses counted by a
`gc.callbacks` entry and, while a profiler session is on, written as
`tpuserve.gc` marks from the thread that collected; the engine's loop in CPU
beside wall and its `account` phase in three parts; the process's CPU by the
role of its threads; and the benchmark's readers of all three
(`benchmark/host_time.py`) against hand-written scrapes and traces, the
parent's output among them."""

import _thread
import asyncio
import gc
import glob
import os
import threading
import time
from types import SimpleNamespace

import jax
import pytest
from jax.profiler import ProfileData

from benchmark import gen_loop, host_spans, host_time, prom, spec, trace_reduce
from tests import test_gen_loop as hand
from tests.test_genserve_paged import dec_item, dec_rt  # noqa: F401 — dec_rt is a fixture
from tpuserve import obs
from tpuserve.config import ModelConfig, ServerConfig
from tpuserve.genserve.engine import ACCOUNT_PARTS, LOOP_PHASES
from tpuserve.server import ServerState
from tpuserve.telemetry import TimeSeriesStore

NEW_METRICS = ("host_gc_pause_ms_per_s", "idle_host_gc_pct", "gen_loop_cpu_share_pct",
               "gen_account_trees_pct", "event_loop_cpu_ms_per_item", "decode_cpu_ms_per_item",
               "stage_cpu_ms_per_item", "runtime_cpu_ms_per_item")


def reader(name):
    return spec.load_module("layer_metrics", name).read


@pytest.fixture
def clocks():
    """A registry with the host's clocks on it, and the collector left as it
    was found: no automatic collection runs inside a test, so every one that
    is counted is one the test forced."""
    was_on, had = gc.isenabled(), obs._on_gc in gc.callbacks
    gc.disable()
    metrics = obs.Metrics()
    hc = obs.HostClocks(metrics)
    try:
        yield metrics, hc
    finally:
        hc.close()
        if had:
            gc.callbacks.append(obs._on_gc)
        if was_on:
            gc.enable()


def gc_counts(metrics, generation):
    return (metrics.counter(f"host_gc_collections_total{{generation={generation}}}").value,
            metrics.counter(f"host_gc_seconds_total{{generation={generation}}}").value)


def burn(seconds):
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        sum(range(2000))


# -- the collector's pauses ----------------------------------------------------------------------

@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_forced_collection_lands_in_both_families_under_its_generation(clocks, generation):
    metrics, _hc = clocks
    metrics.publish()
    before = {g: gc_counts(metrics, g) for g in (0, 1, 2)}
    t0 = time.perf_counter()
    gc.collect(generation)
    wall = time.perf_counter() - t0
    assert gc_counts(metrics, generation) == before[generation]  # a sum kept aside until it is read
    text = metrics.render_prometheus()                           # rendering /metrics reads it
    for g in (0, 1, 2):
        n, s = gc_counts(metrics, g)
        if g == generation:
            assert n == before[g][0] + 1 and 0 < s - before[g][1] <= wall
        else:
            assert (n, s) == before[g]
    assert f'host_gc_collections_total{{generation="{generation}"}}' in text
    assert f'host_gc_seconds_total{{generation="{generation}"}}' in text


def test_installing_twice_counts_once_and_close_removes_the_callback(clocks):
    metrics, hc = clocks
    hc.install()
    second = obs.HostClocks(obs.Metrics())  # one more registry: still one entry, the process's
    assert gc.callbacks.count(obs._on_gc) == 1
    metrics.publish()
    n0, _ = gc_counts(metrics, 2)
    gc.collect(2)
    metrics.publish()
    metrics.publish()  # what was published is not published again
    assert gc_counts(metrics, 2)[0] == n0 + 1
    second.close()
    assert obs._on_gc not in gc.callbacks
    gc.collect(2)
    metrics.publish()
    assert gc_counts(metrics, 2)[0] == n0 + 1  # nothing counts once it is gone


def test_the_server_installs_it_with_its_metrics_and_stop_removes_it():
    had = obs._on_gc in gc.callbacks
    cfg = ServerConfig(models=[ModelConfig(name="toy", family="toy", batch_buckets=[1],
                                           dtype="float32", num_classes=10, parallelism="single")],
                       decode_threads=1)
    state = ServerState(cfg)
    try:
        assert gc.callbacks.count(obs._on_gc) == 1
        text = state.metrics.render_prometheus()
        assert "host_gc_seconds_total" in text and 'host_thread_cpu_seconds_total{role="event_loop"}' in text
    finally:
        asyncio.run(state.stop())
    assert obs._on_gc not in gc.callbacks
    if had:
        gc.callbacks.append(obs._on_gc)


# -- the start-up heap, frozen while a server of the process serves (ISSUE 52) -------------------------

def toy_state():
    state = ServerState(ServerConfig(
        models=[ModelConfig(name="toy", family="toy", batch_buckets=[1], dtype="float32",
                            num_classes=10, parallelism="single")], decode_threads=1))
    state.build()
    return state


def frozen_gauge(state):
    state.metrics.publish()
    return state.metrics.gauge("host_gc_frozen_objects").value


def still(held, now=None):
    """The frozen count as it stands (or as the gauge read it), which has to be
    `held` less the few frozen objects whose last reference went since (they are
    freed like any other)."""
    now = gc.get_freeze_count() if now is None else now
    assert 0.99 * held <= now <= held
    return now


def test_a_started_server_holds_the_heap_frozen_and_a_stopped_one_leaves_it_as_it_was():
    async def go():
        before, thresholds, was_on = gc.get_freeze_count(), gc.get_threshold(), gc.isenabled()
        state = toy_state()
        assert gc.get_freeze_count() == before and frozen_gauge(state) == before  # built is not ready
        await state.start()
        try:
            held = gc.get_freeze_count()
            assert held > max(before, 10_000) and still(held, frozen_gauge(state))  # jax alone is more
            assert 'host_gc_frozen_objects ' in state.metrics.render_prometheus()
            # nothing else of the collector is touched: what comes after the freeze is collected as before
            assert gc.get_threshold() == thresholds and gc.isenabled() == was_on
            ring = []
            ring.append(ring)
            del ring
            assert gc.collect() >= 1 and still(held)
            state.host_clocks.freeze_heap()  # once, however often it is asked
            assert still(held)
        finally:
            await state.stop()
        # all of it is given back (the few hundred objects the interpreter itself starts with frozen too)
        assert gc.get_freeze_count() == frozen_gauge(state) <= before
    asyncio.run(go())


def test_two_servers_of_a_process_freeze_once_and_the_last_to_stop_unfreezes():
    async def go():
        before = gc.get_freeze_count()
        a, b = toy_state(), toy_state()
        await a.start()
        held = gc.get_freeze_count()
        await b.start()  # what B allocated since is not worth a second walk
        try:
            assert still(held) > before
            await a.stop()
            assert still(held) and still(held, frozen_gauge(b))  # B still serves
        finally:
            await b.stop()
        assert gc.get_freeze_count() <= before
    asyncio.run(go())


def test_closing_clocks_that_never_froze_leaves_another_servers_freeze_alone():
    metrics = obs.Metrics()
    had = obs._on_gc in gc.callbacks
    holder, other = obs.HostClocks(metrics), obs.HostClocks(obs.Metrics())
    before = gc.get_freeze_count()
    try:
        holder.freeze_heap()
        held = gc.get_freeze_count()
        assert held > before
        other.close()  # it holds nothing
        assert still(held)
        holder.close()
        assert gc.get_freeze_count() <= before
        holders = obs._heap_holders
        holder.close()  # twice is once
        assert obs._heap_holders == holders >= 0
    finally:
        holder.close()
        if had:
            gc.callbacks.append(obs._on_gc)


def session(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # the level the benchmark's traced run records at
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def spans_of(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return host_spans.read_profile(ProfileData.from_file(path))["spans"], path


def test_a_long_collection_is_one_mark_on_its_threads_line_and_a_short_one_is_none(clocks, tmp_path):
    metrics, _hc = clocks
    heap = [{"k": [i]} for i in range(200_000)]  # a full collection walks these: milliseconds
    metrics.publish()
    before = {g: gc_counts(metrics, g) for g in (0, 2)}

    def collect():
        with obs.trace_span("tpuserve.probe"):  # what else this thread's line holds
            pass
        gc.collect(2)
        gc.collect(0)  # the young generation is empty now: microseconds, no mark

    session(str(tmp_path))
    try:
        t = threading.Thread(target=collect, name="pipe-fetch_0")
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    del heap
    spans, _path = spans_of(str(tmp_path))
    marks = [s for s in spans if s["name"] == "gc"]
    metrics.publish()
    assert gc_counts(metrics, 0)[0] == before[0][0] + 1 and gc_counts(metrics, 2)[0] == before[2][0] + 1
    assert len(marks) == 1, marks
    (mark,) = marks
    assert int(mark["args"]["generation"]) == 2 and int(mark["args"]["collected"]) >= 0
    assert {"dur_us", "ago_us"} <= set(mark["args"])
    # the mark is the interval the counter counted, placed as `tpuserve.gen_loop` marks are
    seconds = gc_counts(metrics, 2)[1] - before[2][1]
    assert seconds >= obs.GC_MARK_S
    assert (mark["t1"] - mark["t0"]) / 1e9 == pytest.approx(seconds, abs=2e-6)
    (probe,) = [s for s in spans if s["name"] == "probe"]
    assert mark["line"] == probe["line"] and mark["t0"] >= probe["t1"]
    # the short one was counted and not marked
    assert gc_counts(metrics, 0)[1] - before[0][1] < obs.GC_MARK_S


def test_no_session_no_mark_and_the_callback_never_imports_jax(clocks, monkeypatch):
    """With no profiler session on a long collection costs the two calls and
    writes nothing; where jax was never resolved (the router) the callback
    does not resolve it."""
    metrics, _hc = clocks
    heap = [{"k": [i]} for i in range(100_000)]
    calls = []
    monkeypatch.setattr(obs, "trace_mark", lambda *a, **k: calls.append(a))
    monkeypatch.setattr(obs, "_annotation", None)
    gc.collect(2)
    assert calls == []
    del heap


def test_the_telemetry_stores_sample_reads_the_sums_too(clocks):
    metrics, _hc = clocks
    store = TimeSeriesStore(metrics)
    store.sample(now=1.0)
    gc.collect(1)
    store.sample(now=2.0)
    assert store.counter_increase("host_gc_collections_total{generation=1}", now=2.0) == 1.0
    assert store.counter_increase("host_thread_cpu_seconds_total{role=event_loop}", now=2.0) >= 0.0


# -- the server's CPU by thread --------------------------------------------------------------------

@pytest.mark.parametrize("name, role", [
    ("MainThread", "event_loop"), ("tpuserve-ingest-1", "event_loop"), ("tpuserve_3", "decode"),
    ("pipe-fetch_0", "stage"), ("pipe-postproc_1", "stage"), ("compile_2", "compile"),
    ("tpuserve-telemetry", "other"), ("asyncio_0", "other"), (None, "runtime")])
def test_a_threads_role_is_what_the_program_named_it(name, role):
    assert obs.thread_role(name) == role and role in obs.THREAD_ROLES


def cpu_by_role(metrics):
    metrics.publish()
    return {r: metrics.counter(f"host_thread_cpu_seconds_total{{role={r}}}").value
            for r in obs.THREAD_ROLES}


def test_a_named_busy_threads_cpu_lands_in_its_role_and_stays_when_the_thread_ends(clocks):
    metrics, _hc = clocks
    before = cpu_by_role(metrics)
    burned, done = threading.Event(), threading.Event()

    def work():
        burn(0.3)
        burned.set()
        done.wait()

    t = threading.Thread(target=work, name="pipe-assemble_0")
    t.start()
    assert burned.wait(30)
    alive = cpu_by_role(metrics)
    # user + system as the kernel charges them, in clock ticks of 10 ms
    assert alive["stage"] - before["stage"] >= 0.15
    assert alive["decode"] == before["decode"] and alive["compile"] == before["compile"]
    done.set()
    t.join()
    after = cpu_by_role(metrics)
    assert all(after[r] >= alive[r] for r in obs.THREAD_ROLES)  # no role's sum goes back
    assert after["stage"] == alive["stage"]


def test_a_thread_python_did_not_start_is_the_runtimes(clocks):
    metrics, _hc = clocks
    before = cpu_by_role(metrics)
    state = {}

    def work():
        state["tid"] = threading.get_native_id()
        burn(0.3)
        state["burned"] = True
        while not state.get("stop"):
            time.sleep(0.005)

    _thread.start_new_thread(work, ())  # `threading.enumerate()` does not know it
    while not state.get("burned"):
        time.sleep(0.01)
    assert state["tid"] not in {t.native_id for t in threading.enumerate()}
    alive = cpu_by_role(metrics)
    state["stop"] = True
    assert alive["runtime"] - before["runtime"] >= 0.15
    # this thread is the main one: its own CPU is the event loop's
    burn(0.1)
    assert cpu_by_role(metrics)["event_loop"] - alive["event_loop"] >= 0.05


# -- the loop's phases in CPU beside wall, and `account` by part --------------------------------------

def test_the_loops_cpu_is_no_more_than_its_wall_and_accounts_parts_no_more_than_the_phase(dec_rt):  # noqa: F811
    model, _ = dec_rt

    async def go(eng, metrics):
        await asyncio.gather(*[eng.submit(dec_item(model, 4 + i, max_new=10, first=1 + 9 * i))
                               for i in range(6)])
        await asyncio.sleep(0.02)  # the loop reaches its wait
        def by(family, label, names):
            return {n: metrics.counter(f"{family}{{model={eng.name},{label}={n}}}").value for n in names}
        return (by("gen_loop_seconds_total", "phase", LOOP_PHASES),
                by("gen_loop_cpu_seconds_total", "phase", LOOP_PHASES),
                by("gen_account_seconds_total", "part", ACCOUNT_PARTS), eng.pipeline_stats()["loop"])

    wall, cpu, parts, stats = hand.run_engine(dec_rt, go)
    assert set(cpu) == set(LOOP_PHASES) and set(parts) == {"finish", "trees", "sums"}
    for phase in ("sweep", "account"):  # no await inside: CPU is the thread's own, and under its wall
        assert 0 < cpu[phase] <= wall[phase] + 1e-4, phase
    assert all(0 <= cpu[p] for p in LOOP_PHASES) and sum(cpu.values()) <= sum(wall.values()) + 1e-3
    assert all(v > 0 for v in parts.values())
    assert sum(parts.values()) <= wall["account"] + 1e-6
    assert sum(parts.values()) == pytest.approx(wall["account"], rel=0.2)  # less a few counter updates
    # /stats: CPU ms an iteration beside the wall it has, and account's parts
    assert set(stats["cpu_ms_per_iteration"]) == set(LOOP_PHASES)
    assert stats["cpu_ms_per_iteration"]["account"] <= stats["ms_per_iteration"]["account"] + 0.1
    assert set(stats["account_ms_per_iteration"]) == set(ACCOUNT_PARTS)


def test_the_loops_readers_read_the_programs_scrapes(dec_rt):  # noqa: F811
    model, _ = dec_rt

    async def go(eng, metrics):
        await eng.submit(dec_item(model, 5))  # warm: the first scrape finds every family
        await asyncio.sleep(0.02)
        start = prom.parse(metrics.render_prometheus())
        await asyncio.gather(*[eng.submit(dec_item(model, 4 + i, max_new=8, first=1 + 9 * i))
                               for i in range(6)])
        await asyncio.sleep(0.02)
        return prom.delta(prom.parse(metrics.render_prometheus()), start), eng.name

    delta, name = hand.run_engine(dec_rt, go)
    run = {"metrics_delta": delta, "model_name": name, "notes": []}
    share, trees = reader("gen_loop_cpu_share_pct")(run), reader("gen_account_trees_pct")(run)
    assert 0 < share <= 100.5 and 0 < trees < 100
    cpu, wall = host_time.loop_cpu_seconds(run), gen_loop.loop_seconds(run)
    assert share == pytest.approx(100 * (cpu["sweep"] + cpu["account"]) / (wall["sweep"] + wall["account"]))
    parts = host_time.account_parts(run)
    assert trees == pytest.approx(100 * parts["trees"] / sum(parts.values()))
    assert "account=" in run["notes"][0] and "trees=" in run["notes"][1]
    # the parent's program: the same scrapes without the two families
    parent = {k: v for k, v in delta.items()
              if not k.startswith(("gen_loop_cpu_seconds_total", "gen_account_seconds_total"))}
    assert len(parent) == len(delta) - len(LOOP_PHASES) - len(ACCOUNT_PARTS)
    for metric in ("gen_loop_cpu_share_pct", "gen_account_trees_pct"):
        assert reader(metric)({**run, "metrics_delta": parent}) is None
    assert reader("gen_loop_serial_ms_per_iter")({**run, "metrics_delta": parent}) is not None  # as before


# -- the readers against hand-written scrapes ---------------------------------------------------------

START = """
host_gc_seconds_total{generation="0"} 1.0
host_gc_seconds_total{generation="1"} 0.5
host_gc_seconds_total{generation="2"} 2.0
host_gc_collections_total{generation="0"} 1000
host_gc_collections_total{generation="1"} 90
host_gc_collections_total{generation="2"} 8
host_thread_cpu_seconds_total{role="event_loop"} 10.0
host_thread_cpu_seconds_total{role="decode"} 4.0
host_thread_cpu_seconds_total{role="stage"} 3.0
host_thread_cpu_seconds_total{role="compile"} 60.0
host_thread_cpu_seconds_total{role="runtime"} 20.0
host_thread_cpu_seconds_total{role="other"} 1.0
items_total{model="m"} 100
"""
END = """
host_gc_seconds_total{generation="0"} 1.09
host_gc_seconds_total{generation="1"} 0.53
host_gc_seconds_total{generation="2"} 2.18
host_gc_collections_total{generation="0"} 1900
host_gc_collections_total{generation="1"} 150
host_gc_collections_total{generation="2"} 10
host_thread_cpu_seconds_total{role="event_loop"} 46.0
host_thread_cpu_seconds_total{role="decode"} 13.0
host_thread_cpu_seconds_total{role="stage"} 7.5
host_thread_cpu_seconds_total{role="compile"} 60.0
host_thread_cpu_seconds_total{role="runtime"} 38.0
host_thread_cpu_seconds_total{role="other"} 1.9
items_total{model="m"} 1000
"""


def scraped(**over):
    delta = prom.delta(prom.parse(END), prom.parse(START))
    return {"metrics_delta": delta, "model_name": "m", "notes": [], "server_cpu_s": 68.9,
            "load": SimpleNamespace(seconds=45.0, items_in_window=900), **over}


def test_the_collectors_pause_a_second_from_two_scrapes():
    run = scraped()
    # 90 + 30 + 180 ms inside the collector in 45 s
    assert reader("host_gc_pause_ms_per_s")(run) == pytest.approx(300 / 45)
    (note,) = run["notes"]
    assert "gen 0: 900 collections, 90.0 ms, mean 0.100 ms" in note
    assert "gen 2: 2 collections, 180.0 ms, mean 90.000 ms" in note


@pytest.mark.parametrize("role, ms", [("event_loop", 40.0), ("decode", 10.0), ("stage", 5.0),
                                      ("runtime", 20.0)])
def test_a_roles_cpu_an_item_from_two_scrapes(role, ms):
    run = scraped()
    assert reader(f"{role}_cpu_ms_per_item")(run) == pytest.approx(ms)
    if role == "event_loop":  # the one note that prints all six beside the process's CPU from outside
        (note,) = run["notes"]
        assert "compile=0.0000" in note and "other=1.0000" in note
        assert "the six sum to 76.0000 beside server_cpu_ms_per_item 76.5556" in note
    else:
        assert run["notes"] == []


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_gives_none_on_the_parents_output_and_raises_nothing(name):
    """The parent's scrapes hold none of the families and its trace no mark;
    an untraced or a rehearsed run holds no trace at all; and a BERT cell's
    recorded trace from the chip is read through its file as run.py does."""
    assert reader(name)({}) is None
    assert reader(name)({"trace": None, "xplane": None, "metrics_delta": {}, "model_name": "m"}) is None
    parent = scraped(metrics_delta={'items_total{model="m"}': 900.0,
                                    'gen_loop_seconds_total{model="m",phase="account"}': 3.0,
                                    'gen_iterations_total{model="m"}': 2000.0})
    path = os.path.join(spec.HERE, "fixtures", "recorded_v5e_spans.xplane.pb")
    parent.update(trace=trace_reduce.reduce_file(path, 3.0), xplane=path)
    assert reader(name)(parent) is None and parent["notes"] == []


# -- `idle_host_gc_pct` against hand-written traces -----------------------------------------------------
# tests/test_gen_loop.py's trace (its header has the chip's programs and the gaps: A [20, 32), B1 [50, 54),
# B2 [55, 70), C [80, 88) in a window [-5, 95) of 100 ms), with three collections written into it:
#   G1 [57, 67)  generation 2, on the loop's line, all of it inside B2 (the loop: retire, sweep, wait) -> 10 ms idle
#   G2 [30, 35)  generation 1, on the h2d thread's line: A ends at 32, then P1 runs                     ->  2 ms idle
#   G3 [43, 48)  generation 1, on the loop's line, all of it beside a gap: S2 runs [42, 50)             ->  0
GC_MARKS = [("loop", 57, 67, 2, 4321), ("h2d", 30, 35, 1, 12), ("loop", 43, 48, 1, 7)]


def with_marks(host_shift_ms=0.0, marks=GC_MARKS):
    """`read_profile`'s output for the hand-written trace, and the same with
    the collections' marks among its spans, as `read_profile` places a mark:
    [t0, t1) from `dur_us` and `ago_us`, on the line of the thread that wrote it."""
    profile = ProfileData.from_text_proto(hand.xspace(host_shift_ms=host_shift_ms))
    plain = host_spans.read_profile(profile)
    lines = {"loop": next(s["line"] for s in plain["spans"] if s["name"] == "gen_loop"),
             "h2d": next(s["line"] for s in plain["spans"] if s["name"] == "gen_pack")}
    marked = {**plain, "spans": plain["spans"] + [
        {"name": "gc", "line": lines[th], "t0": int((t0 + host_shift_ms) * 1e6),
         "t1": int((t1 + host_shift_ms) * 1e6),
         "args": {"generation": gen, "collected": n, "dur_us": (t1 - t0) * 1e3, "ago_us": 3}}
        for th, t0, t1, gen, n in marks]}
    return plain, marked


def test_a_gap_under_a_mark_is_the_collectors_and_a_mark_beside_a_gap_is_nothing():
    _plain, marked = with_marks()
    gi = host_time.gc_idle(marked, hand.WINDOW_S)
    assert gi["window_s"] == pytest.approx(0.100) and gi["idle_s"] * 1e3 == pytest.approx(12.0)
    by_start = {round(p["start_ms"]): p for p in gi["pauses"]}  # from the window's start at -5
    assert sorted(by_start) == [35, 48, 62]
    assert by_start[62]["idle_ms"] == pytest.approx(10.0) and by_start[62]["generation"] == 2
    assert by_start[62]["collected"] == 4321 and by_start[62]["phase"] == "wait (iter 3)"
    assert by_start[62]["line_writes"][0] == "gen_loop"          # the loop's own thread collected
    assert by_start[35]["idle_ms"] == pytest.approx(2.0) and by_start[35]["span"] == "gen_prefill"
    assert by_start[35]["line_writes"] == ["gen_pack", "gen_prefill"]
    assert by_start[48]["idle_ms"] == 0.0 and by_start[48]["phase"] == "step (iter 2)"
    assert gi["gaps"] == []  # no gap of 20 ms here
    lines = host_time.gc_idle_notes(gi, 0)
    assert "3 tpuserve.gc marks" in lines[0] and "12.0 ms of the device's idle gaps" in lines[0]
    assert len(lines) == 1  # and no pause of 20 ms


@pytest.mark.parametrize("shift_ms", [2.0, -3.0])
def test_the_planes_offset_is_removed_before_a_mark_is_laid_over_the_gaps(shift_ms, monkeypatch):
    """A host clock off by a constant moves the marks with the loop's spans:
    the offset `gen_loop.py` finds for the run brings them back."""
    _plain, marked = with_marks(host_shift_ms=shift_ms)
    assert host_time.gc_idle(marked, hand.WINDOW_S)["idle_s"] * 1e3 != pytest.approx(12.0)
    monkeypatch.setattr(host_time, "read_trace", lambda path: marked)
    att = gen_loop.attribute(marked, hand.WINDOW_S)
    run = scraped(trace=trace_reduce.reduce_profile(ProfileData.from_text_proto(hand.xspace()), hand.WINDOW_S),
                  xplane="a-file", gen_loop=att)
    assert reader("idle_host_gc_pct")(run) == pytest.approx(12.0)
    assert f"offset {-shift_ms:.3f} ms removed" in run["notes"][0]
    # where the run's attribution is not there yet the reader works the offset out itself
    run = scraped(trace=run["trace"], xplane="a-file")
    assert reader("idle_host_gc_pct")(run) == pytest.approx(12.0)
    # a program with the counters and no collection of 1 ms in the traced window: 0, not None
    monkeypatch.setattr(host_time, "read_trace", lambda path: with_marks()[0])
    assert reader("idle_host_gc_pct")(scraped(trace=run["trace"], xplane="a-file")) == 0.0


def test_a_long_pause_and_a_long_gap_are_listed_one_by_one():
    """Times in ms: the chip runs [0, 10) and [40, 50); a collection of
    generation 2 takes [12, 38) in a decode thread while the loop waits for
    its step's out-block."""
    ms = 1_000_000
    data = {"ops": [(0, 10 * ms), (40 * ms, 50 * ms)], "modules": [], "spans": [
        {"name": "gc", "line": ("/host:CPU", 4), "t0": 12 * ms, "t1": 38 * ms,
         "args": {"generation": 2, "collected": 99}},
        {"name": "tokenize", "line": ("/host:CPU", 4), "t0": 1 * ms, "t1": 11 * ms, "args": {}},
        {"name": "gen_loop", "line": ("/host:CPU", 1), "t0": 5 * ms, "t1": 41 * ms,
         "args": {"phase": "step", "iter": 9}},
        {"name": "gen_fetch", "line": ("/host:CPU", 2), "t0": 6 * ms, "t1": 40 * ms, "args": {"iter": 8}}]}
    gi = host_time.gc_idle(data, 0.050)
    assert gi["idle_s"] * 1e3 == pytest.approx(26.0)
    assert gi["gaps"] == [{"start_ms": pytest.approx(10.0), "ms": pytest.approx(30.0),
                           "under_gc_ms": pytest.approx(26.0)}]
    lines = host_time.gc_idle_notes(gi, 0)
    assert len(lines) == 3
    assert "pause 26.0 ms at +12 ms, generation 2, collected 99" in lines[1]
    assert "(which writes tokenize)" in lines[1] and "in phase step (iter 9), span gen_fetch" in lines[1]
    assert "device gap 30.0 ms at +10 ms: 26.0 ms of it under a tpuserve.gc mark" in lines[2]
    assert host_time.gc_idle({**data, "ops": []}, 0.050) is None  # no operation on a chip


def test_marks_of_the_collector_leave_the_loops_attribution_as_it_was():
    plain, marked = with_marks()
    a, b = gen_loop.attribute(plain, hand.WINDOW_S), gen_loop.attribute(marked, hand.WINDOW_S)
    assert a["totals_s"] == b["totals_s"] and a["detail_s"] == b["detail_s"]
    assert a["gaps"] == b["gaps"] and a["clock"] == b["clock"]
    assert {k: v * 1e3 for k, v in b["totals_s"].items()} == pytest.approx(hand.BY_HAND_MS, abs=0.01)


def test_marks_of_the_collector_leave_the_batched_paths_attribution_as_it_was():
    """The chip's recording of a BERT cell (PR 25's fixture), with a
    collection written over its longest gap."""
    path = os.path.join(spec.HERE, "fixtures", "recorded_v5e_spans.xplane.pb")
    plain = host_spans.read_profile(ProfileData.from_file(path))
    a = host_spans.attribute(plain, 3.0)
    lo = min(s for s, _ in plain["ops"])
    g = a["gaps"][0]
    t0 = lo + int(g["start_ms"] * 1e6)
    line = next(s["line"] for s in plain["spans"] if s["name"] == "tokenize")
    marked = {**plain, "spans": plain["spans"] + [
        {"name": "gc", "line": line, "t0": t0, "t1": t0 + int(g["ms"] * 1e6),
         "args": {"generation": 2, "collected": 5}}]}
    b = host_spans.attribute(marked, 3.0)
    assert a["totals_s"] == b["totals_s"] and a["gaps"] == b["gaps"] and a["clock"] == b["clock"]
    assert host_time.gc_idle(marked, 3.0)["idle_s"] > 0


# -- BENCHMARK.json ----------------------------------------------------------------------------------

def test_every_new_metric_has_its_reader_and_lists_only_cells():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(NEW_METRICS)))   # appended together; later PRs append after them
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    generating = next(m for m in bench["per_layer"] if m["name"] == "gen_step_ahead_pct")["workloads"]
    for name in NEW_METRICS:
        m = entries[name]
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", f"{name}.py"))
        assert callable(reader(name)) and m["moves"] == "items_per_s"
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if name.startswith("gen_"):
            assert m["workloads"] == generating and set(generating) <= set(cells)
            assert m["layer"] == "generation engine"
        else:
            assert "workloads" not in m  # every cell, as `server_cpu_ms_per_item`
            assert m["layer"] == ("device" if name == "idle_host_gc_pct" else "HTTP ingest")
        assert m["source"] == ("device_trace" if name == "idle_host_gc_pct" else "program_counter")
        assert m["better"] == ("higher" if name == "gen_loop_cpu_share_pct" else "lower")
    assert len(bench["per_layer"]) >= 79 and len(cells) >= 9
