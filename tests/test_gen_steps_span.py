"""One `gen_steps` span a request, nothing a lane a step (ISSUE 52): what the
engine's loop leaves in a request's tree for the steps it rode, through the toy
paged decoder of tests/test_decoder.py with the loop's passes made by hand
(tests/test_genserve_paged.py `ByHand`), and what it leaves for the collector."""

import asyncio
import gc
import time

import pytest

from tests.test_genserve_paged import (ByHand, dec_item, dec_rt,  # noqa: F401 — dec_rt is a fixture
                                       paged_over)
from tpuserve.batcher import DeadlineExceeded
from tpuserve.config import GenserveConfig
from tpuserve.genserve import engine as engine_mod
from tpuserve.genserve.engine import STEP_RECORD, GenEngine, _StepRecord
from tpuserve.obs import Metrics, TraceContext
from tpuserve.runtime import build_runtime

PAGED = dict(kv_page_tokens=4, prefill_chunk=16)


def by_hand(fix, monkeypatch, **over):
    return ByHand(fix, 0, monkeypatch, **paged_over(**PAGED, **over))


def named(ctx, name):
    return [s for s in ctx.spans if s["name"] == name]


def held_step(eng, seq):
    """(start, end) of step `seq` on the wall clock, from the engine's record."""
    at = seq % eng._steps.size
    assert eng._steps._seq[at] == seq
    return eng._steps._end[at] - eng._steps._step_s[at], eng._steps._end[at]


async def until_done(h, *futures, limit=80):
    for _ in range(limit):
        if all(f.done() for f in futures):
            return
        await h.iterate()
    raise AssertionError("the requests did not finish")


# -- the record alone -----------------------------------------------------------------------------

def test_the_record_gives_a_ranges_bounds_its_longest_step_and_what_it_no_longer_holds():
    rec = _StepRecord(8)
    assert rec.ridden(0, 3) is None  # nothing written yet
    for seq in range(20):
        rec.put(seq, 100.0 + seq, 0.5 if seq != 15 else 0.9)
    # steps 13..18, all held (12..19 are)
    assert rec.ridden(13, 6) == (pytest.approx(112.5), 118.0, {"longest_ms": 900.0, "longest_iteration": 2})
    # steps 5..17: 5..11 were written over, and with them nothing longer
    assert rec.ridden(5, 13) == (pytest.approx(111.5), 117.0,
                                 {"longest_ms": 900.0, "longest_iteration": 10, "held": 6})
    assert rec.ridden(2, 4) is None  # all written over
    # a step that was never recorded (dropped unread) is not taken for the one a turn older
    rec.put(21, 121.0, 0.7)
    assert rec.ridden(19, 3) == (pytest.approx(118.5), 121.0,
                                 {"longest_ms": 700.0, "longest_iteration": 2, "held": 2})
    assert all(not gc.is_tracked(a) for a in (rec._seq, rec._end, rec._step_s))
    assert STEP_RECORD >= 4096


# -- one span a request, whatever it rode -----------------------------------------------------------

@pytest.mark.parametrize("max_new", [2, 5, 12])
def test_a_request_that_rides_n_steps_retires_with_one_gen_steps_span_from_its_first_step_to_its_last(
        dec_rt, monkeypatch, max_new):  # noqa: F811
    model, _ = dec_rt

    async def go():
        async with by_hand(dec_rt, monkeypatch) as h:
            eng = h.eng
            warm = eng.submit(dec_item(model, 4, max_new=3))  # so that the request's first step is not step 0
            await h.iterate(2)
            ctx = TraceContext()
            fut = eng.submit(dec_item(model, 5, max_new=max_new, first=30), ctx=ctx)
            await h.iterate()
            info = next(eng.arena.peek(s) for s in eng.arena.active_slots() if eng.arena.peek(s).ctx is ctx)
            since = info.since_step
            assert since == eng._n_steps - 1 > 0
            await until_done(h, fut, warm)
            return ctx, since, info.iterations, (await fut)["n_tokens"], eng

    ctx, since, rode, n_tokens, eng = asyncio.run(go())
    assert n_tokens == max_new
    (span,), (retire,) = named(ctx, "gen_steps"), named(ctx, "retire")
    assert not named(ctx, "gen_step")
    # every step from its admission to the one that said `done` (the launch gives the first token itself)
    assert span["args"]["steps"] == rode == retire["args"]["iterations"] >= max_new - 1
    first, last = held_step(eng, since), held_step(eng, since + rode - 1)
    assert span["ts_us"] == pytest.approx(first[0] * 1e6, abs=1.0)
    assert span["ts_us"] + span["dur_us"] == pytest.approx(last[1] * 1e6, abs=1.0)
    assert span["tid"] == eng.name and span["args"]["slot"] == retire["args"]["slot"]
    assert "held" not in span["args"]
    took = [held_step(eng, s) for s in range(since, since + rode)]
    longest = max(range(rode), key=lambda i: took[i][1] - took[i][0])
    assert span["args"]["longest_iteration"] == longest
    assert span["args"]["longest_ms"] == pytest.approx((took[longest][1] - took[longest][0]) * 1e3, abs=1e-3)
    # behind its admission and before its retirement
    (admit,) = named(ctx, "admit") + named(ctx, "fold_in")
    assert admit["ts_us"] <= span["ts_us"] and span["ts_us"] + span["dur_us"] <= retire["ts_us"] + retire["dur_us"]


def test_a_tree_has_as_many_spans_after_twelve_steps_as_after_two(dec_rt, monkeypatch):  # noqa: F811
    model, _ = dec_rt

    async def go():
        async with by_hand(dec_rt, monkeypatch) as h:
            trees = []
            for max_new in (2, 12):
                ctx = TraceContext()
                fut = h.eng.submit(dec_item(model, 5, max_new=max_new), ctx=ctx)
                await until_done(h, fut)
                trees.append(ctx)
            return trees

    short, long = asyncio.run(go())
    assert named(long, "gen_steps")[0]["args"]["steps"] >= named(short, "gen_steps")[0]["args"]["steps"] + 10
    assert [s["name"] for s in short.spans] == [s["name"] for s in long.spans] \
        == ["queue", "admit", "gen_steps", "retire"]


def test_a_stretched_step_shows_as_the_longest_in_every_riders_span(dec_rt, monkeypatch):  # noqa: F811
    model, _ = dec_rt

    async def go():
        async with by_hand(dec_rt, monkeypatch) as h:
            eng = h.eng
            a, b = TraceContext(), TraceContext()
            fa = eng.submit(dec_item(model, 5, max_new=10), ctx=a)
            await h.iterate(3)
            fb = eng.submit(dec_item(model, 6, max_new=8, first=20), ctx=b)
            await h.iterate(3)
            sync, stretched = eng._step_sync, eng._n_steps - 1  # the step whose out-block the next pass waits for

            def slow(go_, prev, extracts):
                time.sleep(0.12)
                return sync(go_, prev, extracts)
            eng._step_sync = slow
            await h.iterate()
            eng._step_sync = sync
            infos = {id(eng.arena.peek(s).ctx): eng.arena.peek(s).since_step for s in eng.arena.active_slots()}
            await until_done(h, fa, fb)
            return a, b, infos, stretched

    a, b, since, stretched = asyncio.run(go())
    for ctx in (a, b):
        (span,) = named(ctx, "gen_steps")
        assert span["args"]["longest_ms"] >= 120.0
        assert since[id(ctx)] + span["args"]["longest_iteration"] == stretched
    assert since[id(a)] < since[id(b)]  # one step, two places among a request's own


@pytest.mark.parametrize("how", ["evict", "drain", "engine_failure", "evict_before_a_step"])
def test_a_request_that_goes_any_other_way_gets_its_span_too(dec_rt, monkeypatch, how):  # noqa: F811
    model, _ = dec_rt

    async def go():
        async with by_hand(dec_rt, monkeypatch) as h:
            eng = h.eng
            ctx = TraceContext()
            submit = eng.submit_stream if how == "drain" else eng.submit
            fut = submit(dec_item(model, 5, max_new=10), deadline_at=time.perf_counter() + 3600, ctx=ctx)
            fut = fut[0] if how == "drain" else fut
            await h.iterate(1 if how == "evict_before_a_step" else 5)
            rode = eng.arena.peek(0).iterations
            last = eng._n_steps - 2  # the newest step is queued ahead, unread
            if how.startswith("evict"):
                eng.arena.peek(0).deadline_at = time.perf_counter() - 1.0
                eng._evict_expired()
                with pytest.raises(DeadlineExceeded):
                    await fut
            elif how == "drain":
                eng._stream_kill_at = time.perf_counter() - 1.0
                eng._evict_expired()
            else:
                await eng._fail_active(RuntimeError("device said no"))
                with pytest.raises(RuntimeError, match="device said no"):
                    await fut
            return ctx, rode, last, eng

    ctx, rode, last, eng = asyncio.run(go())
    end = named(ctx, "engine_failure" if how == "engine_failure" else "evict")
    assert len(end) == 1 and end[0]["args"]["iterations"] == rode
    if how == "evict_before_a_step":
        assert rode == 0 and not named(ctx, "gen_steps")  # it rode none
        return
    (span,) = named(ctx, "gen_steps")
    assert span["args"]["steps"] == rode == 4 and span["args"]["slot"] == 0
    assert span["ts_us"] == pytest.approx(held_step(eng, 0)[0] * 1e6, abs=1.0)
    assert span["ts_us"] + span["dur_us"] == pytest.approx(held_step(eng, last)[1] * 1e6, abs=1.0)
    assert ctx.spans.index(span) < ctx.spans.index(end[0])


def test_a_request_older_than_the_record_keeps_its_count_and_says_what_was_held(dec_rt, monkeypatch):  # noqa: F811
    model, _ = dec_rt

    async def go():
        async with by_hand(dec_rt, monkeypatch) as h:
            h.eng._steps = _StepRecord(4)
            ctx = TraceContext()
            fut = h.eng.submit(dec_item(model, 5, max_new=10), ctx=ctx)
            await until_done(h, fut)
            return ctx, h.eng

    ctx, eng = asyncio.run(go())
    (span,), (retire,) = named(ctx, "gen_steps"), named(ctx, "retire")
    steps = span["args"]["steps"]
    # written a pass after the request's last step, before that pass's own record: the ring has its last four
    assert steps == retire["args"]["iterations"] >= 9 and span["args"]["held"] == 4
    assert steps - 4 <= span["args"]["longest_iteration"] < steps
    assert span["ts_us"] == pytest.approx(held_step(eng, steps - 4)[0] * 1e6, abs=1.0)


# -- what is left for the collector ----------------------------------------------------------------

LANES, STEPS = 8, 200


@pytest.fixture(scope="module")
def long_rt(tmp_path_factory):
    from tests.test_decoder import make_model
    model = make_model(tmp_path_factory.mktemp("long"), name="lg", max_new_tokens=STEPS + 8)
    rt = build_runtime(model, compile_forward=False)
    GenEngine(model, rt, Metrics(), GenserveConfig(slots=LANES, **paged_over(**PAGED))).compile()
    return model, rt


def test_two_hundred_steps_at_eight_lanes_leave_a_bounded_record_and_nothing_a_lane_a_step(
        long_rt, monkeypatch):
    model, _ = long_rt

    async def go():
        async with by_hand(long_rt, monkeypatch, slots=LANES) as h:
            eng = h.eng
            ctxs = [TraceContext() for _ in range(LANES)]
            futs = [eng.submit(dec_item(model, 4, max_new=STEPS + 8, first=1 + 5 * i), ctx=c)
                    for i, c in enumerate(ctxs)]
            await h.iterate(6)  # all admitted, prefilled and decoding
            assert eng.arena.n_active == LANES
            record = [id(a) for a in (eng._steps._seq, eng._steps._end, eng._steps._step_s)]
            spans = sum(len(c.spans) for c in ctxs)
            gc.collect()
            gc.disable()  # the count below is then what was allocated and not freed, and nothing else
            try:
                young = gc.get_count()[0]
                steps = eng._n_steps
                await h.iterate(STEPS)
                grew = gc.get_count()[0] - young
            finally:
                gc.enable()
            assert eng._n_steps - steps == STEPS and eng.arena.n_active == LANES
            # the trees did not grow, the record is the three arrays it was, and what the 1,600 lane-steps
            # left alive that the collector tracks is far under one object each (two dicts each before)
            assert sum(len(c.spans) for c in ctxs) == spans
            assert [id(a) for a in (eng._steps._seq, eng._steps._end, eng._steps._step_s)] == record
            assert len(eng._steps._end) == STEP_RECORD
            assert grew < LANES * STEPS / 4, grew
            await until_done(h, *futs)
            return ctxs, [await f for f in futs]

    ctxs, results = asyncio.run(go())
    assert all(r["n_tokens"] == STEPS + 8 for r in results)
    for c in ctxs:
        (span,) = named(c, "gen_steps")
        assert span["args"]["steps"] >= STEPS and len(c.spans) == 4


def test_no_option_chooses_the_per_step_form_and_the_collector_is_left_on():
    import inspect

    import tpuserve
    from tpuserve.genserve import arena
    src = inspect.getsource(engine_mod) + inspect.getsource(arena)
    assert '"gen_step"' not in src and "'gen_step'" not in src
    import glob
    import os
    for path in glob.glob(os.path.join(os.path.dirname(tpuserve.__file__), "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        assert "gc.disable" not in text and "gc.set_threshold" not in text, path
