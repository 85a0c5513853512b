"""Open-loop latency runs from the due time, and a straggler is attempted and
failed, never set aside."""

import asyncio
import time
from dataclasses import dataclass

import pytest
from aiohttp import web

from benchmark import loadgen


@dataclass
class Req:
    body: bytes = b"{}"
    items: int = 2
    cls: str = "c"


def test_latency_is_taken_from_the_due_time():
    r = loadgen.LoadResult(seconds=10.0)
    rec = loadgen._Recorder(r, t0=100.0)
    rec.record(Req(), due=101.0, sent=101.2, done=101.5, ok=True, why="")
    assert r.attempted == 1 and r.failed == 0
    assert r.latencies_ms == [pytest.approx(500.0)]
    assert r.late_ms == [pytest.approx(200.0)]
    assert r.items_in_window == 2


def test_window_accounting():
    r = loadgen.LoadResult(seconds=10.0)
    rec = loadgen._Recorder(r, t0=100.0)
    rec.record(Req(), due=99.0, sent=99.0, done=100.5, ok=True, why="")   # warm-up, answered inside
    rec.record(Req(), due=109.5, sent=109.5, done=110.5, ok=True, why="")  # due inside, answered after
    rec.record(Req(), due=105.0, sent=105.0, done=130.0, ok=False, why="still_out_after_drain")
    rec.record(Req(), due=110.1, sent=110.1, done=110.2, ok=True, why="")  # due after the window
    assert r.attempted == 2 and r.failed == 1
    assert r.items_in_window == 2
    assert r.errors == {"still_out_after_drain": 1}
    assert len(r.latencies_ms) == 1


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert loadgen.percentile(v, 0.5) == 50 and loadgen.percentile(v, 0.95) == 95
    assert loadgen.percentile([7.0], 0.95) == 7.0


async def _serve(handler):
    app = web.Application()
    app.router.add_post("/p", handler)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}/p"


def test_open_loop_sends_at_due_times_and_marks_the_window():
    async def slow(request):
        await request.read()
        await asyncio.sleep(0.01)
        return web.json_response({"results": [{}, {}]})

    async def go():
        runner, url = await _serve(slow)
        calls = {"n": 0}

        async def handler_marks(which):
            calls["n"] += 1

        try:
            reqs = [Req() for _ in range(10)]
            t = time.perf_counter()
            res = await loadgen.open_loop(url, [], [], reqs, [i * 0.05 for i in range(10)],
                                          seconds=0.6, drain_s=0.3, on_window=handler_marks)
            return res, time.perf_counter() - t, calls["n"]
        finally:
            await runner.cleanup()

    res, took, marks = asyncio.run(go())
    assert res.attempted == 10 and res.failed == 0 and marks == 2
    assert res.items_in_window == 20 and took < 3.0


def test_a_request_out_past_the_drain_is_failed_not_dropped():
    async def never(request):
        await request.read()
        await asyncio.sleep(5.0)
        return web.json_response({"results": []})

    async def go():
        runner, url = await _serve(never)
        try:
            t = time.perf_counter()
            res = await loadgen.closed_loop(url, [Req()], clients=2, warmup_s=0.0,
                                            seconds=0.3, drain_s=0.2)
            return res, time.perf_counter() - t
        finally:
            await runner.cleanup()

    res, took = asyncio.run(go())
    assert res.attempted == 2 and res.failed == 2 and res.items_in_window == 0
    assert res.errors == {"still_out_after_drain": 2}
    assert took < 2.0, "bounded by window + drain, not by the server"


def test_wrong_item_count_is_not_correct():
    async def short(request):
        await request.read()
        return web.json_response({"results": [{}]})

    async def go():
        runner, url = await _serve(short)
        try:
            return await loadgen.closed_loop(url, [Req()], clients=1, warmup_s=0.0,
                                             seconds=0.2, drain_s=0.2)
        finally:
            await runner.cleanup()

    res = asyncio.run(go())
    assert res.attempted > 0 and res.failed == res.attempted
    assert set(res.errors) == {"wrong_item_count"}
