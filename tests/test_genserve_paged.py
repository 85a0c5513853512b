"""Paged KV cache + chunked prefill (ISSUE 18): page-ledger safety,
paged==dense token parity, zero recompiles across page/slot churn and
reload, chunked-prefill determinism and non-starvation, page-pressure
admission over HTTP, and the fleet predictor's kv term.
docs/PERFORMANCE.md "Paged KV & chunked prefill"."""

import asyncio
import json

import pytest

from tpuserve.config import GenserveConfig, ModelConfig, ServerConfig
from tpuserve.genserve import (GenEngine, KVPressure, PageCorrupted,
                               PageLedger)
from tpuserve.models import build
from tpuserve.obs import Metrics
from tpuserve.runtime import build_runtime

TG_OPTS = dict(layers=1, d_model=32, heads=2, d_ff=64, vocab_size=512,
               prompt_len=16, max_new_tokens=64)


def tg_cfg(**over) -> ModelConfig:
    base = dict(name="tg", family="textgen", batch_buckets=[1, 2, 4],
                dtype="float32", parallelism="single", max_queue=64,
                request_timeout_ms=60_000.0, options=dict(TG_OPTS))
    base.update(over)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def dense_rt():
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    GenEngine(model, rt, Metrics(), GenserveConfig(slots=4)).compile()
    return model, rt


@pytest.fixture(scope="module")
def paged_rt():
    """Same model config as dense_rt (identical deterministic params), own
    runtime because the paged geometry registers different programs."""
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=4, kv_paging=True, kv_page_tokens=8)).compile()
    return model, rt


@pytest.fixture(scope="module")
def chunked_rt():
    """prefill_chunk=4 is a different geometry again (its prefill program
    closes over the chunk width)."""
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=4, kv_paging=True, kv_page_tokens=8, prefill_chunk=4)).compile()
    return model, rt


def make_engine(fix, metrics=None, slots=4, **gc_over):
    model, rt = fix
    m = metrics or Metrics()
    eng = GenEngine(model, rt, m, GenserveConfig(slots=slots, **gc_over))
    eng.compile()  # reuses the runtime's registered programs
    return eng, m


def paged_over(**over):
    base = dict(kv_paging=True, kv_page_tokens=8)
    base.update(over)
    return base


def prompt_item(model, prompt="hello world", seed=0, max_new=8, temp=0.0):
    body = {"prompt": prompt, "seed": seed, "max_new_tokens": max_new}
    if temp:
        body["temperature"] = temp
    return model.host_decode(json.dumps(body).encode(), "application/json")


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# PageLedger: never double-hands
# ---------------------------------------------------------------------------

def test_page_ledger_never_double_hands():
    led = PageLedger(4, 8)  # sentinel + 3 usable
    assert led.usable == 3 and led.n_free == 3
    a = led.acquire(0, 2)
    assert a == [1, 2] and PageLedger.SENTINEL not in a
    b = led.acquire(1, 1)
    assert b == [3] and led.n_free == 0
    with pytest.raises(IndexError):
        led.acquire(2, 1)  # pool exhausted
    with pytest.raises(PageCorrupted):
        led.acquire(0, 1)  # slot 0 already holds pages
    assert led.release(0) == [1, 2]
    with pytest.raises(PageCorrupted):
        led.release(0)  # double release
    with pytest.raises(PageCorrupted):
        led.release(7)  # foreign release: slot never held pages
    # A tampered free-list (owned page re-listed) is caught at acquire.
    led._free.append(3)
    with pytest.raises(PageCorrupted):
        led.acquire(5, 1)


def test_page_ledger_release_all_and_stats():
    led = PageLedger(6, 16)
    led.acquire(0, 2)
    led.acquire(1, 3)
    s = led.stats()
    assert s["usable"] == 5 and s["reserved"] == 5 and s["free"] == 0
    assert s["utilization"] == 1.0 and s["acquires_total"] == 5
    assert led.release_all() == 5
    assert led.n_free == led.usable and led.n_reserved == 0
    assert led.utilization() == 0.0
    with pytest.raises(ValueError):
        PageLedger(1, 8)  # no room for the sentinel + one real page
    with pytest.raises(ValueError):
        PageLedger(4, 0)


def test_kv_config_validation(paged_rt):
    with pytest.raises(ValueError, match="kv_pages"):
        GenserveConfig(kv_pages=1)
    with pytest.raises(ValueError, match="kv_page_tokens"):
        GenserveConfig(kv_page_tokens=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        GenserveConfig(prefill_chunk=-1)
    # A pool that cannot cover even ONE max-context request rejects at
    # engine construction (pps=ceil(80/8)=10, so 11 is the floor).
    model, rt = paged_rt
    with pytest.raises(ValueError, match="cover"):
        GenEngine(model, rt, Metrics(),
                  GenserveConfig(slots=4, **paged_over(kv_pages=5)))


# ---------------------------------------------------------------------------
# Parity: the tentpole acceptance bar
# ---------------------------------------------------------------------------

def test_paged_matches_dense_token_identical(dense_rt, paged_rt):
    """Default (whole-prompt) paged prefill routes through the SAME dense
    init_state math and the paged decode computes the same attention through
    the block table — tokens must be byte-identical, not approximately
    equal, over mixed lengths / seeds / temperatures."""
    d_model, _ = dense_rt
    p_model, _ = paged_rt
    d_eng, _ = make_engine(dense_rt)
    p_eng, _ = make_engine(paged_rt, **paged_over())

    prompts = [
        ("a", 1, 3, 0.0),
        ("the quick brown fox jumps over the lazy dog again and again", 2,
         12, 0.7),
        ("short prompt", 3, 1, 0.0),
        ("one two three four five six seven eight nine ten eleven twelve "
         "thirteen fourteen fifteen sixteen", 4, 8, 0.3),
        ("hello", 5, 20, 1.0),
        ("mid size prompt with a few words", 6, 5, 0.0),
    ]

    async def drive(eng, model):
        await eng.start()
        futs = [eng.submit(prompt_item(model, p, seed=s, max_new=n, temp=t))
                for (p, s, n, t) in prompts]
        res = await asyncio.gather(*futs)
        await eng.stop()
        return [r["tokens"] for r in res]

    dense = run(drive(d_eng, d_model))
    paged = run(drive(p_eng, p_model))
    assert dense == paged, (dense, paged)
    # The ledger balanced after the drain — every page came home.
    assert p_eng.pages.n_free == p_eng.pages.usable
    assert p_eng.pages.n_reserved == 0


def test_paged_zero_recompiles_across_churn_and_reload(paged_rt):
    """Page churn + slot churn + a publish AND a rollback mid-churn with
    runtime_compiles_total delta exactly 0: page indices and block-table
    rows are traced arguments, never baked into the program."""
    model, rt = paged_rt
    eng, _m = make_engine(paged_rt, **paged_over())
    c0 = rt.compiles_total
    assert c0 >= 3  # prefill/step/extract registered

    async def go():
        await eng.start()
        futs = [eng.submit(prompt_item(model, f"p{i} " + "w " * (i % 13),
                                       seed=i, max_new=1 + (i % 9)))
                for i in range(8)]
        rt.publish(rt.stage_params())  # reload mid-churn
        futs += [eng.submit(prompt_item(model, f"q{i}", seed=100 + i,
                                        max_new=2 + (i % 5)))
                 for i in range(8)]
        rt.rollback()
        futs += [eng.submit(prompt_item(model, f"r{i}", seed=200 + i,
                                        max_new=3)) for i in range(4)]
        res = await asyncio.gather(*futs)
        await eng.stop()
        return res

    res = run(go())
    assert len(res) == 20 and all(r["n_tokens"] >= 1 for r in res)
    assert rt.compiles_total == c0, (rt.compiles_total, c0)
    # Slot AND page accounting survived the churn exactly.
    assert eng.arena.n_active == 0 and eng.arena.n_free == eng.slots
    assert eng.pages.n_reserved == 0
    assert eng.pages.n_free == eng.pages.usable


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------

LONG16 = ("one two three four five six seven eight nine ten eleven twelve "
          "thirteen fourteen fifteen sixteen")


def test_chunked_prefill_deterministic_under_load(chunked_rt):
    """A max-length prompt prefilled in 4-token chunks emits the same
    tokens alone and amid decode load — chunk boundaries are fixed by the
    prompt, not by what else occupies the batch."""
    model, _ = chunked_rt
    e_alone, _ = make_engine(chunked_rt, **paged_over(prefill_chunk=4))
    e_load, _ = make_engine(chunked_rt, **paged_over(prefill_chunk=4))

    async def alone():
        await e_alone.start()
        r = await e_alone.submit(
            prompt_item(model, LONG16, seed=9, max_new=8, temp=0.5))
        await e_alone.stop()
        return r["tokens"]

    async def amid_load():
        await e_load.start()
        futs = [e_load.submit(prompt_item(model, "short one", seed=i + 1,
                                          max_new=3)) for i in range(3)]
        long_f = e_load.submit(
            prompt_item(model, LONG16, seed=9, max_new=8, temp=0.5))
        futs += [e_load.submit(prompt_item(model, "another short",
                                           seed=i + 10, max_new=4))
                 for i in range(3)]
        out = await asyncio.gather(long_f, *futs)
        await e_load.stop()
        return out[0]["tokens"]

    assert run(alone()) == run(amid_load())
    assert e_alone.pages.n_reserved == 0 and e_load.pages.n_reserved == 0


def test_chunked_prefill_never_starves_decode(chunked_rt):
    """THE interleaving property: short decodes admitted alongside a
    max-length prompt all complete while the long one is still working —
    prefill advances one chunk per engine iteration instead of stalling
    the step loop for the whole prompt."""
    model, _ = chunked_rt
    eng, m = make_engine(chunked_rt, **paged_over(prefill_chunk=4))

    async def go():
        await eng.start()
        order = []
        # 16-token prompt -> 4 prefill chunks + 8 decode steps.
        long_f = eng.submit(prompt_item(model, LONG16, seed=1, max_new=8))
        long_f.add_done_callback(lambda f: order.append("long"))
        shorts = []
        for i in range(3):
            f = eng.submit(prompt_item(model, "hi", seed=10 + i, max_new=2))
            f.add_done_callback(lambda f, i=i: order.append(f"s{i}"))
            shorts.append(f)
        await asyncio.gather(long_f, *shorts)
        await eng.stop()
        return order

    order = run(go())
    assert order[-1] == "long", order  # every short finished first
    assert set(order[:-1]) == {"s0", "s1", "s2"}
    # 4 chunks for the long prompt + 1 whole-prompt chunk per short.
    assert m.counter(
        "gen_prefill_chunks_total{model=tg}").value == pytest.approx(7)


# ---------------------------------------------------------------------------
# Page-pressure admission
# ---------------------------------------------------------------------------

def test_kv_pressure_sheds_beyond_backlog_bound():
    """Projected demand beyond one pool turnover of backlog sheds with
    KVPressure (a QueueFull subclass: existing handling still works), and
    the kv_pressure shed reason is counted."""
    # Own runtime: the pool size is part of the compiled state shape.
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    m = Metrics()
    eng = GenEngine(model, rt, m, GenserveConfig(
        slots=4, **paged_over(kv_pages=11)))  # 10 usable, bound 20
    eng.compile()

    async def go():
        await eng.start()
        # Each needs ceil((4 + 60) / 8) = 8 pages.
        item = lambda s: prompt_item(model, "hold the pool please",
                                     seed=s, max_new=60)
        f1, f2 = eng.submit(item(1)), eng.submit(item(2))
        with pytest.raises(KVPressure):
            eng.submit(item(3))  # projected 24 > 20
        await asyncio.gather(f1, f2)
        await eng.stop()

    run(go())
    assert m.counter(
        "sched_sheds_total{model=tg,reason=kv_pressure}").value == 1
    assert eng.pages.n_reserved == 0


def test_kv_clear_s_and_fleet_predictor():
    """kv_clear_s: None while the pool is comfortable, a positive
    clear-time once pressure + evidence exist; the fleet predictor folds
    it in even with an empty queue."""
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    eng = GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=4, **paged_over()))
    eng.compile()
    assert eng.kv_clear_s() is None  # comfortable pool, no evidence
    eng._ewma_step_ms = 10.0
    eng._ewma_iters = 5.0
    eng._ewma_pages = float(eng.pages.usable + 1)  # n_free < typical need
    assert eng.kv_clear_s() == pytest.approx(0.05)

    from tpuserve.config import SchedulerConfig
    from tpuserve.scheduler.fleet import FleetScheduler

    class StubPaged:
        device_time_cb = None

        def estimate_clear_s(self):
            return None  # empty queue

        def kv_clear_s(self):
            return 1.5

        def predicted_service_s(self, n_items=1):
            return 0.5

    sched = FleetScheduler(SchedulerConfig(enabled=True), Metrics())
    sched.register("m", StubPaged(), tg_cfg(name="m"))
    assert sched.predict_completion_s("m") == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# HTTP front door: 503 + Retry-After + observability
# ---------------------------------------------------------------------------

def test_http_kv_pressure_503_and_stats():
    from aiohttp.test_utils import TestClient, TestServer
    from tpuserve.server import ServerState, make_app

    cfg = ServerConfig(
        decode_threads=2,
        genserve=GenserveConfig(enabled=True, slots=4, kv_paging=True,
                                kv_page_tokens=8, kv_pages=11),
        models=[tg_cfg()])
    state = ServerState(cfg)
    state.build()

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            # Warm one request to completion: establishes the step/iters
            # EWMAs that price the Retry-After hint.
            warm = await client.post(
                "/v1/models/tg:generate",
                data=json.dumps({"prompt": "warm", "seed": 1,
                                 "max_new_tokens": 2}),
                headers={"Content-Type": "application/json"})
            assert warm.status == 200, await warm.text()
            # Saturate the pool (10 usable, backlog bound 20) with two
            # 8-page reservations queued engine-side, then the third over
            # HTTP sheds BEFORE enqueue.
            eng = state.batchers["tg"]
            body = lambda s: json.dumps({"prompt": "hold the pool please",
                                         "seed": s, "max_new_tokens": 60})
            item = lambda s: eng.model.host_decode(body(s).encode(),
                                                   "application/json")
            f1, f2 = eng.submit(item(1)), eng.submit(item(2))
            shed = await client.post(
                "/v1/models/tg:generate", data=body(3),
                headers={"Content-Type": "application/json"})
            assert shed.status == 503, await shed.text()
            payload = await shed.json()
            assert payload["reason"] == "kv_pressure"
            assert int(shed.headers["Retry-After"]) >= 1
            # /stats carries the kv block; /metrics the page gauges.
            stats = await (await client.get("/stats")).json()
            kv = stats["genserve"]["tg"]["kv"]
            assert kv["pages"] == 11 and kv["page_tokens"] == 8
            assert kv["kv_bytes"] > 0
            metrics = await (await client.get("/metrics")).text()
            assert 'gen_kv_pages_total{model="tg"}' in metrics
            assert 'gen_kv_pages_free{model="tg"}' in metrics
            assert 'gen_kv_page_utilization{model="tg"}' in metrics
            assert ('sched_sheds_total{model="tg",reason="kv_pressure"}'
                    in metrics)
            await asyncio.gather(f1, f2)
        finally:
            await client.close()

    run(go())


# ---------------------------------------------------------------------------
# Packed prefill (ISSUE 31): the engine's rule, an iteration at a time
# ---------------------------------------------------------------------------
# The tiny decoder takes K = 4 pieces a launch of 16 rows (tiles of one page,
# 4); textgen takes one. The passes of _step_loop are called by hand, in its
# order, so that what an iteration sees is exact.

from tpuserve.genserve import engine as engine_mod  # noqa: E402


@pytest.fixture(scope="module")
def dec_rt(tmp_path_factory):
    from tests.test_decoder import make_model
    model = make_model(tmp_path_factory.mktemp("packed"), name="pk")
    rt = build_runtime(model, compile_forward=False)
    GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=6, kv_paging=True, kv_page_tokens=4, prefill_chunk=16)).compile()
    return model, rt


def dec_item(model, n, max_new=12, first=1):
    body = {"prompt_ids": list(range(first, first + n)), "max_new_tokens": max_new}
    return model.host_decode(json.dumps(body).encode(), "application/json")


class ByHand:
    """An engine whose loop does not run: `iterate` is one pass of it."""

    def __init__(self, fix, hold, monkeypatch, **gc_over):
        monkeypatch.setattr(engine_mod, "PREFILL_HOLD", hold)
        self.eng, self.metrics = make_engine(fix, slots=gc_over.pop("slots", 6), **gc_over)
        self.launches = []   # one list of (slot, start, length) a launch
        sync = self.eng._prefill_sync

        def recorded(pieces):
            self.launches.append([(p.slot, p.start, p.length) for p in pieces])
            sync(pieces)
        self.eng._prefill_sync = recorded

    async def __aenter__(self):
        eng = self.eng
        eng._state = eng._host_zeros(eng._state_struct)
        eng._work_event, eng._idle_event = asyncio.Event(), asyncio.Event()
        eng._running = True
        return self

    async def __aexit__(self, *exc):
        await self.eng.stop()

    async def iterate(self, n=1):
        """`n` passes of the loop (`GenEngine._pass`: sweep, admit, launch, dispatch
        step k, read out(k-1), account, emit, retire), then the event loop's turn, so
        that the tasks answering what a pass read of a retired slot have run."""
        for _ in range(n):
            await self.eng._pass()
        while self.eng._finishing:
            await asyncio.sleep(0)

    def count(self, family):
        return self.metrics.counter(f"{family}{{model={self.eng.name}}}").value


@pytest.mark.parametrize("hold", [0, 1, 2])
def test_a_full_launch_goes_at_once_an_unfilled_one_waits_at_most_n(dec_rt, monkeypatch, hold):
    model, _ = dec_rt

    async def go():
        async with ByHand(dec_rt, hold, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            assert eng._prefill_pieces == 4
            a = eng.submit(dec_item(model, 5))
            await h.iterate()
            # no lane decodes yet: two tiles of four go at once, held by nothing
            assert h.launches == [[(0, 0, 5)]] and h.count("gen_prefill_held_total") == 0
            b = eng.submit(dec_item(model, 6, first=20))
            for waited in range(hold):   # lane 0 decodes: the launch waits, N iterations at most
                await h.iterate()
                assert len(h.launches) == 1 and eng.arena.peek(1).meta["prefill_held"] == waited + 1
                assert eng._decoding() and "prefill_next" in eng.arena.peek(1).meta
            if hold == 0:
                await h.iterate()
                assert h.launches[1:] == [[(1, 0, 6)]]
            c = eng.submit(dec_item(model, 7, first=40))
            d = eng.submit(dec_item(model, 8, first=60))
            await h.iterate()
            if hold == 0:   # c and d fill a launch between them: at once
                assert h.launches[2:] == [[(2, 0, 7), (3, 0, 8)]]
            else:           # b, which waited, and c fill one: at once; d has waited 0 and waits
                assert h.launches[1:] == [[(1, 0, 6), (2, 0, 7)]]
            await h.iterate(hold + 1)
            assert sum(len(x) for x in h.launches) == h.count("gen_prefill_pieces_total")
            assert h.count("gen_prefill_chunks_total") == len(h.launches)
            assert h.count("gen_prefill_tokens_total") == 5 + 6 + 7 + 8
            assert sorted(p for x in h.launches for p in x) \
                == [(0, 0, 5), (1, 0, 6), (2, 0, 7), (3, 0, 8)]
            assert h.count("gen_prefill_held_total") == (0 if hold == 0 else 2)
            await h.iterate(14)
            res = await asyncio.gather(a, b, c, d)
            assert [r["n_tokens"] for r in res] == [12] * 4
            assert eng.pages.n_reserved == 0 and eng.pages.n_reserved_rings == 0
            kv = eng.pipeline_stats()["kv"]
            assert kv["prefill_pieces"] == 4 and kv["prefill_hold"] == hold
            assert kv["pieces_per_launch"] == round(4 / len(h.launches), 3)
            assert kv["tokens_per_launch"] == round(26 / len(h.launches), 1)

    run(go())


def test_order_of_admission_is_kept_and_a_long_prompt_advances_a_launch_an_iteration(
        dec_rt, monkeypatch):
    model, _ = dec_rt

    async def go():
        async with ByHand(dec_rt, 1, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            a = eng.submit(dec_item(model, 3))
            await h.iterate()   # lane 0 decodes from here on
            long_f = eng.submit(dec_item(model, 24, first=10))
            short_f = eng.submit(dec_item(model, 3, first=50))
            await h.iterate()
            # the long prompt's first 16 tokens fill a launch: at once, before the short one,
            # which is not starved behind it (it waits for the tail, an iteration, and no more)
            assert h.launches[1:] == [[(1, 0, 16)]]
            assert eng.arena.peek(1).meta["prefill_next"] == 16 and eng._prefilling == [1, 2]
            await h.iterate()
            assert h.launches[2:] == [[(1, 16, 8), (2, 0, 3)]]
            assert eng._prefilling == [] and h.count("gen_prefill_held_total") == 1
            # a piece that does not fit what is left of a launch is cut at a tile's edge
            x = eng.submit(dec_item(model, 10, first=60))
            y = eng.submit(dec_item(model, 9, first=70))
            await h.iterate()
            assert h.launches[3:] == [[(3, 0, 10), (4, 0, 4)]]
            await h.iterate()   # the rest of y has not waited yet
            await h.iterate()
            assert h.launches[4:] == [[(4, 4, 5)]]
            await h.iterate(14)
            res = await asyncio.gather(a, long_f, short_f, x, y)
            assert [r["n_tokens"] for r in res] == [12] * 5
            return res

    res = run(go())
    # what was cut, held and packed is what each prompt gives alone
    async def alone():
        async with ByHand(dec_rt, 0, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            out = []
            for n, first in ((3, 1), (24, 10), (3, 50), (10, 60), (9, 70)):
                f = h.eng.submit(dec_item(model, n, first=first))
                await h.iterate(15)
                out.append(await f)
            assert all(len(x) == 1 for x in h.launches)
            return out

    assert [r["tokens"] for r in res] == [r["tokens"] for r in run(alone())]


@pytest.mark.parametrize("how", ["disconnect", "deadline"])
def test_a_slot_that_goes_while_its_piece_waits_leaves_the_launch(dec_rt, monkeypatch, how):
    import time

    from tpuserve.batcher import DeadlineExceeded
    model, _ = dec_rt

    async def go():
        async with ByHand(dec_rt, 2, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            a = eng.submit(dec_item(model, 3))
            await h.iterate()
            pages0, rings0 = eng.pages.n_free, eng.pages.n_free_rings
            b = eng.submit(dec_item(model, 6, first=20),
                           deadline_at=time.perf_counter() + 3600.0)
            await h.iterate()
            assert eng._prefilling == [1] and eng.pages.n_free < pages0   # b waits, holding pages
            if how == "disconnect":
                b.cancel()
            else:
                eng.arena.peek(1).deadline_at = time.perf_counter() - 1.0
            c = eng.submit(dec_item(model, 5, first=40))
            await h.iterate()
            # b's pages and ring went back before c took its own; b's piece is in no launch
            assert eng._prefilling == [1] and eng.arena.peek(1).item is not None
            assert (1, 0, 6) not in [p for x in h.launches for p in x]
            await h.iterate(16)
            assert (await a)["n_tokens"] == 12 and (await c)["n_tokens"] == 12
            if how == "deadline":
                with pytest.raises(DeadlineExceeded):
                    await b
            assert [p for x in h.launches for p in x] == [(0, 0, 3), (1, 0, 5)]
            assert eng.pages.n_free == eng.pages.usable and eng.pages.n_free_rings == rings0 + 1

    run(go())


def test_a_launch_that_raises_fails_everything_in_flight_and_the_engine_goes_on(
        dec_rt, monkeypatch):
    model, rt = dec_rt

    async def go():
        async with ByHand(dec_rt, 1, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            a = eng.submit(dec_item(model, 3))
            await h.iterate()
            b = eng.submit(dec_item(model, 8, first=20))
            c = eng.submit(dec_item(model, 8, first=40))
            real = rt.run_program

            def boom(tag, *args, **kw):
                if tag == "prefill":
                    raise RuntimeError("device said no")
                return real(tag, *args, **kw)
            monkeypatch.setattr(rt, "run_program", boom)
            await h.iterate()
            # today's blast radius: the decoding lane and both pieces of the launch
            for f in (a, b, c):
                with pytest.raises(RuntimeError, match="device said no"):
                    await f
            assert eng.arena.n_active == 0 and eng._prefilling == []
            assert eng.pages.n_free == eng.pages.usable
            assert h.count("batch_errors_total") == 1 and h.count("gen_prefill_chunks_total") == 1
            monkeypatch.setattr(rt, "run_program", real)
            d = eng.submit(dec_item(model, 4, first=60))
            await h.iterate(14)
            assert (await d)["n_tokens"] == 12

    run(go())


def test_textgen_takes_one_prompt_a_launch_exactly_as_before(chunked_rt, monkeypatch):
    """K = 1 through the same path: a launch a chunk a slot, starts at
    multiples of the chunk, every launch full, nothing ever held."""
    model, _ = chunked_rt

    async def go():
        async with ByHand(chunked_rt, 2, monkeypatch, slots=4, **paged_over(prefill_chunk=4)) as h:
            eng = h.eng
            assert eng._prefill_pieces == 1
            items = [prompt_item(model, p, seed=s, max_new=n)
                     for p, s, n in (("hi", 3, 8), (LONG16, 1, 4), ("hi there", 4, 4))]
            n_a, _, n_b = (model.prompt_tokens(i) for i in items)
            assert n_a <= 4 and n_b <= 8
            a = eng.submit(items[0])
            await h.iterate(2)   # decoding
            long_f, b = eng.submit(items[1]), eng.submit(items[2])
            await h.iterate(4)
            # an iteration: the next chunk of every prefilling slot, each a launch of its own
            want = [[(0, 0, n_a)]]
            for start in range(0, 16, 4):
                want.append([(1, start, 4)])
                if start < n_b:
                    want.append([(2, start, min(4, n_b - start))])
            assert h.launches == want
            assert h.count("gen_prefill_held_total") == 0
            assert h.count("gen_prefill_pieces_total") == h.count("gen_prefill_chunks_total") \
                == len(want)
            assert h.count("gen_prefill_tokens_total") == n_a + 16 + n_b
            await h.iterate(8)
            return [(await f)["tokens"] for f in (a, long_f, b)]

    packed = run(go())
    eng, _ = make_engine(chunked_rt, **paged_over(prefill_chunk=4))

    async def loop():
        await eng.start()
        out = [(await eng.submit(prompt_item(model, p, seed=s, max_new=n)))["tokens"]
               for p, s, n in (("hi", 3, 8), (LONG16, 1, 4), ("hi there", 4, 4))]
        await eng.stop()
        return out

    assert packed == run(loop())


# ---------------------------------------------------------------------------
# One step queued ahead (ISSUE 41): a pass dispatches step k and reads out(k-1)
# ---------------------------------------------------------------------------

def lp_item(model, ids, max_new):
    body = {"prompt_ids": list(ids), "max_new_tokens": max_new, "logprobs": 2}
    return model.host_decode(json.dumps(body).encode(), "application/json")


def test_a_slot_armed_in_the_pass_its_occupant_retired_is_not_read_from_the_older_out_block(
        dec_rt, monkeypatch):
    """Three requests of different lengths. A (3 tokens) is seen done in
    out(1) and retires in pass 3; pass 4 hands its slot (the arena gives the
    last one freed first) to C, whose one-launch prompt is armed there (hold
    0) BEFORE step 3 is dispatched, and then reads out(2): a step dispatched
    before C's arming, in which slot 0 is still A's lane, done. C is not retired, counted or given a first token
    from it; every answer is what the programs give by hand, without the
    engine (tests/test_decoder.py `serve`)."""
    import numpy as np

    from tests.test_decoder import serve
    model, rt = dec_rt
    prompts = [list(range(1, 6)), list(range(20, 26)), list(range(40, 44))]
    max_news = [3, 9, 4]

    async def go():
        async with ByHand(dec_rt, 0, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            first = h.metrics.histogram(f"gen_first_unit_ms{{model={eng.name}}}")
            read = []   # what each pass's retire was given: (step, its `done`, who was armed when)
            retire = eng._retire

            async def spy(out, seq):
                read.append((seq, np.array(out["done"]), {
                    s: eng.arena.peek(s).armed_step for s in eng.arena.active_slots()}))
                await retire(out, seq)
            eng._retire = spy
            a, b = (eng.submit(lp_item(model, p, n)) for p, n in zip(prompts[:2], max_news))
            await h.iterate(3)
            assert [r[0] for r in read] == [0, 1] and read[1][1][0]      # out(1): A done
            assert eng.arena.active_slots() == [1] and not a.done()     # released; its extract unread
            assert len(eng._extracts) == 1 and eng._ahead.seq == 2
            tokens0, firsts0 = h.count("gen_decode_tokens_total"), first.n
            c = eng.submit(lp_item(model, prompts[2], max_news[2]))
            await h.iterate()
            seq, done, armed = read[2]
            assert seq == 2 and done[0] and armed == {1: 0, 0: 3}       # A's lane, C's slot
            info = eng.arena.peek(0)
            assert info.item is not None and not c.done() and info.first_unit_at is None
            assert info.iterations == 0 and info.since_step == 3
            assert h.count("gen_decode_tokens_total") == tokens0 + 1    # B alone decoded in step 2
            assert first.n == firsts0 and (await a)["n_tokens"] == 3    # A answered a pass after
            await h.iterate()
            assert read[3][0] == 3 and not read[3][1][0] and first.n == firsts0 + 1
            await h.iterate(8)
            res = await asyncio.gather(a, b, c)
            assert eng.pages.n_reserved == 0 and eng.pages.n_reserved_rings == 0
            return res

    res = run(go())
    want, _, _ = serve(model, rt.params_per_mesh[0], [np.asarray(p, np.int32) for p in prompts],
                       max_news, slots=3)
    for got, ref, n in zip(res, want, max_news):
        assert got["tokens"] == ref["tokens"][:n].tolist() and got["n_tokens"] == n
        np.testing.assert_allclose(got["logprobs"]["values"], ref["lp"][:n, :2], atol=1e-4)


def test_the_loop_serves_a_mixed_batch_through_reused_slots_as_the_programs_do_by_hand(dec_rt):
    """The running loop, sixteen requests of different `max_new` through six
    slots (every slot reused, most of them in the pass after a retirement):
    tokens and log-probabilities are the programs' own."""
    import numpy as np

    from tests.test_decoder import serve
    model, rt = dec_rt
    prompts = [list(range(1 + 5 * i, 1 + 5 * i + 3 + i % 5)) for i in range(16)]
    max_news = [2, 9, 1, 5, 12, 3, 7, 4, 1, 6, 2, 11, 3, 8, 5, 2]
    eng, metrics = make_engine(dec_rt, slots=6, **paged_over(kv_page_tokens=4, prefill_chunk=16))

    async def go():
        await eng.start()
        res = await asyncio.gather(*[eng.submit(lp_item(model, p, n))
                                     for p, n in zip(prompts, max_news)])
        await eng.drain(asyncio.get_running_loop().time() + 30)
        left = (eng._ahead, list(eng._extracts), dict(eng._finishing))
        await eng.stop()
        return res, left

    res, left = run(go())
    assert left == (None, [], {})
    want, _, _ = serve(model, rt.params_per_mesh[0], [np.asarray(p, np.int32) for p in prompts],
                       max_news, slots=16)
    for got, ref, n in zip(res, want, max_news):
        assert got["tokens"] == ref["tokens"][:n].tolist() and got["n_tokens"] == n
        np.testing.assert_allclose(got["logprobs"]["values"], ref["lp"][:n, :2], atol=1e-4)
    assert eng.arena.n_free == 6 and eng.pages.n_free == eng.pages.usable
    iters = metrics.counter(f"gen_iterations_total{{model={eng.name}}}").value
    ahead = metrics.counter(f"gen_steps_ahead_total{{model={eng.name}}}").value
    assert iters > 12 and ahead == iters - 1   # one busy stretch: every step but its first


def ledger(eng):
    return (eng.arena.n_active, eng.arena.n_free, eng.pages.n_reserved, eng.pages.n_free,
            eng.pages.n_reserved_rings, eng.pages.n_free_rings)


@pytest.mark.parametrize("how", ["evict", "fail", "stop"])
def test_a_slot_that_goes_with_a_step_in_flight_leaves_nothing_unread_or_unaccounted(
        dec_rt, monkeypatch, how):
    import time

    from tpuserve.batcher import DeadlineExceeded
    model, _ = dec_rt

    async def go():
        async with ByHand(dec_rt, 0, monkeypatch, **paged_over(
                kv_page_tokens=4, prefill_chunk=16)) as h:
            eng = h.eng
            empty = ledger(eng)
            a = eng.submit(dec_item(model, 5, max_new=10), deadline_at=time.perf_counter() + 3600)
            b = eng.submit(dec_item(model, 6, max_new=10, first=20))
            await h.iterate(3)
            assert eng._ahead is not None and eng._ahead.seq == 2 and ledger(eng)[0] == 2
            iters = h.count("gen_iterations_total")
            if how == "evict":
                # A's deadline passes while step 2, which still holds its lane live, is queued
                eng.arena.peek(0).deadline_at = time.perf_counter() - 1.0
                await h.iterate()
                with pytest.raises(DeadlineExceeded):
                    await a
                assert eng.arena.active_slots() == [1] and h.count("gen_evictions_total") == 1
                assert h.count("gen_iterations_total") == iters + 1   # out(2) read, for B
                # the slot goes to C at once; out(3), dispatched before C's arming, is not C's
                c = eng.submit(dec_item(model, 4, max_new=3, first=40))
                await h.iterate(12)
                assert [(await f)["n_tokens"] for f in (b, c)] == [10, 3]
            elif how == "fail":
                await eng._fail_active(RuntimeError("device said no"))
                for f in (a, b):
                    with pytest.raises(RuntimeError, match="device said no"):
                        await f
                # the failed block's out-block is dropped, never read beside the new block
                assert eng._ahead is None and ledger(eng) == empty
                await h.iterate(2)
                assert h.count("gen_iterations_total") == iters
                c = eng.submit(dec_item(model, 4, max_new=3, first=40))
                await h.iterate(6)
                assert (await c)["n_tokens"] == 3
                assert h.count("gen_iterations_total") == iters + 2   # C's own two steps, no older one
            else:
                a2 = eng.submit(dec_item(model, 3, max_new=2, first=60))
                await h.iterate(2)   # a2 done in out(3): its extract dispatched, unread
                assert len(eng._extracts) == 1 and not a2.done()
                await eng.stop()
                for f in (a, b, a2):
                    with pytest.raises(RuntimeError, match="shutting down"):
                        await f
                assert eng._ahead is None and eng._extracts == [] and not eng._finishing
            # a pass that finds no lane dispatches nothing ahead; what was ahead is dropped
            await h.iterate(2) if how != "stop" else None
            assert eng._ahead is None and eng._extracts == [] and not eng._finishing
            assert ledger(eng) == empty
            return True

    assert run(go())


def test_drain_waits_for_the_answers_of_slots_that_are_free_already(dec_rt):
    """`drain` returns once every accepted request is ANSWERED: a retired
    slot is free a pass before its extract is read and its answer set."""
    model, _ = dec_rt
    eng, _ = make_engine(dec_rt, slots=6, **paged_over(kv_page_tokens=4, prefill_chunk=16))

    async def go():
        await eng.start()
        futs = [eng.submit(dec_item(model, 4 + i, max_new=2 + 3 * (i % 4), first=1 + 9 * i))
                for i in range(9)]
        ok = await eng.drain(asyncio.get_running_loop().time() + 30)
        state = (ok, [f.done() for f in futs], eng._ahead, list(eng._extracts),
                 len(eng._finishing), ledger(eng)[:3])
        await eng.stop()
        return state

    ok, done, ahead, extracts, finishing, led = run(go())
    assert ok and all(done) and ahead is None and extracts == [] and finishing == 0
    assert led == (0, 6, 0)
