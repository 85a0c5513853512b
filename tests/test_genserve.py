"""Iteration-level continuous batching (ISSUE 9): scheduler invariants,
slot-arena safety, deadline eviction mid-generation, zero recompiles across
admit/retire/reload churn, engine-vs-locked-batch parity, the generative
cache-key contract, and the HTTP front door (textgen + SD 1.5 through the
engine). docs/PERFORMANCE.md "The generation engine"."""

import asyncio
import json

import pytest

from tpuserve.batcher import DeadlineExceeded, QueueFull
from tpuserve.config import (GenserveConfig, ModelConfig, ServerConfig,
                             load_config)
from tpuserve.genserve import GenEngine, SlotArena, SlotCorrupted, SlotInfo
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
def tg_rt():
    """One compiled textgen model+runtime for every engine test (the three
    gen programs compile once; engines over it are cheap)."""
    model = build(tg_cfg())
    rt = build_runtime(model, compile_forward=False)
    eng = GenEngine(model, rt, Metrics(), GenserveConfig(slots=4))
    eng.compile()
    return model, rt


def make_engine(tg_rt, metrics=None, slots=4, **gc_over):
    model, rt = tg_rt
    m = metrics or Metrics()
    eng = GenEngine(model, rt, m, GenserveConfig(slots=slots, **gc_over))
    eng.compile()  # reuses the runtime's registered programs
    return eng, m


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
# SlotArena: never double-hands
# ---------------------------------------------------------------------------

def test_slot_arena_never_double_hands():
    a = SlotArena(2)
    s0 = a.acquire(SlotInfo(item=None, future=None))
    s1 = a.acquire(SlotInfo(item=None, future=None))
    assert {s0, s1} == {0, 1} and a.n_free == 0
    with pytest.raises(IndexError):
        a.acquire(SlotInfo(item=None, future=None))
    a.release(s0)
    with pytest.raises(SlotCorrupted):
        a.release(s0)  # double release
    # A corrupted free-list (same slot twice) is caught at acquire.
    a._free.append(s1)
    with pytest.raises(SlotCorrupted):
        a.acquire(SlotInfo(item=None, future=None))


def test_slot_arena_release_all():
    a = SlotArena(3)
    infos = [a.acquire(SlotInfo(item=i, future=None)) for i in range(3)]
    assert len(infos) == 3
    out = a.release_all()
    assert [i.item for i in out] == [0, 1, 2]
    assert a.n_free == 3 and a.n_active == 0


# ---------------------------------------------------------------------------
# Scheduler invariants
# ---------------------------------------------------------------------------

def test_short_after_long_finishes_first(tg_rt):
    """THE continuous-batching property: a 2-token request admitted AFTER a
    60-token one completes FIRST — a locked batch would hold it hostage."""
    model, _ = tg_rt
    eng, _m = make_engine(tg_rt)

    async def go():
        await eng.start()
        order = []
        long_f = eng.submit(prompt_item(model, "long", seed=1, max_new=60))
        long_f.add_done_callback(lambda f: order.append("long"))
        await asyncio.sleep(0.02)  # the long one is mid-generation now
        short_f = eng.submit(prompt_item(model, "short", seed=2, max_new=2))
        short_f.add_done_callback(lambda f: order.append("short"))
        rl, rs = await asyncio.gather(long_f, short_f)
        await eng.stop()
        assert order == ["short", "long"], order
        assert rl["n_tokens"] == 60 and rs["n_tokens"] == 2

    run(go())


def test_fold_in_and_early_exit_counters(tg_rt):
    model, _ = tg_rt
    eng, m = make_engine(tg_rt)

    async def go():
        await eng.start()
        long_f = eng.submit(prompt_item(model, "marathon", seed=3, max_new=60))
        await asyncio.sleep(0.02)
        shorts = [eng.submit(prompt_item(model, f"s{i}", seed=10 + i,
                                         max_new=2)) for i in range(3)]
        await asyncio.gather(long_f, *shorts)
        await eng.stop()
        assert m.counter("gen_fold_ins_total{model=tg}").value >= 3
        assert m.counter("gen_early_exits_total{model=tg}").value >= 3
        assert m.counter("gen_iterations_total{model=tg}").value > 0

    run(go())


def test_engine_matches_locked_batch_tokens(tg_rt):
    """Engine path == locked-batch forward path, token for token: both
    share _prefill/_decode_step and the positional (seed, position)
    sampling fold, so identical requests are bit-identical across the two
    schedulers (and across batch compositions)."""
    model, _ = tg_rt
    eng, _m = make_engine(tg_rt)

    async def go():
        await eng.start()
        res = await eng.submit(prompt_item(model, "parity check run",
                                           seed=5, max_new=17))
        await eng.stop()
        return res

    engine_res = run(go())
    rt2 = build_runtime(model)  # forward buckets (the locked path)
    item = prompt_item(model, "parity check run", seed=5, max_new=17)
    out = rt2.fetch(rt2.run((1,), model.assemble([item], (1,))))
    locked = model.host_postprocess(out, 1)[0]
    assert locked["tokens"] == engine_res["tokens"]
    assert locked["n_tokens"] == 17


def test_deadline_eviction_mid_generation(tg_rt):
    """A request whose deadline lands mid-generation 504s at the stamped
    instant (within one iteration of it) and frees its slot for queued
    work. Iterations are chaos-slowed to 10 ms so the 60-token run
    provably spans the 80 ms deadline on any host speed."""
    import time

    from tpuserve.faults import FaultInjector

    model, _ = tg_rt
    eng, m = make_engine(tg_rt)
    eng.injector = FaultInjector.single("slow_dispatch", delay_ms=10.0)

    async def go():
        await eng.start()
        t0 = time.perf_counter()
        doomed = eng.submit(prompt_item(model, "doomed", seed=6, max_new=60),
                            deadline_at=t0 + 0.08)
        with pytest.raises(DeadlineExceeded):
            await doomed
        elapsed = time.perf_counter() - t0
        # At the stamped instant (within ~one slowed iteration), not at
        # generation end: 60 iterations x 10 ms would be >= 600 ms.
        assert 0.08 <= elapsed < 0.4, elapsed
        assert m.counter("gen_evictions_total{model=tg}").value == 1
        assert m.counter("deadline_exceeded_total{model=tg}").value == 1
        # The freed slot serves the next request.
        eng.injector = None
        ok = await eng.submit(prompt_item(model, "alive", seed=7, max_new=2))
        assert ok["n_tokens"] == 2
        await eng.stop()

    run(go())


def test_queued_deadline_expires_without_admission(tg_rt):
    """Deadline already expired while queued -> fast 504, never admitted."""
    import time

    model, _ = tg_rt
    eng, m = make_engine(tg_rt)

    async def go():
        await eng.start()
        fut = eng.submit(prompt_item(model, "late", seed=8, max_new=4),
                         deadline_at=time.perf_counter() - 0.001)
        with pytest.raises(DeadlineExceeded):
            await fut
        assert m.counter("gen_admitted_total{model=tg}").value == 0
        await eng.stop()

    run(go())


def test_zero_recompiles_across_churn_and_reload(tg_rt):
    """The acceptance bar: sustained admit/retire churn with mixed lengths,
    plus a publish AND a rollback mid-churn, with runtime_compiles_total
    delta exactly 0 — slot churn and version churn reuse the registered
    step/insert/extract programs."""
    model, rt = tg_rt
    eng, _m = make_engine(tg_rt)
    c0 = rt.compiles_total
    assert c0 >= 3  # insert/step/extract registered

    async def go():
        await eng.start()
        futs = [eng.submit(prompt_item(model, f"p{i}", seed=i,
                                       max_new=2 + (i % 9)))
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
    # Slot accounting survived the churn exactly.
    assert eng.arena.n_active == 0 and eng.arena.n_free == eng.slots


def test_queue_full_sheds(tg_rt):
    model, _ = tg_rt
    eng, m = make_engine(tg_rt)
    eng.cfg.max_queue = 2

    async def go():
        await eng.start()
        try:
            # Not yet admitted: the loop hasn't run between submits.
            eng.submit(prompt_item(model, "a", max_new=2))
            eng.submit(prompt_item(model, "b", max_new=2))
            with pytest.raises(QueueFull):
                eng.submit(prompt_item(model, "c", max_new=2))
            assert m.counter("shed_total{model=tg}").value == 1
        finally:
            eng.cfg.max_queue = 64
            await eng.stop()

    run(go())


def test_cancelled_request_frees_slot(tg_rt):
    model, _ = tg_rt
    eng, _m = make_engine(tg_rt)

    async def go():
        await eng.start()
        fut = eng.submit(prompt_item(model, "gone", seed=9, max_new=60))
        await asyncio.sleep(0.02)
        assert eng.arena.n_active >= 1
        fut.cancel()
        ok = await eng.submit(prompt_item(model, "here", seed=10, max_new=2))
        assert ok["n_tokens"] == 2
        # The cancelled slot was reaped by the loop.
        for _ in range(50):
            if eng.arena.n_active == 0:
                break
            await asyncio.sleep(0.01)
        assert eng.arena.n_active == 0
        await eng.stop()

    run(go())


def test_step_failure_contained_and_loop_survives(tg_rt):
    """An injected step failure fails the in-flight set with the cause,
    resets the state block, and the engine keeps serving."""
    from tpuserve.faults import FaultInjected, FaultInjector

    model, _ = tg_rt
    eng, m = make_engine(tg_rt)

    async def go():
        await eng.start()
        eng.injector = FaultInjector.single("batch_error", count=1)
        with pytest.raises(FaultInjected):
            await eng.submit(prompt_item(model, "boom", seed=11, max_new=8))
        assert m.counter("batch_errors_total{model=tg}").value == 1
        ok = await eng.submit(prompt_item(model, "fine", seed=12, max_new=3))
        assert ok["n_tokens"] == 3
        eng.injector = None
        await eng.stop()

    run(go())


def test_watchdog_revives_dead_step_loop(tg_rt):
    from tpuserve.faults import FaultInjector

    model, _ = tg_rt
    eng, _m = make_engine(tg_rt)

    async def go():
        await eng.start()
        eng.injector = FaultInjector.single("kill_group_loop", count=1)
        fut = eng.submit(prompt_item(model, "stalled", seed=13, max_new=2))
        for _ in range(100):
            if eng._loop_task.done():
                break
            await asyncio.sleep(0.01)
        assert eng._loop_task.done()  # chaos killed the loop
        eng.injector = None
        assert eng.revive_group_loops() == 1
        res = await asyncio.wait_for(fut, timeout=10)
        assert res["n_tokens"] == 2
        assert eng.revive_group_loops() == 0  # healthy loop: no-op
        await eng.stop()

    run(go())


def test_drain_waits_for_mid_generation_work(tg_rt):
    model, _ = tg_rt
    eng, _m = make_engine(tg_rt)

    async def go():
        await eng.start()
        fut = eng.submit(prompt_item(model, "draining", seed=14, max_new=20))
        await asyncio.sleep(0.02)
        loop = asyncio.get_running_loop()
        ok = await eng.drain(loop.time() + 30.0)
        assert ok and fut.done() and (await fut)["n_tokens"] == 20
        await eng.stop()

    run(go())


def test_staged_canary_runs_short_generation(tg_rt):
    """The lifecycle's staged-canary hook: a candidate tree proves itself
    on a real end-to-end generation without touching the live state."""
    model, rt = tg_rt
    eng, _m = make_engine(tg_rt)
    staged = rt.stage_params()
    eng.staged_canary_sync(staged)  # must not raise
    c0 = rt.compiles_total
    eng.staged_canary_sync(staged)
    assert rt.compiles_total == c0  # canaries never compile


def test_textgen_option_validation():
    with pytest.raises(ValueError, match="heads"):
        build(tg_cfg(options={**TG_OPTS, "d_model": 33}))


# ---------------------------------------------------------------------------
# Generative cache-key contract (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_generation_cache_keys_include_sampling_params(tg_rt):
    """Two prompts differing ONLY in seed / temperature / max_new_tokens
    digest to distinct cache keys — the item carries every sampling param,
    so aliasing is structurally impossible."""
    from tpuserve.cache import item_digest

    model, _ = tg_rt
    base = prompt_item(model, "same prompt", seed=1, max_new=8)
    digests = {
        item_digest(base),
        item_digest(prompt_item(model, "same prompt", seed=2, max_new=8)),
        item_digest(prompt_item(model, "same prompt", seed=1, max_new=9)),
        item_digest(prompt_item(model, "same prompt", seed=1, max_new=8,
                                temp=0.7)),
    }
    assert len(digests) == 4
    # And identical params digest identically (the hit path exists).
    assert item_digest(base) == item_digest(
        prompt_item(model, "same prompt", seed=1, max_new=8))


def test_sd15_cache_keys_include_seed():
    from tpuserve.cache import item_digest
    from tpuserve.models import build as mbuild

    sd = mbuild(ModelConfig(
        name="sd", family="sd15", batch_buckets=[1], dtype="float32",
        parallelism="single", image_size=32,
        options=dict(steps=2, vocab_size=128, text_layers=1, text_d_model=16,
                     text_heads=2, unet_ch=8, unet_mults=[1, 2], unet_res=1,
                     unet_attn_levels=[0], unet_heads=2, vae_ch=8,
                     vae_mults=[1, 2])))
    a = sd.host_decode(b'{"prompt": "x", "seed": 1}', "application/json")
    b = sd.host_decode(b'{"prompt": "x", "seed": 2}', "application/json")
    assert item_digest(a) != item_digest(b)


def test_cacheable_false_skips_server_cache():
    from tpuserve.config import CacheConfig
    from tpuserve.server import ServerState

    cfg = ServerConfig(
        decode_threads=2, startup_canary=False,
        cache=CacheConfig(enabled=True),
        models=[ModelConfig(name="toy", family="toy", batch_buckets=[1, 2],
                            dtype="float32", num_classes=10,
                            parallelism="single", cacheable=False)])
    state = ServerState(cfg)
    state.build()

    async def go():
        await state.start()
        try:
            # cacheable=false: no ModelCache built despite [cache] enabled.
            assert state.caches == {}
        finally:
            await state.stop()

    run(go())


def test_cacheable_false_skips_router_cache():
    """Router-side generation-key contract: the wire cache digests the raw
    body (seed differences always split keys), and a cacheable=false model
    gets NO router cache at all."""
    from tpuserve.config import CacheConfig
    from tpuserve.workerproc.router import RouterState

    cfg = ServerConfig(
        cache=CacheConfig(enabled=True),
        models=[
            ModelConfig(name="gen", family="textgen", cacheable=False),
            ModelConfig(name="tg", family="textgen"),
        ])
    cfg.router.enabled = True
    state = RouterState(cfg)
    assert "gen" not in state.caches   # opted out
    cache = state.caches["tg"]         # cacheable (params ride in the body)
    k1 = cache.key_for(("generate", "application/json",
                        b'{"prompt": "p", "seed": 1}'))
    k2 = cache.key_for(("generate", "application/json",
                        b'{"prompt": "p", "seed": 2}'))
    assert k1 != k2


def test_genserve_config_toml(tmp_path):
    p = tmp_path / "g.toml"
    p.write_text("""
[genserve]
enabled = true
slots = 6
admit_per_step = 2

[[model]]
name = "tg"
family = "textgen"
cacheable = false
""")
    cfg = load_config(str(p))
    assert cfg.genserve.enabled and cfg.genserve.slots == 6
    assert cfg.genserve.admit_per_step == 2
    assert cfg.models[0].cacheable is False
    with pytest.raises(ValueError, match="admit_per_step"):
        GenserveConfig(admit_per_step=-1)


# ---------------------------------------------------------------------------
# HTTP front door through the engine
# ---------------------------------------------------------------------------

def _gen_server(**over):
    from tpuserve.server import ServerState

    base = dict(
        decode_threads=2,
        genserve=GenserveConfig(enabled=True, slots=4),
        models=[ModelConfig(name="tg", family="textgen",
                            batch_buckets=[1, 2, 4], dtype="float32",
                            parallelism="single",
                            request_timeout_ms=60_000.0,
                            options=dict(TG_OPTS))])
    base.update(over)
    cfg = ServerConfig(**base)
    state = ServerState(cfg)
    state.build()
    return state


def test_http_textgen_through_engine():
    from aiohttp.test_utils import TestClient, TestServer
    from tpuserve.server import make_app

    state = _gen_server()

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/models/tg:generate",
                data=json.dumps({"prompt": "hello", "seed": 4,
                                 "max_new_tokens": 6}),
                headers={"Content-Type": "application/json"})
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["n_tokens"] == 6 and len(body["tokens"]) == 6
            # Engine-served model: forward buckets were never compiled,
            # only the three gen programs.
            assert state.runtimes["tg"].compile_forward is False
            variants = {tuple(v["bucket"]) for v in
                        state.runtimes["tg"].variants_summary()}
            assert variants == {("extract", 4), ("insert", 4), ("step", 4)}
            # /stats carries the genserve block; /metrics the counters.
            stats = await (await client.get("/stats")).json()
            assert stats["genserve"]["tg"]["mode"] == "genserve"
            assert stats["pipeline"]["models"]["tg"]["mode"] == "genserve"
            metrics = await (await client.get("/metrics")).text()
            assert 'gen_iterations_total{model="tg"}' in metrics
            # Bad sampling params reject at decode (400), not mid-engine.
            bad = await client.post(
                "/v1/models/tg:generate",
                data=json.dumps({"prompt": "x", "max_new_tokens": 10_000}),
                headers={"Content-Type": "application/json"})
            assert bad.status == 400
            # Per-request deadline -> fast 504 through the engine, with
            # iterations chaos-slowed so the generation provably outlives
            # the 50 ms budget on any host.
            from tpuserve.faults import FaultInjector

            state.batchers["tg"].injector = FaultInjector.single(
                "slow_dispatch", delay_ms=10.0)
            try:
                slow = await client.post(
                    "/v1/models/tg:generate?timeout_ms=50",
                    data=json.dumps({"prompt": "slow", "seed": 1,
                                     "max_new_tokens": 64}),
                    headers={"Content-Type": "application/json"})
                assert slow.status == 504, await slow.text()
            finally:
                state.batchers["tg"].injector = None
        finally:
            await client.close()

    run(go())


def test_http_reload_engine_staged_canary():
    """:reload on an engine-served model runs the engine's staged canary
    (a short real generation) and publishes with zero recompiles; an
    injected regression rejects at the staged_canary gate with the old
    version serving."""
    from aiohttp.test_utils import TestClient, TestServer
    from tpuserve.faults import FaultInjector
    from tpuserve.server import make_app

    state = _gen_server()

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            c0 = state.metrics.counter(
                "runtime_compiles_total{model=tg}").value
            r = await client.post("/admin/models/tg:reload")
            assert r.status == 200, await r.text()
            assert (await r.json())["version"] == 2
            assert state.metrics.counter(
                "runtime_compiles_total{model=tg}").value == c0
            # Regressed candidate: rejected at the staged canary, v2 serves.
            state.lifecycles["tg"].injector = FaultInjector.single(
                "reload_regressed", count=1)
            r2 = await client.post("/admin/models/tg:reload")
            assert r2.status == 409, await r2.text()
            assert (await r2.json())["stage"] == "staged_canary"
            ok = await client.post(
                "/v1/models/tg:generate",
                data=json.dumps({"prompt": "still here", "seed": 2,
                                 "max_new_tokens": 3}),
                headers={"Content-Type": "application/json"})
            assert ok.status == 200
            assert state.runtimes["tg"].version == 2
        finally:
            state.lifecycles["tg"].injector = None
            await client.close()

    run(go())


def test_http_cache_hits_generative(tg_rt):
    from aiohttp.test_utils import TestClient, TestServer
    from tpuserve.config import CacheConfig
    from tpuserve.server import make_app

    state = _gen_server(cache=CacheConfig(enabled=True))

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            body = json.dumps({"prompt": "cache me", "seed": 7,
                               "max_new_tokens": 4})
            hdrs = {"Content-Type": "application/json"}
            r1 = await client.post("/v1/models/tg:generate", data=body,
                                   headers=hdrs)
            b1 = await r1.read()
            r2 = await client.post("/v1/models/tg:generate", data=body,
                                   headers=hdrs)
            assert await r2.read() == b1
            c = state.caches["tg"].stats()
            assert c["hits"] == 1 and c["misses"] == 1
            # Seed change -> different key -> a second real generation.
            r3 = await client.post(
                "/v1/models/tg:generate",
                data=json.dumps({"prompt": "cache me", "seed": 8,
                                 "max_new_tokens": 4}), headers=hdrs)
            assert r3.status == 200
            assert state.caches["tg"].stats()["misses"] == 2
        finally:
            await client.close()

    run(go())


@pytest.mark.slow
def test_http_sd15_through_engine():
    """SD 1.5 (tiny variant) serves txt2img through the iteration-level
    engine: PNG out, deterministic in (prompt, seed), per-slot step
    counters visible in /stats."""
    from aiohttp.test_utils import TestClient, TestServer
    from tpuserve.server import ServerState, make_app

    cfg = ServerConfig(
        decode_threads=2,
        genserve=GenserveConfig(enabled=True, slots=2),
        models=[ModelConfig(
            name="sd", family="sd15", batch_buckets=[1, 2], dtype="float32",
            parallelism="single", image_size=32,
            request_timeout_ms=120_000.0,
            options=dict(steps=3, guidance=5.0, vocab_size=512,
                         text_layers=1, text_d_model=32, text_heads=2,
                         unet_ch=16, unet_mults=[1, 2], unet_res=1,
                         unet_attn_levels=[0], unet_heads=2, vae_ch=16,
                         vae_mults=[1, 2]))])
    state = ServerState(cfg)
    state.build()

    async def go():
        client = TestClient(TestServer(make_app(state)))
        await client.start_server()
        try:
            hdrs = {"Content-Type": "application/json"}
            body = json.dumps({"prompt": "a red fox", "seed": 7})
            r1, r2 = await asyncio.gather(
                client.post("/v1/models/sd:generate", data=body,
                            headers=hdrs),
                client.post("/v1/models/sd:generate",
                            data=json.dumps({"prompt": "blue", "seed": 9}),
                            headers=hdrs))
            assert r1.status == 200 and r2.status == 200
            png1 = await r1.read()
            assert png1[:8] == b"\x89PNG\r\n\x1a\n"
            assert r1.content_type == "image/png"
            # Deterministic: same (prompt, seed) -> byte-identical PNG.
            r1b = await client.post("/v1/models/sd:generate", data=body,
                                    headers=hdrs)
            assert await r1b.read() == png1
            stats = await (await client.get("/stats")).json()
            assert stats["genserve"]["sd"]["iterations_total"] > 0
        finally:
            await client.close()

    run(go())


# ---------------------------------------------------------------------------
# A finished lane is frozen (ISSUE 41): the contract the step ahead rests on
# ---------------------------------------------------------------------------
# The engine reads step k's out-block while step k+1 runs, so a lane that
# out(k) reports done has ridden step k+1 by the time its extract reads it,
# and its pages may be another request's by then. Every registered generating
# family is held to GenerativeModel.step's docstring here, through its own
# compiled programs: the two without a benchmark cell and the mesh decode
# path included.

SD_OPTS = dict(steps=3, vocab_size=128, text_layers=1, text_d_model=16, text_heads=2,
               unet_ch=8, unet_mults=[1, 2], unet_res=1, unet_attn_levels=[0],
               unet_heads=2, vae_ch=8, vae_mults=[1, 2])
FROZEN_SLOTS = 3


def _frozen_case(name, tmp_path):
    """(model, runtime arguments, [genserve] keys, short item, long item)."""
    from tpuserve.config import ParallelConfig

    ids = lambda model, n, max_new, first=1: model.host_decode(json.dumps(  # noqa: E731
        {"prompt_ids": list(range(model.v_first + first, model.v_first + first + n)),
         "max_new_tokens": max_new}).encode(),
        "application/json")
    if name.startswith("textgen"):
        sharded = "sharded" in name
        model = build(tg_cfg(parallelism="sharded", tp=2) if sharded else tg_cfg())
        rt_kw = {"parallel": ParallelConfig(n_chips=4)} if sharded else {}
        gc = dict(kv_paging=True, kv_page_tokens=8) if "paged" in name else {}
        return (model, rt_kw, gc, prompt_item(model, "short", seed=2, max_new=4),
                prompt_item(model, "a long one", seed=1, max_new=40))
    if name == "sd15":
        model = build(ModelConfig(name="sd", family="sd15", batch_buckets=[1], dtype="float32",
                                  parallelism="single", image_size=32, options=dict(SD_OPTS)))
        item = lambda p, s: model.host_decode(  # noqa: E731
            json.dumps({"prompt": p, "seed": s}).encode(), "application/json")
        return model, {}, {}, item("a fox", 1), item("a hen", 2)
    maker = {"decoder": "tests.test_decoder", "hybrid": "tests.test_hybrid",
             "hybrid_ffn": "tests.test_hybrid_ffn", "mla": "tests.test_mla",
             "mla_sc": "tests.test_mla_sc", "mla_hc": "tests.test_mla_hc",
             "decoder_sink": "tests.test_decoder_sink",
             "hybrid_delta": "tests.test_hybrid_delta", "eva": "tests.test_eva",
             "hybrid_conv": "tests.test_hybrid_conv", "mla_sel": "tests.test_mla_sel",
             "hybrid_ffn_moe": "tests.test_hybrid_ffn_moe",
             "hybrid_blk": "tests.test_hybrid_blk"}[name]
    import importlib
    module = importlib.import_module(maker)
    model = module.make_model(str(tmp_path), name="fz")
    page = getattr(module, "FROZEN_PAGE", 4)   # a family whose pages must be wider says so
    return (model, {}, dict(kv_paging=True, kv_page_tokens=page, prefill_chunk=8),
            ids(model, 5, 4), ids(model, 6, 10, first=20))


FROZEN_CASES = ["textgen-dense", "textgen-paged", "textgen-sharded-dense", "textgen-sharded-paged",
                "sd15", "decoder", "hybrid", "hybrid_ffn", "mla", "mla_sc", "mla_hc",
                "decoder_sink", "hybrid_delta", "eva", "hybrid_conv", "mla_sel", "hybrid_ffn_moe",
                "hybrid_blk"]


# ---------------------------------------------------------------------------
# What a slot keeps is said once (ISSUE 70): every paged family's CachePlan
# ---------------------------------------------------------------------------
# /stats ``kv`` at the toy geometry, as the commit before ISSUE 70 printed it
# (six functions of the engine over four tuples a family), for the families whose
# own files pin no number: (kv_bytes, row_bytes_per_token, page_positions,
# state_bytes_per_slot, state_bytes).
KV_PINNED = {"decoder": (28672, 256, 4, 0, 0), "hybrid_ffn_moe": (229376, 2048, 4, 45824, 137472),
             "hybrid_blk": (117760, 320, 8, 12288, 36864), "textgen": (63488, 256, 8, 0, 0)}
PLAN_CASES = [c for c in FROZEN_CASES if "-" not in c and c != "sd15"] + ["textgen-paged"]


@pytest.mark.parametrize("case", PLAN_CASES)
def test_the_plan_gives_every_leaf_one_kind_and_its_bytes_add_up(case, tmp_path):
    import importlib

    import jax
    import numpy as np

    from tpuserve.genserve.model import LeafKind
    family = case.split("-")[0]
    model, rt_kw, gc, _short, _long = _frozen_case(case, tmp_path)
    if family == "textgen":
        slots, page = FROZEN_SLOTS, gc["kv_page_tokens"]
    else:
        toy = importlib.import_module(f"tests.test_{family}")
        slots, page = toy.SLOTS, toy.PAGE
    plan = model.kv_plan(slots, page)
    assert plan.pages == slots * plan.pages_per_slot + 1 and plan.slots == slots
    nbytes = lambda tree: sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize  # noqa: E731
                              for x in jax.tree_util.tree_leaves(tree))
    # one kind a leaf, and the leaf is shaped as its kind says it is addressed
    by_kind = {kind: plan.leaves(kind) for kind in LeafKind}
    assert sorted(sum(by_kind.values(), ())) == sorted(plan.state) and set(plan.kinds) <= set(plan.state)
    pooled = plan.pages + (slots + 1) * plan.ring_pages
    for leaf in plan.state:
        for x in jax.tree_util.tree_leaves(plan.state[leaf]):
            kind = plan.kind(leaf)
            if kind is LeafKind.POOL:
                assert any(d and d % pooled == 0 for d in x.shape), (leaf, x.shape)
            elif kind is LeafKind.RINGS:
                assert x.shape[0] == slots + 1 and plan.ring_tokens in x.shape, (leaf, x.shape)
            elif kind is LeafKind.SLOT:
                assert x.shape[0] == slots, (leaf, x.shape)
            else:
                assert pooled not in x.shape and (not x.shape or x.shape[0] != slots + 1), leaf
    if hasattr(model, "_leaves"):   # what the programs find their leaves by is the plan's
        assert {leaf: plan.kind(leaf) for leaf in model._leaves()} == plan.kinds
    # the bytes by kind are the state's, and the derived ones divide them
    assert [plan.bytes_of(kind) for kind in LeafKind] \
        == [nbytes([plan.state[leaf] for leaf in by_kind[kind]]) for kind in LeafKind]
    assert sum(plan.bytes_of(kind) for kind in LeafKind) == nbytes(plan.state)
    assert plan.pool_bytes == pooled * plan.page_bytes > 0
    assert plan.page_bytes == plan.page_positions * plan.row_bytes
    assert plan.ring_bytes == plan.bytes_of(LeafKind.RINGS) \
        + (slots + 1) * plan.ring_pages * plan.page_bytes
    assert bool(plan.ring_bytes) == bool(plan.ring_tokens)
    assert plan.slot_bytes % slots == 0
    # a request at the family's longest context takes a whole row of the block table
    worst = (None, np.int32(model.max_prompt), None, np.int32(model.max_new))
    assert plan.pages_for(model.context_tokens(worst)) == plan.pages_per_slot
    assert plan.pages_for(1) == 1 and plan.state["bt"].shape == (slots, plan.pages_per_slot)
    # and /stats says the plan's numbers
    rt = build_runtime(model, compile_forward=False, **rt_kw)
    eng = GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=slots, kv_paging=True, kv_page_tokens=page, prefill_chunk=gc.get("prefill_chunk", 0)))
    kv = eng.pipeline_stats()["kv"]
    said = (kv["kv_bytes"], kv["row_bytes_per_token"], kv["page_positions"],
            kv["state_bytes_per_slot"], kv["state_bytes"])
    assert said == (plan.pool_bytes, plan.row_bytes, plan.page_positions,
                    plan.slot_bytes // slots, plan.slot_bytes)
    assert ("ring_bytes" in kv) == bool(plan.ring_pages)
    assert said == KV_PINNED.get(family, said)


def test_the_frozen_lane_cases_name_every_registered_generating_family():
    import importlib

    from tpuserve import models
    from tpuserve.genserve.model import GenerativeModel
    generating = {
        f for f in models.families()
        if any(isinstance(v, type) and issubclass(v, GenerativeModel) and v is not GenerativeModel
               for v in vars(importlib.import_module(models._REGISTRY[f])).values())}
    assert generating == {c.split("-")[0] for c in FROZEN_CASES}


@pytest.mark.parametrize("case", FROZEN_CASES)
def test_a_lane_whose_out_block_said_done_is_not_changed_by_one_more_step(case, tmp_path):
    import jax
    import numpy as np

    from tpuserve.genserve.model import PrefillPiece
    model, rt_kw, gc, short, long_ = _frozen_case(case, tmp_path)
    rt = build_runtime(model, compile_forward=False, **rt_kw)
    eng = GenEngine(model, rt, Metrics(), GenserveConfig(slots=FROZEN_SLOTS, **gc))
    eng.compile()
    slots, lane, other = FROZEN_SLOTS, 1, 0
    state = eng._host_zeros(eng._state_struct)
    pps = eng.plan.pages_per_slot if eng.paging else 0

    def fold(state, slot, item):
        if not eng.paging:
            return rt.run_program("insert", state, np.int32(slot), item)
        row = eng._cache_row(list(range(1 + slot * pps, 1 + (slot + 1) * pps)), slot + 1)
        n, chunk = model.prompt_tokens(item), eng._prefill_chunk
        for s in range(0, n, chunk):
            state = rt.run_program("prefill", state, model.pack_prefill(
                [PrefillPiece(slot, item, s, min(chunk, n - s), row)], chunk, eng._prefill_pieces))
        return state

    # the short request first, so that the other lane is still at work when it is done
    state = fold(state, lane, short)
    state, out = rt.run_program("step", state)
    state = fold(state, other, long_)
    assert not bool(np.asarray(out["done"])[lane])
    for _ in range(eng._max_steps_guard):
        state, out = rt.run_program("step", state)
        if bool(np.asarray(out["done"])[lane]):
            break
    assert bool(np.asarray(out["done"])[lane]) and not bool(np.asarray(out["done"])[other])
    before = jax.tree_util.tree_map(np.array, state)  # the step donates its block
    answer = jax.tree_util.tree_map(np.array, rt.run_program("extract", state, np.int32(lane)))
    state, out2 = rt.run_program("step", state)
    assert bool(np.asarray(out2["done"])[lane])
    after = jax.tree_util.tree_map(np.array, state)
    # the lane's rows of every leaf, and the pages and the ring it holds, bit for bit;
    # the other lane's step was a real one (something of the block did change)
    n_pages = eng.pages.pages if eng.paging else -1
    mine = np.arange(1 + lane * pps, 1 + (lane + 1) * pps)
    # a family whose rings lie in the page leaves, before the pages (ISSUE 55): a ring is
    # ``per_ring`` pages of them, ring 0 the sentinel
    per_ring = eng.plan.ring_pages if eng.paging else 0
    pooled = n_pages + (slots + 1) * per_ring if per_ring else -1
    held, changed = 0, False
    flat_b = jax.tree_util.tree_flatten_with_path(before)[0]
    for (path, b), a in zip(flat_b, jax.tree_util.tree_leaves(after)):
        where = jax.tree_util.keystr(path)
        changed |= not np.array_equal(a, b)
        if pooled in b.shape:
            ax = b.shape.index(pooled)
            its = np.concatenate([np.arange(per_ring * (lane + 1), per_ring * (lane + 2)),
                                  (slots + 1) * per_ring + mine])
            np.testing.assert_array_equal(np.take(a, its, ax), np.take(b, its, ax), err_msg=where)
            held += 1
        elif b.ndim and b.shape[0] == slots:
            np.testing.assert_array_equal(a[lane], b[lane], err_msg=where)
        elif n_pages in b.shape:
            ax = b.shape.index(n_pages)
            np.testing.assert_array_equal(np.take(a, mine, ax), np.take(b, mine, ax), err_msg=where)
            held += 1
        elif b.ndim and b.shape[0] == slots + 1:  # a window ring a slot, ring 0 the sentinel
            np.testing.assert_array_equal(a[lane + 1], b[lane + 1], err_msg=where)
            held += 1
    assert changed and (held > 0) == eng.paging
    # so what extract reads of the lane after the step ahead is what it read before it
    again = jax.tree_util.tree_map(np.array, rt.run_program("extract", state, np.int32(lane)))
    jax.tree_util.tree_map(np.testing.assert_array_equal, answer, again)
