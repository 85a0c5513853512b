"""Pipelined host execution engine (ISSUE 3; tpuserve.hostpipe +
batcher stage pipeline).

Overlap is proven with fake *timed* stages: a runtime whose fetch sleeps a
known duration and a model whose assemble sleeps a known duration, both
recording wall-clock intervals. With depth-k staging, batch N+1's assembly
must run while batch N computes, aggregate stage busy time must exceed
elapsed wall time, arena recycling must never hand out an in-use buffer,
and depth-k dispatch must preserve per-request result mapping and the
PR-2 deadline 504 semantics.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from tpuserve.batcher import DeadlineExceeded, ModelBatcher
from tpuserve.config import ModelConfig, PipelineConfig
from tpuserve.hostpipe import AssemblyArena, SlotPool, SlotsClosed, StageExecutors
from tpuserve.models import build
from tpuserve.models.base import ServingModel
from tpuserve.obs import PIPELINE_STAGES, Metrics


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class Recorder:
    """Thread-safe (stage, start, end, tag) interval log."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: list[tuple] = []

    def record(self, stage, t0, t1, tag=None):
        with self._lock:
            self.events.append((stage, t0, t1, tag))

    def intervals(self, stage):
        with self._lock:
            return [(t0, t1, tag) for s, t0, t1, tag in self.events if s == stage]


class FakeModel:
    """Minimal direct-mode model: items are scalar floats, the host batch is
    a (bucket, 4) float32 array whose row 0 column carries the item value.
    Defines assemble_into (alongside assemble) so the batcher takes the
    arena path."""

    def __init__(self, cfg, rec: Recorder, assemble_s=0.0):
        self.cfg = cfg
        self.name = cfg.name
        self.rec = rec
        self.assemble_s = assemble_s

    def bucket_for(self, n, **kw):
        for b in self.cfg.batch_buckets:
            if b >= n:
                return (b,)
        return (self.cfg.batch_buckets[-1],)

    def input_signature(self, bucket):
        import jax

        return jax.ShapeDtypeStruct((bucket[0], 4), np.float32)

    def group_key(self, item):
        return None

    def assemble(self, items, bucket):
        out = np.zeros((bucket[0], 4), np.float32)
        return self.assemble_into(items, bucket, out)

    def assemble_into(self, items, bucket, out):
        t0 = time.perf_counter()
        if self.assemble_s:
            time.sleep(self.assemble_s)
        out[:] = 0
        for i, it in enumerate(items):
            out[i, :] = float(it)
        self.rec.record("assemble", t0, time.perf_counter(),
                        tag=float(items[0]))
        return out

    def host_postprocess(self, outputs, n_valid):
        return [float(outputs[i, 0]) for i in range(n_valid)]


class FakeRuntime:
    """Direct-mode runtime whose fetch (the compute wait) sleeps a
    per-batch duration keyed by the batch's first item value."""

    def __init__(self, rec: Recorder, compute_s=0.1, per_batch=None):
        self.rec = rec
        self.compute_s = compute_s
        self.per_batch = per_batch or {}
        self.n_replicas = 1

    def pick_replica(self):
        return 0

    def run(self, bucket, host_batch, replica=0, params_override=None):
        t0 = time.perf_counter()
        out = np.array(host_batch, copy=True)  # device_put semantics
        self.rec.record("h2d", t0, time.perf_counter(), tag=float(out[0, 0]))
        return out

    def fetch(self, outputs):
        t0 = time.perf_counter()
        tag = float(outputs[0, 0])
        time.sleep(self.per_batch.get(tag, self.compute_s))
        self.rec.record("fetch", t0, time.perf_counter(), tag=tag)
        return outputs


def fake_cfg(**over):
    base = dict(name="fake", family="toy", batch_buckets=[1],
                deadline_ms=5.0, dtype="float32", num_classes=10,
                parallelism="single", max_queue=64, max_inflight=2)
    base.update(over)
    return ModelConfig(**base)


def make_fake_batcher(rec=None, compute_s=0.1, per_batch=None, assemble_s=0.0,
                      pipeline_cfg=None, **cfg_over):
    rec = rec or Recorder()
    cfg = fake_cfg(**cfg_over)
    model = FakeModel(cfg, rec, assemble_s=assemble_s)
    rt = FakeRuntime(rec, compute_s=compute_s, per_batch=per_batch)
    metrics = Metrics()
    b = ModelBatcher(model, rt, metrics, pipeline_cfg=pipeline_cfg)
    return b, metrics, rec


# -- overlap (the tentpole's proof) ------------------------------------------

def test_pipeline_overlaps_assembly_with_compute():
    """Batch N+1's assemble runs while batch N's compute is in flight, and
    aggregate stage busy time exceeds elapsed wall time (the acceptance
    criterion's pipelining proof, at unit scale). Assembly (80 ms) is not
    small against the compute (50 ms) here, so once the first batch has
    been measured the batcher closes batches ahead of the device section
    (ISSUE 26: when the device time queued falls to twice the staging
    time, bounded by assemble_ahead); where it is small nothing is
    assembled ahead (tests/test_late_close.py)."""
    async def go():
        b, metrics, rec = make_fake_batcher(
            compute_s=0.05, assemble_s=0.08,
            pipeline_cfg=PipelineConfig(depth=2, assemble_ahead=2))
        await b.start()
        assert b._use_arena and b.arena is not None
        # Nothing measured yet: the gate counts the device section.
        assert b._close_wait_s(b.depth - 1, False) == 0
        assert b._close_wait_s(b.depth, False) == float("inf")
        t0 = time.perf_counter()
        futs = [b.submit(float(i + 1)) for i in range(6)]
        res = await asyncio.wait_for(asyncio.gather(*futs), timeout=10)
        elapsed = time.perf_counter() - t0
        assert b._inflight_peak > b.depth  # batches were closed ahead
        assert b.pipeline_stats()["admission"] == b.depth + 2
        await b.stop()

        assert res == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
        fetches = rec.intervals("fetch")
        assembles = rec.intervals("assemble")
        assert len(fetches) == 6 and len(assembles) == 6
        busy = sum(t1 - t0 for t0, t1, _ in fetches + assembles)
        # 6 x 0.05 fetch + 6 x 0.08 assemble = 0.78 s of stage time; with
        # depth 2 it must pack into well under the sequential sum.
        assert busy > elapsed, (busy, elapsed)
        assert elapsed < 0.62, elapsed  # sequential would be >= 0.78
        # Direct interval evidence: a later batch's assemble ran
        # concurrently with an earlier batch's compute (>= 20 ms overlap).
        overlapped = any(
            min(a1, fe) - max(a0, fs) > 0.02
            for a0, a1, atag in assembles
            for fs, fe, ftag in fetches
            if atag != ftag
        )
        assert overlapped, (assembles, fetches)

    run(go())


def test_depth_bounds_concurrent_device_batches():
    """depth=1 serializes the device section: fetch intervals never
    overlap each other, however many batches admission closes ahead (at
    most assemble_ahead = 3 past the one slot) — at depth 1 the section's
    spare slots are never given out."""
    async def go():
        b, _, rec = make_fake_batcher(
            compute_s=0.08,
            pipeline_cfg=PipelineConfig(depth=1, assemble_ahead=3))
        await b.start()
        futs = [b.submit(float(i + 1)) for i in range(3)]
        await asyncio.wait_for(asyncio.gather(*futs), timeout=10)
        await b.stop()
        fetches = sorted(rec.intervals("fetch"))
        for (_, e_prev, _), (s_next, _, _) in zip(fetches, fetches[1:]):
            assert s_next >= e_prev - 1e-4, fetches

    run(go())


def test_depth_k_preserves_result_ordering():
    """Out-of-order completion (batch 1 slow, batch 2 fast) still resolves
    each future with its own request's result."""
    async def go():
        b, _, rec = make_fake_batcher(
            per_batch={1.0: 0.2, 2.0: 0.02, 3.0: 0.02},
            pipeline_cfg=PipelineConfig(depth=2, assemble_ahead=2))
        await b.start()
        futs = [b.submit(float(i + 1)) for i in range(3)]
        res = await asyncio.wait_for(asyncio.gather(*futs), timeout=10)
        await b.stop()
        assert res == [1.0, 2.0, 3.0]
        # The fast batches really did finish before the slow one.
        done_order = [tag for _, _, tag in sorted(rec.intervals("fetch"),
                                                  key=lambda iv: iv[1])]
        assert done_order[-1] == 1.0, done_order

    run(go())


@pytest.mark.parametrize("ahead", [0, 1])
def test_deadline_504_while_waiting_for_staging_slot(ahead):
    """PR-2 semantics through the pipelined path: a deadlined request stuck
    behind a slow in-flight batch fails AT its deadline (DeadlineExceeded,
    counted), not when the device section finally frees — whether its batch
    still waits to close (the gate counts the one staging slot) or was
    closed ahead and waits, assembled, for the slot itself."""
    async def go():
        b, metrics, rec = make_fake_batcher(
            compute_s=0.5,
            pipeline_cfg=PipelineConfig(depth=1, assemble_ahead=4))
        await b.start()
        # The gate by count, one slot and `ahead` batches past it.
        b._gate._wait_s = lambda held, full: \
            0.0 if held < 1 + ahead else float("inf")
        slow = b.submit(1.0)
        await asyncio.sleep(0.05)  # batch 1 occupies the only staging slot
        t0 = time.perf_counter()
        doomed = b.submit(2.0, deadline_at=t0 + 0.08)
        with pytest.raises(DeadlineExceeded):
            await asyncio.wait_for(doomed, timeout=10)
        waited = time.perf_counter() - t0
        assert waited < 0.35, waited
        assert metrics.counter(
            "deadline_exceeded_total{model=fake}").value == 1
        # Closed and assembled only where it was admitted ahead.
        assert len(rec.intervals("assemble")) == 1 + ahead
        assert await asyncio.wait_for(slow, timeout=10) == 1.0
        await b.stop()

    run(go())


# -- assembly arena ----------------------------------------------------------

def test_arena_never_hands_out_in_use_buffer():
    rec = Recorder()
    model = FakeModel(fake_cfg(batch_buckets=[4]), rec)
    arena = AssemblyArena(model, slots=2)
    outstanding: set[int] = set()
    lock = threading.Lock()

    def worker(n):
        for _ in range(n):
            lease = arena.acquire((4,))
            with lock:
                assert id(lease.buf) not in outstanding
                outstanding.add(id(lease.buf))
            time.sleep(0.001)
            with lock:
                outstanding.remove(id(lease.buf))
            arena.release(lease)

    threads = [threading.Thread(target=worker, args=(50,)) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert arena.leased == 0
    s = arena.stats()
    assert s["buckets"]["[4]"]["pooled"] <= 2


def test_arena_recycles_and_overflows():
    rec = Recorder()
    model = FakeModel(fake_cfg(batch_buckets=[2]), rec)
    arena = AssemblyArena(model, slots=1)
    a = arena.acquire((2,))
    b = arena.acquire((2,))  # pool exhausted -> overflow allocation
    assert a.pooled and not b.pooled
    assert a.buf is not b.buf
    assert arena.overflow_total == 1
    arena.release(a)
    arena.release(b)  # overflow buffer is NOT pooled
    c = arena.acquire((2,))
    assert c.buf is a.buf  # free-list recycled the pooled buffer
    arena.release(c)
    assert arena.stats()["buckets"]["[2]"]["free"] == 1


def test_batcher_recycles_arena_buffers_end_to_end():
    """Sequential batches reuse pooled buffers (no per-batch allocation) and
    every result is correct despite the reuse."""
    async def go():
        b, _, _ = make_fake_batcher(
            compute_s=0.0,
            pipeline_cfg=PipelineConfig(depth=1, assemble_ahead=0,
                                        arena_slots=1))
        await b.start()
        for i in range(6):
            assert await asyncio.wait_for(
                b.submit(float(i + 10)), timeout=10) == float(i + 10)
        stats = b.arena.stats()
        await b.stop()
        assert stats["overflow_total"] == 0
        assert stats["buckets"]["[1]"]["pooled"] == 1  # one buffer, 6 batches

    run(go())


# -- SlotPool ----------------------------------------------------------------

def test_slotpool_acquire_release():
    async def go():
        p = SlotPool(2)
        s1 = await p.acquire()
        s2 = await p.acquire()
        assert p.in_use == 2 and p.try_acquire() is None
        with pytest.raises(asyncio.TimeoutError):
            await p.acquire(timeout_s=0.02)
        waiter = asyncio.ensure_future(p.acquire())
        await asyncio.sleep(0.01)
        p.release(s1)
        assert await asyncio.wait_for(waiter, timeout=1) == s1
        p.release(s2)

    run(go())


def test_slotpool_close_wakes_waiters():
    async def go():
        p = SlotPool(1)
        await p.acquire()
        waiter = asyncio.ensure_future(p.acquire())
        await asyncio.sleep(0.01)
        p.close()
        with pytest.raises(SlotsClosed):
            await asyncio.wait_for(waiter, timeout=1)
        with pytest.raises(SlotsClosed):
            await p.acquire()

    run(go())


# -- StageExecutors ----------------------------------------------------------

def test_stage_executors_dedicated_pools_and_gauges():
    async def go():
        m = Metrics()
        st = StageExecutors(PipelineConfig(), m)
        try:
            names = {}
            for stage in PIPELINE_STAGES:
                names[stage] = await st.run(
                    "m", stage, lambda: threading.current_thread().name)
            for stage, tname in names.items():
                assert tname.startswith(f"pipe-{stage}"), (stage, tname)
            s = st.stats()
            assert set(s["workers"]) == set(PIPELINE_STAGES)
            assert all(v == 0 for v in s["depth"].values())
            assert all(s["submitted_total"][k] == 1 for k in PIPELINE_STAGES)
            assert m.gauge("pipeline_stage_depth{model=m,stage=h2d}").value == 0
        finally:
            st.shutdown()

    run(go())


# -- assemble_into equivalence ------------------------------------------------

def test_base_assemble_into_matches_assemble():
    cfg = ModelConfig(name="toy", family="toy", batch_buckets=[4],
                      dtype="float32", num_classes=10, parallelism="single")
    model = build(cfg)
    assert type(model).assemble is ServingModel.assemble
    rng = np.random.default_rng(0)
    items = [rng.integers(0, 255, (8, 8, 3), dtype=np.uint8) for _ in range(3)]
    want = model.assemble(items, (4,))
    # Dirty buffer: assemble_into must zero the padded rows, not trust them.
    buf = np.full((4, 8, 8, 3), 7, dtype=np.uint8)
    got = model.assemble_into(items, (4,), buf)
    assert got is buf
    np.testing.assert_array_equal(got, want)


def test_bert_assemble_into_matches_assemble():
    cfg = ModelConfig(
        name="bert", family="bert", batch_buckets=[2], seq_buckets=[8],
        dtype="float32", num_classes=4, parallelism="single",
        options=dict(layers=1, d_model=16, heads=2, d_ff=32, vocab_size=64))
    model = build(cfg)
    items = [np.array([5, 6, 7], np.int32), np.array([9], np.int32)]
    want = model.assemble(items, (2, 8))
    # A dirty buffer of the program's signature (on one device: ids,
    # segments and the [CLS] positions of documents that may share rows).
    bufs = tuple(np.full(s.shape, 33, s.dtype)
                 for s in model.input_signature((2, 8)))
    got = model.assemble_into(items, (2, 8), bufs)
    assert len(got) == len(want) == len(bufs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_custom_assemble_without_assemble_into_skips_arena():
    """A model overriding assemble but not assemble_into must fall back to
    the allocating path (equivalence unprovable)."""
    class Custom(FakeModel):
        def assemble(self, items, bucket):
            return super().assemble(items, bucket)
        assemble_into = ServingModel.assemble_into  # not a real override

    async def go():
        rec = Recorder()
        cfg = fake_cfg()
        model = Custom(cfg, rec)
        b = ModelBatcher(model, FakeRuntime(rec, compute_s=0.0), Metrics())
        await b.start()
        assert not b._use_arena and b.arena is None
        assert await asyncio.wait_for(b.submit(3.0), timeout=10) == 3.0
        await b.stop()

    run(go())


# -- runtime h2d/dispatch split ----------------------------------------------

def test_runtime_h2d_dispatch_split_matches_run():
    from tpuserve.runtime import build_runtime

    cfg = ModelConfig(name="toy", family="toy", batch_buckets=[2],
                      dtype="float32", num_classes=10, parallelism="single")
    model = build(cfg)
    rt = build_runtime(model)
    batch = np.random.default_rng(1).integers(0, 255, (2, 8, 8, 3),
                                              dtype=np.uint8)
    want = rt.fetch(rt.run((2,), batch))
    dev = rt.h2d((2,), batch)
    got = rt.fetch(rt.dispatch((2,), dev))
    np.testing.assert_allclose(got["probs"], want["probs"], rtol=1e-6)
    np.testing.assert_array_equal(got["indices"], want["indices"])


def test_donation_shape_check():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuserve.runtime import _donation_shapes_ok

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = NamedSharding(mesh, P())
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, np.float32)
    # identity-shaped: every input leaf aliases an output leaf
    assert _donation_shapes_ok(f32((4, 8)), sh, f32((4, 8)), sh)
    # classifier-shaped: input cannot alias the smaller output
    assert not _donation_shapes_ok(f32((4, 8)), sh, f32((4, 3)), sh)
    # two equal inputs, one matching output: only one can alias
    assert not _donation_shapes_ok(
        [f32((4, 8)), f32((4, 8))], sh, [f32((4, 8)), f32((4, 3))], sh)
