"""Content-addressed result cache + single-flight coalescing (ISSUE 5):
digest stability, LRU/TTL bookkeeping, coalescing fan-out, version-churn
stale drops, and the honest-accounting invariants a measurement relies on.

Everything here is unit-level against ModelCache with hand-driven futures;
the HTTP integration (hit fast path, client-batch slot merge) lives in
test_http.py and the lifecycle-churn end-to-end in test_lifecycle.py.
"""

import asyncio
import json

import numpy as np
import pytest

from tpuserve.cache import (CacheEntry, ModelCache, counter_snapshot,
                            hit_rate, item_digest)
from tpuserve.config import CacheConfig
from tpuserve.obs import Metrics


def make_cache(version=1, **cfg_over) -> tuple[ModelCache, Metrics, list]:
    """Cache with a mutable version cell: bump live_version[0] to simulate a
    lifecycle publish/rollback."""
    live_version = [version]
    metrics = Metrics()
    cache = ModelCache("toy", CacheConfig(enabled=True, **cfg_over), metrics,
                       version_fn=lambda: live_version[0])
    return cache, metrics, live_version


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


# ---------------------------------------------------------------------------
# Content digest
# ---------------------------------------------------------------------------

def test_item_digest_stable_across_copies():
    a = np.arange(192, dtype=np.uint8).reshape(8, 8, 3)
    assert item_digest(a) == item_digest(a.copy())
    # Non-contiguous views digest by content, not layout.
    assert item_digest(a[:, ::1]) == item_digest(np.ascontiguousarray(a))


def test_item_digest_sensitive_to_content_shape_dtype():
    a = np.arange(64, dtype=np.uint8)
    b = a.copy()
    b[0] += 1
    assert item_digest(a) != item_digest(b)
    # Same bytes, different shape / dtype must not collide.
    assert item_digest(a) != item_digest(a.reshape(8, 8))
    assert item_digest(a) != item_digest(a.view(np.int8))


def test_item_digest_structures():
    a = np.arange(16, dtype=np.float32)
    # dict key order is canonicalized; tuple vs list is distinguished.
    assert (item_digest({"x": a, "y": 1})
            == item_digest({"y": 1, "x": a}))
    assert item_digest((a, 1)) != item_digest([a, 1])
    assert item_digest("1") != item_digest(1)


def test_key_for_binds_live_version():
    cache, _, live_version = make_cache(version=3)
    a = np.arange(8, dtype=np.uint8)
    k3 = cache.key_for(a)
    live_version[0] = 4
    assert cache.key_for(a) != k3
    assert k3.startswith("3:")


# ---------------------------------------------------------------------------
# get / put bookkeeping
# ---------------------------------------------------------------------------

def test_put_get_and_hit_counting():
    cache, metrics, _ = make_cache()
    cache.put("k", {"top_k": [1, 2]})
    e = cache.get("k")
    assert e is not None and e.value == {"top_k": [1, 2]}
    assert cache.get("missing") is None
    # Hits count; a miss in get() does NOT (the miss is counted at
    # submit_through, where exactly one leader exists per flight).
    assert metrics.counter("cache_hits_total{model=toy}").value == 1
    assert metrics.counter("cache_misses_total{model=toy}").value == 0


def test_lru_eviction_prefers_stale_entries():
    cache, metrics, _ = make_cache(capacity=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") is not None  # touch: "a" is now most-recent
    cache.put("c", 3)  # evicts "b", the least-recently-used
    assert cache.get("b") is None
    assert cache.get("a") is not None and cache.get("c") is not None
    assert metrics.counter("cache_evictions_total{model=toy}").value == 1
    assert metrics.gauge("cache_entries{model=toy}").value == 2


def test_ttl_expiry():
    cache, _, _ = make_cache(ttl_s=10.0)
    cache.put("k", 1)
    assert cache.get("k") is not None
    # Backdate the entry past the TTL instead of sleeping.
    cache._entries["k"] = CacheEntry(1, None, cache._entries["k"].at - 11.0)
    assert cache.get("k") is None
    assert cache.stats()["entries"] == 0


def test_put_preserializes_json_body():
    cache, _, _ = make_cache()
    val = {"top_k": [{"class": 1, "prob": 0.5}]}
    cache.put("k", val)
    assert cache.get("k").body == json.dumps(val).encode()
    # Oversized and non-JSON values cache by value only (body None).
    big_cache, _, _ = make_cache(max_body_bytes=4)
    big_cache.put("k", val)
    assert big_cache.get("k").body is None
    cache.put("png", b"\x89PNG")
    assert cache.get("png").body is None and cache.get("png").value == b"\x89PNG"


# ---------------------------------------------------------------------------
# Single-flight coalescing
# ---------------------------------------------------------------------------

def test_single_flight_coalesces_identical_misses():
    async def go():
        cache, metrics, _ = make_cache()
        loop = asyncio.get_running_loop()
        base = loop.create_future()
        calls = []

        def submit():
            calls.append(1)
            return base

        waiters = [cache.submit_through("k", submit) for _ in range(4)]
        assert len(calls) == 1  # ONE batch slot for four identical requests
        base.set_result({"top_k": [7]})
        res = await asyncio.gather(*waiters)
        assert res == [{"top_k": [7]}] * 4
        assert metrics.counter("cache_misses_total{model=toy}").value == 1
        assert metrics.counter("cache_coalesced_total{model=toy}").value == 3
        # The flight populated the cache and is no longer inflight.
        assert cache.get("k").value == {"top_k": [7]}
        assert cache.stats()["inflight"] == 0

    run(go())


def test_failed_flight_fans_error_and_populates_nothing():
    async def go():
        cache, metrics, _ = make_cache()
        base = asyncio.get_running_loop().create_future()
        waiters = [cache.submit_through("k", lambda: base) for _ in range(3)]
        base.set_exception(RuntimeError("poison batch"))
        for w in waiters:
            with pytest.raises(RuntimeError, match="poison batch"):
                await w
        assert cache.get("k") is None  # a failed batch caches NOTHING
        assert cache.stats()["entries"] == 0
        # The next identical request leads a fresh flight (no stuck state).
        base2 = asyncio.get_running_loop().create_future()
        w2 = cache.submit_through("k", lambda: base2)
        base2.set_result(1)
        assert await w2 == 1
        assert metrics.counter("cache_misses_total{model=toy}").value == 2

    run(go())


def test_mid_flight_version_change_drops_result_from_cache():
    async def go():
        cache, metrics, live_version = make_cache(version=1)
        key = cache.key_for(np.arange(8, dtype=np.uint8))
        base = asyncio.get_running_loop().create_future()
        w = cache.submit_through(key, lambda: base)
        live_version[0] = 2  # publish lands while the batch is in flight
        base.set_result({"top_k": [1]})
        # The waiter still gets its result (same as an uncached request
        # spanning the publish) but no future lookup can observe it.
        assert await w == {"top_k": [1]}
        assert cache.get(key) is None
        assert cache.stats()["entries"] == 0
        assert metrics.counter(
            "cache_stale_drops_total{model=toy}").value == 1

    run(go())


def test_waiter_cancellation_never_cancels_the_flight():
    async def go():
        cache, _, _ = make_cache()
        base = asyncio.get_running_loop().create_future()
        w1 = cache.submit_through("k", lambda: base)
        w2 = cache.submit_through("k", lambda: base)
        w1.cancel()  # client disconnect
        assert not base.cancelled()
        base.set_result(42)
        assert await w2 == 42  # the other waiter is unaffected
        assert cache.get("k").value == 42  # and the flight still populated

    run(go())


def test_submit_exception_propagates_with_nothing_registered():
    async def go():
        cache, metrics, _ = make_cache()

        def submit():
            raise RuntimeError("queue full")

        with pytest.raises(RuntimeError, match="queue full"):
            cache.submit_through("k", submit)
        assert cache.stats()["inflight"] == 0
        assert metrics.counter("cache_misses_total{model=toy}").value == 0

    run(go())


def test_coalesce_disabled_every_miss_submits():
    async def go():
        cache, metrics, _ = make_cache(coalesce=False)
        loop = asyncio.get_running_loop()
        bases, calls = [], []

        def submit():
            calls.append(1)
            bases.append(loop.create_future())
            return bases[-1]

        w1 = cache.submit_through("k", submit)
        w2 = cache.submit_through("k", submit)
        assert len(calls) == 2  # no flight registry: both lead
        for b in bases:
            b.set_result(1)
        assert await asyncio.gather(w1, w2) == [1, 1]
        assert metrics.counter("cache_coalesced_total{model=toy}").value == 0

    run(go())


# ---------------------------------------------------------------------------
# Accounting helpers (the cache smoke's)
# ---------------------------------------------------------------------------

def test_hit_rate_definition():
    assert hit_rate({"hits": 0, "misses": 0, "coalesced": 0}) is None
    assert hit_rate({"hits": 3, "misses": 1, "coalesced": 0}) == 0.75
    # Coalesced waiters are NOT hits: they occupied a real flight.
    assert hit_rate({"hits": 0, "misses": 1, "coalesced": 3}) == 0.0


def test_counter_snapshot_roundtrip():
    cache, metrics, _ = make_cache()
    cache.put("k", 1)
    cache.get("k")
    snap = counter_snapshot(metrics, "toy")
    assert snap == {"hits": 1.0, "misses": 0.0, "coalesced": 0.0}
