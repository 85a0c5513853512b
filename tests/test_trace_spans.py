"""The program's spans on the profiler's clock (ISSUE 25): obs.trace_span /
obs.trace_mark write `tpuserve.*` annotations into jax.profiler's own trace,
from the thread that does the work, exactly while a profiler session is on.

One tiny BERT is served over HTTP through the batcher under a session
started with the options the benchmark's traced run uses
(python_tracer_level 0, host_tracer_level 1); the capture is read back with
jax.profiler.ProfileData, as benchmark/host_spans.py and /debug/profile do.
"""

import asyncio
import glob
import json
import os
import time

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from tpuserve import obs
from tpuserve.config import ModelConfig, ServerConfig
from tpuserve.server import ServerState, make_app

SPANS = ("tpuserve.parse", "tpuserve.tokenize", "tpuserve.accumulate",
         "tpuserve.slot_wait", "tpuserve.staging_wait", "tpuserve.assemble",
         "tpuserve.h2d", "tpuserve.launch", "tpuserve.fetch",
         "tpuserve.postproc")
# The arguments each span must carry (ISSUE 25's table); `model` on all.
ARGS = {
    "tpuserve.parse": {"bytes"},
    "tpuserve.tokenize": {"items", "tokens"},
    "tpuserve.accumulate": {"batch", "n", "reason", "dur_us", "ago_us"},
    "tpuserve.slot_wait": {"batch", "dur_us", "ago_us"},
    "tpuserve.staging_wait": {"batch", "replica", "dur_us", "ago_us"},
    "tpuserve.assemble": {"batch", "bucket", "n"},
    "tpuserve.h2d": {"batch", "bucket", "n"},
    "tpuserve.fetch": {"batch", "bucket", "n"},
    "tpuserve.postproc": {"batch", "bucket", "n"},
    "tpuserve.launch": {"bucket", "replica"},
}


def _bert_cfg(**kw) -> ModelConfig:
    base = dict(name="bert", family="bert", batch_buckets=[1, 4],
                seq_buckets=[16], deadline_ms=30.0, dtype="float32",
                num_classes=3, parallelism="single",
                request_timeout_ms=20_000.0,
                options=dict(layers=1, d_model=16, heads=2, d_ff=32,
                             vocab_size=512))
    base.update(kw)
    return ModelConfig(**base)


def _start_session(log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _read_spans(log_dir: str) -> list[dict]:
    """Every tpuserve.* event of the capture: name, line (plane and line
    id: threads share names), start, end, args."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("tpuserve."):
                    out.append({"name": ev.name, "line": (plane.name, n),
                                "t0": ev.start_ns,
                                "t1": ev.start_ns + ev.duration_ns,
                                "args": dict(ev.stats)})
    return out


@pytest.fixture(scope="module")
def served():
    """A tiny BERT behind the real HTTP app; yields (run, client, state)."""
    cfg = ServerConfig(models=[_bert_cfg()], decode_threads=2,
                       startup_canary=False)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    try:
        yield loop.run_until_complete, client, state
    finally:
        loop.run_until_complete(client.close())
        loop.close()


def _n_ids(model, texts) -> int:
    """Ids the tokenizer makes of the texts, [CLS] and [SEP] included."""
    return sum(len(model.tokenizer.tokenize(t)) + 2 for t in texts)


async def _post(client, texts):
    r = await client.post("/v1/models/bert:classify",
                          data=json.dumps({"texts": texts}).encode(),
                          headers={"Content-Type": "application/json"})
    body = await r.json()
    assert r.status == 200, body
    return body


def test_session_holds_every_span_with_its_arguments(served, tmp_path):
    run, client, state = served
    texts = ["one two three", "four five", "six"]
    run(_post(client, ["warm up"]))  # nothing compiles inside the session
    _start_session(str(tmp_path))
    try:
        run(_post(client, texts))
    finally:
        jax.profiler.stop_trace()
    spans = _read_spans(str(tmp_path))
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert set(SPANS) <= set(by_name), sorted(by_name)
    for name, want in ARGS.items():
        for s in by_name[name]:
            assert want | {"model"} <= set(s["args"]), (name, s["args"])
            assert s["args"]["model"] == "bert"
    # One batch id from accumulate to postproc: the request's three texts
    # form one batch.
    bids = {s["args"]["batch"] for s in spans if "batch" in s["args"]}
    assert len(bids) == 1, bids
    for name in ("tpuserve.accumulate", "tpuserve.slot_wait",
                 "tpuserve.staging_wait", "tpuserve.assemble", "tpuserve.h2d",
                 "tpuserve.fetch", "tpuserve.postproc"):
        assert len(by_name[name]) == 1, name
    acc = by_name["tpuserve.accumulate"][0]["args"]
    # n is the count at the flush decision; items that queued during the
    # slot wait are folded in afterwards (the stages' n is the final 3).
    assert 1 <= acc["n"] <= 3 and acc["reason"] in ("target", "timer")
    assert by_name["tpuserve.h2d"][0]["args"]["bucket"] == "4x16"
    assert by_name["tpuserve.h2d"][0]["args"]["n"] == 3
    tok = by_name["tpuserve.tokenize"][0]["args"]
    assert tok["items"] == 3
    assert tok["tokens"] == _n_ids(state.models["bert"], texts)
    # launch is nested in h2d, on one thread.
    (launch,), (h2d,) = by_name["tpuserve.launch"], by_name["tpuserve.h2d"]
    assert launch["line"] == h2d["line"]
    assert h2d["t0"] <= launch["t0"] and launch["t1"] <= h2d["t1"]
    assert launch["args"]["bucket"] == "4x16"
    # tokenize is nested in parse, in a decode thread; the stages run in
    # threads of their own, in order.
    (parse,), (tokz,) = by_name["tpuserve.parse"], by_name["tpuserve.tokenize"]
    assert parse["line"] == tokz["line"] != h2d["line"]
    assert parse["t0"] <= tokz["t0"] and tokz["t1"] <= parse["t1"]
    order = [by_name[f"tpuserve.{s}"][0] for s in
             ("assemble", "h2d", "fetch", "postproc")]
    assert all(a["t1"] <= b["t0"] for a, b in zip(order, order[1:]))
    assert len({s["line"] for s in order}) == 4
    # A mark is placed where it was measured: accumulate ends no later
    # than the assemble stage begins.
    a = by_name["tpuserve.accumulate"][0]
    end_ns = a["t0"] - a["args"]["ago_us"] * 1e3
    assert end_ns <= order[0]["t0"]
    assert a["args"]["dur_us"] >= 0


def test_compile_span(tmp_path):
    """tpuserve.compile wraps each bucket's compilation."""
    from tpuserve import models as modelzoo
    from tpuserve.runtime import build_runtime

    model = modelzoo.build(_bert_cfg(name="bertc", batch_buckets=[2]))
    _start_session(str(tmp_path))
    try:
        build_runtime(model, metrics=obs.Metrics())
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in _read_spans(str(tmp_path))
             if s["name"] == "tpuserve.compile"]
    assert [s["args"]["bucket"] for s in spans] == ["2x16"]
    assert spans[0]["args"]["model"] == "bertc"


def test_no_session_records_nothing_and_raises_nothing(served, tmp_path):
    """With no profiler session on, the helpers do nothing: the requests
    served before a session starts leave no event in it, a mark writes
    nothing, and neither raises."""
    run, client, _state = served
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()
    with obs.trace_span("tpuserve.test_off", model="m", batch=1) as span:
        span.set_metadata(tokens=3)
    obs.trace_mark("tpuserve.test_off_mark", 1.0, 2.0, model="m", batch=1)
    run(_post(client, ["before the session"]))
    _start_session(str(tmp_path))
    try:
        with obs.trace_span("tpuserve.test_on", model="m"):
            pass
        t = time.perf_counter()
        obs.trace_mark("tpuserve.test_on_mark", t - 0.5, t - 0.25, batch=7)
    finally:
        jax.profiler.stop_trace()
    spans = {s["name"]: s for s in _read_spans(str(tmp_path))}
    assert set(spans) == {"tpuserve.test_on", "tpuserve.test_on_mark"}
    mark = spans["tpuserve.test_on_mark"]["args"]
    assert mark["batch"] == 7 and mark["dur_us"] == 250_000
    assert 250_000 <= mark["ago_us"] < 2_000_000


def _metric(text: str, name: str, **labels) -> float:
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            if all(f'{k}="{v}"' in line for k, v in labels.items()):
                total += float(line.split(" # ")[0].rsplit(" ", 1)[1])
                seen = True
    assert seen, (name, labels)
    return total


def test_counters_and_both_flush_reasons_tick(served):
    run, client, state = served
    frame = ["a b", "c d", "e f", "g h"]

    async def go():
        before = await (await client.get("/metrics")).text()
        await _post(client, frame)
        await _post(client, ["alone"])
        return before, await (await client.get("/metrics")).text()

    before, after = run(go())

    def d(name, **labels):
        return _metric(after, name, model="bert", **labels) \
            - _metric(before, name, model="bert", **labels)

    assert d("ingest_tokens_total") == _n_ids(state.models["bert"],
                                              frame + ["alone"])
    assert d("ingest_tokenize_cpu_seconds_total") > 0
    assert d("latency_ms_count", phase="tokenize") == 2
    flushes = d("batcher_flushes_total", reason="target") \
        + d("batcher_flushes_total", reason="timer")
    assert flushes == d("latency_ms_count", phase="slot_wait") >= 2
    assert flushes == d("batches_total")
    # tokenize is a part of parse, for the same requests.
    assert d("latency_ms_sum", phase="tokenize") \
        <= d("latency_ms_sum", phase="parse")


def test_flush_reasons_target_and_timer():
    """A full bucket flushes for `target`; a lone item waits out the timer
    and flushes for `timer` (fixed-timer batching, so the target is the
    largest bucket). The bucket is full when its ROWS are: documents of 12
    tokens, so that no two share a row of 16."""

    from tpuserve import models as modelzoo
    from tpuserve.batcher import ModelBatcher
    from tpuserve.config import AdaptiveConfig
    from tpuserve.runtime import build_runtime

    metrics = obs.Metrics()
    model = modelzoo.build(_bert_cfg(name="bertf", batch_buckets=[2],
                                     deadline_ms=20.0))
    rt = build_runtime(model, metrics=metrics)
    item = model.host_decode(b'{"text": "a b c d e f g h i j"}',
                             "application/json")
    assert model.item_units(item, model.group_key(item)) == 12

    async def go():
        b = ModelBatcher(model, rt, metrics,
                         adaptive_cfg=AdaptiveConfig(enabled=False))
        await b.start()
        try:
            g = model.group_key(item)
            await asyncio.gather(b.submit(item, group=g),
                                 b.submit(item, group=g))
            await b.submit(item, group=g)
        finally:
            await b.stop()

    asyncio.new_event_loop().run_until_complete(go())
    c = metrics.counter_values()
    assert c["batcher_flushes_total{model=bertf,reason=target}"] == 1
    assert c["batcher_flushes_total{model=bertf,reason=timer}"] == 1


def test_profile_endpoint_answers_spans_on_the_profilers_clock(served):
    """POST /debug/profile reads the capture's xplane: the tpuserve.* spans
    of requests served during the window come back as Chrome events with
    their arguments; on the CPU backend there is no device plane and the
    metadata says so (never a 5xx)."""
    from tpuserve.telemetry import ProfileCapture

    run, client, state = served
    cap = ProfileCapture(state.metrics)

    async def go():
        task = asyncio.ensure_future(cap.capture(400.0))
        await asyncio.sleep(0.15)
        await _post(client, ["during the capture"])
        return await task

    out = run(go())
    meta = out["tpuserve_profile"]
    assert meta["clock"] == "profiler"
    assert meta["device_trace"].startswith("unavailable")
    assert meta["device_events"] == 0
    names = meta["span_names"]
    assert {"tpuserve.tokenize", "tpuserve.accumulate", "tpuserve.h2d",
            "tpuserve.launch", "tpuserve.fetch"} <= set(names), names
    evs = {e["name"]: e for e in out["traceEvents"]}
    assert evs["tpuserve.h2d"]["args"]["bucket"] == "1x16"
    acc = evs["tpuserve.accumulate"]
    assert acc["dur"] == acc["args"]["dur_us"]
    # drawn where it was measured: it ends before the h2d stage starts
    assert acc["ts"] + acc["dur"] <= evs["tpuserve.h2d"]["ts"]
    assert not cap.armed


def test_stats_names_device_memory(served):
    """/stats topology.devices[i].memory: read on demand from
    device.memory_stats(); None where the backend reports none (the CPU)."""
    from tpuserve.parallel import distributed

    run, client, _state = served

    async def go():
        return await (await client.get("/stats")).json()

    devs = run(go())["topology"]["devices"]
    assert len(devs) == len(jax.local_devices())
    assert {"id", "kind", "memory"} <= set(devs[0])
    assert devs[0]["memory"] is None  # the CPU backend reports none

    class _Dev:
        id, device_kind = 3, "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_in_use": 1, "peak_bytes_in_use": 2,
                    "peak_bytes_reserved": 3, "bytes_limit": 4,
                    "largest_alloc_size": 5}

    real = jax.local_devices
    jax.local_devices = lambda: [_Dev()]
    try:
        (d,) = distributed.local_devices_info()
    finally:
        jax.local_devices = real
    assert d == {"id": 3, "kind": "TPU v5 lite",
                 "memory": {"bytes_in_use": 1, "peak_bytes_in_use": 2,
                            "peak_bytes_reserved": 3, "bytes_limit": 4}}
