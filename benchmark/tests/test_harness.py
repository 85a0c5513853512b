"""What the harness takes from a family's and a traffic kind's own files
(PR 27): the serve file's tables, how weights reach the server, what the
answers of a response are, how they are compared; and what it does where a
module gives none of these, which is what the BERT cells run. CPU, no server
but the in-process one of the `_post` cases."""

import asyncio
import glob
import json
import os
import tomllib
from dataclasses import dataclass

import numpy as np
import pytest
from aiohttp import web
from jax.profiler import ProfileData

from benchmark import check, host_spans, loadgen, run, spec, trace_reduce

FIXTURES = os.path.join(spec.HERE, "fixtures")
READERS = sorted(os.path.basename(p)[:-3] for p in
                 glob.glob(os.path.join(spec.HERE, "layer_metrics", "*.py")))


# -- the serve file ----------------------------------------------------------------

BERT_TOML = """host = "127.0.0.1"
port = 1234

[[model]]
name = "model"
family = "bert"
weights = "/w"
num_classes = 5
dtype = "float32"
parallelism = "single"
batch_buckets = [4, 16]
seq_buckets = [16, 64]
deadline_ms = 10.0
request_timeout_ms = 60000.0

[model.options]
layers = 2
d_model = 64
heads = 4
d_ff = 128
vocab_size = 2048
vocab_file = "/v"
"""


def _serve_toml(tmp_path, config, weights):
    cfg = spec.load_config(spec.load_benchmark(), config)
    path = str(tmp_path / "serve.toml")
    run.write_serve_toml(path, cfg, 1234, weights, {"vocab_file": "/v"})
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_serve_file_of_a_classifier_is_what_it_was(tmp_path):
    assert _serve_toml(tmp_path, "rehearsal-tiny", "/w") == BERT_TOML


def test_serve_file_with_tables_and_without_classes_or_weights(tmp_path):
    text = _serve_toml(tmp_path, "rehearsal-gen-tiny", None)
    got = tomllib.loads(text)
    assert got["genserve"] == {"enabled": True, "slots": 8, "kv_paging": True,
                               "kv_page_tokens": 8, "kv_pages": 0, "prefill_chunk": 8}
    assert got["decode_threads"] == 4 and got["port"] == 1234
    (model,) = got["model"]
    assert "num_classes" not in model and "weights" not in model
    assert model["family"] == "textgen" and model["batch_buckets"] == [1, 4, 8]
    assert model["options"]["prompt_len"] == 32 and model["options"]["vocab_file"] == "/v"
    assert text.index("[genserve]") < text.index("[[model]]"), \
        "a table after [[model]] would be the model's"


def test_a_table_of_tables_is_refused():
    with pytest.raises(TypeError):
        run.toml_value({"a": 1})


# -- the check ---------------------------------------------------------------------

def _pinned_sample():
    rng = np.random.default_rng(27)
    ref = rng.normal(0.0, 0.05, (24, 5))
    ref_logp = ref - np.log(np.exp(ref).sum(axis=-1, keepdims=True))
    noisy = ref + rng.normal(0.0, 0.003, ref.shape)
    probs = np.exp(noisy) / np.exp(noisy).sum(axis=-1, keepdims=True)
    served = [{"top_k": [{"class": int(c), "prob": float(p[c])} for c in rng.permutation(5)]}
              for p in probs]
    return served, probs, ref_logp


def test_the_default_comparison_is_the_classifiers_statistic_digit_for_digit():
    served, probs, ref_logp = _pinned_sample()
    stat, line = check.compare_class_probs(served, ref_logp, {"assumed": {"num_classes": 5}})
    assert stat == check.rms_centred_logit_error(probs, ref_logp)
    assert stat == pytest.approx(PINNED_STATISTIC, rel=1e-12)
    assert line.startswith(f"rms_centred_logit_error={stat:.6g} over 24 texts x 5 classes")


PINNED_STATISTIC = 0.0027837808944209848  # by check.py as it was before PR 27, on this sample


def test_a_family_without_compare_gets_the_default_and_one_with_it_its_own():
    bert, textgen = (spec.load_module("reference", f) for f in ("bert", "textgen"))
    assert not hasattr(bert, "compare") and not hasattr(bert, "prepare")
    assert getattr(bert, "compare", check.compare_class_probs) is check.compare_class_probs
    assert all(callable(getattr(textgen, f)) for f in ("prepare", "reference_answers", "compare"))


# -- the item count ------------------------------------------------------------------

@dataclass
class Req:
    body: bytes = b"{}"
    items: int = 2
    cls: str = "c"


GENERATION = {"text": "ab cd", "tokens": [7, 8], "n_tokens": 2}
PROMPTS = spec.load_module("traffic", "prompts")


@pytest.mark.parametrize("answer,items,answers,want", [
    ({"results": [{}, {}]}, 2, None, (True, "")),
    ({"results": [{}]}, 2, None, (False, "wrong_item_count")),
    ({"top_k": [{"class": 1, "prob": 1.0}]}, 1, None, (True, "")),
    (GENERATION, 1, None, (False, "wrong_item_count")),
    (GENERATION, 1, PROMPTS.answers_of, (True, "")),
    ({"text": "", "tokens": [], "n_tokens": 0}, 1, PROMPTS.answers_of, (False, "wrong_item_count")),
    ({"results": [{}]}, 1, PROMPTS.answers_of, (False, "wrong_item_count")),
], ids=["results", "results-short", "top_k", "generation-by-default", "generation",
        "generation-empty", "results-as-generation"])
def test_post_counts_items_by_the_traffic_kinds_answers(answer, items, answers, want):
    async def handler(request):
        await request.read()
        return web.json_response(answer)

    async def go():
        import time

        import aiohttp

        app = web.Application()
        app.router.add_post("/p", handler)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        url = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}/p"
        try:
            async with aiohttp.ClientSession() as session:
                extra = () if answers is None else (answers,)
                return await loadgen._post(session, url, Req(items=items),
                                           time.perf_counter() + 5.0, *extra)
        finally:
            await runner.cleanup()

    assert asyncio.run(go()) == want


def test_items_in_window_stays_the_requests_items():
    r = loadgen.LoadResult(seconds=10.0)
    loadgen._Recorder(r, t0=100.0).record(Req(items=16), 101.0, 101.0, 102.0, True, "")
    assert r.items_in_window == 16


# -- the readers -------------------------------------------------------------------

def test_every_reader_is_listed_and_every_listed_reader_is_a_file():
    assert set(READERS) == {m["name"] for m in spec.load_benchmark()["per_layer"]}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_an_empty_run(name):
    assert spec.load_module("layer_metrics", name).read({}) is None


BATCHER_FAMILIES = ("runtime_variant_batches_total", "latency_ms", "batcher_flushes_total",
                    "batcher_batch_items_total", "ingest_tokens_total", "items_total")


@pytest.mark.parametrize("name", READERS)
def test_a_reader_does_not_raise_on_a_run_the_batcher_did_not_serve(name):
    """Scrapes with the engine's families only, a load, and a device trace
    with no `jit_forward` module and no span of the program."""
    with open(os.path.join(FIXTURES, "hand.xspace.txt"), encoding="utf-8") as f:
        trace = trace_reduce.reduce_profile(ProfileData.from_text_proto(f.read()))
    load = loadgen.LoadResult(seconds=3.0, attempted=4, items_in_window=4,
                              latencies_by_class={"c": [5.0, 6.0]}, late_ms=[0.1, 0.2])
    run_info = {"metrics_delta": {'gen_iterations_total{model="model"}': 40.0,
                                  'requests_total{model="model",status="ok"}': 4.0},
                "model_name": "model", "load": load, "server_cpu_s": 0.5,
                "compiles_in_window": 0.0, "peaks": None, "flops": None, "notes": [],
                "sizes": {"d_model": 3}, "trace": trace, "xplane": None, "host_spans": None}
    v = spec.load_module("layer_metrics", name).read(run_info)
    assert v is None or isinstance(v, float)
    if name in ("batch_fill_ratio", "token_fill_ratio", "queue_ms_p50", "slot_wait_ms_p50",
                "timer_flush_share", "close_join_share", "tokenize_ms_p50", "exec_roofline_share"):
        assert v is None, "nothing of the batcher to read here"


def test_close_join_share_reads_both_labels():
    read = spec.load_module("layer_metrics", "close_join_share").read
    d = {'batcher_batch_items_total{model="model",joined="close"}': 970.0,
         'batcher_batch_items_total{model="model",joined="accumulate"}': 30.0,
         'batcher_batch_items_total{model="other",joined="accumulate"}': 500.0}
    assert read({"metrics_delta": d, "model_name": "model"}) == pytest.approx(97.0)
    assert read({"metrics_delta": {"items_total": 5.0}, "model_name": "model"}) is None


# -- launches whole inside the traced window -------------------------------------------

CUT_EDGES = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 500000000 }
    events { metadata_id: 1 offset_ps: 5500000000 duration_ps: 4000000000 }
    events { metadata_id: 1 offset_ps: 9500000000 duration_ps: 2000000000 } }
  lines { id: 2 name: "XLA Ops"
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11500000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_forward(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_forward(2)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.1 = bf16[8,128,64]{2,1,0} fusion(%p0)" } } }
"""


def test_the_median_launch_is_over_launches_whole_inside_the_window():
    """Four launches of a 4 ms program, the first and the last cut by the
    tracer's edges to 1 and 2 ms: a median over all four reads 3 ms."""
    r = trace_reduce.reduce_profile(ProfileData.from_text_proto(CUT_EDGES))
    top = r["top_module"]
    assert top["name"] == "jit_forward(1)" and top["launches"] == 4 and top["whole_launches"] == 2
    assert top["launch_s"] == pytest.approx(4e-3)
    run_info = {"trace": r, "notes": [], "sizes": {"d_model": 64}}
    read = spec.load_module("layer_metrics", "exec_ms_per_batch").read
    assert read(run_info) == pytest.approx(4.0)
    assert "2 of them whole inside it" in run_info["notes"][0]


@pytest.mark.parametrize("fixture,launches,whole,ms", [
    # recorded_v5e.md: launches of 242.57 (cut by the tracer's start), 304.785 and 6.3 ms
    ("recorded_v5e.xplane.pb", 2, 1, 304.785021),
    # the (256, 512) program: 99.57 (cut), 304.721606, 304.746713 ms
    ("recorded_v5e_spans.xplane.pb", 3, 2, (304.721606 + 304.746713) / 2),
])
def test_recorded_traces_read_the_whole_launch(fixture, launches, whole, ms):
    r = trace_reduce.reduce_file(os.path.join(FIXTURES, fixture))
    top = r["top_module"]
    assert (top["launches"], top["whole_launches"]) == (launches, whole)
    assert top["launch_s"] * 1e3 == pytest.approx(ms, rel=1e-9)
    assert trace_reduce.bucket_of(top, 768) == (256, 512)


# -- the gaps' names ---------------------------------------------------------------

def test_breakdown_idle_gaps_carry_the_gaps_names():
    path = os.path.join(FIXTURES, "recorded_v5e_spans.xplane.pb")
    reduced = trace_reduce.reduce_file(path)
    run_info = {"trace": reduced, "xplane": path}
    gaps = run.named_idle_gaps(run_info)
    assert gaps and len(gaps) <= 10
    assert all(name in host_spans.STATES and isinstance(s, float) for name, s in gaps)
    # recorded_v5e_spans.json: the longest gap is 56.87 ms, 54.30 of it `staging_wait`
    assert gaps[0][0] == "staging_wait" and gaps[0][1] == pytest.approx(0.05686573375)
    assert [s for _n, s in gaps] == sorted((s for _n, s in gaps), reverse=True)
    assert run.named_idle_gaps({"trace": None}) is None, \
        "no trace: the gaps stay as trace_reduce names them"
