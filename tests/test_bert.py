"""BERT family + text path (C3/C4, SURVEY.md §3d): tokenizer behavior,
(batch, seq) bucketing, seq-bucket/padding invariance, HTTP end-to-end.
VERDICT.md r2 item 3."""

import asyncio
import json

import numpy as np
import pytest

from tpuserve.config import ModelConfig
from tpuserve.models import build
from tpuserve.text import (
    CLS, PAD, SEP, UNK, WordPieceTokenizer, basic_tokenize, synthetic_vocab,
)

TINY = dict(layers=2, d_model=32, heads=2, d_ff=64, vocab_size=512)


def tiny_cfg(**over) -> ModelConfig:
    base = dict(
        name="bert", family="bert", batch_buckets=[1, 2],
        seq_buckets=[8, 16], deadline_ms=5.0, dtype="float32",
        num_classes=4, parallelism="single", request_timeout_ms=30_000.0,
        options=dict(TINY),
    )
    base.update(over)
    return ModelConfig(**base)


# -- tokenizer ----------------------------------------------------------------

def test_basic_tokenize():
    assert basic_tokenize("Hello, World!") == ["hello", ",", "world", "!"]
    assert basic_tokenize("Café") == ["cafe"]  # accent stripped
    assert basic_tokenize("a中b") == ["a", "中", "b"]  # CJK isolated


def test_wordpiece_greedy_longest_match():
    vocab = {t: i for i, t in enumerate(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
         "un", "##aff", "##able", "##a", "##ff", "aff"])}
    tok = WordPieceTokenizer(vocab)
    assert tok.wordpiece("unaffable") == ["un", "##aff", "##able"]
    assert tok.wordpiece("zzz") == [UNK]


def test_encode_pads_and_masks():
    tok = WordPieceTokenizer(synthetic_vocab(2048))
    ids, mask = tok.encode("hello world", 16)
    assert ids.shape == (16,) and mask.shape == (16,)
    assert ids[0] == tok.cls_id
    n = int(mask.sum())
    assert ids[n - 1] == tok.sep_id
    assert np.all(ids[n:] == tok.pad_id) and np.all(mask[n:] == 0)


def test_encode_truncates():
    tok = WordPieceTokenizer(synthetic_vocab(2048))
    ids, mask = tok.encode("word " * 100, 8)
    assert ids.shape == (8,) and int(mask.sum()) == 8
    assert ids[-1] == tok.sep_id


def test_synthetic_vocab_deterministic_and_unkless():
    v1, v2 = synthetic_vocab(4096), synthetic_vocab(4096)
    assert v1 == v2
    tok = WordPieceTokenizer(v1)
    assert UNK not in tok.tokenize("arbitrary ascii text 123!")


def test_vocab_file_roundtrip(tmp_path):
    p = tmp_path / "vocab.txt"
    p.write_text("\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
                            "hello", "##s"]))
    tok = WordPieceTokenizer.from_vocab_file(str(p))
    assert tok.tokenize("hellos") == ["hello", "##s"]


# -- model + bucketing --------------------------------------------------------

def test_full_size_matches_published_figures():
    """BERT-base with the standard 30,522-token vocab is ~110M params."""
    import jax
    import numpy as np

    from tpuserve.config import ModelConfig

    m = build(ModelConfig(name="b", family="bert", dtype="float32",
                          num_classes=2, options={"vocab_size": 30522}))
    p = jax.eval_shape(m.init_params, jax.random.key(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(p))
    assert 105e6 < n < 115e6, n


@pytest.fixture(scope="module")
def served():
    """Tiny BERT behind the real runtime (module-scoped: compiles 4 buckets)."""
    from tpuserve.runtime import build_runtime

    model = build(tiny_cfg())
    rt = build_runtime(model)
    return model, rt


def test_buckets_cross_product(served):
    model, rt = served
    assert model.buckets() == [(1, 8), (1, 16), (2, 8), (2, 16)]
    assert sorted(rt.executables) == sorted(model.buckets())


def test_group_key_picks_seq_bucket(served):
    model, _ = served
    short = model.host_decode(b'{"text": "hi"}', "application/json")
    long = model.host_decode(
        json.dumps({"text": "many words " * 6}).encode(), "application/json")
    assert model.group_key(short) == 8
    assert model.group_key(long) == 16
    assert model.bucket_for(2, group=8) == (2, 8)
    assert model.bucket_for(3, group=16) == (2, 16)  # clamps to largest batch


def test_seq_bucket_invariance(served):
    """The same text produces the same logits in the 8- and 16-seq buckets:
    padded lanes and extra padded positions cannot leak into real lanes."""
    model, rt = served
    item = model.host_decode(b'{"text": "hello world"}', "application/json")
    out8 = rt.fetch(rt.run((1, 8), model.assemble([item], (1, 8))))
    out16 = rt.fetch(rt.run((1, 16), model.assemble([item], (1, 16))))
    np.testing.assert_allclose(out8["probs"], out16["probs"], atol=1e-5)
    np.testing.assert_array_equal(out8["indices"], out16["indices"])


def test_batch_padding_invariance(served):
    """A request's result is identical alone vs sharing a padded batch."""
    model, rt = served
    a = model.host_decode(b'{"text": "alpha beta"}', "application/json")
    b_ = model.host_decode(b'{"text": "gamma"}', "application/json")
    solo = rt.fetch(rt.run((1, 8), model.assemble([a], (1, 8))))
    pair = rt.fetch(rt.run((2, 8), model.assemble([a, b_], (2, 8))))
    np.testing.assert_allclose(solo["probs"][0], pair["probs"][0], atol=1e-5)


def test_text_plain_body(served):
    model, _ = served
    item = model.host_decode(b"raw text body", "text/plain")
    assert item.dtype == np.int32 and item.ndim == 1


def test_bad_json_raises(served):
    model, _ = served
    with pytest.raises(ValueError):
        model.host_decode(b'{"no_text": 1}', "application/json")


# -- documents that share a row ------------------------------------------------

def _packing_cfg(path, **over):
    """A model whose rows may be shared, on the XLA pair (float32) or, with
    the test steering the platform's name, on the whole-sequence kernel."""
    if path == "fused":
        return tiny_cfg(batch_buckets=[2], seq_buckets=[256], dtype="bfloat16",
                        options=dict(layers=1, d_model=128, heads=2, d_ff=128,
                                     vocab_size=512), **over)
    return tiny_cfg(batch_buckets=[2], seq_buckets=[64],
                    options=dict(TINY, layers=2), **over)


def _documents(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [np.concatenate([[2], rng.integers(5, 500, n - 2), [3]])
            .astype(np.int32) for n in lengths]


def _forward(model, path, monkeypatch):
    import importlib

    import jax

    if path == "fused":
        fa = importlib.import_module("tpuserve.ops.fused_attention")
        monkeypatch.setattr(fa, "platform_here", lambda: "tpu")
        monkeypatch.setattr(fa, "_interpret_here", lambda: True)
    return jax.jit(model.forward)


# Documents a row as fractions of the row's width, in the order they lie in
# row 0 (row 1 holds one more): one, two and the cap of eight, ending exactly
# at the row's end or leaving part of it empty.
SHARED = {"one": [0.5], "two-to-the-end": [0.25, 0.75], "two-short": [0.2, 0.3],
          "eight-to-the-end": [0.125] * 8,
          "eight-short": [0.06, 0.1, 0.12, 0.05, 0.2, 0.08, 0.1, 0.07]}


@pytest.mark.parametrize("path", ["dense", "fused"])
@pytest.mark.parametrize("layout", sorted(SHARED))
def test_a_document_answers_in_a_shared_row_as_alone(layout, path, monkeypatch):
    import jax

    model = build(_packing_cfg(path))
    assert model.packs_rows
    (bucket,) = [b for b in model.buckets() if b[0] == 2]
    s = bucket[1]
    docs = _documents([max(3, int(f * s)) for f in SHARED[layout]] + [s // 3])
    params = model.init_params(jax.random.key(0))
    fwd = _forward(model, path, monkeypatch)
    rows = [0] * (len(docs) - 1) + [1]
    together = fwd(params, model.assemble(docs, bucket, rows))
    assert together["probs"].shape == (2 * model.ROW_ITEMS, 4)
    assert model.traced_paths(bucket) == {"attention": path}
    atol = 1e-5 if path == "dense" else 0.02
    for i, doc in enumerate(docs):
        alone = fwd(params, model.assemble([doc], bucket))
        np.testing.assert_allclose(np.asarray(together["probs"])[i],
                                   np.asarray(alone["probs"])[0], atol=atol)
    results = model.host_postprocess(
        jax.tree_util.tree_map(np.asarray, together), len(docs))
    assert len(results) == len(docs)


def test_one_document_a_row_is_the_unshared_program_bit_for_bit():
    """On the XLA path a launch of one document a row answers what the
    program of (ids, mask) answers: the mesh's, and every launch's before
    rows were shared."""
    import jax

    shared = build(_packing_cfg("dense"))
    plain = build(_packing_cfg("dense", parallelism="replica"))
    assert shared.packs_rows and not plain.packs_rows
    docs = _documents([64, 20])
    params = shared.init_params(jax.random.key(0))
    got = jax.jit(shared.forward)(params, shared.assemble(docs, (2, 64)))
    want = jax.jit(plain.forward)(params, plain.assemble(docs, (2, 64)))
    assert np.array_equal(np.asarray(got["probs"])[:2], np.asarray(want["probs"]))
    assert np.array_equal(np.asarray(got["indices"])[:2],
                          np.asarray(want["indices"]))


@pytest.mark.parametrize("fault", ["cls_off_by_one", "segments_swapped",
                                   "positions_not_restarted"])
def test_a_misplaced_document_is_caught(fault):
    """What assemble hands the program, got wrong one way at a time: the
    second document of a row no longer answers as it does alone."""
    import jax

    model = build(_packing_cfg("dense"))
    docs = _documents([20, 30])
    params = model.init_params(jax.random.key(0))
    fwd = jax.jit(model.forward)
    alone = np.asarray(fwd(params, model.assemble([docs[1]], (2, 64)))["probs"])[0]
    ids, seg, cls_at = model.assemble(docs, (2, 64), [0, 0])
    right = np.asarray(fwd(params, (ids, seg, cls_at))["probs"])[1]
    np.testing.assert_allclose(right, alone, atol=1e-5)
    if fault == "cls_off_by_one":
        cls_at = cls_at.copy()
        cls_at[1] += 1
    elif fault == "segments_swapped":
        seg = np.where(seg == 1, 2, np.where(seg == 2, 1, 0)).astype(np.int32)
        seg[0, 19], seg[0, 20] = seg[0, 20], seg[0, 19]
    else:   # one run of a single number: positions run on through both
        seg = (seg != 0).astype(np.int32)
    wrong = np.asarray(fwd(params, (ids, seg, cls_at))["probs"])[1]
    assert np.abs(wrong - alone).max() > 1e-3


def test_assemble_lays_the_documents_of_a_row_one_after_another():
    model = build(_packing_cfg("dense"))
    docs = _documents([10, 54, 7, 20])
    ids, seg, cls_at = model.assemble(docs, (2, 64), [0, 0, 1, 1])
    assert list(cls_at[:4]) == [0, 10, 64, 71] and not cls_at[4:].any()
    assert (seg[0, :10] == 1).all() and (seg[0, 10:] == 2).all()
    assert np.array_equal(ids[0], np.concatenate(docs[:2]))
    assert (seg[1, :7] == 1).all() and (seg[1, 7:27] == 2).all()
    assert not seg[1, 27:].any() and (ids[1, 27:] == model.tokenizer.pad_id).all()
    # What fits no row is refused, not cut: too many tokens, too many items.
    with pytest.raises(ValueError, match="cannot take"):
        model.assemble(_documents([40, 30]), (2, 64), [0, 0])
    with pytest.raises(ValueError, match="cannot take"):
        model.assemble(_documents([3] * 9), (2, 64), [0] * 9)


@pytest.mark.parametrize("over,want", [
    ({}, True),
    ({"parallelism": "replica"}, False),
    ({"parallelism": "sharded"}, False),
    ({"options": {**TINY, "moe_experts": 2}}, False),
    ({"quantize": "int8c"}, True),
])
def test_rows_are_shared_where_the_program_keeps_documents_apart(over, want):
    """Decided from the configuration the model was built with, by no key
    of its own: one device and a dense feed-forward. A mesh and the routed
    feed-forward answer one item a row and a program of (ids, mask)."""
    model = build(tiny_cfg(**over))
    assert model.packs_rows is want
    assert model.row_shape(16) == ((16, model.ROW_ITEMS) if want else (1, 1))
    item = np.arange(11, dtype=np.int32)
    assert model.item_units(item, 16) == (11 if want else 1)
    assert len(model.input_signature((2, 16))) == (3 if want else 2)


# -- the one exception: a mesh takes (ids, mask) and one document a row ---------

@pytest.mark.parametrize("mode", ["sharded", "replica"])
def test_a_mesh_answers_as_one_device_with_shared_rows(mode):
    """The same documents through `build_runtime` on the suite's eight CPU
    devices, one a row, and on one device, two of them in one row: the same
    top-k, from the same seeded weights."""
    from tpuserve.runtime import build_runtime

    tiny = dict(TINY, layers=1)
    mesh_model = build(tiny_cfg(parallelism=mode, batch_buckets=[8],
                                seq_buckets=[16], options=tiny))
    one = build(tiny_cfg(batch_buckets=[8], seq_buckets=[16], options=tiny))
    assert one.packs_rows and not mesh_model.packs_rows
    assert len(mesh_model.input_signature((8, 16))) == 2
    docs = _documents([5, 9, 16, 3])
    rt_mesh, rt_one = build_runtime(mesh_model), build_runtime(one)
    bucket = (8, 16)
    assert bucket in rt_mesh.executables
    got = rt_mesh.fetch(rt_mesh.run(bucket, mesh_model.assemble(docs, bucket)))
    want = rt_one.fetch(rt_one.run(
        bucket, one.assemble(docs, bucket, [0, 0, 1, 2])))
    np.testing.assert_array_equal(got["indices"][:4], want["indices"][:4])
    np.testing.assert_allclose(got["probs"][:4], want["probs"][:4], atol=1e-5)


def test_nonpositive_sp_rejected_at_config():
    with pytest.raises(ValueError, match="sp"):
        tiny_cfg(sp=0)


# -- HTTP end-to-end ----------------------------------------------------------

def test_bert_http_end_to_end():
    from aiohttp.test_utils import TestClient, TestServer

    from tpuserve.config import ServerConfig
    from tpuserve.server import ServerState, make_app

    cfg = ServerConfig(models=[tiny_cfg()], decode_threads=2)
    state = ServerState(cfg)
    state.build()
    app = make_app(state)
    loop = asyncio.new_event_loop()

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/v1/models/bert:classify",
                data=json.dumps({"text": "serve this text please"}).encode(),
                headers={"Content-Type": "application/json"})
            assert resp.status == 200, await resp.text()
            body = await resp.json()
            assert len(body["top_k"]) == 4
            assert abs(sum(e["prob"] for e in body["top_k"]) - 1.0) < 1e-3

            # per-(batch, seq) executables are visible in the inventory
            resp = await client.get("/v1/models")
            inv = await resp.json()
            assert inv["bert"]["buckets"] == [[1, 8], [1, 16], [2, 8], [2, 16]]

            # malformed JSON -> 400
            resp = await client.post(
                "/v1/models/bert:classify", data=b"{oops",
                headers={"Content-Type": "application/json"})
            assert resp.status == 400

            # {"texts": [...]} client batch -> {"results": [...]} in order
            resp = await client.post(
                "/v1/models/bert:classify",
                data=json.dumps({"texts": ["first text", "second one"]}).encode(),
                headers={"Content-Type": "application/json"})
            assert resp.status == 200, await resp.text()
            body = await resp.json()
            assert len(body["results"]) == 2
            solo = await client.post(
                "/v1/models/bert:classify",
                data=json.dumps({"text": "second one"}).encode(),
                headers={"Content-Type": "application/json"})
            assert (await solo.json()) == body["results"][1]

            # non-string entries -> 400
            resp = await client.post(
                "/v1/models/bert:classify",
                data=json.dumps({"texts": ["ok", 7]}).encode(),
                headers={"Content-Type": "application/json"})
            assert resp.status == 400
        finally:
            await client.close()

    loop.run_until_complete(go())
    loop.close()
