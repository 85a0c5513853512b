"""The `hybrid_ffn` family (ISSUE 40) against its plain reference at a small
size on the CPU: packed, chunked prefill and then decode through lane-dense
pages AND a recurrent state a slot equal the reference's one full pass; each of
the four multipliers moves the logits as the reference says; a tied head is an
untied one holding the embedding's transpose; heads of 64 in pages of 128
lanes, on the gather path and through the padded queries the TPU's kernel is
handed; a slot's next tenant and lanes that are not live; a bfloat16 state is
seen. Logits (served log-probabilities) are compared, never sampled tokens."""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import hybrid_ffn_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import PrefillPiece
from tpuserve.models import build, hybrid, hybrid_ffn
from tpuserve.models.paged_lm import LOGPROBS

# Two periods of (mamba, mamba, attention): both kinds, each with its
# feed-forward; heads of 64 over 2 KV heads, so a page's row holds both.
ARCH = {
    "vocab_size": 96, "hidden_size": 128, "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_n_groups": 1, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_expand": 2, "mamba_chunk_size": 256,
    "num_attention_heads": 2, "num_key_value_heads": 2, "shared_intermediate_size": 96,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "tie_word_embeddings": True, "position_embedding_type": "nope",
    "weight_scales": {"embed": 0.0833, "qk": 5.66},
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3
# Float32 against float32: served and reference differ by the order of their
# sums alone (chunked against token by token, key blocks against one softmax).
# At the published multipliers a log-probability is about -4.5 and the largest
# gap read over cases (a), (c), (e), (f) is 4.8e-7, one unit in its last place:
# TOL is 10x that. A fault (a state from the wrong slot, a padded row that
# moved it, a wrong half of a packed row) reads hundredths or more, a bfloat16
# state 4.7e-4 after 64 steps (case f): 100x TOL.
TOL = 5e-6
# With the multipliers at 1 (case b) logits are 8x as large and the stream
# grows by a whole sublayer an addition: the largest sound gap read is 2.9e-5;
# a multiplier left out reads 0.8 or more.
TOL_ONES = 1e-4


def make_model(tmp_path, arch=ARCH, name="hf", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="hybrid_ffn", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct, state_dtype=None):
    block = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)
    if state_dtype is not None:
        block["ssm"] = [s.astype(state_dtype) for s in block["ssm"]]
    return block


def piece_of(model, prompts, max_news, slot, start, length):
    pps = model.kv_plan(1, PAGE).pages_per_slot
    ids = np.zeros((model.max_prompt,), np.int32)
    ids[: len(prompts[slot])] = prompts[slot]
    item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
            np.float32(0.0), np.int32(LOGPROBS))
    return PrefillPiece(slot, item, start, length,
                        np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32))


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, state=None,
          slots=SLOTS, state_dtype=None, steps=None):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done. ``launches``: a list of
    launches, each a list of (slot, start, length); without it each prompt
    goes alone, a chunk a launch."""
    pps = model.kv_plan(1, PAGE).pages_per_slot
    if state is None:
        state = zeros(model.kv_plan(slots, PAGE).state, state_dtype)
    k = model.kv_prefill_pieces(chunk, PAGE)
    prefill = jax.jit(model.prefill_chunk, static_argnames=("chunk",))
    step = jax.jit(model.step)
    if launches is None:
        launches = [[(slot, start, min(chunk, len(prompts[slot]) - start))]
                    for slot in range(len(prompts))
                    for start in range(0, len(prompts[slot]), chunk)]
    for pieces in launches:
        launch = model.pack_prefill(
            [piece_of(model, prompts, max_news, *p) for p in pieces], chunk, k)
        state = prefill(params, state, launch, chunk=chunk)
    out = None
    for _ in range(max(max_news) + 1 if steps is None else steps):
        state, out = step(params, state)
    return [jax.tree_util.tree_map(np.asarray, model.extract(params, state, np.int32(s)))
            for s in range(len(prompts))], out, state


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("hybrid_ffn"))
    return model, model.init_params(jax.random.key(0))


# 19 tokens: three launches at a chunk of 8; 11: two; 5: one.
PROMPTS = [np.random.default_rng(0).integers(0, 96, n) for n in (19, 5, 11)]
MAX_NEWS = [6, 12, 3]
# Pieces of several slots and sizes in one launch, a prompt over four launches
# (its state carried between them), padded tails (a piece of 1, of 3, of 7).
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)]]


def gaps(arch, prompts, served):
    """Per request: served minus reference log-probabilities at the ids the
    server named, teacher-forced on the served tokens."""
    m = ref.Model(arch, SEED, "float32")
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = []
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        out.append(s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1))
    return out


def worst(arch, prompts, served) -> float:
    return max(float(np.abs(g).max()) for g in gaps(arch, prompts, served))


# -- (a) the served function is the reference's one full pass ------------------------------------

def test_packed_chunked_prefill_then_decode_is_the_reference_in_one_full_pass(whole):
    model, params = whole
    assert model._kv_pack() == 2 and model._page_shape(7, PAGE) == (1, 7, PAGE, 128)
    served, out, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    assert bool(np.all(np.asarray(out["done"])))
    assert [int(s["n_new"]) for s in served] == MAX_NEWS
    assert worst(ARCH, PROMPTS, served) < TOL
    # a prompt in 3, 1 and 2 launches of its own gives the same answers as the packed launches
    alone, _, _ = serve(model, params, PROMPTS, MAX_NEWS)
    assert worst(ARCH, PROMPTS, alone) < TOL
    for a, b in zip(served, alone):
        np.testing.assert_allclose(a["lp"], b["lp"], atol=TOL)


# -- (b) each multiplier moves the logits as the reference says ----------------------------------

@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 12), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.015625), ("logits_scaling", 8)])
def test_a_multiplier_alone_moves_the_logits_as_the_reference_says(tmp_path, key, value):
    ones = {"embedding_multiplier": 1, "residual_multiplier": 1, "attention_multiplier": 1,
            "logits_scaling": 1}
    plain_arch, arch = dict(ARCH, **ones), dict(ARCH, **{**ones, key: value})
    prompts, news = [PROMPTS[2], PROMPTS[1]], [4, 3]
    launches = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 7)], [(1, 4, 1)]]
    model = make_model(tmp_path, arch, name="one")
    served, _, _ = serve(model, model.init_params(jax.random.key(0)), prompts, news,
                         launches=launches)
    assert worst(arch, prompts, served) < TOL_ONES
    # ... and it is not the model with every multiplier at 1
    assert worst(plain_arch, prompts, served) > 1000 * TOL_ONES


# -- (c) a tied head is an untied one holding the embedding's transpose ----------------------------

def test_a_tied_head_serves_what_an_untied_head_of_the_transpose_serves(tmp_path, whole):
    tied, pt = whole
    untied = make_model(tmp_path, dict(ARCH, tie_word_embeddings=False), name="untied")
    pu = untied.init_params(jax.random.key(0))
    assert "head" not in pt and pu["head"].shape == (128, 96)
    pu = dict(pu, head=pu["embed"].T)
    a, _, _ = serve(tied, pt, PROMPTS, MAX_NEWS, launches=PACKED)
    b, _, _ = serve(untied, pu, PROMPTS, MAX_NEWS, launches=PACKED)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["lp_ids"], y["lp_ids"])
        # the contraction runs over the other operand's axis: the sums' order alone
        np.testing.assert_allclose(x["lp"], y["lp"], atol=TOL)


# -- (d) heads of 64 in rows of 128 lanes ----------------------------------------------------------

def packed_pools(model, rng, b, pps, P, dtype):
    """Random K and V by head, (b, ctx, KV, hd), and the same rows written
    into packed pools through the block table ``bt`` (b, pps)."""
    kv, hd, ctx = model.kv, model.hd, pps * P
    k, v = (jnp.asarray(rng.standard_normal((b, ctx, kv, hd)), dtype) for _ in range(2))
    bt = jnp.asarray(1 + rng.permutation(b * pps).reshape(b, pps), jnp.int32)
    kp = vp = jnp.zeros(model._page_shape(b * pps + 1, P), dtype)
    page = jnp.repeat(bt, P, axis=1).reshape(-1)
    off = jnp.tile(jnp.arange(P), b * pps)
    kp = model._write_pages(kp, page, off, k.reshape(b * ctx, kv, hd))
    vp = model._write_pages(vp, page, off, v.reshape(b * ctx, kv, hd))
    return k, v, kp, vp, bt


# Context lengths (pos + 1) that end inside a page, at its edge and past it.
POS = np.asarray([2, 7, 8, 20, 0], np.int32)


def test_decode_over_packed_pages_is_plain_attention_over_the_same_keys(whole):
    model, _ = whole
    assert model.hd == 64 and model._scale() == 1 / 64
    rng = np.random.default_rng(4)
    P, pps, b = 8, 3, len(POS)
    k, v, kp, vp, bt = packed_pools(model, rng, b, pps, P, jnp.float32)
    assert kp.shape == (1, b * pps + 1, P, 128)
    q = jnp.asarray(rng.standard_normal((b, model.heads, 64)) * 8, jnp.float32)
    mask = (jnp.arange(pps * P)[None, :] <= POS[:, None])[:, None, :]
    want = model._attend(q[:, None], k, v, mask)[:, 0]
    got = model._decode_full(q, kp, vp, bt, jnp.asarray(POS))
    # the same products summed in the same order over a gathered copy of the rows
    np.testing.assert_allclose(got, want, atol=1e-6)
    # prefill's key blocks take the packed rows apart by head
    blk = model._by_head(jnp.take(kp, bt[1], axis=1))
    np.testing.assert_array_equal(np.asarray(blk), np.asarray(k[1].transpose(1, 0, 2)))


def paged_attention_in_plain_jnp(q, k_pages, v_pages, lengths, page_indices, *,
                                 pages_per_compute_block):
    """The contract of jax's TPU kernel, for a CPU: q (b, H, W) unscaled
    scores over pages (KV, pages, P, W), lanes of `lengths` positions."""
    assert page_indices.shape[1] % pages_per_compute_block == 0
    b, H, W = q.shape
    kvp, _, P, _ = k_pages.shape
    k = jnp.take(k_pages, page_indices, axis=1).reshape(kvp, b, -1, W)
    v = jnp.take(v_pages, page_indices, axis=1).reshape(kvp, b, -1, W)
    s = jnp.einsum("bkgw,kbcw->bkgc", q.reshape(b, kvp, H // kvp, W).astype(jnp.float32),
                   k.astype(jnp.float32))
    s = jnp.where(jnp.arange(k.shape[2])[None, None, None, :] < lengths[:, None, None, None],
                  s, -jnp.inf)
    o = jnp.einsum("bkgc,kbcw->bkgw", jax.nn.softmax(s, axis=-1), v.astype(jnp.float32))
    return o.reshape(b, H, W).astype(q.dtype)


def test_the_tpu_branch_pads_each_query_into_its_own_half_of_a_packed_row(tmp_path, monkeypatch):
    """The TPU branch of ``_decode_full`` traced on the CPU: the backend's
    name steered here, in the test, and jax's kernel replaced by its contract
    in plain ``jnp``. What is tested is the arithmetic around it: the padded
    queries, the scale folded into them, the half that is kept, the block."""
    from jax.experimental.pallas.ops.tpu import paged_attention as pa

    arch = dict(ARCH, num_attention_heads=8, num_key_value_heads=4, hidden_size=512,
                mamba_n_heads=16, mamba_d_head=64)
    model = make_model(tmp_path, arch, name="tpu", dtype="bfloat16")
    assert model._kv_pack() == 2 and model.heads // model.kv == 2
    rng = np.random.default_rng(9)
    P, pps, b = 8, 3, len(POS)
    k, v, kp, vp, bt = packed_pools(model, rng, b, pps, P, jnp.bfloat16)
    assert kp.shape == (2, b * pps + 1, P, 128)
    q = jnp.asarray(rng.standard_normal((b, 8, 64)) * 8, jnp.bfloat16)
    blocks = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "paged_attention", lambda *a, **kw: blocks.append(
        kw["pages_per_compute_block"]) or paged_attention_in_plain_jnp(*a, **kw))
    got = model._decode_full(q, kp, vp, bt, jnp.asarray(POS))
    monkeypatch.undo()
    assert blocks == [3]      # 512 positions at most, and a divisor of the block table's 3 pages
    mask = (jnp.arange(pps * P)[None, :] <= POS[:, None])[:, None, :]
    want = model._attend(q[:, None], k, v, mask)[:, 0]
    # bfloat16 queries scaled before the product and a bfloat16 context: 2 ** -8 of values near 1
    np.testing.assert_allclose(got, want, atol=2e-2)
    assert float(jnp.abs(want).max()) > 0.5
    # the padding itself, exactly: a head's row is zero but for its own half
    qp = model._pad_queries(q, 4, 2).reshape(b, 2, 2, 2, 2, 64)
    np.testing.assert_array_equal(np.asarray(qp[:, :, 0, :, 0]), np.asarray(
        q.reshape(b, 2, 2, 2, 64)[:, :, 0]))
    assert not np.asarray(qp[:, :, 0, :, 1]).any() and not np.asarray(qp[:, :, 1, :, 0]).any()
    o = jnp.asarray(rng.standard_normal((b, 8, 128)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(model._own_part(o, 4, 2)).reshape(b, 2, 2, 2, 64)[:, :, 1],
        np.asarray(o).reshape(b, 2, 2, 2, 2, 64)[:, :, 1, :, 1])


# -- (e) a slot's next tenant, free and frozen lanes ------------------------------------------------

def test_a_slot_reused_by_a_shorter_request_starts_from_zeros_and_idle_lanes_keep_their_state(whole):
    model, params = whole
    _, _, state = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    # every lane is done: a step changes no state, bit for bit
    again, _ = jax.jit(model.step)(params, state)
    for key in ("ssm", "conv"):
        for a, b in zip(state[key], again[key]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # shorter requests into the same slots, the block as the first tenants left it
    prompts = [PROMPTS[2][:7], PROMPTS[0][:3], PROMPTS[1][:2]]
    news = [4, 7, 2]
    reused, _, _ = serve(model, params, prompts, news, state=state)
    alone, _, _ = serve(model, params, prompts, news)
    for a, b in zip(reused, alone):
        n = int(b["n_new"])   # rows past it are the earlier tenant's, never returned
        assert np.array_equal(a["tokens"][:n], b["tokens"][:n]) and int(a["n_new"]) == n
        np.testing.assert_array_equal(a["lp"][:n], b["lp"][:n])
    # a lane whose prompt is half in (frozen) keeps its state while the others step
    _, _, mid = serve(model, params, PROMPTS, MAX_NEWS, steps=0,
                      launches=[[(0, 0, 8)], [(1, 0, 5)]])
    stepped = mid
    for _ in range(3):
        stepped, out = jax.jit(model.step)(params, stepped)
    assert int(out["n_new"][1]) == 4 and int(out["n_new"][0]) == 0
    for key in ("ssm", "conv"):
        for a, b in zip(mid[key], stepped[key]):
            np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
            assert not np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


# -- (f) the tolerance sees a state kept in a lower precision ----------------------------------------

def test_a_bfloat16_state_fails_the_tolerance_after_64_steps(tmp_path):
    model = make_model(tmp_path, name="long", max_new_tokens=65)
    params = model.init_params(jax.random.key(0))
    prompts, news = [PROMPTS[0], PROMPTS[2]], [65, 65]
    sound, _, _ = serve(model, params, prompts, news)
    assert worst(ARCH, prompts, sound) < TOL
    low, _, _ = serve(model, params, prompts, news, state_dtype=jnp.bfloat16)
    assert worst(ARCH, prompts, low) > 50 * TOL


# -- the family's edges ---------------------------------------------------------------------------------

@pytest.mark.parametrize("key,value,error", [
    ("position_embedding_type", "rope", NotImplementedError),
    ("num_local_experts", 8, NotImplementedError),
    ("attention_bias", True, NotImplementedError),
    ("share", {"mamba_heads": [0, 2]}, NotImplementedError),
    ("layer_types", ["mamba"] * 5 + ["window"], ValueError),
    ("mamba_expand", 4, ValueError),
])
def test_a_key_the_family_does_not_implement_is_refused(tmp_path, key, value, error):
    with pytest.raises(error, match=key):
        make_model(tmp_path, dict(ARCH, **{key: value}), name="bad")


def test_the_mamba_layer_is_the_hybrid_familys_own_code(whole):
    model, _ = whole
    for name in ("_split_in", "_split_xbc", "_decay", "_gated_norm", "_scan_tiles",
                 "_mamba_prefill", "_mamba_step", "_attn_step"):
        assert getattr(hybrid_ffn.HybridFfnServing, name) is getattr(hybrid.HybridServing, name)
    with pytest.raises(NotImplementedError, match="generation engine"):
        model.forward(None, None)


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, root)
    path = os.path.join(root, "benchmark", "reference", "hybrid_ffn.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_hybrid_ffn_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


def test_the_published_sizes_give_the_bytes_a_token_and_a_slot_that_stats_reports(tmp_path):
    """The cell's configuration, shapes only (nothing is allocated)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "granite-4.0-h-micro.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    arch = {k: v for k, v in cfg.items()
            if k not in ("name", "source", "family", "published", "reduced", "deployment",
                         "assumed", "serve", "check")}
    model = make_model(tmp_path, arch, name="pub", dtype="bfloat16",
                       max_prompt_tokens=1024, max_new_tokens=512)
    sig = model.kv_plan(80, 128, 1280).state
    assert [s.shape for s in sig["kf"]] == [(4, 1280, 128, 128)] * 4
    nbytes = lambda leaves: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)  # noqa: E731
    assert nbytes(sig["kf"] + sig["vf"]) // (1280 * 128) == 8192
    assert nbytes(sig["ssm"] + sig["conv"]) // 80 == 76_437_504 == 36 * (2_097_152 + 26_112)
    assert model._scale() == 0.015625 and model.tied and len(model.m_layers) == 36
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.draw_params(0))))
    assert abs(n_params - 3.191e9) < 2e6


# -- through the engine: the counters and /stats ------------------------------------------------------

def test_through_the_engine_two_requests_move_the_counters_by_what_was_served(tmp_path):
    from tpuserve.config import GenserveConfig
    from tpuserve.genserve import GenEngine
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime

    model = make_model(tmp_path, name="eng")
    rt = build_runtime(model, compile_forward=False)
    metrics = Metrics()
    eng = GenEngine(model, rt, metrics, GenserveConfig(
        slots=SLOTS, kv_paging=True, kv_page_tokens=PAGE, prefill_chunk=CHUNK))
    eng.compile()
    model.bind_metrics(metrics)
    prompts = [PROMPTS[0].tolist(), PROMPTS[1].tolist()]   # 19 tokens (3 pieces) and 5 (1)
    max_news = [6, 9]

    async def go():
        await eng.start()
        futs = [eng.submit(model.host_decode(json.dumps(
            {"prompt_ids": p, "max_new_tokens": m, "logprobs": 8}).encode(), "application/json"))
            for p, m in zip(prompts, max_news)]
        out = await asyncio.gather(*futs)
        await eng.stop()
        return out

    loop = asyncio.new_event_loop()
    try:
        results = loop.run_until_complete(go())
    finally:
        loop.close()
    by_hand, _, _ = serve(model, rt.params_per_mesh[0], PROMPTS[:2], max_news)
    for got, want, n in zip(results, by_hand, max_news):
        assert got["tokens"] == want["tokens"][:n].tolist() and got["n_tokens"] == n
        np.testing.assert_allclose(got["logprobs"]["values"], want["lp"][:n], atol=1e-4)
    c = metrics.counter_values()
    n_m, tokens, steps = 4, 19 + 5, (6 - 1) + (9 - 1)
    assert c["gen_prefill_tokens_total{model=eng}"] == tokens
    assert c["ssm_tokens_total{model=eng,phase=prefill}"] == n_m * tokens
    assert c["ssm_tokens_total{model=eng,phase=decode}"] == n_m * steps
    assert c["ssm_state_rows_total{model=eng,phase=decode}"] == n_m * steps
    assert c["ssm_pieces_total{model=eng,start=zero}"] == 2
    assert c["ssm_pieces_total{model=eng,start=carried}"] == 2
    assert c["ssm_state_rows_total{model=eng,phase=prefill}"] == n_m * 4
    assert c["gen_context_tokens_total{model=eng,phase=prefill}"] == 19 * 20 // 2 + 5 * 6 // 2
    assert not any(name.startswith("moe_") for name in c)
    kv = eng.pipeline_stats()["kv"]
    per_slot = n_m * (8 * 32 * 8 * 4 + 3 * (8 * 32 + 2 * 8) * 4)
    assert kv["state_bytes_per_slot"] == per_slot and kv["state_bytes"] == per_slot * SLOTS
    assert metrics.gauge("gen_state_bytes{model=eng}").value == per_slot * SLOTS
    assert kv["row_bytes_per_token"] == 2 * 2 * 2 * 64 * 4     # 2 layers x K, V x 2 heads of 64
