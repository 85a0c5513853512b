"""The `hybrid_delta` family (ISSUE 53) against its plain reference at a small
size on the CPU: packed, chunked prefill and then decode through pages AND a
delta-rule state a slot equal the reference's token-by-token pass; the chunked
form holds with a channel that decays by e^-5 a token; every wrong reading of
the layer fails; the step's kernel (in the Pallas interpreter) is the plain
step; the eight shares of a routed layer add up to the uncut layer. Logits
(served log-probabilities) are compared, never sampled tokens."""

from __future__ import annotations

import asyncio
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import hybrid_delta_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import PrefillPiece
from tpuserve.models import build, hybrid_delta, mixers
from tpuserve.models.paged_lm import LOGPROBS
from tpuserve.ops import delta_update as du

# Two periods of (softmax, delta, delta, delta): both kinds, each with its
# routed layer of 16 experts, 4 picked, and a shared one.
ARCH = {
    "vocab_size": 96, "hidden_size": 128, "num_hidden_layers": 8, "gqa_layers": [0, 4],
    "gqa_interval": 3, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 8,
                           "num_kv_heads": None},
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "first_k_dense_replace": 0, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "tie_word_embeddings": False,
    # Steps of 0.02-0.6 a token: a toy's 30 tokens are several half-lives of its
    # fast channels, so the decay is seen (the cell's range spans a thousand).
    "weight_scales": {"decay_step": [0.02, 0.6]},
}
SEED = 13
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3
# Float32 against float32: served and reference differ by the order of their
# sums (the chunk's triangular solve against token by token, key blocks against
# one softmax) and by a router's pick where two scores tie to the last place
# (none in these prompts). A log-probability is about -4.5; the largest gap read
# over the sound cases is 4.8e-6, a few units in its last place; TOL is 10x
# that. Every wrong reading of case (c) reads 0.9 or more.
TOL = 5e-5


def make_model(tmp_path, arch=ARCH, name="hd", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="hybrid_delta", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def piece_of(model, prompts, max_news, slot, start, length):
    pps = model.kv_plan(1, PAGE).pages_per_slot
    ids = np.zeros((model.max_prompt,), np.int32)
    ids[: len(prompts[slot])] = prompts[slot]
    item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
            np.float32(0.0), np.int32(LOGPROBS))
    return PrefillPiece(slot, item, start, length,
                        np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32))


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, state=None,
          slots=SLOTS, steps=None):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done. ``launches``: a list of
    launches, each a list of (slot, start, length); without it each prompt
    goes alone, a chunk a launch."""
    pps = model.kv_plan(1, PAGE).pages_per_slot
    if state is None:
        state = zeros(model.kv_plan(slots, PAGE).state)
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
    model = make_model(tmp_path_factory.mktemp("hybrid_delta"))
    return model, model.init_params(jax.random.key(0))


# 19 tokens: three launches at a chunk of 8; 11: two; 5: one.
PROMPTS = [np.random.default_rng(0).integers(0, 96, n) for n in (19, 5, 11)]
MAX_NEWS = [6, 12, 3]
# Pieces of several slots and sizes in one launch, a prompt over four launches
# (its state carried between them), padded tails (a piece of 1, of 3, of 7).
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)]]


@pytest.fixture(scope="module")
def served_packed(whole):
    model, params = whole
    return serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)[0]


def worst(served, arch=ARCH, wrong="") -> float:
    """The largest gap of served and reference log-probabilities at the ids
    the server named, teacher-forced on the served tokens."""
    m = ref.Model(arch, SEED, "float32", wrong)
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(PROMPTS, served)]
    out = 0.0
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in PROMPTS])):
        n = int(s["n_new"])
        out = max(out, float(np.abs(
            s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1)).max()))
    return out


# -- (a) the two programs against the reference's token-by-token pass -------------------------------

@pytest.mark.parametrize("launches", [None, PACKED], ids=["a-chunk-a-launch", "packed"])
def test_chunked_prefill_then_decode_is_the_reference_token_by_token(whole, launches):
    """Prefill in one and in several launches (a piece that resumes a stored
    state, padded tails, pieces of several slots in one launch), then decode
    through state and pages."""
    model, params = whole
    served, out, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=launches)
    assert [int(s["n_new"]) for s in served] == MAX_NEWS
    assert worst(served) < TOL
    acc = np.asarray(out["acc"])
    names = [c.counter(model, _Names(), "decode") for c in model.COLUMNS]
    at = names.index("delta_steps_total{model=hd,phase=decode,path=xla}")
    steps = sum(n - 1 for n in MAX_NEWS)
    assert acc[1, at] == 6 * steps and acc[1, at - 1] == 0 and acc[0, at] == 0


class _Names:
    @staticmethod
    def counter(name):
        return name


def test_a_lane_that_is_not_live_keeps_its_state_and_a_new_tenant_starts_from_zeros(whole):
    model, params = whole
    _, _, state = serve(model, params, PROMPTS, MAX_NEWS)
    before = [np.asarray(s) for s in state["ssm"] + state["conv"]]
    assert all(np.abs(b).max() > 0 for b in before)
    state2, _ = jax.jit(model.step)(params, state)          # every lane is done: none is live
    for b, a in zip(before, state2["ssm"] + state2["conv"]):
        assert np.array_equal(b, np.asarray(a))
    # Slot 0 (19 tokens before) to a request of 5: the state it finds is not read.
    prompts = [PROMPTS[1], PROMPTS[1], PROMPTS[1]]
    again, _, _ = serve(model, params, prompts, [4, 4, 4], launches=[[(0, 0, 5)]], state=state2,
                        steps=5)
    fresh, _, _ = serve(model, params, prompts, [4, 4, 4], launches=[[(0, 0, 5)]], steps=5)
    np.testing.assert_array_equal(again[0]["lp"][:4], fresh[0]["lp"][:4])


# -- (b) the chunked form against the recurrence ------------------------------------------------------

@pytest.mark.parametrize("tile,fast", [(128, -5.0), (128, -0.01), (4, -5.0), (24, -1.0)])
def test_the_chunked_form_is_the_recurrence_with_a_fast_channel(whole, tile, fast):
    """One piece of two tiles against the step applied a token at a time, a
    quarter of the channels decaying by `fast` a token (e^-640 over a tile of
    128: `exp(-G_s)` alone would overflow after 18 rows), beta up to 2, a padded
    tail. No inf, no nan, and the same state and outputs."""
    model, _ = whole
    H, D, K = model.kh, model.kd, 2
    rng = np.random.default_rng(tile)
    C, n_live = K * tile, K * tile - 3
    qkv = jnp.asarray(rng.standard_normal((C, 3 * H * D)), jnp.float32)
    g = -np.abs(rng.standard_normal((C, H, D))).astype(np.float32) * 0.05
    g[:, :, ::4] = fast
    beta = rng.uniform(0.0, 2.0, (C, H)).astype(np.float32)
    live = np.arange(C) < n_live
    g, beta = jnp.asarray(g * live[:, None, None]), jnp.asarray(beta * live[:, None])
    lp = {"conv_w": jnp.asarray(rng.standard_normal((4, 3 * H * D)), jnp.float32) * 0.5}
    s0 = jnp.asarray(rng.standard_normal((K, H, D, D)), jnp.float32)
    c0 = jnp.asarray(rng.standard_normal((K, 3, 3 * H * D)), jnp.float32)
    launch = {"slot": jnp.asarray([0, 0]), "start": jnp.asarray([5, 0]),
              "length": jnp.asarray([n_live, 0]), "pages": jnp.zeros((K, 1), jnp.int32)}
    t = model._tiles(launch, C)
    o, s_end, tail = jax.jit(lambda *a: model._delta_tiles(lp, *a, t, s0, c0))(qkv, g, beta)
    # token by token
    S, rows, want = s0[0][None], np.concatenate([np.asarray(c0[0]), np.asarray(qkv)]), []
    for i in range(n_live):
        conv = jnp.sum(jnp.asarray(rows[i:i + 4]) * lp["conv_w"], axis=0)[None]
        q, k, v = model._delta_heads(conv)
        o_i, S = du.delta_step(S, q, k, v, jnp.exp(g[i])[None], beta[i][None],
                               jnp.asarray([True]))
        want.append(np.asarray(o_i[0]))
    got = np.asarray(o)[:n_live]
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s_end)).all()
    scale = float(np.abs(np.stack(want)).max())
    assert float(np.abs(got - np.stack(want)).max()) < 2e-4 * scale
    assert float(np.abs(np.asarray(s_end[0]) - np.asarray(S[0])).max()) \
        < 2e-4 * float(np.abs(np.asarray(S)).max())
    np.testing.assert_array_equal(np.asarray(tail[0]), rows[n_live:n_live + 3])


# -- (c) every wrong reading fails ------------------------------------------------------------------------

def _lin(**kw):
    return dict(ARCH, linear_attn_config={**ARCH["linear_attn_config"], **kw})


@pytest.mark.parametrize("arch,wrong", [
    (dict(ARCH, kda_allow_neg_eigval=False), ""),        # beta without its factor 2
    (ARCH, "decay_head"), (ARCH, "no_correction"), (ARCH, "decay_after"),
    (ARCH, "q_raw"), (ARCH, "k_raw"), (ARCH, "no_silu"),
    (_lin(short_conv_kernel_size=3), ""), (_lin(short_conv_kernel_size=5), ""),
    (dict(ARCH, use_gqa_gate=False), ""), (ARCH, "rope"),
    (dict(ARCH, gqa_layers=[3, 7]), ""),                 # the softmax layer last in its period
], ids=["beta-1", "decay-a-head", "no-correction", "decay-after", "q-raw", "k-raw", "no-silu",
        "conv-3", "conv-5", "no-gate", "rope", "softmax-last"])
def test_a_wrong_reading_of_the_layer_fails_the_tolerance(served_packed, arch, wrong):
    assert worst(served_packed, arch, wrong) > 20 * TOL


# -- (d) the step's kernel ------------------------------------------------------------------------------------

def _step_inputs(rng, b, h, d):
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    k = f(b, h, d)
    return (f(b, h, d, d), f(b, h, d) * d ** -0.5, k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            f(b, h, d), jnp.exp(-jnp.abs(f(b, h, d))),
            jnp.asarray(rng.uniform(0.0, 2.0, (b, h)), jnp.float32))


@pytest.mark.parametrize("lanes,heads,live", [(3, 16, [True, False, True]), (2, 32, [True, True]),
                                              (2, 8, [False, False])])
def test_the_kernel_in_the_interpreter_is_the_plain_step(lanes, heads, live):
    """Unequal beta, a lane that is not live (its state as it was), one and
    two blocks of 16 heads and a block of all 8, and the state donated: written
    back in place."""
    args = _step_inputs(np.random.default_rng(lanes + heads), lanes, heads, 128)
    live = jnp.asarray(live)
    o_want, s_want = du.delta_step(*args, live)
    kernel = jax.jit(functools.partial(du.delta_update, interpret=True), donate_argnums=0)
    text = kernel.lower(*args, live).as_text()
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    o, s = kernel(jnp.array(args[0]), *args[1:], live)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want * live[:, None, None]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want), atol=2e-6)
    assert du.supported(args[0]) and not du.supported(args[0][:, :, :64])


def test_a_step_steered_through_the_kernel_serves_what_the_plain_step_serves(whole, monkeypatch):
    """The family's step with every update in the kernel (the interpreter; the
    backend's name decides on the chip): the same log-probabilities, and the
    counter says which path."""
    model, params = whole
    want, _, _ = serve(model, params, PROMPTS, MAX_NEWS)
    monkeypatch.setattr(type(model), "_delta_path", lambda self, ssm: "kernel")
    monkeypatch.setattr(du, "delta_update", functools.partial(du.delta_update, interpret=True))
    got, out, _ = serve(model, params, PROMPTS, MAX_NEWS)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g["lp"], w["lp"], atol=1e-5)
    names = [c.counter(model, _Names(), "decode") for c in model.COLUMNS]
    at = names.index("delta_steps_total{model=hd,phase=decode,path=kernel}")
    assert np.asarray(out["acc"])[1, at] == 6 * sum(n - 1 for n in MAX_NEWS)


# -- (e) the share and the model ----------------------------------------------------------------------------

def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer(tmp_path):
    """The routed layer's outputs of all 8 shares (2 experts of 16 each), the
    shared expert counted once, are what the uncut reference gives for the
    whole layer: a share is a slice of the model, not a smaller model."""
    rng = np.random.default_rng(5)
    u = rng.standard_normal((11, 128)).astype(np.float32)
    live = jnp.ones((11,), jnp.bool_)
    total = np.zeros((11, 128), np.float32)
    for idx in range(8):
        arch = dict(ARCH, share={"experts_held": [2 * idx, 2]})
        model = make_model(tmp_path, arch, name=f"share{idx}")
        lp = model.init_params(jax.random.key(0))["layer1"]
        assert lp["e_gate"].shape == (2, 128, 32)
        y, _ = model._ffn(lp, jnp.asarray(u), live)
        shared = model._swiglu(jnp.asarray(u), lp["s_gate"], lp["s_up"], lp["s_down"])
        total += np.asarray(y - shared)
    total += np.asarray(shared)
    m = ref.Model(ARCH, SEED, "float32")
    w = m.layer(1)
    want = ref.experts(m, w, u, u) + np.asarray(ref._shared(
        False, jnp.asarray(u), *(jnp.asarray(w[k]) for k in ("s_gate", "s_up", "s_down"))))
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, root)
    path = os.path.join(root, "benchmark", "reference", "hybrid_delta.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_hybrid_delta_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    assert theirs.DEFAULT_SCALES == ref.DEFAULT_SCALES == hybrid_delta.DEFAULT_SCALES
    assert theirs.L2_EPS == ref.L2_EPS == mixers.DeltaMixer.L2_EPS
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


@pytest.mark.parametrize("key,value,error", [
    ("use_rope", True, NotImplementedError), ("kda_use_full_proj", True, NotImplementedError),
    ("first_k_dense_replace", 1, NotImplementedError), ("gqa_layers", [0, 9], ValueError),
    ("share", {"attention_heads": [0, 2]}, NotImplementedError)])
def test_a_key_the_family_does_not_implement_is_refused(tmp_path, key, value, error):
    with pytest.raises(error, match=key):
        make_model(tmp_path, dict(ARCH, **{key: value}), name="bad")


def test_the_published_sizes_give_the_bytes_a_token_and_a_slot_that_stats_reports(tmp_path):
    """The cell's configuration, shapes only (nothing is allocated)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, root)
    from benchmark import spec
    with open(os.path.join(root, "benchmark", "configs", "solar-open2-250b-e8-l4.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    arch = spec.load_module("reference", "hybrid_delta").arch_from_config(cfg)
    model = make_model(tmp_path, arch, name="pub", dtype="bfloat16",
                       max_prompt_tokens=8192, max_new_tokens=512)
    sig = model.kv_plan(192, 128, 4096).state
    nbytes = lambda leaves: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)  # noqa: E731
    assert [s.shape for s in sig["ssm"]] == [(192, 64, 128, 128)] * 3
    assert all(s.dtype == jnp.float32 for s in sig["ssm"])
    assert [s.shape for s in sig["conv"]] == [(192, 3, 24576)] * 3
    assert nbytes(sig["ssm"] + sig["conv"]) // 192 == 13_025_280
    assert [s.shape for s in sig["kf"]] == [(8, 4096, 128, 128)]
    assert nbytes(sig["kf"] + sig["vf"]) == 4096 * 524_288
    assert model.m_layers == [1, 2, 3] and model.attn_gate and model.beta_scale == 2.0
    assert (model.e_first, model.e_count, model.v_first, model.vocab) == (0, 40, 0, 24576)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.draw_params(0))))
    assert abs(n_params - 3.3084e9) < 2e6


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
    n_m, tokens, steps = 6, 19 + 5, (6 - 1) + (9 - 1)
    assert c["gen_prefill_tokens_total{model=eng}"] == tokens
    assert c["ssm_tokens_total{model=eng,phase=prefill}"] == n_m * tokens
    assert c["ssm_tokens_total{model=eng,phase=decode}"] == n_m * steps
    assert c["delta_steps_total{model=eng,phase=decode,path=xla}"] == n_m * steps
    assert not c.get("delta_steps_total{model=eng,phase=decode,path=kernel}")
    assert c["ssm_pieces_total{model=eng,start=zero}"] == 2
    assert c["ssm_pieces_total{model=eng,start=carried}"] == 2
    assert c["moe_tokens_routed_total{model=eng,phase=decode,held=yes}"] == 8 * 4 * steps
    kv = eng.pipeline_stats()["kv"]
    per_slot = n_m * (8 * 16 * 16 * 4 + 3 * (3 * 8 * 16) * 4)
    assert kv["state_bytes_per_slot"] == per_slot and kv["state_bytes"] == per_slot * SLOTS
    assert metrics.gauge("gen_state_bytes{model=eng}").value == per_slot * SLOTS
    assert kv["row_bytes_per_token"] == 2 * 2 * 2 * 32 * 4     # 2 layers x K, V x 2 heads of 32
