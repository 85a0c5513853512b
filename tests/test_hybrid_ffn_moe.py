"""The `hybrid_ffn_moe` family (ISSUE 64) against its plain reference
(`benchmark/reference/hybrid_ffn_moe.py`) at a small size on the CPU: packed,
chunked prefill and then decode, by hand and through the engine, equal the
reference's one full pass; a bfloat16 state, a dropped shared expert, weights
taken over all the logits and the wrong share of the experts all fail the
tolerance; the picks and their weights are the literal `topk` then `softmax`;
the two chips' shares add up to the uncut block with the shared expert counted
once; lanes that are not live move neither state nor counters; the cell's tree
is its `deployment_table` to the parameter; `hybrid_ffn` still refuses experts.
Logits (served log-probabilities) are compared, never sampled tokens."""

from __future__ import annotations

import asyncio
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from tests.test_hybrid_ffn import PACKED, serve  # what the engine does, by hand
from tpuserve.config import ModelConfig
from tpuserve.models import build, hybrid_delta, hybrid_ffn, hybrid_ffn_moe
from tpuserve.ops import moe

ref = spec.load_module("reference", "hybrid_ffn_moe")

# The SECOND chip of two: experts 4-7 of 8 and vocabulary rows 16-79 of 96, so
# that a start of 0 cannot hide a share that is not applied.
SHARE = {"experts_held": [4, 4], "vocab_rows": [16, 64]}
# Two periods of (mamba, mamba, attention), each layer with its routed block: 3
# of 8 experts of 32 a token, a shared expert of 64; the published multipliers.
ARCH = {
    "vocab_size": 96, "hidden_size": 128, "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_n_groups": 1, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_expand": 2, "mamba_chunk_size": 256,
    "num_attention_heads": 2, "num_key_value_heads": 2, "shared_intermediate_size": 64,
    "intermediate_size": 32, "num_local_experts": 8, "num_experts_per_tok": 3,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "tie_word_embeddings": True, "position_embedding_type": "nope",
    "weight_scales": {"embed": 0.0833, "qk": 5.66, "expert_out": 0.5}, "share": SHARE,
}
SEED = 13
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3   # `serve`'s, tests/test_hybrid_ffn.py
# Float32 against float32: served and reference differ by the order of their
# sums alone (chunked against token by token, key blocks against one softmax, a
# grouped product against an expert at a time). A log-probability is about -3.9
# and the largest gap read over the sound cases is 7.2e-7 (after 64 steps; 4.8e-7
# over the packed launches), three units in its last place: TOL is 14x that.
# Every fault below reads 2.5e-3 or more, 250x TOL (a bfloat16 state 2.5e-3
# after 64 steps, weights over all the logits 0.009, the other chip's experts
# 0.034, a dropped shared expert 0.073).
TOL = 1e-5


def make_model(tmp_path, arch=ARCH, name="hm", dtype="float32", family="hybrid_ffn_moe",
               **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family=family, dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("hybrid_ffn_moe"))
    return model, model.init_params(jax.random.key(0))


# Held-row ids (0-63). 19 tokens: three launches at a chunk of 8; 11: two; 5: one.
PROMPTS = [np.random.default_rng(0).integers(0, 64, n) for n in (19, 5, 11)]
MAX_NEWS = [6, 12, 3]


def gaps(arch, prompts, served, model_cls=None):
    """Per request: served minus reference log-probabilities at the ids the
    server named, teacher-forced on the served tokens."""
    m = (model_cls or ref.Model)(arch, SEED, "float32")
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = []
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        out.append(s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1))
    return out


def worst(arch, prompts, served, model_cls=None) -> float:
    return max(float(np.abs(g).max()) for g in gaps(arch, prompts, served, model_cls))


# -- (a) the served function is the reference's one full pass ------------------------------------

def test_packed_chunked_prefill_then_decode_is_the_reference_in_one_full_pass(whole):
    model, params = whole
    assert (model.e_first, model.e_count, model.v_first, model.vocab) == (4, 4, 16, 64)
    assert params["layer0"]["e_gate"].shape == (4, 128, 32) and params["embed"].shape == (64, 128)
    served, out, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    assert bool(np.all(np.asarray(out["done"])))
    assert [int(s["n_new"]) for s in served] == MAX_NEWS
    assert worst(ARCH, PROMPTS, served) < TOL
    # a prompt in 3, 1 and 2 launches of its own gives the same answers as the packed launches
    alone, _, _ = serve(model, params, PROMPTS, MAX_NEWS)
    assert worst(ARCH, PROMPTS, alone) < TOL


def test_through_the_engine_the_answers_are_the_references_and_the_counters_what_was_served(
        tmp_path):
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
    prompts, max_news = PROMPTS[:2], [6, 9]     # 19 tokens (3 pieces) and 5 (1)

    async def go():
        await eng.start()
        futs = [eng.submit(model.host_decode(json.dumps(
            {"prompt_ids": (p + 16).tolist(), "max_new_tokens": m, "logprobs": 8}).encode(),
            "application/json")) for p, m in zip(prompts, max_news)]
        out = await asyncio.gather(*futs)
        await eng.stop()
        return out

    loop = asyncio.new_event_loop()
    try:
        results = loop.run_until_complete(go())
    finally:
        loop.close()
    # the answers name vocabulary rows (16 up); the reference's rows are the held ones
    served = [{"tokens": np.asarray(r["tokens"]) - 16, "n_new": r["n_tokens"],
               "lp": np.asarray(r["logprobs"]["values"], np.float32),
               "lp_ids": np.asarray(r["logprobs"]["ids"]) - 16} for r in results]
    assert [s["n_new"] for s in served] == max_news
    assert worst(ARCH, prompts, served) < TOL
    c = metrics.counter_values()
    layers, tokens, steps = 6, 19 + 5, (6 - 1) + (9 - 1)
    for ph, n in (("prefill", tokens), ("decode", steps)):
        picked = sum(c[f"moe_tokens_routed_total{{model=eng,phase={ph},held={h}}}"]
                     for h in ("yes", "no"))
        assert picked == 3 * layers * n          # every live token's three picks, every layer
        assert 0 < c[f"moe_tokens_routed_total{{model=eng,phase={ph},held=yes}}"] < picked
        assert c[f"moe_expert_steps_total{{model=eng,phase={ph}}}"] \
            == 4 * c[f"moe_layers_total{{model=eng,phase={ph}}}"]
        assert 0 < c[f"moe_experts_hit_total{{model=eng,phase={ph}}}"] \
            <= c[f"moe_expert_steps_total{{model=eng,phase={ph}}}"]
    assert c["ssm_tokens_total{model=eng,phase=prefill}"] == 4 * tokens
    assert c["ssm_pieces_total{model=eng,start=carried}"] == 2
    stats = eng.pipeline_stats()
    assert stats["share"] == {"experts_held": [4, 4], "experts": 8, "vocab_rows": [16, 64],
                              "vocab": 96}


# -- (b) the tolerance sees each fault --------------------------------------------------------------

class NoSharedExpert(ref.Model):
    def layer(self, i):
        w = super().layer(i)
        w["s_down"] = np.zeros_like(w["s_down"])
        return w


def test_a_bfloat16_state_fails_the_tolerance_after_64_steps(tmp_path):
    model = make_model(tmp_path, name="long", max_new_tokens=65)
    params = model.init_params(jax.random.key(0))
    prompts, news = [PROMPTS[0], PROMPTS[2]], [65, 65]
    sound, _, _ = serve(model, params, prompts, news)
    assert worst(ARCH, prompts, sound) < TOL
    low, _, _ = serve(model, params, prompts, news, state_dtype=jnp.bfloat16)
    assert worst(ARCH, prompts, low) > 50 * TOL


@pytest.mark.parametrize("fault", ["no_shared_expert", "weights_over_all_logits",
                                   "the_other_chips_experts"])
def test_a_wrong_reading_of_the_routed_block_fails_the_tolerance(whole, monkeypatch, fault):
    """The reference computes a wrong reading; the served answers, which are
    sound, must then stand far from it."""
    model, params = whole
    served, _, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    arch, cls = ARCH, None
    if fault == "no_shared_expert":
        cls = NoSharedExpert
    elif fault == "the_other_chips_experts":
        arch = dict(ARCH, share=dict(SHARE, experts_held=[0, 4]))
    else:   # softmax over all eight logits, the picks' weights NOT over their own sum
        def over_all(m, router, v):
            top, _ = sound_picks(m, router, v)
            with jax.default_matmul_precision("highest"):
                p = np.asarray(jax.nn.softmax(jnp.asarray(v) @ jnp.asarray(router), axis=-1))
            return top, np.take_along_axis(p, top, axis=-1)
        sound_picks = ref.picks
        monkeypatch.setattr(ref, "picks", over_all)
    assert worst(arch, PROMPTS, served, cls) > 200 * TOL


# -- (c) the picks and their weights -----------------------------------------------------------------

def test_the_picks_and_weights_are_the_literal_topk_then_softmax_with_near_ties():
    """`topk_route(scoring="softmax", normalize=True)` against the published
    form written literally (the k largest LOGITS, lower number first among
    equals, a softmax over those alone), on rows that hold exact ties and pairs
    3e-6 apart about the edge of the k: a softmax over all is monotone and the
    picks' sum divides out. (Two logits a unit in the last place apart may
    share one rounded probability; then the lower number wins here and the
    larger logit there, between weights equal to that last place.)"""
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((256, 72)).astype(np.float32)
    order = np.argsort(-logits, axis=-1)
    rows = np.arange(256)
    at10, at11, at3 = order[:, 9], order[:, 10], order[:, 2]
    logits[rows[:96], at11[:96]] = logits[rows[:96], at10[:96]]             # exact ties at the edge
    logits[rows[96:192], at11[96:192]] = logits[rows[96:192], at10[96:192]] - np.float32(3e-6)
    logits[rows[192:], at3[192:]] = logits[rows[192:], order[192:, 1]]      # and inside the ten
    w, e = moe.topk_route(jnp.asarray(logits), 10, scoring="softmax", normalize=True)
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :10]
    picked = np.take_along_axis(logits, top, axis=-1)
    z = np.exp(picked - picked.max(axis=-1, keepdims=True))
    want = z / z.sum(axis=-1, keepdims=True)
    assert np.array_equal(np.asarray(e), top)
    np.testing.assert_allclose(np.asarray(w), want, rtol=2e-6, atol=0)
    np.testing.assert_allclose(np.asarray(w).sum(axis=-1), 1.0, atol=1e-6)
    # the reference's own function is that literal form
    eye = np.eye(72, dtype=np.float32)
    m = type("M", (), {"top_k": 10})
    top_ref, w_ref = ref.picks(m, eye, logits)
    assert np.array_equal(top_ref, top) and np.allclose(w_ref, want, rtol=1e-6)


def test_the_mix_in_takes_scoring_and_bias_from_the_family(whole, tmp_path):
    """`RoutedExperts` serves three families: the two it had keep sigmoid
    scores and a selection bias a layer; this one draws no bias at all."""
    model, params = whole
    assert (model.route_scoring, model.route_bias, model.route_eps) == ("softmax", False, 0.0)
    assert "e_bias" not in params["layer0"] and list(model._expert_vectors()) == []
    base = hybrid_delta.RoutedExperts
    assert (base.route_scoring, base.route_bias) == ("sigmoid", True)
    from tests import test_hybrid_conv, test_hybrid_delta
    for t in (test_hybrid_delta, test_hybrid_conv):
        other = t.make_model(str(tmp_path))
        assert (other.route_scoring, other.route_bias) == ("sigmoid", True)
        tree = jax.eval_shape(lambda m=other: m.draw_params(0))
        assert "e_bias" in tree[f"layer{other.e_layers[0]}"]


# -- (d) the two chips' shares add up ---------------------------------------------------------------

def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_block(tmp_path):
    """Experts 0-3's part plus experts 4-7's part plus the shared expert ONCE,
    each from the PROGRAM on its own share, equal the reference's whole block
    on the uncut architecture."""
    chips = [make_model(tmp_path, dict(ARCH, share=dict(SHARE, experts_held=[first, 4])),
                        name=f"chip{first}") for first in (0, 4)]
    params = [c.init_params(jax.random.key(0)) for c in chips]
    uncut = {k: v for k, v in ARCH.items() if k != "share"}
    m = ref.Model(uncut, SEED, "float32")
    rng = np.random.default_rng(3)
    v = rng.standard_normal((40, 128)).astype(np.float32)
    v /= np.sqrt(np.mean(v * v, axis=-1, keepdims=True))
    live = jnp.ones((40,), bool)
    for i in (0, 2, 5):    # behind a Mamba-2 mixer, behind attention, the last layer
        lp = [p[f"layer{i}"] for p in params]
        for k in ("router", "s_gate", "s_up", "s_down"):   # whole on both chips
            assert np.array_equal(np.asarray(lp[0][k]), np.asarray(lp[1][k]))
        parts = [np.asarray(c._routed(q, jnp.asarray(v), live)[0]) for c, q in zip(chips, lp)]
        shared = np.asarray(chips[0]._shared(lp[0], jnp.asarray(v)))
        w = m.layer(i)
        assert w["e_gate"].shape == (8, 128, 32)
        want = ref.experts(m, w, v, v) + np.asarray(
            ref.hy._project(ref.hf._gated(jnp.asarray(v), jnp.asarray(w["s_gate"]),
                                          jnp.asarray(w["s_up"])), jnp.asarray(w["s_down"])))
        assert float(np.abs(parts[0]).max()) > 0.05 and float(np.abs(parts[1]).max()) > 0.05
        np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=2e-6)
        # the shared expert twice, or one chip's part alone, is far from it
        assert float(np.abs(parts[0] + parts[1] + 2 * shared - want).max()) > 0.1
        assert float(np.abs(parts[1] + shared - want).max()) > 0.05


# -- (e) lanes that are not live ------------------------------------------------------------------------

def test_a_dead_lanes_state_and_counters_stand_still(whole):
    model, params = whole
    _, _, state = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    again, out = jax.jit(model.step)(params, state)   # every lane is done
    for key in ("ssm", "conv"):
        for a, b in zip(state[key], again[key]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    before, after = np.asarray(state["acc"]), np.asarray(out["acc"])
    # picks held, picks absent, experts hit, the context, the scan layers' tokens: nothing
    # moved; the column of expert layers RUN did (the program ran them, on no live token)
    assert np.array_equal(after[:, [0, 1, 2, 4, 5]], before[:, [0, 1, 2, 4, 5]])
    assert after[1, 3] - before[1, 3] == model.e_count * model.n_layers
    # a lane whose prompt is half in (frozen) keeps its state while another steps
    _, _, mid = serve(model, params, PROMPTS, MAX_NEWS, steps=0,
                      launches=[[(0, 0, 8)], [(1, 0, 5)]])
    stepped = mid
    for _ in range(3):
        stepped, out = jax.jit(model.step)(params, stepped)
    assert int(out["n_new"][1]) == 4 and int(out["n_new"][0]) == 0
    picked = np.asarray(out["acc"], np.int64)[1, :2].sum() - np.asarray(mid["acc"], np.int64)[1, :2].sum()
    assert picked == 3 * 3 * 6     # three steps of lane 1 alone: three picks in each of six layers
    for key in ("ssm", "conv"):
        for a, b in zip(mid[key], stepped[key]):
            np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
            assert not np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


# -- (f) the cell's tree and its scopes -------------------------------------------------------------------

def test_the_cells_tree_holds_what_its_deployment_table_says(tmp_path):
    """The configuration's parameters, counted from the shapes of the program's
    own tree at the published widths (abstract: nothing is allocated), against
    `deployment_table`, every group and the whole to the parameter."""
    cfg = spec.load_json("configs", "granite-4.0-h-small-e2-l10.json")
    table = cfg["deployment_table"]
    arch = ref.arch_from_config(cfg)
    assert arch["share"] == {"experts_held": [0, 36], "vocab_rows": [0, 50176]}
    assert (arch["num_local_experts"], arch["vocab_size"], arch["num_hidden_layers"]) \
        == (72, 100352, 10)
    assert "".join(k[0] for k in arch["layer_types"]) == "mmmmmammmm"
    assert cfg["published"]["layer_types"][:10] == arch["layer_types"]
    model = make_model(tmp_path, arch, name="cell", dtype="bfloat16",
                       max_prompt_tokens=4096, max_new_tokens=512)
    tree = jax.eval_shape(lambda: model.draw_params(0))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert size(tree) == table["total"] == 4_757_211_776
    mamba, attn = tree["layer0"], tree["layer5"]
    routed = ("e_gate", "e_up", "e_down")
    assert mamba["e_gate"].shape == (table["experts_held_a_layer"], 4096, 768)
    assert mamba["router"].shape == (4096, 72) and mamba["s_gate"].shape == (4096, 1536)
    assert size([mamba[k] for k in routed]) == 36 * table["routed_expert"]
    assert size(mamba["router"]) == table["router"]
    assert size([mamba[k] for k in ("s_gate", "s_up", "s_down")]) == table["shared_expert"]
    assert size([mamba[k] for k in ("norm1", "norm2")]) == table["norms_a_layer"]
    mixer = ("w_in", "w_out", "conv_w", "conv_b", "A_log", "D", "dt_bias", "gate_norm")
    assert size([mamba[k] for k in mixer]) == table["mamba_mixer"]
    assert size([attn[k] for k in ("wq", "wk", "wv", "wo")]) == table["attention_mixer"]
    assert size(mamba) == table["mamba_layer"] and size(attn) == table["attention_layer"]
    assert size(tree["embed"]) == table["embedding_also_head"] and "head" not in tree
    assert size(tree["norm_f"]) == table["final_norm"]
    assert (len(model.m_layers), len(model.a_layers)) \
        == (table["mamba_layers"], table["attention_layers"]) == (9, 1)
    # the cache beside it, as `/stats` will report it and the configuration says
    sig = model.kv_plan(96, 128, 2048).state
    nbytes = lambda leaves: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)  # noqa: E731
    assert nbytes(sig["ssm"] + sig["conv"]) // 96 == 38_204_928 == 9 * (4 * 2 ** 20 + 50_688)
    assert nbytes(sig["kf"] + sig["vf"]) == 2 ** 30 and model._scale() == 0.0078125
    assert (model.top_k, model.n_experts, model.expert_width, model.shared_width) \
        == (10, 72, 768, 1536)


def test_the_routed_block_is_named_in_the_programs_and_hybrid_ffns_are_not(whole, tmp_path):
    """`moe_layer` holds `moe_route`, `moe_dispatch` and `moe_experts`;
    `moe_shared` stands beside it; the dense sibling's programs name neither."""
    from tests import test_hybrid_ffn

    def stacks(model):
        pps = model.kv_plan(1, PAGE).pages_per_slot
        sig = model.kv_plan(SLOTS, PAGE).state
        params = jax.eval_shape(lambda: model.draw_params(0))
        text = jax.jit(model.step).lower(params, sig).as_text(debug_info=True)
        # the operations' name stacks alone (`scripts/lower_programs.py` `name_stacks`):
        # the locations beside them carry file names, `test_moe_routed.py` among them
        return "\n".join(re.findall(r'loc\("(jit\([^"]*)"', text))

    text = stacks(whole[0])
    for scope in ("moe_layer/moe_route", "moe_layer/moe_dispatch", "moe_layer/moe_experts",
                  "/moe_shared/", "ssm_update", "attn_decode"):
        assert scope in text, scope
    assert "moe_layer/moe_shared" not in text and "moe_shared/moe_" not in text
    assert "moe_" not in stacks(test_hybrid_ffn.make_model(str(tmp_path)))


# -- the family's edges ---------------------------------------------------------------------------------

@pytest.mark.parametrize("family,change,error", [
    ("hybrid_ffn", {}, NotImplementedError),                       # experts in the config
    # the dense sibling with no experts in the config: the share of them is what it refuses
    ("hybrid_ffn", {"num_local_experts": 0, "num_experts_per_tok": 0}, NotImplementedError),
    ("hybrid_ffn_moe", {"num_experts_per_tok": 9}, ValueError),
    ("hybrid_ffn_moe", {"share": {"experts_held": [6, 4]}}, ValueError),
    ("hybrid_ffn_moe", {"share": {"mamba_heads": [0, 2]}}, NotImplementedError),
    ("hybrid_ffn_moe", {"position_embedding_type": "rope"}, NotImplementedError),
])
def test_what_each_sibling_does_not_implement_is_refused(tmp_path, family, change, error):
    with pytest.raises(error):
        make_model(tmp_path, dict(ARCH, **change), name="bad", family=family)
    assert hybrid_ffn.HybridFfnServing.ROUTED is False
    assert hybrid_ffn_moe.HybridFfnMoeServing.ROUTED is True
