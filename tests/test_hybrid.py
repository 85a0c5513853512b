"""The `hybrid` family (ISSUE 32) against its plain reference at a small size
on the CPU: packed, chunked prefill and decode through pages AND a recurrent
state a slot equal the recurrence run token by token; padded rows, free lanes
and a slot's earlier tenant leave a state alone; the four shares of each layer
kind add up to the uncut layer; the counters move by what was served."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import hybrid_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import PrefillPiece
from tpuserve.models import build
from tpuserve.models import hybrid as hyb
from tpuserve.models.paged_lm import rms_norm

ARCH = {
    "vocab_size": 96, "hidden_size": 32, "hybrid_override_pattern": "MEM*EM",
    "num_hidden_layers": 6, "layer_norm_epsilon": 1e-5, "mamba_num_heads": 8,
    "mamba_head_dim": 4, "n_groups": 4, "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 4,
    "use_conv_bias": True, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "n_routed_experts": 16, "num_experts_per_tok": 5, "moe_intermediate_size": 24,
    "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 24, "n_shared_experts": 1,
    "routed_scaling_factor": 5, "norm_topk_prob": True, "mlp_hidden_act": "relu2",
    "n_group": 1, "topk_group": 1, "time_step_min": 0.001, "time_step_max": 0.1,
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3


def make_model(tmp_path, arch=ARCH, name="hy", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="hybrid", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct, state_dtype=None):
    """A zero block; ``state_dtype`` keeps the recurrent state in another type
    than the family declares (the program stores what the block holds)."""
    block = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)
    if state_dtype is not None:
        block["ssm"] = [s.astype(state_dtype) for s in block["ssm"]]
    return block


def piece_of(model, prompts, max_news, slot, start, length):
    pps = model.kv_plan(1, PAGE).pages_per_slot
    ids = np.zeros((MAX_PROMPT,), np.int32)
    ids[: len(prompts[slot])] = prompts[slot]
    item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
            np.float32(0.0), np.int32(hyb.LOGPROBS))
    return PrefillPiece(slot, item, start, length,
                        np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32))


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, state=None,
          slots=SLOTS, state_dtype=None, steps=None):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done. ``launches``: a list of
    launches, each a list of (slot, start, length); without it each prompt
    goes alone, a chunk a launch. ``state``: the block an earlier call left."""
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
    model = make_model(tmp_path_factory.mktemp("hybrid"))
    return model, model.init_params(jax.random.key(0))


PROMPTS = [np.random.default_rng(0).integers(0, 96, n) for n in (19, 5, 11)]
MAX_NEWS = [6, 12, 3]
# Pieces of several slots and sizes in one launch, a prompt over four launches
# (its state carried between them), padded tails (a piece of 1, of 3, of 7).
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)]]


def gaps(arch, prompts, served, dtype="float32"):
    """Per request: served minus reference log-probabilities at the ids the
    server named, teacher-forced on the served tokens."""
    m = ref.Model(arch, SEED, dtype)
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = []
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        out.append(s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1))
    return out


# -- (a) the served function is the recurrence ------------------------------------------------

def test_packed_chunked_prefill_then_decode_is_the_recurrence_token_by_token(whole):
    model, params = whole
    served, out, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    assert bool(np.all(np.asarray(out["done"])))
    assert [int(s["n_new"]) for s in served] == MAX_NEWS
    for g in gaps(ARCH, PROMPTS, served):
        assert float(np.abs(g).max()) < 2e-5
    # a prompt a launch at a time gives the same tokens as the packed launches
    alone, _, _ = serve(model, params, PROMPTS, MAX_NEWS)
    for a, b in zip(served, alone):
        assert np.array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["lp"], b["lp"], atol=2e-5)


def test_bfloat16_serves_within_a_tolerance_that_a_bfloat16_state_fails(tmp_path):
    """Served in bfloat16 with the state in float32 the centred gaps stay
    under TOL; with the state block kept in bfloat16 (each step's rounding
    feeds the next) the same requests pass it. Long generations, so that the
    state is carried through many steps."""
    model = make_model(tmp_path, name="bf", dtype="bfloat16",
                       max_new_tokens=48)
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(5).integers(0, 96, n) for n in (20, 9, 16)]
    news = [48, 48, 48]

    def rms(state_dtype):
        served, _, _ = serve(model, params, prompts, news, state_dtype=state_dtype)
        # The reference holds the served type's values; the tokens are the served ones.
        m = ref.Model(ARCH, SEED, "bfloat16")
        seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]])
                for p, s in zip(prompts, served)]
        # The STATE's own error: the program's state against the reference's
        # recurrence is not fetched; the logits carry it.
        flat = []
        for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
            g = s["lp"] - np.take_along_axis(lp, s["lp_ids"], axis=-1)
            flat.append((g - g.mean(axis=-1, keepdims=True)).ravel())
        return float(np.sqrt(np.mean(np.concatenate(flat) ** 2)))

    sound, low = rms(None), rms(jnp.bfloat16)
    assert low > sound
    TOL = float(np.sqrt(sound * low))   # between the two readings, room on both sides
    assert sound < TOL < low, (sound, low)
    assert low > 1.15 * sound, (sound, low)


# -- (b) the chunked scan is the recurrence; padding leaves the state alone ---------------------

def test_the_chunked_scan_is_the_step_applied_token_by_token_and_padding_moves_nothing(whole):
    model, params = whole
    lp = params["layer0"]
    rng = np.random.default_rng(3)
    n = 13                                   # tiles of 4: three whole, one of a single row
    u = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    # by steps: one lane, token after token
    ssm = jnp.zeros((1, model.mh, model.mp, model.mn), jnp.float32)
    conv = jnp.zeros((1, model.conv_k - 1, model.conv_ch), jnp.float32)
    outs = []
    for t in range(n):
        y, ssm, conv = model._mamba_step(lp, u[t:t + 1], jnp.ones((1,), bool), ssm, conv)
        outs.append(y[0])
    # by the chunked scan: one piece of 13 rows in a launch of 16
    launch = {"slot": jnp.asarray([0, 0, 0, 0]), "start": jnp.zeros((4,), jnp.int32),
              "length": jnp.asarray([n, 0, 0, 0]), "pages": jnp.zeros((4, 9), jnp.int32)}
    tiles = model._tiles(launch, 16)
    y2, ssm2, conv2 = model._mamba_prefill(
        lp, u, tiles, jnp.full((2,) + ssm.shape[1:], 7.0), jnp.full((2,) + conv.shape[1:], 7.0),
        launch["slot"], launch["start"], launch["length"])
    np.testing.assert_allclose(y2[:n], jnp.stack(outs), atol=2e-5)
    np.testing.assert_allclose(ssm2[0], ssm[0], atol=1e-5)      # started from zeros, not 7
    np.testing.assert_array_equal(np.asarray(conv2[0]), np.asarray(conv[0]))
    assert np.all(np.asarray(ssm2[1]) == 7.0) and np.all(np.asarray(conv2[1]) == 7.0)
    # the same 13 rows behind a wider padded tail: nothing moves
    wide = dict(launch, slot=jnp.asarray([0, 0]), start=jnp.zeros((2,), jnp.int32),
                length=jnp.asarray([n, 0]), pages=jnp.zeros((2, 9), jnp.int32))
    u_wide = jnp.concatenate([u[:n], jnp.asarray(rng.standard_normal((19, 32)), jnp.float32)])
    _, ssm3, conv3 = model._mamba_prefill(
        lp, u_wide, model._tiles(wide, 32), jnp.zeros_like(ssm2), jnp.zeros_like(conv2),
        wide["slot"], wide["start"], wide["length"])
    np.testing.assert_allclose(ssm3[0], ssm2[0], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(conv3[0]), np.asarray(conv2[0]))
    # carried: rows 0-7 in one launch, 8-12 in the next, from what the slot holds
    a = dict(launch, length=jnp.asarray([8, 0, 0, 0]))
    b = dict(launch, start=jnp.asarray([8, 0, 0, 0]), length=jnp.asarray([5, 0, 0, 0]))
    _, s1, c1 = model._mamba_prefill(lp, u[:16], model._tiles(a, 16), jnp.zeros_like(ssm2),
                                     jnp.zeros_like(conv2), a["slot"], a["start"], a["length"])
    u_b = jnp.concatenate([u[8:13], u[:11]])
    y4, s4, c4 = model._mamba_prefill(lp, u_b, model._tiles(b, 16), s1, c1,
                                      b["slot"], b["start"], b["length"])
    np.testing.assert_allclose(y4[:5], jnp.stack(outs[8:]), atol=2e-5)
    np.testing.assert_allclose(s4[0], ssm[0], atol=1e-5)


# -- (c) the share adds up ------------------------------------------------------------------------

def shares(tmp_path, key, value_of):
    return [make_model(tmp_path, dict(ARCH, share={key: value_of(i)}), name=f"{key}{i}")
            for i in range(4)]


def test_the_four_mamba_shares_are_the_uncut_layer(tmp_path, whole):
    model, params = whole
    parts = shares(tmp_path, "mamba_heads", lambda i: [i, 4])
    assert [(m.mh, m.mg, m.mh_first) for m in parts] == [(2, 1, 0), (2, 1, 2), (2, 1, 4), (2, 1, 6)]
    rng = np.random.default_rng(8)
    us = [jnp.asarray(rng.standard_normal((2, 32)), jnp.float32) for _ in range(4)]
    live = jnp.asarray([True, True])

    def run(m, p):
        ssm = jnp.zeros((2, m.mh, m.mp, m.mn), jnp.float32)
        conv = jnp.zeros((2, m.conv_k - 1, m.conv_ch), jnp.float32)
        ys = []
        for u in us:     # several steps: the state and the convolution's rows are in it
            y, ssm, conv = m._mamba_step(p["layer0"], u, live, ssm, conv)
            ys.append(y)
        return jnp.stack(ys)

    want = run(model, params)
    got = sum(run(m, m.init_params(jax.random.key(0))) for m in parts)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_four_head_shares_with_the_replicated_kv_head_are_whole_attention(tmp_path, whole):
    model, params = whole
    parts = shares(tmp_path, "attention_heads", lambda i: [i, 4])
    # 8 query heads over 2 KV heads on 4 chips: 2 query heads and ONE KV head a chip
    assert [(m.heads, m.h_first, m.kv, m.kv_first) for m in parts] == \
        [(2, 0, 1, 0), (2, 2, 1, 0), (2, 4, 1, 1), (2, 6, 1, 1)]
    u = jnp.asarray(np.random.default_rng(2).standard_normal((7, 32)), jnp.float32)
    mask = jnp.tril(jnp.ones((7, 7), bool))

    def run(m, p):
        q, k, v = m._qkv(p["layer3"], u)
        return m._attn_out(p["layer3"], m._attend(q, k, v, mask))

    want = run(model, params)
    got = sum(run(m, m.init_params(jax.random.key(0))) for m in parts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="does not divide"):
        make_model(tmp_path, dict(ARCH, share={"attention_heads": [0, 3]}), name="bad")


def test_the_four_expert_shares_and_the_shared_expert_once_are_the_uncut_layer(tmp_path, whole):
    model, params = whole
    parts = shares(tmp_path, "experts_held", lambda i: [4 * i, 4])
    u = jnp.asarray(np.random.default_rng(4).standard_normal((9, 32)), jnp.float32)
    live = jnp.ones((9,), bool)
    want, st = model._experts(params["layer1"], u, live)
    assert int(st["routed_held"]) == 9 * 5 and int(st["routed_absent"]) == 0
    got, held = 0.0, 0
    for m in parts:
        p = m.init_params(jax.random.key(0))
        y, s = m._experts(p["layer1"], u, live)
        got, held = got + y, held + int(s["routed_held"])
        assert int(s["routed_held"]) + int(s["routed_absent"]) == 45
    shared = model._relu2(u, params["layer1"]["s_w1"], params["layer1"]["s_w2"])
    assert held == 45
    np.testing.assert_allclose(got - 3 * shared, want, atol=5e-5)


def test_a_share_serves_what_the_reference_gives_for_the_same_share(tmp_path):
    arch = dict(ARCH, share={"experts_held": [8, 4], "attention_heads": [3, 4],
                             "mamba_heads": [2, 4], "vocab_rows": [48, 24]})
    model = make_model(tmp_path, arch, name="shared")
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(1).integers(0, 24, n) for n in (13, 6)]
    served, _, _ = serve(model, params, prompts, [5, 4],
                         launches=[[(0, 0, 8)], [(0, 8, 4), (1, 0, 4)], [(0, 12, 1), (1, 4, 2)]])
    for g in gaps(arch, prompts, served):
        assert float(np.abs(g).max()) < 2e-5
    # A launch wide enough that the dispatch's row bound is under its picks (32 x 5 picks, a quarter
    # of the experts held: 128 rows of 160): the compact branch runs in every prefill launch and in
    # no step (3 lanes), the answer is the reference's and the narrow launches' tokens.
    from tpuserve.obs import Metrics

    metrics = Metrics()
    model.bind_metrics(metrics)
    wide_launches, out, _ = serve(model, params, prompts, [5, 4], chunk=32)
    for g in gaps(arch, prompts, wide_launches):
        assert float(np.abs(g).max()) < 2e-5
    for a, b in zip(wide_launches, served):
        assert np.array_equal(a["tokens"], b["tokens"])
    model.observe_step(out)
    c = metrics.counter_values()
    assert c["moe_layers_compact_total{model=shared,phase=prefill}"] \
        == c["moe_layers_total{model=shared,phase=prefill}"] == 2 * 2     # 2 layers x 2 launches
    assert c["moe_layers_total{model=shared,phase=decode}"] == 2 * 6
    assert c.get("moe_layers_compact_total{model=shared,phase=decode}", 0) == 0


# -- (d) a slot's next tenant, free and frozen lanes ------------------------------------------------

def test_a_slot_reused_answers_as_alone_and_other_lanes_harm_no_state(whole):
    model, params = whole
    first, _, state = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    # every lane is done: a step changes no state, bit for bit
    again, _ = jax.jit(model.step)(params, state)
    for key in ("ssm", "conv"):
        for a, b in zip(state[key], again[key]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # other requests into the same slots, the block as the first left it
    prompts = [PROMPTS[2], PROMPTS[0][:9], PROMPTS[1]]
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


# -- the recipe, the two copies of the reference --------------------------------------------------------

def test_the_recipe_draws_inside_the_ranges_and_a_share_is_a_slice(tmp_path, whole):
    model, params = whole
    lp = params["layer0"]
    delta = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert delta.min() >= 0.001 * 0.999 and delta.max() <= 0.1 * 1.001
    a = np.exp(np.asarray(lp["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and lp["A_log"].dtype == jnp.float32
    assert abs(float(np.asarray(lp["D"]).mean()) - 1.0) < 0.3
    assert float(np.abs(np.asarray(params["layer1"]["e_bias"])).max()) <= 0.06
    part = make_model(tmp_path, dict(ARCH, share={"mamba_heads": [1, 4]}), name="slice")
    pp = part.init_params(jax.random.key(0))["layer0"]
    hp, gn = 8 * 4, 4 * 8
    np.testing.assert_array_equal(np.asarray(pp["dt_bias"]), np.asarray(lp["dt_bias"])[2:4])
    # W_in's columns: z and x of heads 2-3, B and C of group 1, dt of heads 2-3
    cols = np.r_[8:16, hp + 8:hp + 16, 2 * hp + 8:2 * hp + 16,
                 2 * hp + gn + 8:2 * hp + gn + 16, 2 * hp + 2 * gn + 2:2 * hp + 2 * gn + 4]
    np.testing.assert_array_equal(np.asarray(pp["w_in"]), np.asarray(lp["w_in"])[:, cols])
    np.testing.assert_array_equal(np.asarray(pp["w_out"]), np.asarray(lp["w_out"])[2:4])
    # the reference draws the same values
    m = ref.Model(ARCH, SEED, "float32")
    w = m.layer(0)
    np.testing.assert_array_equal(
        np.asarray(lp["w_in"])[:, :hp], w["in_z"].reshape(32, -1))
    for key in ("dt_bias", "A_log", "D"):
        np.testing.assert_allclose(np.asarray(lp[key]), w[key], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["layer1"]["e_bias"]), m.layer(1)["e_bias"],
                               rtol=1e-6, atol=1e-9)


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "reference", "hybrid.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_hybrid_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


def test_a_pattern_with_another_letter_or_length_is_refused(tmp_path):
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        make_model(tmp_path, dict(ARCH, hybrid_override_pattern="ME-*EM"), name="dash")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        make_model(tmp_path, dict(ARCH, num_hidden_layers=5), name="short")
    with pytest.raises(NotImplementedError, match="mlp_hidden_act"):
        make_model(tmp_path, dict(ARCH, mlp_hidden_act="silu"), name="act")
    model = make_model(tmp_path, name="locked")
    with pytest.raises(NotImplementedError, match="generation engine"):
        model.forward(None, None)
    assert rms_norm is hyb.rms_norm      # one norm, the shared module's


# -- (g) through the engine: the counters and /stats ------------------------------------------------------

def test_through_the_engine_two_requests_move_the_counters_by_what_was_served(tmp_path):
    import asyncio

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
    assert eng.pages.rings == 0
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
    n_m, n_e, k = 3, 2, ARCH["num_experts_per_tok"]
    tokens, steps = 19 + 5, (6 - 1) + (9 - 1)
    assert c["gen_prefill_tokens_total{model=eng}"] == tokens
    assert c["ssm_tokens_total{model=eng,phase=prefill}"] == n_m * tokens
    assert c["ssm_tokens_total{model=eng,phase=decode}"] == n_m * steps
    assert c["ssm_state_rows_total{model=eng,phase=decode}"] == n_m * steps
    # 19 tokens at a chunk of 8: one piece from zeros and two carried; 5 tokens: one from zeros
    assert c["ssm_pieces_total{model=eng,start=zero}"] == 2
    assert c["ssm_pieces_total{model=eng,start=carried}"] == 2
    assert c["ssm_state_rows_total{model=eng,phase=prefill}"] == n_m * 4
    routed = sum(v for name, v in c.items() if name.startswith("moe_tokens_routed_total"))
    assert routed == n_e * k * (tokens + steps)
    assert c["moe_expert_steps_total{model=eng,phase=decode}"] \
        >= c["moe_experts_hit_total{model=eng,phase=decode}"] > 0
    # every expert is held here: expert layers ran, none could leave a pick behind
    assert c["moe_layers_total{model=eng,phase=prefill}"] \
        == n_e * c["gen_prefill_chunks_total{model=eng}"]
    assert c["moe_layers_total{model=eng,phase=decode}"] * 16 \
        == c["moe_expert_steps_total{model=eng,phase=decode}"]
    assert not any(v for name, v in c.items() if name.startswith("moe_layers_compact_total"))
    assert c["gen_context_tokens_total{model=eng,phase=prefill}"] == 19 * 20 // 2 + 5 * 6 // 2
    # /stats: the state's bytes a slot and in all, beside pages
    kv = eng.pipeline_stats()["kv"]
    per_slot = n_m * (8 * 4 * 8 * 4 + 3 * (8 * 4 + 2 * 4 * 8) * 4)
    assert kv["state_bytes_per_slot"] == per_slot and kv["state_bytes"] == per_slot * SLOTS
    assert metrics.gauge(f"gen_state_bytes{{model=eng}}").value == per_slot * SLOTS
    assert kv["reserved"] == 0 and kv["pages"] > 0


def test_a_tied_head_is_the_embedding_transposed(tmp_path):
    """``tie_word_embeddings``: no ``head`` is drawn, and the logits are those
    of an untied model whose head holds the same embedding's transpose."""
    tied = make_model(tmp_path, dict(ARCH, tie_word_embeddings=True), name="tied")
    untied = make_model(tmp_path, name="untied")
    pt, pu = tied.init_params(jax.random.key(0)), untied.init_params(jax.random.key(0))
    assert "head" not in pt and "head" in pu
    np.testing.assert_array_equal(np.asarray(pt["embed"]), np.asarray(pu["embed"]))
    x = jnp.asarray(np.random.default_rng(1).standard_normal((5, tied.d)), tied.dtype)
    want = untied._head(dict(pu, head=pu["embed"].T), x)
    # float32 sums in another order (the contraction runs over the other operand's axis)
    np.testing.assert_allclose(tied._head(pt, x), want, rtol=1e-5, atol=2e-5)
