"""The `mla_sc` family (ISSUE 42) against its plain reference at a small size
on the CPU, in float32: packed, chunked prefill and decode through pages of
latent rows equal the reference's one causal pass; each of
four omissions (a cache row in a lower type, the zero-compute term, the
shortcut, a latent factor) fails the same tolerance tenfold; the 32-chip
deployment's shares add up to the uncut layer; the three kinds of pick sum to
k a live token; the compact dispatch and `wide` agree to the bit; a token whose
picks are all zero-compute and one with none; the page signature, the
counters and `/stats`."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import mla_sc_reference as ref
from tests.test_mla import serve
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind
from tpuserve.models import build, mla, mla_sc
from tpuserve.ops import moe

SHARE = {"experts_held": [4, 4], "vocab_rows": [16, 64]}
ARCH = {
    "vocab_size": 96, "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64,
    "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "rms_norm_eps": 1e-5,
    "rope_theta": 10000000, "attention_method": "MLA", "attention_bias": False,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 24, "n_routed_experts": 16,
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
    "routed_scaling_factor": 6, "max_position_embeddings": 131072, "share": SHARE,
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 4
K = ARCH["moe_topk"]
# float32 sums in another order (chunks, key blocks, the absorbed form, experts grouped by a
# sort): a served log-probability stands within 5e-5 of the reference's, as `mla`'s does.
TOL = 5e-5


def make_model(tmp_path, arch=ARCH, name="sc", dtype="float32", **options):
    """The family's tiles and key blocks are 256 wide at the published sizes;
    a toy launch of 8 rows over 4 slots is steered to tiles of one page and
    key blocks of two here, in the test and not through an option of the
    program."""
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="mla_sc", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    model = build(cfg)
    model.TILE_ROWS, model.key_block = PAGE, 2 * PAGE
    return model


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("mla_sc"))
    return model, model.init_params(jax.random.key(0))


PROMPTS = [np.random.default_rng(0).integers(0, 64, n) for n in (19, 5, 11, 2)]
MAX_NEWS = [6, 12, 3, 9]
# Pieces of several slots and sizes in one launch, a prompt over four launches
# (a later launch attends to latents an earlier one cached), padded tails.
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)], [(3, 0, 2)]]


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


def worst(arch, prompts, served):
    return max(float(np.abs(g).max()) for g in gaps(arch, prompts, served))


# -- (a) the served function is the reference's one causal pass -----------------------------

@pytest.mark.parametrize("case", ["packed-over-four-launches", "a-prompt-a-launch",
                                  "every-expert-held"])
def test_chunked_prefill_then_decode_is_the_reference_one_causal_pass(whole, tmp_path, case):
    """Logits, not tokens. Prompts of 19, 5, 11 and 2 tokens over launches of
    8 rows in tiles of a page; decode lane by lane, each over its own key
    blocks of two pages (the longest lane walks four)."""
    model, params = whole
    arch, launches = ARCH, PACKED
    if case == "a-prompt-a-launch":
        launches = None
    elif case == "every-expert-held":
        arch = {k: v for k, v in ARCH.items() if k != "share"}
        model = make_model(tmp_path, arch, name="all")
        params = model.init_params(jax.random.key(0))
    assert model.kv_prefill_pieces(CHUNK, PAGE) == 2
    served, out, _ = serve(model, params, PROMPTS, MAX_NEWS, chunk=CHUNK, launches=launches,
                           slots=SLOTS)
    assert bool(np.all(np.asarray(out["done"])))
    assert [int(s["n_new"]) for s in served] == MAX_NEWS
    assert worst(arch, PROMPTS, served) < TOL
    # the device's sums: the three kinds of pick are k a live token a layer
    acc = np.asarray(out["acc"]).astype(np.int64)
    tokens, steps = sum(len(p) for p in PROMPTS), sum(n - 1 for n in MAX_NEWS)
    for phase, n in ((0, tokens), (1, steps)):
        assert acc[phase, 0] + acc[phase, 1] + acc[phase, 14] == 2 * K * n
        assert acc[phase, 14] > 0
    assert (acc[0, 1] == 0) == (case == "every-expert-held")
    assert acc[0, 4] == sum(n * (n + 1) // 2 for n in (19, 5, 11, 2))
    # rows attended and walked sum over the four attentions; a step is absorbed
    if launches is not None:
        assert acc[0, 5] == 4 * sum(start + n for launch in PACKED for _s, start, n in launch)
    assert acc[1, 5] == 4 * acc[1, 4] > 0 and acc[1, 6] >= acc[1, 5]
    assert acc[1, 7] == max(MAX_NEWS) + 1 and acc[1, 8] == 0
    # every tile of a piece and every live lane of a step walked in XLA (the CPU has no kernel)
    if launches is not None:
        assert acc[0, 10] == 0 and acc[0, 11] == 2 + 2 + 1 + 1 + 1 + 1 + 2 + 1
    assert acc[1, 10] == 0 and acc[1, 11] == steps


def test_prefill_through_the_kernels_walk_is_prefill_through_the_einsum_walk(tmp_path, monkeypatch):
    """Tiles of 64 rows (past the toy's break-even: the expanded form) over key
    blocks of 64 positions, launches of four tiles that carry pieces of
    several prompts and later pieces over what earlier launches cached: with
    every tile steered to the kernel (run in the Pallas interpreter; steered
    here, in the test) the served tokens are the einsum walk's and the
    log-probabilities stand within the family's tolerance, of each other and
    of the reference; the device's sums say which walk ran."""
    from tests.test_mla import steer_to_the_kernel

    model = make_model(tmp_path, name="tiles", max_prompt_tokens=320)
    model.TILE_ROWS, model.key_block = 64, 64
    assert model._form(64) == "expanded" and model.kv_prefill_pieces(256, PAGE) == 4
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(1).integers(0, 64, n) for n in (300, 70, 150)]
    news = [3, 4, 2]
    packed = [[(0, 0, 128), (1, 0, 64)], [(0, 128, 128), (1, 64, 6), (2, 0, 64)],
              [(2, 64, 86), (0, 256, 44)]]
    plain, out, _ = serve(model, params, prompts, news, chunk=256, launches=packed, slots=SLOTS)
    acc = np.asarray(out["acc"]).astype(np.int64)
    assert acc[0, 10] == 0 and acc[0, 11] == 10
    calls = steer_to_the_kernel(monkeypatch)
    kernel, out, _ = serve(model, params, prompts, news, chunk=256, launches=packed, slots=SLOTS)
    assert len(calls) == 4 * 4   # traced once: a call a tile of an attention, side by side
    acc = np.asarray(out["acc"]).astype(np.int64)
    assert acc[0, 10] == 10 and acc[0, 11] == 0 and acc[1, 10] == 0 and acc[1, 11] > 0
    for a, b in zip(kernel, plain):
        assert np.array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["lp"], b["lp"], atol=TOL)
    assert worst(ARCH, prompts, kernel) < TOL


# -- (b) what may not be left out ---------------------------------------------------------------

@pytest.mark.parametrize("omission", ["a-bfloat16-cache-row", "no-zero-compute-term",
                                      "no-shortcut", "no-factor-on-the-kv-latent",
                                      "no-factor-on-the-query-latent"])
def test_each_omission_fails_the_tolerance_tenfold(whole, tmp_path, monkeypatch, omission):
    model, params = whole
    cache_dtype = None
    if omission == "a-bfloat16-cache-row":
        cache_dtype = jnp.bfloat16
    elif omission == "no-zero-compute-term":
        def absent(*a, real=None, **k):
            y, stats = moe.held_experts_swiglu(*a, **k)
            return y, dict(stats, routed_zero=jnp.int32(0))
        monkeypatch.setattr(mla_sc, "held_experts_swiglu", absent)
    elif omission == "no-shortcut":
        model = make_model(tmp_path, name="cut")
        routed = model._routed
        model._routed = lambda lp, u, live: (lambda y, st: (jnp.zeros_like(y), st))(
            *routed(lp, u, live))
    else:
        model = make_model(tmp_path, name="flat")
        if omission == "no-factor-on-the-kv-latent":
            model.kv_scale = 1.0
        else:
            model.q_scale = 1.0
    served, _, _ = serve(model, params, PROMPTS, MAX_NEWS, chunk=CHUNK, launches=PACKED,
                         slots=SLOTS, cache_dtype=cache_dtype)
    assert worst(ARCH, PROMPTS, served) > 10 * TOL


# -- (c) the shares add up ----------------------------------------------------------------------

def test_the_shares_routed_parts_and_the_zero_term_once_are_the_uncut_layer(tmp_path):
    """Four chips of 4 experts each: every share's `_routed` is its held
    experts' part plus the zero-compute term; the four parts with the zero
    term counted ONCE are what the reference gives for the whole layer."""
    uncut = {k: v for k, v in ARCH.items() if k != "share"}
    m = ref.Model(uncut, SEED, "float32")
    u = np.asarray(np.random.default_rng(3).standard_normal((40, 64)), np.float32)
    u /= np.sqrt(np.mean(u * u, axis=-1, keepdims=True))
    router, e_bias = m.router(1)
    with jax.default_matmul_precision("highest"):
        p = np.asarray(jax.nn.softmax(jnp.asarray(u) @ router, axis=-1))
    top, wt = ref.picks(m, p, e_bias)
    (held, zero), = ref.routed(m, m.held_experts(1), [u], [top], [wt], False, parts=True)
    assert float(np.abs(zero).max()) > 0.1 and float(np.abs(held).max()) > 0.1
    parts, stats = [], []
    for first in (0, 4, 8, 12):
        model = make_model(tmp_path, dict(ARCH, share={"experts_held": [first, 4]}),
                           name=f"share{first}")
        lp = model.init_params(jax.random.key(0))["layer1"]
        y, st = model._routed(lp, jnp.asarray(u), jnp.ones((40,), bool))
        parts.append(np.asarray(y) - zero)
        stats.append({k: int(v) for k, v in st.items()})
        # one chip's part is the reference's with the same share
        mine = ref.Model(dict(ARCH, share={"experts_held": [first, 4]}), SEED, "float32")
        np.testing.assert_allclose(y, ref.routed(mine, mine.held_experts(1), [u], [top], [wt], False)[0],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sum(parts) + zero, held + zero, rtol=1e-5, atol=1e-5)
    n_zero = int(np.sum(top >= 16))
    assert sum(st["routed_held"] for st in stats) == 40 * K - n_zero
    assert all(st["routed_zero"] == n_zero and
               st["routed_held"] + st["routed_absent"] + st["routed_zero"] == 40 * K
               for st in stats)


# -- (d) the dispatch's two branches, and picks of one kind ------------------------------------------

def test_held_picks_over_the_compact_bound_take_wide_to_the_same_bits(whole, monkeypatch):
    """256 tokens x 4 picks over 4 held of 24 outputs: the compact branch
    carries 256 rows. A bias that pulls picks onto the held experts puts more
    held picks in the launch than that: `wide` runs, and its answer is, bit
    for bit, what the program gives when its `cond` is made to take `wide`
    for a launch that fits (the layer's own predicate reported as ever)."""
    model, params = whole
    lp = params["layer0"]
    u = jnp.asarray(np.random.default_rng(5).standard_normal((256, 64)), jnp.float32)
    live = jnp.asarray(np.random.default_rng(6).random(256) < 0.9)
    assert moe._row_bound(256 * K, 4, 24) == 256
    real_cond = jax.lax.cond

    def run(lp, take):
        seen = []

        def cond(pred, *branches):
            seen.append(pred)
            return real_cond(pred if take is None or len(seen) > 1 else take, *branches)

        monkeypatch.setattr(jax.lax, "cond", cond)
        try:
            y, st = jax.jit(model._routed)(lp, u, live)
        finally:
            monkeypatch.setattr(jax.lax, "cond", real_cond)
        return np.asarray(y).view(np.uint32), {k: int(v) for k, v in st.items()}

    y, st = run(lp, None)
    assert st["compact"] == 1 and 0 < st["routed_held"] <= 256
    y_wide, st_wide = run(lp, False)
    assert np.array_equal(y, y_wide) and st_wide == st
    pulled = dict(lp, e_bias=lp["e_bias"].at[4:8].set(10.0))
    y, st = run(pulled, None)
    assert st["compact"] == 0 and st["routed_held"] > 256
    y_short, _ = run(pulled, True)   # made to take `compact`, it would leave picks behind
    assert not np.array_equal(y, y_short)
    assert st["routed_held"] + st["routed_absent"] + st["routed_zero"] == K * int(np.sum(live))


@pytest.mark.parametrize("kind", ["every-pick-zero-compute", "no-pick-zero-compute"])
def test_a_token_whose_picks_are_all_zero_compute_and_one_with_none(whole, kind):
    model, params = whole
    lp = params["layer1"]
    u = jnp.asarray(np.random.default_rng(8).standard_normal((24, 64)), jnp.float32)
    sign = 10.0 if kind == "every-pick-zero-compute" else -10.0
    lp = dict(lp, e_bias=lp["e_bias"].at[16:].set(sign))
    y, st = model._routed(lp, u, jnp.ones((24,), bool))
    r = jnp.matmul(u, lp["router"], precision=jax.lax.Precision.HIGHEST)
    w, e = moe.topk_route(r, K, normalize=False, scale=6.0, select_bias=lp["e_bias"])
    if kind == "every-pick-zero-compute":
        assert bool(np.all(np.asarray(e) >= 16))
        assert int(st["routed_zero"]) == 24 * K and int(st["routed_held"]) == 0 \
            and int(st["routed_absent"]) == 0 and int(st["experts_hit"]) == 0
        np.testing.assert_allclose(y, np.sum(w, -1, keepdims=True) * np.asarray(u), rtol=1e-6)
    else:
        assert bool(np.all(np.asarray(e) < 16)) and int(st["routed_zero"]) == 0
        plain, _ = moe.held_experts_swiglu(u, w, e, 4, lp["e_gate"], lp["e_up"], lp["e_down"],
                                           live=jnp.ones((24,), bool), of=24)
        assert np.array_equal(np.asarray(y), np.asarray(plain))


# -- (e) shapes, the recipe, refusals ----------------------------------------------------------------

def test_the_page_signature_holds_two_latent_rows_a_layer_and_the_attention_is_mlas(whole):
    model, params = whole
    plan = model.kv_plan(SLOTS, PAGE, 9)
    sig = plan.state
    assert len(sig["ckv"]) == len(sig["kr"]) == 4
    assert sig["ckv"][3].shape == (9, PAGE, 32) and sig["kr"][3].shape == (9, 2, 128)
    assert sig["acc"].shape == (2, 15) and plan.leaves(LeafKind.POOL) == ("ckv", "kr")
    # shared, not copied: the attention's functions are `mla.LatentServing`'s own
    for name in ("_project", "_write_keys", "_attend_tile", "_attend_tiles", "_walk", "_form",
                 "_attn_out", "_attention", "_step_plan", "_prefill_plan"):
        assert getattr(type(model), name) is getattr(mla.LatentServing, name)
    assert model.q_scale == pytest.approx((64 / 48) ** 0.5) and model.kv_scale == pytest.approx(2 ** 0.5)
    fresh = build(model.cfg)
    assert (fresh.TILE_ROWS, fresh.key_block, fresh.step_keys) == (256, 256, 512)
    assert fresh.kv_prefill_pieces(1024, 128) == 4 and fresh.kv_prefill_pieces(2048, 128) == 8
    assert model.share_stats() == {"experts_held": [4, 4], "experts": 16, "zero_experts": 8,
                                   "vocab_rows": [16, 64], "vocab": 96}
    # the recipe is the reference's: the held experts and rows of the PUBLISHED tensors
    m = ref.Model(ARCH, SEED, "float32")
    w, lp = m.attention(1, 0), params["layer1"]["attn0"]
    np.testing.assert_array_equal(np.asarray(lp["w_qb"])[..., :16], np.asarray(w["w_qb_nope"]))
    np.testing.assert_array_equal(np.asarray(lp["w_kva"])[:, 32:], np.asarray(w["w_kva_r"]))
    np.testing.assert_array_equal(np.asarray(params["layer1"]["mlp1"]["w_down"]),
                                  np.asarray(m.mlp(1, 1)["w_down"]))
    router, e_bias = m.router(0)
    assert router.shape == (64, 24) and float(np.abs(e_bias).max()) <= 0.06
    np.testing.assert_array_equal(np.asarray(params["layer0"]["router"]), np.asarray(router))
    np.testing.assert_allclose(np.asarray(params["layer0"]["e_bias"]), e_bias, rtol=1e-6, atol=1e-9)
    np.testing.assert_array_equal(np.asarray(params["layer0"]["e_up"]),
                                  m.expert_block(0, 4, 4)["e_up"])
    np.testing.assert_array_equal(np.asarray(params["embed"]), m.embed())
    assert params["embed"].shape == (64, 64) and params["head"].shape == (64, 64)


@pytest.mark.parametrize("key,value,error", [
    ("attention_bias", True, NotImplementedError), ("attention_method", "MHA", NotImplementedError),
    ("zero_expert_type", "copy", NotImplementedError), ("q_lora_rank", None, NotImplementedError),
    ("rope_scaling", {"type": "yarn"}, NotImplementedError),
    ("share", {"experts_held": [14, 4]}, ValueError)])
def test_a_key_the_family_does_not_implement_is_refused(tmp_path, key, value, error):
    with pytest.raises(error, match=key.split("_")[0]):
        make_model(tmp_path, dict(ARCH, **{key: value}), name="refused")


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "reference", "mla_sc.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_mla_sc_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 64, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


def test_the_references_pass_in_two_calls_is_its_pass_in_one():
    """The check's pass is made in two calls (the prompts while the server
    starts, the served tokens after, continued from the cached rows): the same
    hidden states as one pass over the whole sequences, the control too."""
    m = ref.Model(ARCH, SEED, "bfloat16")
    seqs = [np.random.default_rng(7).integers(0, 64, n) for n in (17, 9)]
    cut = (11, 8)
    for low in (False, True):
        whole = ref.hidden_states(m, seqs, low)
        layers, last, carry = ref.prompt_pass(m, [s[:c] for s, c in zip(seqs, cut)], low)
        tails, _ = ref.forward(m, layers, [s[c:] for s, c in zip(seqs, cut)], carry, low)
        for h, h0, tail, c in zip(whole, last, tails, cut):
            np.testing.assert_allclose(h0, h[c - 1:c], rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(tail, h[c:], rtol=2e-5, atol=2e-5)


# -- (f) through the engine: the counters and /stats -------------------------------------------------

def test_through_the_engine_requests_move_the_counters_by_what_was_served(tmp_path):
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
    prompts = [(PROMPTS[0] + 16).tolist(), (PROMPTS[1] + 16).tolist()]   # ids of the held rows
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
    by_hand, _, _ = serve(model, rt.params_per_mesh[0], PROMPTS[:2], max_news, slots=SLOTS)
    for got, want, n in zip(results, by_hand, max_news):
        assert got["tokens"] == (want["tokens"][:n] + 16).tolist() and got["n_tokens"] == n
        np.testing.assert_allclose(got["logprobs"]["values"], want["lp"][:n], atol=1e-4)
    with pytest.raises(ValueError, match="held here"):
        model.host_decode(json.dumps({"prompt_ids": [3]}).encode(), "application/json")
    c = metrics.counter_values()
    tokens, steps = 19 + 5, (6 - 1) + (9 - 1)
    for ph, n in (("prefill", tokens), ("decode", steps)):
        kinds = [c[f"moe_tokens_routed_total{{model=eng,phase={ph},held=yes}}"],
                 c[f"moe_tokens_routed_total{{model=eng,phase={ph},held=no}}"],
                 c[f"moe_routed_zero_total{{model=eng,phase={ph}}}"]]
        assert sum(kinds) == 2 * K * n and all(v > 0 for v in kinds)
    assert c["mla_launches_total{model=eng,phase=decode,form=absorbed}"] \
        == c["gen_iterations_total{model=eng}"]
    assert c["mla_rows_attended_total{model=eng,phase=prefill}"] == 4 * (8 + 16 + 19 + 5)
    assert c["mla_rows_attended_total{model=eng,phase=decode}"] \
        == 4 * c["gen_context_tokens_total{model=eng,phase=decode}"]
    assert c["mla_rows_walked_total{model=eng,phase=decode}"] \
        >= c["mla_rows_attended_total{model=eng,phase=decode}"]
    assert c["moe_layers_total{model=eng,phase=decode}"] == 2 * c["gen_iterations_total{model=eng}"]
    stats = eng.pipeline_stats()
    assert stats["share"] == model.share_stats()
    row = 32 + 64
    assert stats["kv"]["row_bytes_per_token"] == 4 * row * 4   # four attentions, one row each
    assert metrics.gauge("gen_kv_row_bytes{model=eng}").value == 4 * row * 4


# -- (h) a step's walk in the kernel (ISSUE 44) ------------------------------------------------------

LANE_ARCH = dict(ARCH, kv_lora_rank=128)


def test_on_the_tpu_a_step_walks_every_lane_in_one_kernel_call_an_attention(tmp_path, monkeypatch):
    """With the backend named `tpu` and the kernel run in the interpreter, a
    step of a bfloat16 model at widths the kernel takes is ONE call of
    `ops/lane_attention.py` an attention (four: two layers of two) for every
    lane: contexts of 40, 9, 21 and 10 tokens over key blocks of two pages,
    two lanes never armed. It is the XLA step's (`mla`'s fallback, lane by
    lane) state and log-probabilities to bfloat16's rounding, and the device's
    sums count every live lane under `walk=kernel`, none under `xla`, and each
    lane's own whole key blocks, as the fallback counts them at cells as wide."""
    from tests.test_mla import same_step, steps_both_walks

    model = make_model(tmp_path, LANE_ARCH, name="lanes", dtype="bfloat16", max_prompt_tokens=48)
    model.key_block = model.step_keys = 32
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(3).integers(0, 64, n) for n in (40, 9, 21, 10)]
    pairs, calls = steps_both_walks(model, params, prompts, [8] * 4, monkeypatch, 6, 16)
    assert calls == 2 * model.n_layers
    acc = same_step(pairs, 4)
    assert acc[1, 10] == 4 and acc[1, 11] == 0 and acc[1, 7] == 1
    own = (2 + 1 + 1 + 1 + 1 + 1) * 32   # a lane that is not live: one block
    assert acc[1, 5] == 4 * (41 + 10 + 22 + 11) and acc[1, 6] == 4 * own
    by_lane = np.asarray(pairs[0][0][0]["acc"]).astype(np.int64)   # the fallback's own sums
    assert by_lane[1, 6] == 4 * own and by_lane[1, 5] == acc[1, 5]


@pytest.mark.parametrize("refused", ["float32", "a-page-of-8"])
def test_a_shape_the_decode_kernel_refuses_walks_lane_by_lane_in_xla_and_counts_there(
        tmp_path, monkeypatch, refused):
    """The backend named `tpu`, a shape `fits` does not take: the step is the
    XLA step (lane by lane, `mla`'s fallback) to the bit, no kernel call is
    traced, the lanes count under `walk=xla`."""
    from tests.test_mla import same_step, steps_both_walks

    dtype, page = ("float32", 16) if refused == "float32" else ("bfloat16", 8)
    model = make_model(tmp_path, LANE_ARCH, name="refused", dtype=dtype, max_prompt_tokens=48)
    model.key_block = model.step_keys = 32
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(3).integers(0, 64, n) for n in (40, 9)]
    pairs, calls = steps_both_walks(model, params, prompts, [8, 8], monkeypatch, 4, page, steps=2)
    assert not calls
    acc = same_step(pairs, 2, atol=0)
    assert acc[1, 10] == 0 and acc[1, 11] == 2


@pytest.mark.parametrize("family", ["mla", "mla_sc"])
def test_the_fallback_step_is_mlas_and_the_reference_across_a_key_blocks_edge(tmp_path, family):
    """Off the TPU a step attends lane by lane in XLA, `mla_sc` through the very
    function `mla` does (`mla._attention`'s map over `_attend_tile`). Prompts of
    7 and 15 tokens end one short of an edge of the key blocks of two pages (8
    positions): the first step's walk takes a block more than the prompt's did,
    and a lane of each length steps beside the other. Either family's served
    log-probabilities are its float32 reference's, and it counts the whole key
    blocks of every lane's own need, an attention."""
    from tests import test_mla

    assert mla_sc.ShortcutLatentServing.step is mla.LatentServing.step
    if family == "mla":
        model, arch, attentions = test_mla.make_model(tmp_path, name="edge"), test_mla.ARCH, 1
        model.key_block = 2 * PAGE   # its own, where `mla` walks the module's KEY_BLOCK
        gaps_of = test_mla.gaps
    else:
        model, arch, attentions, gaps_of = make_model(tmp_path, name="edge"), ARCH, 4, gaps
    assert model._block_pages(PAGE, model.kv_plan(1, PAGE).pages_per_slot) == 2
    params = model.init_params(jax.random.key(0))
    lengths, news = (7, 15), [4, 4]
    prompts = [np.random.default_rng(9).integers(0, 64, n) for n in lengths]
    served, out, _ = serve(model, params, prompts, news, chunk=CHUNK, slots=SLOTS)
    assert [int(s["n_new"]) for s in served] == news
    assert max(float(np.abs(g).max()) for g in gaps_of(arch, prompts, served)) < TOL
    # Five steps: a lane at position p walks p // 8 + 1 blocks of 8 rows in each of its
    # three live steps, and one once it is done, as the two lanes never armed do.
    blocks = sum(p // 8 + 1 for n in lengths for p in range(n, n + 3)) + 2 * 2 + 5 * (SLOTS - 2)
    acc = np.asarray(out["acc"]).astype(np.int64)
    assert acc[1, 10] == 0 and acc[1, 11] == 2 * 3   # every live lane of every step in XLA
    assert acc[1, 6] == attentions * blocks * 8 and acc[1, 5] == attentions * acc[1, 4]
