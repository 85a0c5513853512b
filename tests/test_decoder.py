"""The `decoder` family (ISSUE 28) against its plain reference at a small size
on the CPU: chunked paged prefill and decode through both cache kinds, the
shares adding up to the uncut layers, the rotary kinds against the formula,
the weights recipe, and the two copies of the reference."""

from __future__ import annotations

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import decoder_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.engine import LOOP_PHASES
from tpuserve.genserve.model import PrefillPiece
from tpuserve.models import build, seeded
from tpuserve.models import decoder as dec
from tpuserve.ops.moe import held_experts_swiglu, topk_route

ARCH = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True, "gating": "per-head",
    "sliding_window": 8, "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                           "original_max_position_embeddings": 16, "beta_slow": 1,
                           "beta_fast": 32, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3


def make_model(tmp_path, arch=ARCH, name="dec", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="decoder", dtype="float32", batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def serve(model, params, prompts, max_news, chunk=CHUNK, order=None, launches=None,
          state=None, slots=SLOTS):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done. ``launches`` is a list of
    launches, each a list of (slot, start, length); without it each prompt
    goes alone, a chunk a launch, in ``order``. ``state``: the block an earlier
    call left, its pages and rings handed out again. Returns extract() per
    slot, the last step's out-block and the state."""
    pps = model.kv_plan(1, PAGE).pages_per_slot
    if state is None:
        state = zeros(model.kv_plan(slots, PAGE).state)
    k = model.kv_prefill_pieces(chunk, PAGE)
    prefill = jax.jit(model.prefill_chunk, static_argnames=("chunk",))
    step = jax.jit(model.step)
    if launches is None:
        launches = [[(slot, start, min(chunk, len(prompts[slot]) - start))]
                    for slot in order or range(len(prompts))
                    for start in range(0, len(prompts[slot]), chunk)]

    def piece(slot, start, length):
        ids = np.zeros((MAX_PROMPT,), np.int32)
        ids[: len(prompts[slot])] = prompts[slot]
        item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
                np.float32(0.0), np.int32(dec.LOGPROBS))
        cache = {"pages": np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32),
                 "ring": np.int32(slot + 1)}
        return PrefillPiece(slot, item, start, length, cache)

    for pieces in launches:
        state = prefill(params, state, model.pack_prefill([piece(*p) for p in pieces], chunk, k),
                        chunk=chunk)
    for _ in range(max(max_news) + 1):
        state, out = step(params, state)
    assert bool(np.all(np.asarray(out["done"])[: len(prompts)]))
    return [jax.tree_util.tree_map(np.asarray, model.extract(params, state, np.int32(s)))
            for s in range(len(prompts))], out, state


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("decoder"))
    return model, model.init_params(jax.random.key(0))


def reference_log_probs(arch, prompts, served):
    m = ref.Model(arch, SEED, "float32")
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    return ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])


# -- served path against the full forward pass --------------------------------------

def test_chunked_prefill_then_decode_is_the_full_forward_pass(whole):
    """Chunk (8) smaller than the prompts, window (8) smaller than the
    context, a prompt shorter than a page, rings wrapped by prefill and again
    by decode; folded in out of order so lanes are at different positions."""
    model, params = whole
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (19, 3, 24)]
    max_news = [12, 12, 7]
    served, out, _ = serve(model, params, prompts, max_news, order=[2, 0, 1])
    want = reference_log_probs(ARCH, prompts, served)
    for s, lp, n_new in zip(served, want, max_news):
        assert s["n_new"] == n_new
        got = s["lp"][:n_new]
        at_ids = np.take_along_axis(lp, s["lp_ids"][:n_new].astype(np.int64), axis=-1)
        np.testing.assert_allclose(got, at_ids, atol=2e-4)
        # the ids named are the reference's eight most likely, the token its first
        assert np.array_equal(s["lp_ids"][:n_new, 0], np.argmax(lp, axis=-1))
        assert np.array_equal(s["tokens"][:n_new], np.argmax(lp, axis=-1))
    # the device's sums: every live pick is held or absent, experts hit are counted
    acc = np.asarray(out["acc"]).astype(np.int64)
    sparse, k = 4, ARCH["num_experts_per_tok"]
    assert acc[0, 0] + acc[0, 1] == sparse * k * sum(len(p) for p in prompts)
    assert acc[1, 0] + acc[1, 1] == sparse * k * sum(n - 1 for n in max_news)
    assert 0 < acc[1, 2] <= acc[1, 3]
    assert acc[0, 4] == sum(n * (n + 1) // 2 for n in map(len, prompts))


@pytest.mark.parametrize("chunk", [4, 24])
def test_chunk_width_does_not_change_the_answer(whole, chunk):
    model, params = whole
    prompts = [np.arange(5, 22, dtype=np.int32)]
    a, _, _ = serve(model, params, prompts, [6], chunk=CHUNK)
    b, _, _ = serve(model, params, prompts, [6], chunk=chunk)
    assert np.array_equal(a[0]["tokens"], b[0]["tokens"])
    np.testing.assert_allclose(a[0]["lp"][:6], b[0]["lp"][:6], atol=1e-4)


# -- a launch of several prompts' pieces (ISSUE 31) --------------------------------------
# A launch of 16 rows in K = 4 tiles of one page (4); window 8. Each case: the
# prompts' lengths and the launches as lists of (slot, start, length).
PACKED = {
    "a-K-short-prompts-in-one-launch":
        ([3, 4, 2, 4], [[(0, 0, 3), (1, 0, 4), (2, 0, 2), (3, 0, 4)]]),
    "b-a-long-tail-not-aligned-then-two-short":
        ([23, 3, 4], [[(0, 0, 16)], [(0, 16, 7), (1, 0, 3), (2, 0, 4)]]),
    "c-cut-at-a-start-no-multiple-of-chunk-or-window":
        ([4, 21], [[(0, 0, 4), (1, 0, 12)], [(1, 12, 9)]]),
    "d-longer-than-the-window-in-one-launch-and-a-ring-that-wraps-between":
        ([14, 22], [[(0, 0, 14)], [(1, 0, 12)], [(1, 12, 10)]]),
    "e-a-ring-and-pages-a-retired-request-left-full":
        ([5, 7], [[(0, 0, 5), (1, 0, 7)]]),
    "f-two-prompts-end-in-one-launch":
        ([6, 3, 9], [[(2, 0, 8)], [(0, 0, 6), (1, 0, 3), (2, 8, 1)]]),
}


@pytest.mark.parametrize("case", list(PACKED))
def test_a_packed_launch_is_each_prompt_alone(whole, case):
    """Against the float32 reference and against one prompt a launch: every
    generated position's log-probabilities and the tokens (the first and the
    next 8), each request through its own lane."""
    model, params = whole
    lengths, launches = PACKED[case]
    assert model.kv_prefill_pieces(16, PAGE) == 4
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in lengths]
    max_news = [9, 7, 9, 9][: len(prompts)]
    state = None
    if case.startswith("e-"):  # three requests that fill every page and ring they hold
        full = [rng.integers(0, 96, MAX_PROMPT).astype(np.int32) for _ in range(3)]
        _, _, state = serve(model, params, full, [MAX_NEW] * 3, chunk=16, slots=4)
    packed, out, _ = serve(model, params, prompts, max_news, chunk=16, launches=launches,
                           state=state, slots=4)
    alone, _, _ = serve(model, params, prompts, max_news, chunk=16, slots=4)
    want = reference_log_probs(ARCH, prompts, packed)
    for got, one, lp, n_new in zip(packed, alone, want, max_news):
        assert got["n_new"] == n_new
        at_ids = np.take_along_axis(lp, got["lp_ids"][:n_new].astype(np.int64), axis=-1)
        np.testing.assert_allclose(got["lp"][:n_new], at_ids, atol=2e-4)
        assert np.array_equal(got["tokens"][:n_new], np.argmax(lp, axis=-1))
        assert np.array_equal(got["tokens"][:n_new], one["tokens"][:n_new])
        np.testing.assert_allclose(got["lp"][:n_new], one["lp"][:n_new], atol=1e-4)
    # the device's sums run over all pieces: every prompt token once, at its own position
    if state is None:
        acc = np.asarray(out["acc"]).astype(np.int64)
        assert acc[0, 0] + acc[0, 1] == 4 * ARCH["num_experts_per_tok"] * sum(lengths)
        assert acc[0, 4] == sum(n * (n + 1) // 2 for n in lengths)


def test_pack_prefill_lays_pieces_at_whole_tiles_and_refuses_too_many(whole):
    model, _ = whole
    item = model.host_decode(json.dumps({"prompt_ids": list(range(1, 12))}).encode(),
                             "application/json")
    cache = {"pages": np.arange(1, 10, dtype=np.int32), "ring": np.int32(2)}
    launch = model.pack_prefill(
        [PrefillPiece(2, item, 3, 5, cache), PrefillPiece(0, item, 0, 2, cache)], 16, 4)
    assert launch["ids"].tolist() == [4, 5, 6, 7, 8, 0, 0, 0, 1, 2] + [0] * 6
    assert launch["slot"].tolist() == [2, 0, 0, 0] and launch["length"].tolist() == [5, 2, 0, 0]
    assert launch["pages"].shape == (4, 9) and launch["ring"].tolist() == [2, 2, 0, 0]
    with pytest.raises(ValueError, match="do not fit"):
        model.pack_prefill([PrefillPiece(s, item, 0, 5, cache) for s in range(3)], 16, 4)
    # K by the chunk and the page: whole pages a tile, at most MAX_PIECES of them
    sizes = ((8, 4), (24, 4), (1024, 128), (2048, 128), (24, 5))
    assert [model.kv_prefill_pieces(c, p) for c, p in sizes] == [2, 6, 8, 8, 1]


# -- the shares add up -----------------------------------------------------------------

def test_the_two_expert_shares_and_the_shared_expert_once_are_the_uncut_layer(tmp_path, whole):
    model, params = whole
    lp = params["layer2"]
    u = jnp.asarray(np.random.default_rng(1).standard_normal((13, 32)), jnp.float32)
    whole_y, _ = model._ffn(lp, 2, u, None)
    shared = model._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"])
    parts = []
    for first in (0, 4):
        half = make_model(tmp_path, {**ARCH, "share": {"experts_held": [first, 4]}},
                          name=f"e{first}")
        hp = half.init_params(jax.random.key(0))["layer2"]
        assert np.array_equal(hp["e_up"], lp["e_up"][first:first + 4])
        y, stats = half._ffn(hp, 2, u, None)
        parts.append(y - shared)
        assert int(stats["routed_held"]) + int(stats["routed_absent"]) == 13 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole_y, atol=1e-5)
    # and the uncut layer is the reference's
    m = ref.Model(ARCH, SEED, "float32")
    w = m.layer(2)
    want = ref._swiglu(u, w["s_gate"], w["s_up"], w["s_down"]) + ref.experts(m, w, np.asarray(u))
    np.testing.assert_allclose(whole_y, want, atol=2e-5)


def test_the_two_head_halves_are_whole_attention(tmp_path, whole):
    model, params = whole
    u = jnp.asarray(np.random.default_rng(2).standard_normal((11, 32)), jnp.float32)
    pos = jnp.arange(11)
    mask = pos[:, None] >= pos[None, :]
    for i in (0, 1):  # a full layer and a window layer (mask aside)
        def out(mod, lp):
            q, k, v, gate = mod._qkv(lp, i, u, pos)
            return mod._attn_out(lp, mod._attend(q, k, v, mask), gate)
        total = 0
        for idx in (0, 1):
            half = make_model(tmp_path, {**ARCH, "share": {"attention_heads": [idx, 2]}},
                              name=f"h{idx}")
            total = total + out(half, half.init_params(jax.random.key(0))[f"layer{i}"])
        np.testing.assert_allclose(total, out(model, params[f"layer{i}"]), atol=1e-5)


def test_the_two_vocabulary_slices_side_by_side_are_the_whole_logits(tmp_path, whole):
    model, params = whole
    x = jnp.asarray(np.random.default_rng(3).standard_normal((5, 32)), jnp.float32)
    parts = []
    for first in (0, 48):
        half = make_model(tmp_path, {**ARCH, "share": {"vocab_rows": [first, 48]}},
                          name=f"v{first}")
        hp = half.init_params(jax.random.key(0))
        assert np.array_equal(hp["embed"], params["embed"][first:first + 48])
        parts.append(half._head(hp, x))
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), model._head(params, x), atol=1e-6)


def test_a_share_serves_what_the_reference_gives_for_the_same_share(tmp_path):
    arch = {**ARCH, "share": {"experts_held": [4, 4], "attention_heads": [1, 2],
                              "vocab_rows": [48, 48]}}
    model = make_model(tmp_path, arch, name="share")
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(8).integers(0, 48, 13).astype(np.int32)]
    served, _, _ = serve(model, params, prompts, [9])
    lp = reference_log_probs(arch, prompts, served)[0]
    got = served[0]["lp"][:9]
    np.testing.assert_allclose(
        got, np.take_along_axis(lp, served[0]["lp_ids"][:9].astype(np.int64), axis=-1), atol=2e-4)
    # A launch wide enough that the dispatch's row bound is under its picks (64 x 3 picks, half the
    # experts held: 128 rows of 192): the compact branch runs in the prefill launch and in no step
    # (3 lanes), the answer is the reference's and the narrow launches' tokens.
    from tpuserve.obs import Metrics

    metrics = Metrics()
    model.bind_metrics(metrics)
    wide_launch, out, _ = serve(model, params, prompts, [9], chunk=64)
    np.testing.assert_allclose(wide_launch[0]["lp"][:9], np.take_along_axis(
        lp, wide_launch[0]["lp_ids"][:9].astype(np.int64), axis=-1), atol=2e-4)
    assert np.array_equal(wide_launch[0]["tokens"], served[0]["tokens"])
    model.observe_step(out)
    c = metrics.counter_values()
    assert c["moe_layers_compact_total{model=share,phase=prefill}"] \
        == c["moe_layers_total{model=share,phase=prefill}"] == 4         # 4 sparse layers, 1 launch
    assert c["moe_layers_total{model=share,phase=decode}"] == 4 * 10
    assert c.get("moe_layers_compact_total{model=share,phase=decode}", 0) == 0
    item = model.host_decode(json.dumps({"prompt_ids": [48, 95], "logprobs": 2}).encode(),
                             "application/json")
    assert list(item[0][:2]) == [0, 47]
    with pytest.raises(ValueError, match="rows held here"):
        model.host_decode(json.dumps({"prompt_ids": [47]}).encode(), "application/json")
    out = model.finalize({"tokens": np.arange(12), "n_new": 2, "lp_ids": np.zeros((12, 8), int),
                          "lp": np.zeros((12, 8))}, item)
    assert out["tokens"] == [48, 49] and np.shape(out["logprobs"]["ids"]) == (2, 2)


# -- routing ---------------------------------------------------------------------------------

def test_topk_route_and_held_experts_drop_nothing():
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((40, 8)), jnp.float32)
    w, e = topk_route(logits, 3, normalize=True, scale=2.5)
    np.testing.assert_allclose(np.sum(w, axis=-1), 2.5, rtol=1e-6)
    p = jax.nn.softmax(logits, axis=-1)
    assert np.array_equal(np.sort(e, axis=-1), np.sort(np.argsort(-p, axis=-1)[:, :3], axis=-1))
    x = jnp.asarray(rng.standard_normal((40, 6)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((8, 6, 5)), jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((8, 5, 6)), jnp.float32)
    # every token on one expert: no capacity, so none is dropped
    e_one = jnp.full((40, 3), 2, jnp.int32).at[:, 1].set(5).at[:, 2].set(7)
    y, stats = held_experts_swiglu(x, w, e_one, 0, wg, wu, wd)
    want = sum(w[:, j:j + 1] * ((jax.nn.silu(x @ wg[ex]) * (x @ wu[ex])) @ wd[ex])
               for j, ex in enumerate((2, 5, 7)))
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert int(stats["routed_held"]) == 120 and int(stats["experts_hit"]) == 3
    live = jnp.arange(40) < 10
    y2, stats2 = held_experts_swiglu(x, w, e_one, 4, wg[4:], wu[4:], wd[4:], live=live)
    assert int(stats2["routed_held"]) == 20 and int(stats2["routed_absent"]) == 10
    assert np.all(np.asarray(y2[10:]) == 0) and int(stats2["experts_hit"]) == 2


# -- rotary ------------------------------------------------------------------------------------

def test_yarn_and_partial_rotary_against_the_formula():
    rp = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
          "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
          "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
    inv, att, dim = dec.rope_inv_freq(rp, 128)
    assert dim == 64 and att == pytest.approx(0.1 * math.log(128) + 1.0)
    base = 500000.0 ** (-np.arange(0, 64, 2) / 64)

    def cdim(rot):
        return 64 * math.log(8192 / (rot * 2 * math.pi)) / (2 * math.log(500000))
    low, high = math.floor(cdim(32)), math.ceil(cdim(1))
    for j in range(32):
        ramp = min(1.0, max(0.0, (j - low) / (high - low)))
        assert inv[j] == pytest.approx(base[j] * (1 - ramp) + base[j] / 128 * ramp, rel=1e-6)
    assert inv[0] == pytest.approx(1.0) and inv[-1] == pytest.approx(base[-1] / 128, rel=1e-6)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 2, 128)), jnp.float32)
    y = np.asarray(dec.apply_rope(x, jnp.asarray([0, 5, 9000]), inv, att, dim))
    assert np.array_equal(y[..., 64:], np.asarray(x)[..., 64:])        # the rest pass
    np.testing.assert_allclose(y[0, :, :64], att * np.asarray(x)[0, :, :64], rtol=1e-6)
    ang = 9000 * inv[3]
    want = att * (np.asarray(x)[2, 1, 3] * math.cos(ang) - np.asarray(x)[2, 1, 35] * math.sin(ang))
    assert y[2, 1, 3] == pytest.approx(want, rel=1e-4)
    inv_d, one, dim_d = dec.rope_inv_freq({"rope_type": "default", "rope_theta": 10000}, 128)
    assert dim_d == 128 and one == 1.0 and inv_d[1] == pytest.approx(10000 ** (-2 / 128))
    for kind in ("full_attention", "sliding_attention"):
        mine = dec.rope_inv_freq(ARCH["rope_parameters"][kind], 8)
        theirs = ref.rope_inv_freq(ARCH["rope_parameters"][kind], 8)
        assert np.array_equal(mine[0], theirs[0]) and mine[1:] == theirs[1:]


# -- the recipe ----------------------------------------------------------------------------------

def test_the_recipe_gives_the_same_bits_twice_a_layer_alone_and_a_share_as_a_slice(tmp_path, whole):
    model, params = whole
    again = model.init_params(jax.random.key(123))
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)):
        assert np.array_equal(a, b)
    m = ref.Model(ARCH, SEED, "float32")
    w3 = m.layer(3)  # one layer alone, by the reference's own lines
    for name, arr in w3.items():
        assert np.array_equal(arr, params["layer3"][name]), name
    assert np.array_equal(m.embed(), params["embed"]) and np.array_equal(m.head(), params["head"])
    full = seeded.draw(7, "t", (6, 10), 0.5, jnp.bfloat16)
    part = seeded.draw(7, "t", (2, 4), 0.5, jnp.bfloat16, full_shape=(6, 10), start=(3, 5))
    assert np.array_equal(np.asarray(part), np.asarray(full)[3:5, 5:9])
    assert not np.array_equal(np.asarray(full), np.asarray(seeded.draw(8, "t", (6, 10), 0.5, jnp.bfloat16)))
    big = np.asarray(seeded.draw(1, "big", (512, 512), 0.02, jnp.float32))
    assert abs(big.std() / 0.02 - 1) < 0.02 and abs(big.mean()) < 2e-4
    bf = make_model(tmp_path, name="bf")
    bf.dtype = jnp.dtype("bfloat16")
    drawn = bf.device_params(jax.devices()[0])
    assert drawn["layer1"]["e_up"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(drawn["layer1"]["e_up"].astype(jnp.float32)),
                          ref.Model(ARCH, SEED, "bfloat16").layer(1)["e_up"])


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "reference", "decoder.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_decoder_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


# -- through the engine, and the two-kind ledger ---------------------------------------------------

def test_through_the_engine_both_cache_kinds_come_back_and_the_counters_move(tmp_path):
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
    assert eng.pages.rings == SLOTS + 1
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 96, n).tolist() for n in (19, 3, 24, 10, 7)]
    max_news = [12, 5, 7, 3, 12]

    async def go():
        await eng.start()
        futs = [eng.submit(model.host_decode(json.dumps(
            {"prompt_ids": p, "max_new_tokens": m, "logprobs": 8}).encode(), "application/json"))
            for p, m in zip(prompts, max_news)]
        out = await asyncio.gather(*futs)
        await eng.stop()
        return out

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    loop = asyncio.new_event_loop()
    try:
        results = loop.run_until_complete(go())
    finally:
        loop.close()
        jax.profiler.stop_trace()
    # the engine's loop is on the profiler's clock: every span, with its arguments
    import glob

    from jax.profiler import ProfileData
    seen: dict[str, set] = {}
    phases, iters = set(), {}
    for path in glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tpuserve.gen_"):
                        stats = dict(e.stats)
                        seen.setdefault(e.name, set()).update(stats)
                        phases.add(stats.get("phase"))
                        if "iter" in stats:
                            iters.setdefault(e.name, set()).add(int(stats["iter"]))
    assert set(seen) == {"tpuserve.gen_admit", "tpuserve.gen_prefill", "tpuserve.gen_step",
                         "tpuserve.gen_fetch", "tpuserve.gen_retire",
                         # ISSUE 36: the loop by phase, and the workers' spans it was blind to
                         "tpuserve.gen_loop", "tpuserve.gen_pack", "tpuserve.gen_extract",
                         "tpuserve.gen_finalize"}
    assert {"model", "slot", "start"} <= seen["tpuserve.gen_prefill"]
    assert {"model", "lanes"} <= seen["tpuserve.gen_step"]
    assert {"model", "slot", "dur_us", "ago_us"} <= seen["tpuserve.gen_admit"]
    assert {"model", "slot", "dur_us", "ago_us"} <= seen["tpuserve.gen_retire"]
    assert {"model", "phase", "iter", "dur_us", "ago_us"} <= seen["tpuserve.gen_loop"]
    for name in ("gen_pack", "gen_prefill", "gen_step", "gen_fetch", "gen_extract", "gen_finalize"):
        assert {"model", "iter"} <= seen["tpuserve." + name], name
    # every phase but the idle engine's wait was entered with work to do, and
    # a worker's span carries the number of a pass of the loop that the marks know
    assert phases - {None} >= {"sweep", "admit", "prefill", "step", "account", "emit", "retire"}
    assert phases - {None} <= set(LOOP_PHASES)
    for name, seen_iters in iters.items():
        assert seen_iters <= iters["tpuserve.gen_loop"] | {max(iters["tpuserve.gen_loop"]) + 1}, name
    # a fetch carries the pass of the STEP it waits for (ISSUE 41: a pass dispatches step k and
    # reads out(k-1)); the one step never fetched is the last, dispatched ahead and then dropped
    # unread because every slot had retired
    assert iters["tpuserve.gen_fetch"] <= iters["tpuserve.gen_step"]
    assert iters["tpuserve.gen_step"] - iters["tpuserve.gen_fetch"] <= {max(iters["tpuserve.gen_step"])}
    # five requests through three slots: pages and rings were handed out again
    assert eng.pages.n_reserved == 0 and eng.pages.n_reserved_rings == 0
    assert eng.pages.n_free_rings == SLOTS
    params = rt.params_per_mesh[0]
    by_hand, _, _ = serve(model, params, [np.asarray(p, np.int32) for p in prompts[:3]], max_news[:3])
    for got, want, n in zip(results, by_hand, max_news):
        assert got["tokens"] == want["tokens"][:n].tolist() and got["n_tokens"] == n
        np.testing.assert_allclose(got["logprobs"]["values"], want["lp"][:n], atol=1e-4)
    c = metrics.counter_values()
    assert c["gen_prefill_tokens_total{model=eng}"] == sum(map(len, prompts))
    assert c["gen_decode_tokens_total{model=eng}"] >= sum(m - 1 for m in max_news)
    k, sparse = ARCH["num_experts_per_tok"], 4
    routed = sum(v for name, v in c.items() if name.startswith("moe_tokens_routed_total"))
    assert routed == sparse * k * (sum(map(len, prompts)) + sum(m - 1 for m in max_news))
    assert c["moe_experts_hit_total{model=eng,phase=decode}"] \
        <= c["moe_expert_steps_total{model=eng,phase=decode}"]
    # every expert is held here: expert layers ran, none could leave a pick behind
    assert c["moe_layers_total{model=eng,phase=prefill}"] \
        == sparse * c["gen_prefill_chunks_total{model=eng}"]
    assert c["moe_layers_total{model=eng,phase=decode}"] * ARCH["num_experts"] \
        == c["moe_expert_steps_total{model=eng,phase=decode}"]
    assert not any(v for name, v in c.items() if name.startswith("moe_layers_compact_total"))
    assert c["gen_kv_ring_steps_total{model=eng}"] > 0 and c["gen_kv_page_steps_total{model=eng}"] > 0


def test_two_kind_ledger_never_double_hands_and_returns_all():
    from tpuserve.genserve import PageCorrupted, PageLedger

    rng = np.random.default_rng(12)
    led = PageLedger(40, 4, rings=7)
    held: dict[int, tuple[list[int], int]] = {}
    for _ in range(600):
        slot = int(rng.integers(0, 12))
        if slot in held:
            pages, ring = held.pop(slot)
            assert led.ring_of(slot) == ring
            assert led.release(slot) == pages and led.ring_of(slot) == PageLedger.SENTINEL
            continue
        count = int(rng.integers(1, 9))
        if not led.can_cover(count):
            with pytest.raises(IndexError):
                led.acquire(slot, count)
            assert not led.holds(slot)
            continue
        pages = led.acquire(slot, count)
        ring = led.ring_of(slot)
        assert 1 <= ring < 7 and all(1 <= p < 40 for p in pages)
        assert all(ring != r for _p, r in held.values())
        assert not set(pages) & {p for ps, _r in held.values() for p in ps}
        held[slot] = (pages, ring)
        assert led.n_reserved == sum(len(ps) for ps, _r in held.values())
        assert led.n_reserved_rings == len(held)
    led.release_all()
    assert led.n_free == 39 and led.n_free_rings == 6 and led.n_reserved_rings == 0
    led.acquire(0, 1)
    led._free_rings.append(led.ring_of(0))  # a tampered free list is caught at the next hand-out
    with pytest.raises(PageCorrupted):
        led.acquire(1, 1)
    with pytest.raises(ValueError):
        PageLedger(4, 4, rings=1)
    assert PageLedger(4, 4).ring_of(0) == 0 and PageLedger(4, 4).can_cover(3)


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
