"""The `hybrid_blk` family (ISSUE 68) against its plain reference
(`benchmark/reference/hybrid_blk.py`) at a small size on the CPU: packed, chunked
prefill and then decode through the pages, the pooled keys and the state a slot
equal the reference's ONE full pass, on prompts that cross `dense_len`, a block's
edge, a pooled window's edge and a launch's edge; the picks equal the
reference's `top_k` block for block; first and local blocks are always kept; a
slot reused after a longer request sees none of its pooled keys or state; the
linear-attention plain form against the recurrence token by token; the
program's tree against the configuration's `deployment_table`. Logits (served
log-probabilities) are compared, never sampled tokens."""

from __future__ import annotations

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind, PrefillPiece
from tpuserve.models import build, mixers
from tpuserve.models.paged_lm import LOGPROBS
from tpuserve.ops import block_scores as bsc

ref = spec.load_module("reference", "hybrid_blk")

# One period: attention over picked blocks, then three linear-attention layers.
# 4 query heads on 2 KV heads of 16 (groups of 2), 4 linear heads of 16; windows
# of 4 keys at stride 2, blocks of 8, 4 picks of which the first block and the 2
# local ones are forced, dense under 64: a prompt of 70+ has more than 4 blocks
# to choose from, so `dense_len`, the window and the picks all bite.
ARCH = {
    "model_type": "minicpm_sala", "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True, "attn_use_rope": False,
    "attn_use_output_gate": True, "use_output_gate": True, "use_output_norm": True, "qk_norm": True,
    "intermediate_size": 96, "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 100.0,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16, "scale_depth_layers": 32,
    "tie_word_embeddings": False,
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
                      "init_blocks": 1, "window_size": 16, "dense_len": 64},
    # 12 E[id] of unit RMS, a head whose logits have unit deviation after / 4
    "weight_scales": {"embed": 0.0833, "head": 4.0},
}
SEED = 23
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 104, 12, 8, 32, 3
FROZEN_PAGE = PAGE   # tests/test_genserve.py's frozen-lane case: pages are whole blocks of 8 keys
# Float32 against float32: served and reference differ by the order of their
# sums (key blocks under a running softmax, a chunked recurrence against a token
# at a time). The largest gap read over the sound cases is about 1e-5.
TOL = 1e-4


def make_model(tmp_path, arch=ARCH, name="hb", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="hybrid_blk", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def piece_of(model, prompts, max_news, slot, start, length, page=PAGE):
    pps = model.kv_plan(1, page).pages_per_slot
    ids = np.zeros((model.max_prompt,), np.int32)
    ids[: len(prompts[slot])] = prompts[slot]
    item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
            np.float32(0.0), np.int32(LOGPROBS))
    return PrefillPiece(slot, item, start, length,
                        np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32))


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, state=None,
          slots=SLOTS, steps=None, page=PAGE):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done. ``launches``: a list of
    launches, each a list of (slot, start, length); without it each prompt
    goes alone, a chunk a launch."""
    pps = model.kv_plan(1, page).pages_per_slot
    if state is None:
        state = zeros(model.kv_plan(slots, page).state)
    k = model.kv_prefill_pieces(chunk, page)
    prefill = jax.jit(model.prefill_chunk, static_argnames=("chunk",))
    step = jax.jit(model.step)
    if launches is None:
        launches = [[(slot, start, min(chunk, len(prompts[slot]) - start))]
                    for slot in range(len(prompts))
                    for start in range(0, len(prompts[slot]), chunk)]
    for pieces in launches:
        launch = model.pack_prefill(
            [piece_of(model, prompts, max_news, *p, page=page) for p in pieces], chunk, k)
        state = prefill(params, state, launch, chunk=chunk)
    out = None
    for _ in range(max(max_news) + 1 if steps is None else steps):
        state, out = step(params, state)
    return [jax.tree_util.tree_map(np.asarray, model.extract(params, state, np.int32(s)))
            for s in range(len(prompts))], out, state


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("hybrid_blk"))
    return model, model.init_params(jax.random.key(0))


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n) for n in lengths]


def worst(served, prompts, arch=ARCH) -> float:
    """The largest gap of served and reference log-probabilities at the ids
    the server named, teacher-forced on the served tokens, the reference in ONE
    full pass."""
    m = ref.Model(arch, SEED, "float32")
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = 0.0
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        out = max(out, float(np.abs(
            s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1)).max()))
    return out


# -- (a) the two programs against the reference's full pass ---------------------------------------------

CASES = {
    # 90 tokens, three launches of 32: the prefill crosses dense_len = 64 at a tile's
    # and a launch's edge, its decode of 12 crosses a block's edge (96) and pooled
    # windows' edges; 20 and 5 stay dense
    "a-chunk-a-launch": (prompts_of(90, 5, 20), [12, 6, 3], None, PAGE),
    # pieces of several slots in one launch (tiles of 8 rows), a prompt cut inside a
    # pooled window (a piece of 13 ends at 45: the window 44..47 closes a launch later),
    # a padded tail, the picked rows in a launch of their own
    "packed": (prompts_of(78, 9, seed=1), [10, 4],
               [[(0, 0, 32)], [(0, 32, 13), (1, 0, 9)], [(0, 45, 24)], [(0, 69, 9)]], PAGE),
    # decode alone crosses dense_len: a prompt of 60 (dense) and 12 steps, of which
    # the last 8 pick their blocks
    "decode-crosses-dense-len": (prompts_of(60, seed=2), [12], None, PAGE),
    # two blocks a page
    "pages-of-two-blocks": (prompts_of(88, 17, seed=3), [9, 5], None, 2 * PAGE),
    # a prompt of whole launches and whole blocks, the longest served
    "whole-launches": (prompts_of(96, seed=4), [8], None, PAGE),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_prefill_then_decode_is_the_reference_full_pass(whole, case):
    model, params = whole
    prompts, max_news, launches, page = CASES[case]
    served, _out, _ = serve(model, params, prompts, max_news, launches=launches, page=page)
    assert [int(s["n_new"]) for s in served] == max_news
    assert worst(served, prompts) < TOL


def test_prefill_then_decode_with_the_launches_block_scores_in_the_kernel(tmp_path, monkeypatch):
    """ISSUE 69: every picked tile's block scores through `ops/block_scores.py` (in
    the Pallas interpreter; the toy's heads of 16 are steered past `supported`),
    pages of two blocks so that a tile is 16 rows: the reference's full pass
    still, and the launches count themselves under `path=kernel`, the steps under
    `path=xla`."""
    traced, real = [], bsc.block_scores

    def in_the_interpreter(*a, **kw):
        traced.append(a[0].shape)
        return real(*a, **kw, interpret=True)

    monkeypatch.setattr(bsc, "block_scores", in_the_interpreter)
    monkeypatch.setattr(mixers.BlockSelectAttention, "_select_path",
                        lambda self, T, pps, P: "kernel")
    model = make_model(str(tmp_path))
    params = model.init_params(jax.random.key(0))
    prompts, max_news = prompts_of(88, 17, seed=3), [9, 5]
    served, out, _ = serve(model, params, prompts, max_news, page=2 * PAGE)
    assert traced == [(16, 4, 16)]                       # one trace, the launch's tile
    assert worst(served, prompts) < TOL
    names = [c.counter(model, _Names(), "PH") for c in model.COLUMNS]
    acc = np.asarray(out["acc"])
    kernel, xla = (names.index(f"blk_selects_total{{model=hb,phase=PH,path={p}}}")
                   for p in ("kernel", "xla"))
    # the launch at 64..87 has picked rows, the two before it and the short prompt's none
    assert acc[0, kernel] == 1 and acc[0, xla] == 0
    # the long prompt's lane decodes past dense_len: 9 tokens, the first the launch's own
    assert acc[1, kernel] == 0 and acc[1, xla] == 8


class _Names:
    """A registry that answers a counter's name."""
    counter = staticmethod(lambda name: name)


def test_the_picked_rows_really_drop_keys(whole):
    """The same prompt with `topk` raised to every block reads differently: the
    cases above are not dense attention under another name."""
    model, params = whole
    prompts, max_news = prompts_of(90, seed=5), [4]
    served, _, _ = serve(model, params, prompts, max_news)
    dense = dict(ARCH, sparse_config=dict(ARCH["sparse_config"], dense_len=4096, topk=512,
                                          window_size=4096))
    assert worst(served, prompts) < TOL < 100 * TOL < worst(served, prompts, dense)


def test_a_reused_slot_sees_none_of_the_longer_tenants_pooled_keys_or_state(whole):
    model, params = whole
    long, short = prompts_of(90, seed=6), prompts_of(70, seed=7)
    _, _, state = serve(model, params, long, [12], slots=1)
    assert all(float(jnp.abs(s).max()) > 0 for s in state["ssm"])
    assert float(jnp.abs(state["kc"][0]).max()) > 0
    state2, _ = jax.jit(model.step)(params, state)          # the lane is done: not live
    for leaf in ("ssm", "kc", "kf", "vf"):
        for b, a in zip(state[leaf], state2[leaf]):
            assert np.array_equal(np.asarray(b), np.asarray(a)), leaf
    again, _, _ = serve(model, params, short, [6], state=state2, slots=1)
    fresh, _, _ = serve(model, params, short, [6], slots=1)
    np.testing.assert_array_equal(again[0]["lp"][:6], fresh[0]["lp"][:6])
    assert worst(again, short) < TOL


# -- (b) the picks ---------------------------------------------------------------------------------------

def _pooled_state(model, k):
    """k (S, KV, hd) in pages of PAGE behind the identity block table, and the
    pooled keys the program's own writes leave."""
    S, kv, hd = k.shape
    pps = -(-S // PAGE)
    kp = jnp.zeros(model._page_shape(pps + 1, PAGE), jnp.float32)
    bt = jnp.arange(1, pps + 1)[None, :]
    at = jnp.arange(S)
    kp = model._write_pages(kp, bt[0][at // PAGE], at % PAGE, jnp.asarray(k))
    kc = jnp.zeros(model._pooled_shape(pps + 1, PAGE), jnp.float32)
    kc = model._pool_write(kc, kp, jnp.repeat(bt, S, axis=0), at, model._windows_done(at))
    return kp, kc, bt


def test_the_picks_are_the_references_top_k_block_for_block(whole):
    model, _ = whole
    rng = np.random.default_rng(8)
    S, kv, hd, H = 104, 2, 16, 4
    k = rng.standard_normal((S, kv, hd)).astype(np.float32) * 2
    q = rng.standard_normal((S, H, hd)).astype(np.float32) * 2
    kp, kc, bt = _pooled_state(model, k)
    sp = ref.mixer_dims(ref.Model(ARCH, SEED, "float32"), "minicpm4")[8]
    pos = np.arange(64, S)
    want = np.asarray(ref.select_blocks(jnp.asarray(q[pos]), jnp.asarray(k), jnp.asarray(pos), sp,
                                        hd ** -0.5))                        # (R, KV, 13)
    score = np.asarray(ref.block_scores(jnp.asarray(q[pos]), jnp.asarray(k), jnp.asarray(pos), sp,
                                        hd ** -0.5))
    # no NEAR ties; exact ones abound (two neighbours whose shared window is the
    # largest of both), and there the lower index wins on both sides
    gaps = np.diff(np.sort(np.where(np.isfinite(score), score, np.nan), axis=-1), axis=-1)
    assert (gaps == 0).sum() > 20 and np.nanmin(np.where(gaps == 0, np.nan, gaps)) > 1e-6
    nb = want.shape[-1]
    got = np.asarray(model._tile_keep(jnp.asarray(q[pos]), kc, bt[0], jnp.asarray(pos), nb, PAGE))
    np.testing.assert_array_equal(got.transpose(1, 0, 2), want)
    # every picked row keeps exactly topk blocks: the first, its own and the one before
    own = pos // 8
    assert (want.sum(-1) == 4).all() and want[:, :, 0].all()
    for r, b in enumerate(own):
        assert want[r, :, b].all() and want[r, :, b - 1].all() and not want[r, :, b + 1:].any()
    # a step's picks, lane by lane, are the same sets
    lanes = np.asarray([70, 88, 103])
    o = model._decode_picked(jnp.asarray(q[lanes]), kp, kp, kc, jnp.repeat(bt, 3, axis=0),
                             jnp.asarray(lanes))
    sets = want[lanes - 64]                                                 # (3, KV, nb)
    kh = np.repeat(k, H // kv, axis=1)
    for n, t in enumerate(lanes):
        for h in range(H):
            see = sets[n, h // 2][np.arange(S) // 8] & (np.arange(S) <= t)
            s = np.where(see, kh[:, h] @ q[t, h] * hd ** -0.5, -np.inf)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(np.asarray(o[n, h]), (p / p.sum()) @ kh[:, h], atol=2e-5)


def test_a_row_under_dense_len_keeps_every_block_and_a_dense_lane_reads_every_key(whole):
    model, _ = whole
    rng = np.random.default_rng(9)
    k = rng.standard_normal((72, 2, 16)).astype(np.float32)
    q = rng.standard_normal((72, 4, 16)).astype(np.float32)
    kp, kc, bt = _pooled_state(model, k)
    pos = jnp.asarray([10, 63, 64])
    keep = np.asarray(model._tile_keep(jnp.asarray(q[np.asarray(pos)]), kc, bt[0], pos, 9, PAGE))
    assert keep[:, :2].all() and (keep[:, 2].sum(-1) == 4).all()
    o = model._decode_blocks(jnp.asarray(q[np.asarray(pos)]), kp, kp, kc, jnp.repeat(bt, 3, axis=0),
                             pos, jnp.asarray([True, True, False]))
    kh = np.repeat(k, 2, axis=1)
    for n, t in enumerate((10, 63)):
        s = np.einsum("shd,hd->hs", kh[: t + 1], q[t]) * 0.25
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), kh[: t + 1])
        np.testing.assert_allclose(np.asarray(o[n]), want, atol=2e-5)


# -- (c) the linear-attention mixer -----------------------------------------------------------------------

def _recurrence(model, q, k, v, live, s0):
    lam = np.exp(model.l_log_decay)[:, None, None]
    S, out = s0.copy(), []
    for t in range(q.shape[0]):
        if live[t]:
            S = lam * S + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hi,hij->hj", q[t], S))
    return np.stack(out), S


@pytest.mark.parametrize("tile,sub", [(8, 128), (16, 4), (32, 8)])
def test_the_chunked_plain_form_is_the_recurrence_token_by_token(whole, monkeypatch, tile, sub):
    """Two pieces in one launch (one carried from a stored state, one from
    zeros), padded rows leaving the state alone, sub-tiles inside a tile."""
    model, _ = whole
    monkeypatch.setattr(mixers.LightningMixer, "SUB", sub)
    rng = np.random.default_rng(10)
    K, H, D = 4, 4, 16
    C = K * tile
    lengths = [2 * tile - 3, tile - 1]                       # tiles 0-1 and 2; tile 3 is nobody's
    launch = {"slot": jnp.asarray([1, 0, 0, 0]), "start": jnp.asarray([tile, 0, 0, 0]),
              "length": jnp.asarray(lengths + [0, 0]), "pages": jnp.zeros((K, 2), jnp.int32)}
    t = model._tiles(launch, C)
    q, k, v = (rng.standard_normal((C, H, D)).astype(np.float32) for _ in range(3))
    ssm = rng.standard_normal((3, H, D, D)).astype(np.float32)
    (s0,), _ = model._piece_starts(launch["slot"], launch["start"], states=(jnp.asarray(ssm),))
    o, s_end = model._lightning_tiles(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), t, s0)
    o, s_end, live = np.asarray(o), np.asarray(s_end), np.asarray(t["valid"])
    want0, end0 = _recurrence(model, q[: 2 * tile], k[: 2 * tile], v[: 2 * tile], live[: 2 * tile],
                              ssm[1])
    want1, end1 = _recurrence(model, q[2 * tile: 3 * tile], k[2 * tile: 3 * tile],
                              v[2 * tile: 3 * tile], live[2 * tile: 3 * tile], np.zeros_like(ssm[0]))
    scale = 1e-4 * max(1.0, float(np.abs(want0).max()))
    np.testing.assert_allclose(o[: lengths[0]], want0[: lengths[0]], atol=scale)
    np.testing.assert_allclose(o[2 * tile: 2 * tile + lengths[1]], want1[: lengths[1]], atol=scale)
    np.testing.assert_allclose(s_end[0], end0, atol=scale)
    np.testing.assert_allclose(s_end[1], end1, atol=scale)


def test_a_step_is_one_application_and_a_lane_that_is_not_live_keeps_its_state(whole):
    model, params = whole
    rng = np.random.default_rng(11)
    lp = params["layer1"]
    u = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    ssm = jnp.asarray(rng.standard_normal((3, 4, 16, 16)), jnp.float32)
    live, pos = jnp.asarray([True, False, True]), jnp.asarray([5, 9, 70])
    _out, new = model._lightning_step(lp, u, live, pos, ssm)
    q, k, v = (np.asarray(x, np.float32) for x in model._lightning_qkv(lp, u, pos))
    lam = np.exp(model.l_log_decay)[:, None, None]
    for lane in (0, 2):
        np.testing.assert_allclose(np.asarray(new[lane]), lam * np.asarray(ssm[lane])
                                   + k[lane][:, :, None] * v[lane][:, None, :], rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(new[1]), np.asarray(ssm[1]))
    np.testing.assert_allclose(np.exp(model.l_log_decay),
                               np.exp(-2.0 ** (-8.0 * np.arange(1, 5) / 4)), rtol=1e-6)


# -- (d) the leaves, the keys, the published sizes --------------------------------------------------------

def test_the_family_keeps_three_page_leaves_and_one_state_leaf(whole):
    model, _ = whole
    plan = model.kv_plan(SLOTS, PAGE, 20)
    sig = plan.state
    assert plan.leaves(LeafKind.SLOT) == ("ssm",)
    assert plan.leaves(LeafKind.POOL) == model._leaves(LeafKind.POOL) == ("kf", "vf", "kc")
    assert sorted(model._leaves()) == sorted(("kf", "vf", "kc", "ssm"))
    assert "conv" not in sig
    assert [s.shape for s in sig["ssm"]] == [(SLOTS, 4, 16, 16)] * 3
    assert [s.shape for s in sig["kf"]] == [(2, 20, PAGE, 16)] == [s.shape for s in sig["vf"]]
    assert [s.shape for s in sig["kc"]] == [(20 * PAGE // 2, 2 * 16)]      # four rows a page
    assert tuple(mixers.PatternMixers._state_signature(
        SimpleNamespace(mh=1, mp=1, mn=1, conv_k=2, conv_ch=1, dtype=jnp.float32, m_layers=[0]),
        1)) == ("ssm", "conv")
    with pytest.raises(ValueError, match="whole number of blocks"):
        model.kv_plan(SLOTS, 4, 20).state


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("lightning_nkv", 2),
    ("share", {"vocab_rows": [0, 48]}),
    ("mixer_types", ["minicpm4", "lightning-attn", "mamba", "lightning-attn"]),
    ("sparse_config", {**ARCH["sparse_config"], "block_size": 7}),
    ("sparse_config", {**ARCH["sparse_config"], "topk": 2})])
def test_a_key_the_family_does_not_serve_is_refused(tmp_path, key, value):
    with pytest.raises(NotImplementedError):
        make_model(str(tmp_path), dict(ARCH, **{key: value}))


@pytest.mark.parametrize("flags", [
    {"attn_use_rope": True}, {"lightning_use_rope": False}, {"qk_norm": False},
    {"use_output_norm": False, "use_output_gate": False, "attn_use_output_gate": False},
    {"tie_word_embeddings": True, "weight_scales": {"embed": 0.0833}}])
def test_the_published_flags_the_other_way_are_the_references_too(tmp_path, flags):
    arch = dict(ARCH, **flags)
    model = make_model(str(tmp_path), arch)
    prompts = prompts_of(75, seed=12)
    served, _, _ = serve(model, model.init_params(jax.random.key(0)), prompts, [5], slots=1)
    assert worst(served, prompts, arch) < TOL


def test_the_programs_tree_is_the_configurations_deployment_table():
    """Parameter for parameter, without drawing one: the published widths
    through the program's own shapes against the file's table."""
    cfg = spec.load_json("configs", "minicpm-sala-l4.json")
    arch = ref.arch_from_config(cfg)
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        model = make_model(tmp, arch, dtype="bfloat16")
    tree = jax.eval_shape(lambda: model.draw_params(0))
    by_layer = {name: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(sub))
                for name, sub in tree.items()}
    table = cfg["deployment_table"]
    assert by_layer["layer0"] == table["minicpm4_layer"] == 253_763_840
    for i in (1, 2, 3):
        assert by_layer[f"layer{i}"] == table["lightning_layer"] == 285_221_248
    assert by_layer["embed"] + by_layer["head"] + by_layer["norm_f"] == table["embedding_head_gain"]
    assert sum(by_layer.values()) == table["held"] == 1_711_117_696
    # the whole model: 8 and 24 of the two kinds
    kinds = cfg["published"]["mixer_types"]
    assert kinds.count("minicpm4") * table["minicpm4_layer"] \
        + kinds.count("lightning-attn") * table["lightning_layer"] \
        + table["embedding_head_gain"] == table["model"]
    assert model.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and model.logits_scaling == 16.0
    sig = model.kv_plan(16, 64, 16624).state
    assert sig["ssm"][0].shape == (16, 32, 128, 128) and sig["kc"][0].shape == (16624 * 4, 256)
