"""The `mla` family (ISSUE 34) against its plain reference at a small size on
the CPU: packed, chunked prefill and decode through pages of latent rows
equal the reference's one causal pass in the expanded form; the absorbed and
the expanded form agree on the same cache; a cache kept in a lower precision
or a dropped rotary part fails the written tolerance; slots, free lanes and
the sentinel page; what the page signature holds; the counters."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import mla_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind, PrefillPiece
from tpuserve.models import build, paged_lm
from tpuserve.models import mla

ARCH = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 64,
    "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 32000000, "rope_interleave": True,
    "rope_scaling": None, "first_k_dense_replace": 1, "intermediate_size": 96,
    "moe_intermediate_size": 24, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "hidden_act": "silu", "moe_layer_freq": 1, "num_nextn_predict_layers": 1,
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3
R, ROPE = ARCH["kv_lora_rank"], ARCH["qk_rope_head_dim"]
ROW = R + ROPE


def make_model(tmp_path, arch=ARCH, name="la", dtype="float32", tile_rows=PAGE, **options):
    """``tile_rows``: the family's tiles are a key block wide at the published
    sizes; a toy launch of 8 rows is steered to tiles of one page here, in
    the test and not through an option of the program."""
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="mla", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    model = build(cfg)
    model.TILE_ROWS = tile_rows
    return model


def zeros(struct, cache_dtype=None):
    block = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)
    if cache_dtype is not None:  # the program stores what the block holds
        for leaf in ("ckv", "kr"):
            block[leaf] = [c.astype(cache_dtype) for c in block[leaf]]
    return block


def piece_of(model, prompts, max_news, page, slot, start, length):
    pps = model.kv_plan(1, page).pages_per_slot
    ids = np.zeros((model.max_prompt,), np.int32)
    ids[: len(prompts[slot])] = prompts[slot]
    item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
            np.float32(0.0), np.int32(mla.LOGPROBS))
    return PrefillPiece(slot, item, start, length,
                        np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32))


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, state=None,
          slots=SLOTS, cache_dtype=None, steps=None, page=PAGE):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program, then steps until every lane is done."""
    pps = model.kv_plan(1, page).pages_per_slot
    if state is None:
        state = zeros(model.kv_plan(slots, page).state, cache_dtype)
    k = model.kv_prefill_pieces(chunk, page)
    prefill = jax.jit(model.prefill_chunk, static_argnames=("chunk",))
    step = jax.jit(model.step)
    if launches is None:
        launches = [[(slot, start, min(chunk, len(prompts[slot]) - start))]
                    for slot in range(len(prompts))
                    for start in range(0, len(prompts[slot]), chunk)]
    for pieces in launches:
        launch = model.pack_prefill(
            [piece_of(model, prompts, max_news, page, *p) for p in pieces], chunk, k)
        state = prefill(params, state, launch, chunk=chunk)
    out = None
    for _ in range(max(max_news) + 1 if steps is None else steps):
        state, out = step(params, state)
    return [jax.tree_util.tree_map(np.asarray, model.extract(params, state, np.int32(s)))
            for s in range(len(prompts))], out, state


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("mla"))
    return model, model.init_params(jax.random.key(0))


PROMPTS = [np.random.default_rng(0).integers(0, 96, n) for n in (19, 5, 11)]
MAX_NEWS = [6, 12, 3]
# Pieces of several slots and sizes in one launch, a prompt over four launches
# (a later launch attends to latents an earlier one cached), padded tails.
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)]]


def gaps(arch, prompts, served, dtype="float32", centred=False):
    """Per request: served minus reference log-probabilities at the ids the
    server named, teacher-forced on the served tokens."""
    m = ref.Model(arch, SEED, dtype)
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = []
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        g = s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1)
        out.append(g - g.mean(axis=-1, keepdims=True) if centred else g)
    return out


# -- (a) the served function is the reference's one causal pass -----------------------------

@pytest.mark.parametrize("case", ["absorbed-tiles-of-a-page", "expanded-tiles-of-64",
                                  "several-key-blocks", "rope-8-a-position-a-row"])
def test_packed_chunked_prefill_then_decode_is_the_reference_one_causal_pass(
        tmp_path, monkeypatch, case):
    """Logits, not tokens. The first case's prefill tiles (4 rows) take the
    absorbed form, the second's (64 rows: past the toy's break-even of 32) the
    expanded one; decode is absorbed in both. The third walks key blocks of
    two pages, so a tile's running softmax passes over several. The toy's
    rotary keys (64 wide) lie two positions to a row of 128 lanes, as at the
    published sizes; the fourth case's (8 wide, 16 to a row: more than a page
    of 4 holds) one position a row."""
    arch = dict(ARCH, qk_rope_head_dim=8) if case == "rope-8-a-position-a-row" else ARCH
    if case == "expanded-tiles-of-64":
        model = make_model(tmp_path, name="wide", tile_rows=64, max_prompt_tokens=320)
        assert model._form(64) == "expanded" and model._form(1) == "absorbed"
        prompts = [np.random.default_rng(1).integers(0, 96, n) for n in (300, 70, 150)]
        news, chunk = [5, 12, 3], 128
        packed = [[(0, 0, 64), (1, 0, 64)], [(0, 64, 128)], [(1, 64, 6), (0, 192, 64)],
                  [(0, 256, 44), (2, 0, 64)], [(2, 64, 86)]]
    else:
        if case == "several-key-blocks":
            monkeypatch.setattr(paged_lm, "KEY_BLOCK", 2 * PAGE)
        model = make_model(tmp_path, arch, name="narrow")
        assert model._form(PAGE) == "absorbed"
        prompts, news, chunk, packed = PROMPTS, MAX_NEWS, CHUNK, PACKED
    assert model.kv_prefill_pieces(chunk, PAGE) == 2
    kr = model.kv_plan(SLOTS, PAGE, 9).state["kr"][0]
    assert kr.shape == ((9, 4, 8) if case == "rope-8-a-position-a-row" else (9, 2, 128))
    params = model.init_params(jax.random.key(0))
    served, out, _ = serve(model, params, prompts, news, chunk=chunk, launches=packed)
    assert bool(np.all(np.asarray(out["done"])))
    assert [int(s["n_new"]) for s in served] == news
    for g in gaps(arch, prompts, served):
        assert float(np.abs(g).max()) < 5e-5
    # a prompt a launch at a time gives the same tokens as the packed launches
    alone, _, _ = serve(model, params, prompts, news, chunk=chunk)
    for a, b in zip(served, alone):
        assert np.array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["lp"], b["lp"], atol=5e-5)
    # the device's sums: every prompt token once at its own position; a
    # piece's whole context once; the launches by form
    acc = np.asarray(out["acc"]).astype(np.int64)
    lengths = [len(p) for p in prompts]
    assert acc[0, 0] == 2 * ARCH["num_experts_per_tok"] * sum(lengths) and acc[0, 1] == 0
    assert acc[0, 4] == sum(n * (n + 1) // 2 for n in lengths)
    assert acc[0, 5] == sum(start + n for launch in packed for _s, start, n in launch)
    col = 8 if case == "expanded-tiles-of-64" else 7
    assert acc[0, col] == len(packed) and acc[0, 15 - col] == 0
    assert acc[1, 7] == max(news) + 1 and acc[1, 8] == 0      # every step is absorbed
    assert acc[1, 5] == acc[1, 4] > 0 and acc[1, 6] >= acc[1, 5]


# -- (b) two forms of one function ------------------------------------------------------------

def test_the_absorbed_and_the_expanded_form_agree_on_the_same_cache(whole, monkeypatch):
    monkeypatch.setattr(paged_lm, "KEY_BLOCK", 2 * PAGE)   # three key blocks
    model, params = whole
    lp = params["layer1"]
    rng = np.random.default_rng(3)
    n, T = 22, 8
    u = jnp.asarray(rng.standard_normal((n, 64)), jnp.float32)
    qn, qr, c_kv, k_r = model._project(lp, u, jnp.arange(n))
    pps = model.kv_plan(1, PAGE).pages_per_slot
    pages = jnp.arange(1, 1 + pps)
    at = (pages[jnp.arange(n) // PAGE], jnp.arange(n) % PAGE)
    pool = (model._write_pages(jnp.zeros((1 + pps, PAGE, R), jnp.float32), *at, c_kv),
            model._write_keys(jnp.zeros((1 + pps, PAGE // 2, 2 * ROPE), jnp.float32), *at, k_r,
                              runs=True))
    # a step's write of one position a lane lands where the launch's run of two put it
    again = model._write_keys(jnp.zeros_like(pool[1]), at[0][5:6], at[1][5:6], k_r[5:6], runs=False)
    np.testing.assert_array_equal(np.asarray(again[2, 0, ROPE:]), np.asarray(pool[1][2, 0, ROPE:]))
    assert float(jnp.abs(again).sum()) == float(jnp.abs(k_r[5]).sum())
    qpos = jnp.arange(n - T, n)
    outs = [model._attend_tile(lp, qn[-T:], qr[-T:], pool, pages, qpos, jnp.int32(n - 1), form)
            for form in mla.FORMS]
    assert outs[0].shape == (T, 4, 16)
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)
    # and both are plain causal attention over keys and values made from the latents
    k = jnp.einsum("cr,rhn->chn", c_kv, lp["w_kb"])
    v = jnp.einsum("cr,rhv->chv", c_kv, lp["w_vb"])
    s = (jnp.einsum("thn,chn->htc", qn[-T:], k) + jnp.einsum("thr,cr->htc", qr[-T:], k_r)) / 80 ** 0.5
    s = jnp.where((jnp.arange(n)[None, :] <= qpos[:, None])[None], s, -jnp.inf)
    want = jnp.einsum("htc,chv->thv", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(outs[1], want, atol=2e-5)


def walk_case(h, t, page, kb, pos0, live, blocks, dtype=jnp.float32, widths=(32, 16, 16, 8),
              seed=0):
    """The kernel's arguments for one tile of ``t`` rows at position ``pos0``
    whose first ``live`` rows are a prompt's (none: a tile of no piece, whose
    last live position ``_tiles`` gives as 0), over pools of pages of ``page``
    positions (two positions' rotary keys a row) and a shuffled block-table row
    of ``blocks`` key blocks of ``kb`` pages, and the plain float32 attention
    of the rows over the keys and values made from the same latents."""
    r, dn, dv, dr = widths
    rng = np.random.default_rng(seed)
    pages = blocks * kb + 2
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    qn, qr = draw(h, t, dn), draw(h, t, dr)
    ckv, kr = draw(pages, page, r), draw(pages, page // 2, 2 * dr)
    w_kb, w_vb = draw(h, r, dn) / r ** 0.5, draw(h, r, dv) / r ** 0.5
    rows = jnp.asarray(rng.permutation(pages - 1)[: blocks * kb] + 1, jnp.int32)
    last = pos0 + live - 1 if live else 0
    need = last // (kb * page) + 1
    args = (jnp.concatenate([qn, qr, qr], axis=-1), jnp.concatenate([w_kb, w_vb], axis=-1),
            ckv, kr, rows, jnp.int32(need), jnp.int32(pos0))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    n = last + 1 if live else kb * page   # a tile of no piece sees the block it walks
    c_kv = f32(ckv[rows]).reshape(-1, r)[:n]
    k_r = f32(kr[rows]).reshape(-1, dr)[:n]
    rounded = lambda a: f32(a.astype(dtype))  # noqa: E731  (the expansion is kept as served)
    k = rounded(jnp.einsum("cr,hrn->hcn", c_kv, f32(w_kb)))
    v = rounded(jnp.einsum("cr,hrv->hcv", c_kv, f32(w_vb)))
    scale = (dn + dr) ** -0.5
    s = (jnp.einsum("htn,hcn->htc", f32(qn), k) + jnp.einsum("htd,cd->htc", f32(qr), k_r)) * scale
    see = jnp.arange(n)[None, :] <= (pos0 + jnp.arange(t))[:, None]
    want = jnp.einsum("htc,hcv->htv", jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), -1), v)
    return args, {"block_pages": kb, "scale": scale}, np.asarray(want[:, :live or t])


# (heads, tile rows, page, pages a key block, first position, live rows, blocks of the row)
WALKS = {
    "one-block-needed-of-four": (2, 64, 16, 4, 0, 64, 4),
    "a-tile-in-its-prompts-first-block": (2, 32, 16, 4, 32, 32, 3),
    "four-blocks-ending-on-the-diagonal-block": (2, 64, 16, 4, 192, 64, 5),
    "the-last-live-row-mid-page": (2, 64, 16, 4, 128, 21, 4),
    "a-tile-of-no-piece": (2, 64, 16, 4, 448, 0, 3),
    "32-heads-of-1024-rows": (32, 1024, 128, 8, 1024, 1024, 3),
    "64-heads-of-256-rows": (64, 256, 128, 2, 512, 256, 4),
    "bfloat16": (4, 128, 64, 2, 256, 128, 4),
}


@pytest.mark.parametrize("case", list(WALKS))
def test_the_kernels_walk_is_causal_attention_over_the_pools_as_they_lie(case, monkeypatch):
    """``ops/tile_attention.py`` in the Pallas interpreter against plain float32
    attention over keys and values made from the same latents: the running
    softmax over the blocks a tile needs and no further, the diagonal inside a
    block and between sub-blocks (steered to 32 x 32 at the toy tiles), the
    rotary keys two positions a row, the cells' two shapes at toy widths. A
    tile of no piece (``has`` false: its last live position reads 0) walks one
    block of whatever its row names, all of it seen. In float32 the
    kernel rounds nothing, so it stands within float32's sums in another
    order; in bfloat16 its probabilities round into the second product, a few
    thousandths, as the walk in XLA does."""
    from tpuserve.ops import tile_attention as ta

    h, t, page, kb, pos0, live, blocks = WALKS[case]
    if t < 256:
        monkeypatch.setattr(ta, "BLOCK_Q", 32)
        monkeypatch.setattr(ta, "BLOCK_K", 32)
    bf = case == "bfloat16"
    args, kw, want = walk_case(h, t, page, kb, pos0, live, blocks,
                               jnp.bfloat16 if bf else jnp.float32,
                               (128, 128, 128, 64) if bf else (32, 16, 16, 8))
    got = ta.tile_walk(*args, **kw, interpret=True)
    assert got.shape == (t, h, want.shape[2]) and got.dtype == args[0].dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))   # the rows past the prompt too
    got = np.asarray(got.astype(jnp.float32)).transpose(1, 0, 2)
    np.testing.assert_allclose(got[:, :want.shape[1]], want, atol=6e-3 if bf else 2e-5)
    assert ta.fits(1024, 128, 8, 512, 128, 128, 128, jnp.bfloat16) \
        and ta.fits(256, 128, 2, 512, 128, 128, 128, jnp.bfloat16) \
        and not ta.fits(1024, 128, 8, 512, 128, 128, 128, jnp.float32) \
        and not ta.fits(4, 4, 2, 32, 16, 16, 128, jnp.bfloat16)


def interpret_the_kernel(monkeypatch):
    """The kernel runs in the Pallas interpreter -> a list that grows by one
    each time a call of it is traced."""
    import functools

    from tpuserve.ops import tile_attention as ta

    calls = []
    monkeypatch.setattr(ta, "tile_walk", functools.partial(
        lambda *a, f=ta.tile_walk, **k: calls.append(1) or f(*a, interpret=True, **k)))
    return calls


def steer_to_the_kernel(monkeypatch):
    """The family's trace-time choice, steered here and not by an option:
    every expanded tile walks in the kernel, interpreted (the backend's name
    would steer the experts' kernels too)."""
    monkeypatch.setattr(mla.LatentServing, "_walk", lambda self, form, *a: (
        "kernel" if form == "expanded" else "xla"))
    return interpret_the_kernel(monkeypatch)


def test_on_the_tpu_an_expanded_tile_walks_in_the_kernel_and_is_the_same_attention(
        tmp_path, monkeypatch):
    """With the backend named `tpu` and the kernel run in the interpreter, an
    expanded tile over three key blocks is ONE kernel call, and what the
    einsum walk gives on the CPU, to bfloat16's rounding."""
    arch = dict(ARCH, num_attention_heads=2, kv_lora_rank=128, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128)
    model = make_model(tmp_path, arch, name="kern", dtype="bfloat16", max_prompt_tokens=384,
                       max_new_tokens=0)
    lp = model.init_params(jax.random.key(0))["layer1"]
    page, n, T = 128, 384, 128
    u = jnp.asarray(np.random.default_rng(2).standard_normal((n, 64)), jnp.bfloat16)
    qn, qr, c_kv, k_r = model._project(lp, u, jnp.arange(n))
    pages = jnp.arange(1, 4)
    at = (pages[jnp.arange(n) // page], jnp.arange(n) % page)
    pools = (model._write_pages(jnp.zeros((4, page, 128), jnp.bfloat16), *at, c_kv),
             model._write_keys(jnp.zeros((4, page // 2, 128), jnp.bfloat16), *at, k_r, runs=True))
    monkeypatch.setattr(paged_lm, "KEY_BLOCK", page)
    args = (lp, qn[-T:], qr[-T:], pools, pages, jnp.arange(n - T, n), jnp.int32(n - 1), "expanded")
    assert model._walk("expanded", T, pools, 3) == "xla"
    plain = model._attend_tile(*args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls = interpret_the_kernel(monkeypatch)
    assert model._walk("expanded", T, pools, 3) == "kernel" \
        and model._walk("absorbed", T, pools, 3) == "xla"
    kernel = model._attend_tile(*args)
    assert len(calls) == 1 and kernel.shape == plain.shape == (T, 2, 128)
    np.testing.assert_allclose(kernel.astype(jnp.float32), plain, atol=2e-2)


def test_a_launchs_tiles_walk_different_block_table_rows_in_the_kernel(tmp_path, monkeypatch):
    """A launch of four tiles of 64 rows: two pieces of two prompts and a tile
    of no piece side by side, each tile over its own prompt's pages (a
    later piece over what an earlier launch cached), in float32: the kernel's
    walk (interpreted) and the einsum walk give the same state and the same
    served log-probabilities, and the device's sums say which walk ran."""
    model = make_model(tmp_path, name="tiles", tile_rows=64, max_prompt_tokens=320)
    monkeypatch.setattr(paged_lm, "KEY_BLOCK", 16 * PAGE)   # key blocks of 64 positions
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(1).integers(0, 96, n) for n in (300, 70, 150)]
    news, chunk = [3, 4, 2], 256
    packed = [[(0, 0, 128), (1, 0, 64)], [(0, 128, 128), (1, 64, 6), (2, 0, 64)],
              [(2, 64, 86), (0, 256, 44)]]
    assert model.kv_prefill_pieces(chunk, PAGE) == 4
    plain, out, _ = serve(model, params, prompts, news, chunk=chunk, launches=packed)
    acc = np.asarray(out["acc"]).astype(np.int64)
    assert acc[0, 10] == 0 and acc[0, 11] == 2 + 1 + 2 + 1 + 1 + 2 + 1   # tiles of a piece
    assert acc[1, 10] == 0 and acc[1, 11] == sum(n - 1 for n in news)     # live lanes a step
    calls = steer_to_the_kernel(monkeypatch)
    kernel, out, _ = serve(model, params, prompts, news, chunk=chunk, launches=packed)
    assert len(calls) == 3 * 4   # traced once: a call a tile of a layer, side by side
    acc = np.asarray(out["acc"]).astype(np.int64)
    assert acc[0, 10] == 10 and acc[0, 11] == 0 and acc[1, 10] == 0
    for a, b in zip(kernel, plain):
        assert np.array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["lp"], b["lp"], atol=5e-5)
    for g in gaps(ARCH, prompts, kernel):
        assert float(np.abs(g).max()) < 5e-5


# -- (b2) a step's walk: every lane's own key blocks in one kernel call (ISSUE 44) -----------------

def lane_case(h, page, kb, pps, lasts, live, dtype=jnp.float32, widths=(32, 8), seed=0):
    """The decode kernel's arguments for lanes whose last positions are
    ``lasts`` (``live`` false: a lane that is not live, which the step gives
    position 0), over pools of pages of ``page`` positions (two positions'
    rotary keys a row) and a shuffled block table of ``pps`` pages a lane in
    key blocks of ``kb`` pages, and the plain float32 absorbed attention of
    each lane over its own latent rows as they lie."""
    from tpuserve.ops import lane_attention as la

    r, dr = widths
    rng = np.random.default_rng(seed)
    b = len(lasts)
    pages = b * pps + 1
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    ql, qr = draw(b, h, r), draw(b, h, dr)
    ckv, kr = draw(pages, page, r), draw(pages, page // 2, 2 * dr)
    bt = jnp.asarray(rng.permutation(pages - 1)[: b * pps].reshape(b, pps) + 1, jnp.int32)
    last = jnp.asarray(np.where(live, lasts, 0), jnp.int32)
    work = la.work_list(last, bt, page, kb)
    scale = (r + dr) ** -0.5
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    want = []
    for lane in range(b):
        n = int(last[lane]) + 1
        c_kv = f32(ckv[bt[lane]]).reshape(-1, r)[:n]
        k_r = f32(kr[bt[lane]]).reshape(-1, dr)[:n]
        s = (f32(ql[lane]) @ c_kv.T + f32(qr[lane]) @ k_r.T) * scale
        want.append(jax.nn.softmax(s, -1) @ c_kv)
    return (ql, jnp.concatenate([qr, qr], axis=-1), ckv, kr, work), scale, bt, \
        np.asarray(jnp.stack(want))


# (heads, page, pages a key block, pages a lane): the two cells' shapes at toy widths
LANE_SHAPES = {"32-heads-8-pages-a-cell": (32, 16, 8, 20), "64-heads-2-pages-a-cell": (64, 16, 2, 11)}
# the lane under test among neighbours of other lengths, one of them not live
LANE_ENDS = {"one-key": lambda P, c, n: 0, "a-page-less-one": lambda P, c, n: P - 2,
             "exactly-a-page": lambda P, c, n: P - 1, "a-page-plus-one": lambda P, c, n: P,
             "several-key-blocks": lambda P, c, n: 2 * c + 3,
             "the-longest-the-table-holds": lambda P, c, n: n - 1}


@pytest.mark.parametrize("lane", list(LANE_ENDS))
@pytest.mark.parametrize("shape", list(LANE_SHAPES))
def test_the_decode_kernel_is_each_lanes_attention_over_its_own_pages_as_they_lie(shape, lane):
    """``ops/lane_attention.py`` in the Pallas interpreter against plain float32
    absorbed attention over the pools as they lie: lanes of very different
    lengths side by side in ONE call, each walking its own key blocks and no
    further (the running softmax from a lane's first block to its last, the
    mask inside the last), the rotary keys two positions a row, a lane that
    is not live among them (one item; its row comes back finite). In float32
    the kernel rounds nothing, so it stands within float32's sums in another
    order."""
    from tpuserve.ops import lane_attention as la

    h, page, kb, pps = LANE_SHAPES[shape]
    c, most = kb * page, pps * page
    lasts = np.array([most // 2, LANE_ENDS[lane](page, c, most), 5, c, most - 1, 3])
    live = np.array([True, True, False, True, True, True])
    args, scale, _, want = lane_case(h, page, kb, pps, lasts, live)
    got = la.lane_walk(*args, scale=scale, interpret=True)
    assert got.shape == want.shape and got.dtype == args[0].dtype
    assert bool(jnp.all(jnp.isfinite(got)))   # the lane that is not live too
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert int(args[4]["items"]) == int(np.sum(np.where(live, lasts, 0) // c + 1))


@pytest.mark.parametrize("shape", list(LANE_SHAPES))
def test_the_decode_kernel_in_bfloat16_rounds_as_the_walk_in_xla_does(shape):
    """At whole 128-lane widths in bfloat16 (what ``fits`` takes): the
    probabilities round into the second product and the context to the
    served type, a few thousandths."""
    from tpuserve.ops import lane_attention as la

    h, page, kb, pps = LANE_SHAPES[shape]
    lasts = np.array([pps * page - 1, 0, page, 7])
    args, scale, _, want = lane_case(h, page, kb, pps, lasts, np.array([True, True, True, False]),
                                     jnp.bfloat16, (128, 64))
    got = la.lane_walk(*args, scale=scale, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want, atol=1e-2)
    assert la.fits(128, 512, 128, jnp.bfloat16) and la.fits(16, 128, 128, jnp.bfloat16) \
        and not la.fits(128, 512, 128, jnp.float32) and not la.fits(8, 128, 128, jnp.bfloat16) \
        and not la.fits(16, 32, 128, jnp.bfloat16) and not la.fits(16, 128, 64, jnp.bfloat16)


@pytest.mark.parametrize("kb", [1, 2, 8])
def test_the_work_list_is_every_lanes_own_blocks_and_nothing_else(kb):
    """The list a step builds once: lane after lane, a lane's key blocks in
    order; its length the sum of the live lanes' own blocks and ONE item a
    lane that is not live (its row's first block: nothing uninitialised reaches
    the stream); an item's pages the lane's own, and page 0 where a page lies
    whole past the lane's position; past its length only padding a cell never
    reads."""
    from tpuserve.ops import lane_attention as la

    page, pps = 16, 11
    lasts = np.array([0, 15, 16, 100, 175, 47, 31])
    live = np.array([True, True, True, True, True, False, True])
    last = np.where(live, lasts, 0)
    bt = np.arange(1, 1 + len(lasts) * pps, dtype=np.int32).reshape(len(lasts), pps)
    work = jax.tree_util.tree_map(np.asarray, la.work_list(jnp.asarray(last), jnp.asarray(bt),
                                                           page, kb))
    need = last // (kb * page) + 1
    n = int(work["items"])
    assert n == need.sum() == need[live].sum() + 1
    assert work["lane"].shape == work["block"].shape == (len(lasts) * -(-pps // kb),)
    np.testing.assert_array_equal(work["lane"][:n], np.repeat(np.arange(len(lasts)), need))
    np.testing.assert_array_equal(work["block"][:n], np.concatenate([np.arange(k) for k in need]))
    pages = work["pages"].reshape(-1, kb)
    for item in range(n):
        lane, at = work["lane"][item], work["block"][item] * kb + np.arange(kb)
        mine = np.pad(bt[lane], (0, -pps % kb))[at]
        np.testing.assert_array_equal(pages[item], np.where(at * page <= last[lane], mine, 0))
    assert work["lane"].max() < len(lasts) and work["block"].max() < -(-pps // kb) \
        and 0 <= pages.min() and pages.max() <= bt.max()


class NamedTpu:
    """``jax`` as ``mla`` sees it with the backend named ``tpu``: the family's
    trace-time choice (``_walk``) takes its TPU branch, and nothing else does
    (the name itself would steer the experts' kernels too)."""

    default_backend = staticmethod(lambda: "tpu")

    def __getattr__(self, name):
        return getattr(jax, name)


def on_the_tpu_with_the_kernels_interpreted(monkeypatch):
    """-> a list that grows by one each time a call of the decode kernel is
    traced."""
    import functools

    from tpuserve.ops import lane_attention as la

    calls = []
    monkeypatch.setattr(mla, "jax", NamedTpu())
    monkeypatch.setattr(la, "lane_walk", functools.partial(
        lambda *a, f=la.lane_walk, **k: calls.append(1) or f(*a, interpret=True, **k)))
    return calls


def steps_both_walks(model, params, prompts, news, monkeypatch, slots, page, steps=4):
    """Prefill once (XLA), then ``steps`` steps: each step both ways FROM THE
    SAME STATE, the walk in XLA and (backend named ``tpu``, kernel
    interpreted) the walk the family chooses -> per step (XLA's, the other's)
    (state, out), and the kernel calls traced a trace of the step."""
    _, _, state = serve(model, params, prompts, news, chunk=page, slots=slots, steps=0, page=page)
    plain, before, plains = jax.jit(model.step), [state], []
    for _ in range(steps):   # traced and run before anything is steered
        plains.append(plain(params, before[-1]))
        before.append(plains[-1][0])
    with monkeypatch.context() as m:
        calls, traces = on_the_tpu_with_the_kernels_interpreted(m), []
        steered = jax.jit(lambda p, s: traces.append(1) or model.step(p, s))
        pairs = [(a, steered(params, s)) for a, s in zip(plains, before)]
    return pairs, len(calls) / len(traces)


LANE_ARCH = dict(ARCH, num_attention_heads=4, kv_lora_rank=128, qk_rope_head_dim=64)


def same_step(pairs, lanes_live, atol=3e-2):
    """The steered step is the XLA step: the same tokens' log-probabilities
    and pools to bfloat16's rounding (the kernel sums a lane's blocks in
    float32 in another order; its output rounds to the served type as the
    walk's does), the same positions and flags to the bit."""
    for (sa, oa), (sb, ob) in pairs:
        for key in ("pos", "n_new", "done", "armed"):
            np.testing.assert_array_equal(np.asarray(sa[key]), np.asarray(sb[key]))
        np.testing.assert_allclose(np.asarray(sa["lp"]), np.asarray(sb["lp"]), atol=atol)
        for a, b in zip(sa["ckv"] + sa["kr"], sb["ckv"] + sb["kr"]):
            np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                                       np.asarray(b.astype(jnp.float32)), atol=atol)
        np.testing.assert_array_equal(np.asarray(oa["n_new"]), np.asarray(ob["n_new"]))
    first_x, first_k = (np.asarray(s["acc"]).astype(np.int64) for s, _ in pairs[0])
    assert first_x[1, 10] == 0 and first_x[1, 11] == lanes_live
    return first_k


def test_on_the_tpu_a_step_walks_every_lane_in_one_kernel_call_an_attention(tmp_path, monkeypatch):
    """With the backend named `tpu` and the kernel run in the interpreter, a
    step of a bfloat16 model at widths the kernel takes is ONE kernel call a
    layer for every lane (contexts of 40, 9 and 21 tokens over key blocks of
    two pages, a fourth lane never armed), the XLA step's state and
    log-probabilities to bfloat16's rounding, and the device's sums count
    every live lane under `walk=kernel`, none under `xla`, and the cache rows
    of each lane's OWN whole key blocks."""
    model = make_model(tmp_path, LANE_ARCH, name="lanes", dtype="bfloat16", max_prompt_tokens=48)
    monkeypatch.setattr(paged_lm, "KEY_BLOCK", 32)
    model.step_keys = 32   # the kernel's cells as wide, steered here as the tiles are
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(3).integers(0, 96, n) for n in (40, 9, 21)]
    pairs, calls = steps_both_walks(model, params, prompts, [8, 8, 8], monkeypatch, 4, 16)
    assert calls == model.n_layers   # a call an attention
    acc = same_step(pairs, 3)
    assert acc[1, 10] == 3 and acc[1, 11] == 0 and acc[1, 7] == 1
    assert acc[1, 5] == 41 + 10 + 22 and acc[1, 6] == (2 + 1 + 1 + 1) * 32


@pytest.mark.parametrize("refused", ["float32", "a-page-of-8"])
def test_a_shape_the_decode_kernel_refuses_walks_in_xla_and_counts_there(
        tmp_path, monkeypatch, refused):
    """The backend named `tpu`, a shape `fits` does not take: the step is the
    XLA step to the bit, no kernel call is traced, the lanes count under
    `walk=xla`."""
    dtype, page = ("float32", 16) if refused == "float32" else ("bfloat16", 8)
    model = make_model(tmp_path, LANE_ARCH, name="refused", dtype=dtype, max_prompt_tokens=48)
    monkeypatch.setattr(paged_lm, "KEY_BLOCK", 32)
    model.step_keys = 32
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(3).integers(0, 96, n) for n in (40, 9)]
    pairs, calls = steps_both_walks(model, params, prompts, [8, 8], monkeypatch, 3, page, steps=2)
    assert not calls
    acc = same_step(pairs, 2, atol=0)
    assert acc[1, 10] == 0 and acc[1, 11] == 2


# -- (c) bfloat16: a tolerance that a lower-precision cache and a dropped rotary part fail ------

def test_bfloat16_serves_within_a_tolerance_that_a_lower_cache_or_no_rotary_part_fails(tmp_path):
    """Served in bfloat16, a generated position's number is the RMS of its
    eight centred gaps to the float32 reference (which holds the served
    type's values), and the statistic their MEDIAN over the positions: a
    swapped pick of two near-tied experts moves a few positions by tenths
    and is what serving this router in bfloat16 is; a lower precision moves
    every position (as benchmark/reference/mla.py `compare` reasons). Why TOL
    is where it is: between the sound reading and the two faults', with room
    on both sides: the SAME program with its latent pages kept in float8
    (e4m3: the nearest type below the served one; the program stores what
    the block holds), and a program whose rotary key is dropped (W_kva's
    rotary columns zero: four fifths of the toy's scores' variance, a third at
    the published sizes). Readings here:
    0.012, 0.071, 0.71."""
    model = make_model(tmp_path, name="bf", dtype="bfloat16", max_new_tokens=24)
    params = model.init_params(jax.random.key(0))
    prompts = [np.random.default_rng(5).integers(0, 96, n) for n in (20, 9, 16)]
    news = [24, 24, 24]

    def median(cache_dtype=None, p=params):
        served, _, _ = serve(model, p, prompts, news, cache_dtype=cache_dtype)
        per = [np.sqrt(np.mean(g ** 2, axis=-1))
               for g in gaps(ARCH, prompts, served, "bfloat16", centred=True)]
        return float(np.median(np.concatenate(per)))

    no_rope = dict(params)
    for i in range(3):
        lp = params[f"layer{i}"]
        no_rope[f"layer{i}"] = dict(lp, w_kva=lp["w_kva"].at[:, 32:].set(0))
    sound, low, dropped = median(), median(jnp.float8_e4m3fn), median(p=no_rope)
    TOL = float(np.sqrt(sound * min(low, dropped)))
    assert sound < TOL < min(low, dropped), (sound, low, dropped)
    assert min(low, dropped) > 3.0 * sound, (sound, low, dropped)


# -- (d) slots, free lanes, the sentinel page -----------------------------------------------------

def test_a_slot_reused_answers_as_alone_and_a_free_lane_writes_only_the_sentinel(whole):
    model, params = whole
    first, _, state = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    # every lane is done: a step writes the sentinel, page 0, and nothing else, bit for bit
    assert all(float(jnp.abs(c[0]).sum()) > 0 for c in state["ckv"] + state["kr"])
    again, _ = jax.jit(model.step)(params, state)
    for a, b in zip(state["ckv"] + state["kr"], again["ckv"] + again["kr"]):
        np.testing.assert_array_equal(np.asarray(a[1:]), np.asarray(b[1:]))
    # other requests into the same slots, the block as the first left it
    prompts = [PROMPTS[2], PROMPTS[0][:9], PROMPTS[1]]
    news = [4, 7, 2]
    reused, _, _ = serve(model, params, prompts, news, state=state)
    alone, _, _ = serve(model, params, prompts, news)
    for a, b in zip(reused, alone):
        n = int(b["n_new"])   # rows past it are the earlier tenant's, never returned
        assert np.array_equal(a["tokens"][:n], b["tokens"][:n]) and int(a["n_new"]) == n
        np.testing.assert_array_equal(a["lp"][:n], b["lp"][:n])
    # a lane whose prompt is half in (not armed) writes none of its pages while the others step
    _, _, mid = serve(model, params, PROMPTS, MAX_NEWS, steps=0,
                      launches=[[(0, 0, 8)], [(1, 0, 5)]])
    stepped = mid
    for _ in range(3):
        stepped, out = jax.jit(model.step)(params, stepped)
    assert int(out["n_new"][1]) == 4 and int(out["n_new"][0]) == 0
    pps = model.kv_plan(1, PAGE).pages_per_slot
    for a, b in zip(mid["ckv"] + mid["kr"], stepped["ckv"] + stepped["kr"]):
        np.testing.assert_array_equal(np.asarray(a[1:1 + pps]), np.asarray(b[1:1 + pps]))
        assert not np.array_equal(np.asarray(a[1 + pps:1 + 2 * pps]),
                                  np.asarray(b[1 + pps:1 + 2 * pps]))


# -- (e) what the pages hold; what is refused --------------------------------------------------------

def test_the_page_signature_holds_one_latent_row_a_token_a_layer_and_no_leaf_by_head(whole, tmp_path):
    model, _ = whole
    plan = model.kv_plan(SLOTS, PAGE, 10)
    sig = plan.state
    # 32 + 64 values a token a layer, each leaf whole rows of 128 lanes or its own width
    assert [c.shape for c in sig["ckv"]] == [(10, PAGE, R)] * 3
    assert [c.shape for c in sig["kr"]] == [(10, PAGE // 2, 2 * ROPE)] * 3
    per_token = sum(int(np.prod(leaf[0].shape)) for leaf in (sig["ckv"], sig["kr"])) // (10 * PAGE)
    assert per_token == ROW
    assert plan.leaves(LeafKind.POOL) == ("ckv", "kr") and plan.leaves(LeafKind.SLOT) == ()
    assert set(sig) == {"ckv", "kr"} | set(model._lane_signature(SLOTS, plan.pages_per_slot))
    # the published sizes: 512 + 64 = 576 values, two positions' rotary keys a row of 128 lanes
    big = make_model(tmp_path, dict(ARCH, kv_lora_rank=512, qk_rope_head_dim=64), name="big")
    sig = big.kv_plan(16, 128, 3200).state
    assert sig["ckv"][0].shape == (3200, 128, 512) and sig["kr"][0].shape == (3200, 64, 128)
    # a latent row goes where it is told and nowhere else
    pool = model._write_pages(jnp.zeros((3, PAGE, R)), jnp.asarray([2, 0]), jnp.asarray([1, 3]),
                              jnp.ones((2, R)))
    assert float(pool.sum()) == 2 * R and float(pool[2, 1].sum()) == R \
        and float(pool[0, 3].sum()) == R


@pytest.mark.parametrize("key,value,error", [
    ("rope_scaling", {"type": "linear", "factor": 40}, NotImplementedError),   # yarn alone is read
    ("q_lora_rank", None, NotImplementedError),
    ("attention_bias", True, NotImplementedError),
    ("hidden_act", "gelu", NotImplementedError),
    ("n_group", 8, NotImplementedError),
    ("topk_group", 4, NotImplementedError),
    ("scoring_func", "softmax", NotImplementedError),
    ("share", {"experts_held": [0, 4]}, NotImplementedError),
])
def test_a_key_the_family_does_not_implement_is_refused(tmp_path, key, value, error):
    with pytest.raises(error, match=key):
        make_model(tmp_path, dict(ARCH, **{key: value}), name="bad")


def test_the_locked_batch_contract_is_not_served_and_tiles_are_a_key_block_wide(tmp_path):
    model = make_model(tmp_path, name="locked", tile_rows=mla.LatentServing.TILE_ROWS)
    with pytest.raises(NotImplementedError, match="generation engine"):
        model.forward(None, None)
    # K by the chunk: tiles of 1,024 rows where the chunk has them, whole pages always
    sizes = ((1024, 128), (2048, 128), (4096, 128), (8192, 128), (16384, 128), (8, 4))
    assert [model.kv_prefill_pieces(c, p) for c, p in sizes] == [1, 2, 4, 8, 8, 1]


def test_the_recipe_is_the_references_and_the_bias_is_small(whole):
    model, params = whole
    m = ref.Model(ARCH, SEED, "float32")
    w = m.attention(1)
    lp = params["layer1"]
    np.testing.assert_array_equal(np.asarray(lp["w_qb"])[..., :16], np.asarray(w["w_qb_nope"]))
    np.testing.assert_array_equal(np.asarray(lp["w_qb"])[..., 16:], np.asarray(w["w_qb_rope"]))
    assert lp["w_qb"].shape == (48, 4, 16 + ROPE) and lp["w_kva"].shape == (64, ROW)
    np.testing.assert_array_equal(np.asarray(lp["w_kva"])[:, :32], np.asarray(w["w_kva_c"]))
    np.testing.assert_array_equal(np.asarray(lp["w_kva"])[:, 32:], np.asarray(w["w_kva_r"]))
    for k in ("w_qa", "w_kb", "w_vb", "wo"):
        np.testing.assert_array_equal(np.asarray(lp[k]), np.asarray(w[k]))
    f = m.ffn(1)
    np.testing.assert_allclose(np.asarray(lp["e_bias"]), f["e_bias"], rtol=1e-6, atol=1e-9)
    assert float(np.abs(np.asarray(lp["e_bias"])).max()) <= 0.06
    blk = m.expert_block(1, 2, 3)
    np.testing.assert_array_equal(np.asarray(lp["e_down"])[2:5], blk["e_down"])
    assert set(params["layer0"]) >= {"w_gate", "w_up", "w_down"} and "router" not in params["layer0"]


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "reference", "mla.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_mla_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


# -- (f) through the engine: the counters and /stats -------------------------------------------------

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
    n_sparse, k = 2, ARCH["num_experts_per_tok"]
    tokens, steps = 19 + 5, (6 - 1) + (9 - 1)
    assert c["gen_prefill_tokens_total{model=eng}"] == tokens
    assert c["gen_decode_tokens_total{model=eng}"] == steps
    assert c["moe_tokens_routed_total{model=eng,phase=prefill,held=yes}"] == n_sparse * k * tokens
    assert c["moe_tokens_routed_total{model=eng,phase=decode,held=yes}"] == n_sparse * k * steps
    assert not any(v for name, v in c.items()
                   if name.startswith("moe_tokens_routed_total") and "held=no" in name)
    assert c["moe_expert_steps_total{model=eng,phase=decode}"] \
        >= c["moe_experts_hit_total{model=eng,phase=decode}"] > 0
    assert c["gen_context_tokens_total{model=eng,phase=prefill}"] == 19 * 20 // 2 + 5 * 6 // 2
    launches = c["gen_prefill_chunks_total{model=eng}"]
    assert c["mla_launches_total{model=eng,phase=prefill,form=absorbed}"] == launches
    assert c["mla_launches_total{model=eng,phase=decode,form=absorbed}"] \
        == c["gen_iterations_total{model=eng}"]
    assert not any(v for name, v in c.items() if "form=expanded" in name)
    # each piece's whole context once: 19 in pieces of 8, 8, 3 and 5 alone
    assert c["mla_rows_attended_total{model=eng,phase=prefill}"] == 8 + 16 + 19 + 5
    assert c["mla_rows_attended_total{model=eng,phase=decode}"] \
        == c["gen_context_tokens_total{model=eng,phase=decode}"] \
        == sum(19 + j for j in range(1, 6)) + sum(5 + j for j in range(1, 9))
    assert c["mla_rows_walked_total{model=eng,phase=decode}"] \
        >= c["mla_rows_attended_total{model=eng,phase=decode}"]
    # /stats: a position's bytes from the signature: one row a layer, not K + V by head
    kv = eng.pipeline_stats()["kv"]
    assert kv["row_bytes_per_token"] == 3 * ROW * 4
    assert kv["kv_bytes"] == 3 * ROW * 4 * PAGE * kv["pages"]
    assert metrics.gauge("gen_kv_row_bytes{model=eng}").value == 3 * ROW * 4
    assert kv["state_bytes"] == 0 and kv["reserved"] == 0 and kv["pages"] > 0


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
