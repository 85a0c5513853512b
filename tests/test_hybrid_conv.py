"""The `hybrid_conv` family (ISSUE 59) against its plain reference at a small
size on the CPU: packed, chunked prefill and then decode through pages AND the
convolution's rows a slot (the ONE leaf this mixer keeps) equal the reference's
full pass; prompts shorter than the taps, a piece that ends on a tile's last
row, a prompt cut at a launch's edge, two prompts in one launch, a padded tail;
every wrong reading of the layer, and every fault of the stored rows, fails;
the other two recurrent mixers keep their two leaves. Logits (served
log-probabilities) are compared, never sampled tokens."""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import hybrid_conv_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind, PrefillPiece
from tpuserve.models import build, hybrid_conv, mixers
from tpuserve.models.paged_lm import LOGPROBS
from tpuserve.ops import moe

# conv conv full conv conv full: both operators, a dense layer and then five
# routed ones of 8 experts, 2 picked, no shared one; 4 query heads on 2 KV heads of 16.
ARCH = {
    "model_type": "lfm2_moe", "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "full_attention"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5, "num_attention_heads": 4,
    "num_key_value_heads": 2,
    # A toy's contexts are tens of positions: a rotary of theta 100 turns its slow
    # pairs over them as theta 1e6 turns the cell's over thousands.
    "rope_parameters": {"rope_theta": 100.0, "rope_type": "default"},
    "intermediate_size": 96, "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "tie_word_embeddings": True,
    # The embedding is also the head: rows at 1 / sqrt(64), logits of unit standard deviation.
    "weight_scales": {"embed": 0.125},
}
SEED = 17
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3
# Float32 against float32: served and reference differ by the order of their
# sums (key blocks under a running softmax against one softmax, a grouped
# product against an expert at a time) and by a router's pick where two scores
# tie to the last place (none in these prompts). A log-probability is about -3;
# the largest gap read over the sound cases is 5.2e-6, a few units in its last
# place; TOL is 10x that. Every wrong reading and every fault of the stored rows
# reads 0.125 or more (`bias_in_weights`: a bias within 0.06 moves a weight by a
# tenth; the rest 1.3 to 5.6).
TOL = 5e-5


def make_model(tmp_path, arch=ARCH, name="hc", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="hybrid_conv", dtype=dtype, batch_buckets=[1],
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
    model = make_model(tmp_path_factory.mktemp("hybrid_conv"))
    return model, model.init_params(jax.random.key(0))


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n) for n in lengths]


# 19 tokens: three launches at a chunk of 8; 11: two; 5: one.
PROMPTS = prompts_of(19, 5, 11)
MAX_NEWS = [6, 12, 3]
# Pieces of several slots and sizes in one launch (tiles of 4 rows), a prompt
# over five launches (its rows carried between them), padded tails (a piece of
# 1, of 3, of 7), pieces that end on a tile's last row (4, 8).
PACKED = [[(0, 0, 4), (1, 0, 4)], [(0, 4, 8)], [(1, 4, 1), (0, 12, 4)],
          [(0, 16, 3), (2, 0, 4)], [(2, 4, 7)]]


def worst(served, prompts=PROMPTS, arch=ARCH, wrong="", chunk=CHUNK) -> float:
    """The largest gap of served and reference log-probabilities at the ids
    the server named, teacher-forced on the served tokens."""
    m = ref.Model(arch, SEED, "float32", wrong, chunk)
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    out = 0.0
    for s, lp in zip(served, ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])):
        n = int(s["n_new"])
        out = max(out, float(np.abs(
            s["lp"][:n] - np.take_along_axis(lp, s["lp_ids"][:n], axis=-1)).max()))
    return out


@pytest.fixture(scope="module")
def served_packed(whole):
    model, params = whole
    return serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)[0]


# -- (a) the two programs against the reference's full pass ---------------------------------------------

CASES = {
    "a-chunk-a-launch": (PROMPTS, MAX_NEWS, None),
    "packed": (PROMPTS, MAX_NEWS, PACKED),
    # shorter than the taps: the convolution reads zeros before position 0, and
    # the slot's rows after a prompt of 1 hold ONE row of b behind a row of zeros
    "prompts-of-1-and-2": (prompts_of(1, 2, 3, seed=1), [5, 5, 5], None),
    # a piece that ends on a tile's last row (8 = two whole tiles) and on a
    # launch's last row, its prompt cut there (16 = two whole launches)
    "cut-at-a-launchs-edge": (prompts_of(16, 8, seed=2), [4, 4], None),
    # two prompts packed in one launch, one tile apart: neither sees the other's rows
    "two-in-one-launch": (prompts_of(4, 3, seed=3), [6, 6], [[(0, 0, 4), (1, 0, 3)]]),
    # a prompt whose decode crosses pages' edges (pages of 4) through rows the steps wrote
    "decode-across-pages": (prompts_of(6, seed=4), [12], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_prefill_then_decode_is_the_reference_full_pass(whole, case):
    model, params = whole
    prompts, max_news, launches = CASES[case]
    served, _out, _ = serve(model, params, prompts, max_news, launches=launches)
    assert [int(s["n_new"]) for s in served] == max_news
    assert worst(served, prompts) < TOL


def test_two_prompts_in_one_launch_are_each_prompt_alone(whole):
    """To the bit: a tile behind another slot's tile starts from ITS slot's
    rows (zeros here), not from the rows of the tile before it."""
    model, params = whole
    prompts, max_news, launches = CASES["two-in-one-launch"]
    both, _, _ = serve(model, params, prompts, max_news, launches=launches)
    for slot in (0, 1):
        alone, _, _ = serve(model, params, prompts, max_news,
                            launches=[[launches[0][slot]]], steps=max(max_news) + 1)
        np.testing.assert_array_equal(both[slot]["lp"], alone[slot]["lp"])


def test_a_lane_that_is_not_live_keeps_its_rows_and_a_new_tenant_starts_from_zeros(whole):
    model, params = whole
    _, _, state = serve(model, params, PROMPTS, MAX_NEWS)
    before = [np.asarray(s) for s in state["conv"]]
    assert len(before) == 4 and all(np.abs(b).max() > 0 for b in before)
    state2, _ = jax.jit(model.step)(params, state)          # every lane is done: none is live
    for b, a in zip(before, state2["conv"]):
        assert np.array_equal(b, np.asarray(a))
    for leaf in ("kf", "vf", "tokens", "lp", "pos"):
        for b, a in zip(jax.tree_util.tree_leaves(state[leaf]),
                        jax.tree_util.tree_leaves(state2[leaf])):
            assert np.array_equal(np.asarray(b), np.asarray(a)), leaf
    # Slot 0 (19 tokens before) to a request of 5: the rows it finds are not read.
    prompts = [PROMPTS[1], PROMPTS[1], PROMPTS[1]]
    again, _, _ = serve(model, params, prompts, [4, 4, 4], launches=[[(0, 0, 5)]], state=state2,
                        steps=5)
    fresh, _, _ = serve(model, params, prompts, [4, 4, 4], launches=[[(0, 0, 5)]], steps=5)
    np.testing.assert_array_equal(again[0]["lp"][:4], fresh[0]["lp"][:4])


# -- (b) a slot's state is the rows alone ----------------------------------------------------------------

def test_the_mixer_names_one_leaf_and_nothing_else_is_allocated(whole):
    """`conv` is the family's whole state a slot: (slots, 2, d) a convolution
    layer in the served type, no `ssm` leaf of any shape; the other two
    recurrent mixers still name their two."""
    model, _ = whole
    plan = model.kv_plan(SLOTS, PAGE, 20)
    sig = plan.state
    assert plan.leaves(LeafKind.SLOT) == ("conv",) and model._leaves() == ("kf", "vf", "conv")
    assert "ssm" not in sig
    assert [s.shape for s in sig["conv"]] == [(SLOTS, 2, 64)] * 4
    assert [s.shape for s in sig["kf"]] == [(2, 20, PAGE, 16)] * 2
    ns = SimpleNamespace(mh=1, mp=1, mn=1, kh=1, kd=1, conv_k=2, conv_ch=1, dtype=jnp.float32,
                         m_layers=[0])
    assert [(leaf, s.kind) for leaf, s in mixers.Mamba2Mixer._mamba_signature(ns, 1).items()] \
        == [(leaf, s.kind) for leaf, s in mixers.DeltaMixer._delta_signature(ns, 1).items()] \
        == [("ssm", LeafKind.SLOT), ("conv", LeafKind.SLOT)]
    assert mixers.RecurrentMixer._piece_starts(
        jnp.asarray([0]), jnp.asarray([0]), rows=(jnp.ones((2, 2, 3)),))[0] == ()


def test_after_a_prompt_of_one_token_the_slot_holds_zeros_then_its_row(whole):
    model, params = whole
    (p,) = prompts_of(1, seed=5)
    _, _, state = serve(model, params, [p], [1], steps=0, slots=1)
    for rows in state["conv"]:
        rows = np.asarray(rows)[0]
        assert np.all(rows[0] == 0) and np.abs(rows[1]).max() > 0


# -- (c) every wrong reading fails ------------------------------------------------------------------------

@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_reading_of_the_layer_fails_the_tolerance(served_packed, wrong):
    """What `PACKED` served (a prompt in five pieces, decode through the
    stored rows) against each wrong reading of `benchmark/reference/hybrid_conv.py`'s
    list, `piece_forgets` at the launches' own edges."""
    assert worst(served_packed, wrong=wrong) > 400 * TOL


def test_the_rotary_before_the_norm_is_seen_only_because_the_gains_differ_by_column(whole):
    """A gain that is ONE value commutes with the rotary (a rotation keeps a
    head's mean square): drawn so, the check would be blind to the order."""
    model, params = whole
    g = np.asarray(params["layer2"]["q_norm"])
    assert g.dtype == np.float32 and g.shape == (16,) and 1.0 <= g.min() < g.max() <= 3.0
    flat = dict(ARCH, weight_scales={"qk_gain": [2.0, 2.0]})
    (p,) = prompts_of(9, seed=6)
    a = ref.log_probs(ref.Model(flat, SEED, "float32"), [p], [0])[0]
    b = ref.log_probs(ref.Model(flat, SEED, "float32", "rope_first"), [p], [0])[0]
    assert float(np.abs(a - b).max()) < TOL


_STEP, _PREFILL = mixers.ConvMixer._conv_step, mixers.ConvMixer._conv_prefill
FAULTS = {
    # the stored rows left out of a step: c_i = w[k-1] b_i
    "a-step-without-its-rows": ("_conv_step", lambda self, lp, u, live, conv: _STEP(
        self, lp, u, live, jnp.zeros_like(conv))),
    # a piece's rows taken from another slot
    "rows-of-another-slot": ("_conv_prefill", lambda self, lp, u, t, conv, slot, start, length:
                             (_PREFILL(self, lp, u, t, jnp.roll(conv, 1, axis=0), slot, start,
                                       length)[0],
                              _PREFILL(self, lp, u, t, conv, slot, start, length)[1])),
    # the stored rows not carried from one piece of a prompt to the next
    "a-piece-from-zeros": ("_conv_prefill", lambda self, lp, u, t, conv, slot, start, length:
                           _PREFILL(self, lp, u, t, conv, slot, jnp.zeros_like(start), length)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_of_the_stored_rows_in_the_program_fails_the_tolerance(whole, monkeypatch, fault):
    """The program broken underneath, the reference as it is."""
    model, params = whole
    method, broken = FAULTS[fault]
    monkeypatch.setattr(mixers.ConvMixer, method, broken)
    served, _, _ = serve(model, params, PROMPTS, MAX_NEWS, launches=PACKED)
    assert worst(served) > 400 * TOL


# -- (d) the router's denominator ------------------------------------------------------------------------

def test_the_weights_are_over_their_sum_plus_eps_and_the_default_is_as_it_was():
    r = jnp.asarray(np.random.default_rng(0).standard_normal((5, 8)), jnp.float32)
    bias = jnp.asarray(np.random.default_rng(1).uniform(-0.06, 0.06, 8), jnp.float32)
    kw = dict(scoring="sigmoid", select_bias=bias)
    w0, e0 = moe.topk_route(r, 2, **kw)
    w1, e1 = moe.topk_route(r, 2, eps=1e-6, **kw)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(r)), np.asarray(e1), axis=-1)
    np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    np.testing.assert_allclose(np.asarray(w1), s / (s.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)
    assert float(np.asarray(w1).sum(-1).max()) < 1.0
    plain = jax.jit(lambda x: moe.topk_route(x, 2, **kw)).lower(r).as_text()
    assert plain == jax.jit(lambda x: moe.topk_route(x, 2, eps=0.0, **kw)).lower(r).as_text()
    assert hybrid_conv.HybridConvServing.route_eps == ref.ROUTE_EPS == 1e-6


# -- (e) the references, the keys, the published sizes -----------------------------------------------------

def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, root)
    path = os.path.join(root, "benchmark", "reference", "hybrid_conv.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_hybrid_conv_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    assert theirs.DEFAULT_SCALES == ref.DEFAULT_SCALES == hybrid_conv.DEFAULT_SCALES
    assert theirs.WRONG == ref.WRONG and theirs.ROUTE_EPS == ref.ROUTE_EPS
    seqs = prompts_of(17, 5, seed=6)
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen


@pytest.mark.parametrize("key,value,error", [
    ("conv_bias", True, NotImplementedError), ("use_expert_bias", False, NotImplementedError),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}, NotImplementedError),
    ("share", {"experts_held": [0, 4]}, NotImplementedError),
    ("layer_types", ["conv"] * 5 + ["mamba"], ValueError),
    ("num_dense_layers", 7, ValueError)])
def test_a_key_the_family_does_not_implement_is_refused(tmp_path, key, value, error):
    with pytest.raises(error, match=key.split("_")[0]):
        make_model(tmp_path, dict(ARCH, **{key: value}), name="bad")


def test_the_published_sizes_give_the_bytes_a_token_and_a_slot_that_stats_reports(tmp_path):
    """The cell's configuration, shapes only (nothing is allocated)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    import sys
    sys.path.insert(0, root)
    from benchmark import spec
    with open(os.path.join(root, "benchmark", "configs", "lfm2-24b-a2b-l10.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    arch = spec.load_module("reference", "hybrid_conv").arch_from_config(cfg)
    served = cfg["assumed"]["served"]
    model = make_model(tmp_path, arch, name="pub", dtype="bfloat16",
                       max_prompt_tokens=served["max_prompt_tokens"],
                       max_new_tokens=served["max_new_tokens"])
    sig = model.kv_plan(512, 128, 4608).state
    nbytes = lambda leaves: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in leaves)  # noqa: E731
    assert "ssm" not in sig and [s.shape for s in sig["conv"]] == [(512, 2, 2048)] * 8
    assert all(s.dtype == jnp.bfloat16 for s in sig["conv"])
    assert nbytes(sig["conv"]) // 512 == 65_536 == 8 * 2 * 2048 * 2
    assert [s.shape for s in sig["kf"]] == [(4, 4608, 128, 128)] * 2     # 8 KV heads of 64, two a row
    assert nbytes(sig["kf"] + sig["vf"]) == 4608 * 524_288
    assert model.m_layers == [0, 1, 3, 4, 5, 7, 8, 9] and model.a_layers == [2, 6]
    assert model.e_layers == list(range(2, 10)) and model.tied and model.hd == 64
    assert (model.n_experts, model.e_count, model.top_k, model.vocab) == (64, 64, 4, 65536)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model.draw_params(0))))
    assert abs(n_params - 5.2671e9) < 1e6


# -- through the engine: the counters and /stats ------------------------------------------------------

def test_through_the_engine_requests_move_the_counters_and_stats_reads_the_one_leaf(tmp_path):
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
    # five routed layers of the six: the dense one counts nothing in the experts' series
    assert c["moe_tokens_routed_total{model=eng,phase=decode,held=yes}"] == 5 * 2 * steps
    layers = c["moe_layers_total{model=eng,phase=decode}"]      # a step or two run past the last token
    assert layers % 5 == 0 and layers >= 5 * (max(max_news) - 1)
    assert not c.get("moe_tokens_routed_total{model=eng,phase=decode,held=no}")
    kv = eng.pipeline_stats()["kv"]
    per_slot = n_m * 2 * 64 * 4                            # 4 layers x 2 rows x 64 x float32
    assert kv["state_bytes_per_slot"] == per_slot and kv["state_bytes"] == per_slot * SLOTS
    assert metrics.gauge("gen_state_bytes{model=eng}").value == per_slot * SLOTS
    assert kv["row_bytes_per_token"] == 2 * 2 * 2 * 16 * 4     # 2 layers x K, V x 2 heads of 16
