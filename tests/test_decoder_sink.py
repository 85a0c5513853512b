"""The `decoder_sink` family (ISSUE 49) against its plain reference at a small
size on the CPU: chunked paged prefill and decode through pages and rings in
three leaves each, each wrong reading of the published keys failing in XLA and
with every step's attention in the kernel (ISSUE 50), the grouped decode kernel
in the interpreter against plain attention over pages and over rings in place
(with the sink, at 8 and 16 query rows a KV head), the sixteen shares adding up
to the uncut layer, the cache's geometry, the weights recipe, and the two
copies of the reference."""

from __future__ import annotations

import contextlib
import functools
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import decoder_sink_reference as ref
from tpuserve.config import ModelConfig
from tpuserve.genserve.model import LeafKind, PrefillPiece
from tpuserve.models import build
from tpuserve.models import decoder as dec
from tpuserve.models import decoder_sink as ds
from tpuserve.models import mla
from tpuserve.models.paged_lm import Heads
from tpuserve.ops import lane_attention as la
from tpuserve.ops.moe import topk_route

# Seven layers as the cell's: a dense global layer, then window x 4, global,
# window. Keys of 12 columns of which int(12 x 0.334) = 4 turn, values of 8;
# 2 KV heads in a global layer and 4 in a window layer; a window of 8; sinks
# drawn in [2, 6], about the logarithm of eight keys' summed weights here.
ARCH = {
    "model_type": "mimo_v2", "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
    "num_hidden_layers": 7, "layernorm_epsilon": 1e-5, "attention_bias": False,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 12, "v_head_dim": 8,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4, "swa_head_dim": 12,
    "swa_v_head_dim": 8, "partial_rotary_factor": 0.334, "rope_theta": 10000000,
    "swa_rope_theta": 10000, "rope_scaling": {"rope_type": "default", "type": "default"},
    "sliding_window": 8, "sliding_window_size": 8, "attention_chunk_size": 8,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "attention_value_scale": 0.707, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 32, "n_shared_experts": None,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16, "norm_topk_prob": True,
    "routed_scaling_factor": None, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "hidden_act": "silu", "tie_word_embeddings": False,
    "weight_scales": {"sink_low": 2.0, "sink_high": 6.0},
}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 24, 12, 4, 8, 3
# float32 program against a float32 reference: what differs is the order of
# sums (a running softmax over key blocks, rings read in ring order, grouped
# experts): 1e-5 relative on log-probabilities of a few units.
ATOL = 2e-4


def make_model(tmp_path, arch=ARCH, name="sink", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="decoder_sink", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


class NamedTpu:
    """``jax`` as ``decoder_sink`` sees it with the backend named ``tpu``: the
    family's trace-time choice (``_walk``) takes its TPU branch, and nothing
    else does (the name itself would steer the experts' kernels too)."""

    default_backend = staticmethod(lambda: "tpu")

    def __getattr__(self, name):
        return getattr(jax, name)


@contextlib.contextmanager
def in_the_kernel(calls=None, any_shape=False):
    """What is traced inside takes ``decoder_sink``'s TPU branch with
    ``head_walk`` in the interpreter (``calls`` gets each call's sink);
    ``any_shape``: at whatever shapes, a toy's float32 heads of 12 columns
    too, which the interpreter takes and ``head_fits`` would refuse."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(ds, "jax", NamedTpu())
        if any_shape:
            m.setattr(la, "head_fits", lambda *a: True)

        def walk(*a, f=la.head_walk, **k):
            if calls is not None:
                calls.append(k.get("sink"))
            return f(*a, interpret=True, **k)

        m.setattr(la, "head_walk", walk)
        yield


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, slots=SLOTS, page=PAGE,
          steps=None, steer=None):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program (``launches``: lists of (slot, start, length); else a prompt alone,
    a chunk a launch), then steps (traced under ``steer()``, where the caller
    has one: `in_the_kernel`) until every lane is done -> (extract() a slot,
    the last step's out-block, the state)."""
    pps = model.kv_plan(1, page).pages_per_slot
    state = zeros(model.kv_plan(slots, page).state)
    k = model.kv_prefill_pieces(chunk, page)
    prefill = jax.jit(model.prefill_chunk, static_argnames=("chunk",))
    step = jax.jit(model.step)
    if launches is None:
        launches = [[(slot, start, min(chunk, len(prompts[slot]) - start))]
                    for slot in range(len(prompts))
                    for start in range(0, len(prompts[slot]), chunk)]

    def piece(slot, start, length):
        ids = np.zeros((model.max_prompt,), np.int32)
        ids[: len(prompts[slot])] = prompts[slot]
        item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
                np.float32(0.0), np.int32(dec.LOGPROBS))
        cache = {"pages": np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32),
                 "ring": np.int32(slot + 1)}
        return PrefillPiece(slot, item, start, length, cache)

    for pieces in launches:
        state = prefill(params, state, model.pack_prefill([piece(*p) for p in pieces], chunk, k),
                        chunk=chunk)
    out = None
    with (steer or contextlib.nullcontext)():
        for _ in range(max(max_news) + 1 if steps is None else steps):
            state, out = step(params, state)
    if steps is None:
        assert bool(np.all(np.asarray(out["done"])[: len(prompts)]))
    return [jax.tree_util.tree_map(np.asarray, model.extract(params, state, np.int32(s)))
            for s in range(len(prompts))], out, state


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    model = make_model(tmp_path_factory.mktemp("sink"))
    return model, model.init_params(jax.random.key(0))


PROMPTS = (19, 3, 24)   # longer than the window and two chunks; inside one page; three chunks
NEWS = [12, 12, 7]      # decode wraps the ring again and crosses pages' edges (4)


PATHS = ("xla", "kernel")   # a step's attention, both kinds: gathered in XLA; `head_walk`


@pytest.fixture(scope="module", params=PATHS)
def served(whole, request):
    """The prompts served with every step's attention on one path: ``xla``, or
    ``kernel`` (the interpreter, steered at the toy's shapes: 7 calls a step,
    the window layers' with their sinks) -> (prompts, results, the last
    out-block, the path)."""
    model, params = whole
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in PROMPTS]
    calls = []
    steer = functools.partial(in_the_kernel, calls, any_shape=True)
    got, out, _ = serve(model, params, prompts, NEWS,
                        steer=steer if request.param == "kernel" else None)
    if request.param == "kernel":   # traced once: a call a layer, a sink a window layer
        assert [s is not None for s in calls] == [bool(p) for p in ARCH["hybrid_layer_pattern"]]
    return prompts, got, out, request.param


def reference_log_probs(arch, prompts, served):
    m = ref.Model(arch, SEED, "float32")
    seqs = [np.concatenate([p, s["tokens"][: s["n_new"] - 1]]) for p, s in zip(prompts, served)]
    return ref.log_probs(m, seqs, [len(p) - 1 for p in prompts])


def gap(served, want):
    """The widest difference of served and reference log-probabilities at the
    ids the server named, over every generated position."""
    worst = 0.0
    for s, lp in zip(served, want):
        n = s["n_new"]
        at_ids = np.take_along_axis(lp, s["lp_ids"][:n].astype(np.int64), axis=-1)
        worst = max(worst, float(np.abs(s["lp"][:n] - at_ids).max()))
    return worst


# -- the served path against the full forward pass -----------------------------------------

def test_chunked_prefill_then_decode_is_the_full_forward_pass(whole, served):
    """Logits, not tokens: every generated position's served log-probabilities
    against the reference's one causal pass. Chunk (8) smaller than the
    prompts, the window (8) smaller than the context, a prompt inside one
    page, rings wrapped by prefill and again by decode, pages' edges crossed
    by decode."""
    model, _ = whole
    prompts, got, out, path = served
    want = reference_log_probs(ARCH, prompts, got)
    assert gap(got, want) < ATOL
    for s, lp, n_new in zip(got, want, NEWS):
        assert s["n_new"] == n_new
        assert np.array_equal(s["tokens"][:n_new], np.argmax(lp, axis=-1))
    # the device's sums: every live pick is held or absent; the global layers' rows
    acc = np.asarray(out["acc"]).astype(np.int64)
    sparse, k, n_global, n_attn = 6, ARCH["num_experts_per_tok"], 2, 7
    assert acc[0, 0] + acc[0, 1] == sparse * k * sum(PROMPTS)
    assert acc[1, 0] + acc[1, 1] == sparse * k * sum(n - 1 for n in NEWS)
    context = [sum(n * (n + 1) // 2 for n in PROMPTS),
               sum(sum(range(p + 1, p + n)) for p, n in zip(PROMPTS, NEWS))]
    assert [acc[0, 4], acc[1, 4]] == context
    names = [c.counter(model, _Names(), "decode").name for c in model.COLUMNS[8:]]
    assert names == [f"attn_rows_attended_total{{model=sink,phase=decode}}",
                     f"attn_rows_walked_total{{model=sink,phase=decode}}",
                     f"attn_walks_total{{model=sink,phase=decode,walk=kernel}}",
                     f"attn_walks_total{{model=sink,phase=decode,walk=xla}}"]
    assert acc[1, 8] == n_global * context[1]                    # attended: the live rows
    assert acc[1, 9] >= acc[1, 8]                                # walked: whole blocks or the table
    # a live lane an attention layer a step, global or window, all on the one path
    lanes = n_attn * sum(n - 1 for n in NEWS)
    assert [acc[1, 10], acc[1, 11]] == ([lanes, 0] if path == "kernel" else [0, lanes])


class _Names:
    def counter(self, name):
        return type("C", (), {"name": name})()


@pytest.mark.parametrize("chunk", [4, 24])
def test_prefill_in_one_launch_and_in_several_is_one_answer(whole, served, chunk):
    model, params = whole
    prompts, got, _, _ = served
    other, _, _ = serve(model, params, prompts, NEWS, chunk=chunk)
    for a, b, n in zip(got, other, NEWS):
        assert np.array_equal(a["tokens"][:n], b["tokens"][:n])
        np.testing.assert_allclose(a["lp"][:n], b["lp"][:n], atol=1e-4)


def test_a_packed_launch_is_each_prompt_alone(whole):
    """A launch of 16 rows in 4 tiles of one page: short prompts side by side,
    and a long prompt's tail beside two short ones."""
    model, params = whole
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (23, 3, 4)]
    news = [9, 7, 9]
    packed, _, _ = serve(model, params, prompts, news, chunk=16, slots=4,
                         launches=[[(0, 0, 16)], [(0, 16, 7), (1, 0, 3), (2, 0, 4)]])
    assert gap(packed, reference_log_probs(ARCH, prompts, packed)) < ATOL


# -- each wrong reading of the published keys fails -------------------------------------------

WRONG = {
    "the-sink-left-out": {"add_swa_attention_sink_bias": False},
    "a-sink-on-the-global-layers-too": {"add_full_attention_sink_bias": True},
    "the-value-scale-left-out": {"attention_value_scale": 1.0},
    "half-the-columns-turned-not-a-third": {"partial_rotary_factor": 0.5},
    "two-thirds-of-the-columns-turned": {"partial_rotary_factor": 0.667},
    "a-window-of-one-less": {"sliding_window": 7},
    "a-window-of-one-more": {"sliding_window": 9},
    "the-global-layers-on-the-window-layers-kv-heads": {"num_key_value_heads": 4},
    "the-window-layers-on-the-global-layers-kv-heads": {"swa_num_key_value_heads": 2},
}


@pytest.mark.parametrize("reading", list(WRONG))
def test_each_wrong_reading_fails_the_tolerance_tenfold(served, reading):
    """The program as it is, on either path, against a reference that reads
    ONE key wrongly: the served log-probabilities miss it by ten tolerances or
    more, so a program with that reading could not pass the test above."""
    prompts, got, _, _ = served
    assert gap(got, reference_log_probs({**ARCH, **WRONG[reading]}, prompts, got)) > 10 * ATOL


def test_the_sink_takes_mass_and_adds_nothing(whole):
    """``_attend``'s rows with a sink sum to less than one, by the sink's own
    share, and are the plain softmax's rows scaled down."""
    model, _ = whole
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((5, 8, 12)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((5, 4, 12)), jnp.float32)
    v = jnp.asarray(np.ones((5, 4, 8)), jnp.float32)     # a row's output is its weights' sum
    mask = jnp.tril(jnp.ones((5, 5), bool))
    sink = jnp.asarray(rng.uniform(-1, 1, 8), jnp.float32)
    plain, sunk = model._attend(q, k, v, mask), model._attend(q, k, v, mask, sink)
    assert plain.shape == sunk.shape == (5, 8, 8)          # (T, H, dv): not the query's width
    np.testing.assert_allclose(plain, 1.0, atol=1e-6)
    s = np.einsum("thd,chd->htc", np.asarray(q), np.repeat(np.asarray(k), 2, axis=1)) / np.sqrt(12)
    s = np.where(np.asarray(mask)[None], s, -np.inf)
    keys = np.exp(s).sum(-1)
    want = keys / (keys + np.exp(np.asarray(sink))[:, None])
    np.testing.assert_allclose(np.asarray(sunk)[..., 0], want.T, rtol=1e-5)
    assert float(np.asarray(sunk).max()) < 1.0


# -- the cache's geometry ---------------------------------------------------------------

CELL_WIDTHS = {**ARCH, "num_attention_heads": 64, "swa_num_attention_heads": 64,
               "num_key_value_heads": 4, "swa_num_key_value_heads": 8, "head_dim": 192,
               "swa_head_dim": 192, "v_head_dim": 128, "swa_v_head_dim": 128,
               "sliding_window": 128, "weight_scales": {}}


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """The cell's heads (64 on 4 and on 8, keys 192 of which 64 turn, values
    128, a window of 128) on a hidden size of 32, in bfloat16."""
    return make_model(tmp_path_factory.mktemp("wide"), CELL_WIDTHS, name="wide",
                      dtype="bfloat16", max_prompt_tokens=2048, max_new_tokens=1024)


def test_the_page_pools_hold_320_values_a_token_a_kv_head_and_every_leaf_is_lane_dense(wide):
    assert wide._heads(0) == Heads(4, 192, 128) and wide._heads(1) == Heads(8, 192, 128)
    assert wide.turning == {"full_attention": 64, "sliding_attention": 64}
    slots, pages, P = 384, 4608, 128
    plan = wide.kv_plan(slots, P, pages)
    sig = plan.state
    assert [sig[leaf][0].shape for leaf in ("kn", "kr", "vf")] == [
        (4, pages, P, 128), (2, pages, P, 128), (4, pages, P, 128)]
    # a slot's ring: 128 places, a place a row with its 8 heads side by side, a key in two parts
    assert [sig[leaf][0].shape for leaf in ("kwn", "kwr", "vw")] == [
        (385, 128, 1024), (385, 128, 512), (385, 128, 1024)]
    assert [len(sig[leaf]) for leaf in wide._leaves()] == [2, 2, 2, 5, 5, 5]
    for leaf in wide._leaves():
        assert all(s.shape[-1] % 128 == 0 and s.dtype == jnp.bfloat16 for s in sig[leaf]), leaf

    def nbytes(leaves):
        return sum(int(np.prod(s.shape)) * 2 for leaf in leaves for s in sig[leaf])

    # what the engine's kv_row_bytes / kv_cache_bytes count: the page leaves' shapes
    assert nbytes(plan.leaves(LeafKind.POOL)) == plan.pool_bytes == pages * 655_360
    assert nbytes(plan.leaves(LeafKind.POOL)) // (pages * P) == plan.row_bytes == 5_120
    assert nbytes(("kwn", "kwr", "vw")) == plan.ring_bytes == 385 * 3_276_800


def test_the_draw_is_the_references_and_the_sink_is_float32(whole):
    model, params = whole
    m = ref.Model(ARCH, SEED, "float32")
    for i in (0, 1, 5):
        w, lp = m.layer(i), params[f"layer{i}"]
        assert set(w) == set(lp) - {"norm1", "norm2"}
        for name, want in w.items():
            assert lp[name].shape == want.shape and np.array_equal(np.asarray(lp[name]), want), name
    sink = np.asarray(params["layer1"]["sink"])
    assert sink.dtype == np.float32 and 2.0 <= sink.min() and sink.max() <= 6.0
    assert "sink" not in params["layer0"] and "sink" not in params["layer5"]
    assert params["layer1"]["wk"].shape == (32, 4, 12) and params["layer5"]["wk"].shape == (32, 2, 12)
    assert params["layer1"]["wv"].shape == (32, 4, 8) and params["layer1"]["wo"].shape == (8, 8, 32)
    assert "s_gate" not in params["layer1"] and params["layer1"]["e_bias"].dtype == jnp.float32


def test_a_config_this_family_does_not_read_is_refused(tmp_path):
    for key, value in (("scoring_func", "softmax"), ("n_group", 2),
                       ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
                       ("partial_rotary_factor", 1.0), ("swa_head_dim", 16),
                       ("add_full_attention_sink_bias", True)):
        with pytest.raises(NotImplementedError, match=key.split("_")[0]):
            make_model(str(tmp_path), {**ARCH, key: value}, name=f"no-{key}")


# -- the share ----------------------------------------------------------------------------

def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer(tmp_path, whole):
    """Sixteen chips of two experts each: their parts of the routed layer add
    up to the whole layer's output (there is no shared expert to count once),
    and that is the uncut reference's."""
    model, params = whole
    lp = params["layer2"]
    u = jnp.asarray(np.random.default_rng(1).standard_normal((13, 32)), jnp.float32)
    whole_y, _ = model._ffn(lp, 2, u, None)
    total, held = 0.0, 0
    for first in range(0, 32, 2):
        part = make_model(str(tmp_path), {**ARCH, "share": {"experts_held": [first, 2]}},
                          name=f"e{first}")
        hp = part.init_params(jax.random.key(0))["layer2"]
        assert np.array_equal(hp["e_up"], lp["e_up"][first:first + 2])
        assert np.array_equal(hp["router"], lp["router"]) and np.array_equal(hp["e_bias"], lp["e_bias"])
        y, stats = part._ffn(hp, 2, u, None)
        total = total + y
        held += int(stats["routed_held"])
        assert int(stats["routed_held"]) + int(stats["routed_absent"]) == 13 * 3
    assert held == 13 * 3                                       # every pick is held by one chip
    np.testing.assert_allclose(total, whole_y, atol=1e-5)
    m = ref.Model(ARCH, SEED, "float32")
    np.testing.assert_allclose(whole_y, ref.experts(m, m.layer(2), np.asarray(u)), atol=2e-5)


def test_the_routers_picks_are_the_ones_mla_gets_for_the_same_logits(tmp_path, whole, monkeypatch):
    """Both families hand `topk_route` the same call: for one router, one bias
    and one input the picks and the weights are equal to the bit."""
    from tests import test_mla

    model, params = whole
    seen = []

    def spy(logits, k, **kw):
        w, e = topk_route(logits, k, **kw)
        seen.append((np.asarray(logits), k, {n: v for n, v in kw.items() if n != "select_bias"},
                     np.asarray(kw["select_bias"]), np.asarray(w), np.asarray(e)))
        return w, e

    monkeypatch.setattr(dec, "topk_route", spy)
    monkeypatch.setattr(mla, "topk_route", spy)
    latent = test_mla.make_model(str(tmp_path), {
        **test_mla.ARCH, "hidden_size": 32, "n_routed_experts": 32, "num_experts_per_tok": 3,
        "routed_scaling_factor": 1.0}, name="m")
    lp = params["layer2"]
    theirs = dict(latent.init_params(jax.random.key(0))["layer2"],
                  router=lp["router"], e_bias=lp["e_bias"])
    u = jnp.asarray(np.random.default_rng(4).standard_normal((17, 32)), jnp.float32)
    model._ffn(lp, 2, u, None)
    latent._ffn(theirs, 2, u, None)
    (r1, k1, kw1, b1, w1, e1), (r2, k2, kw2, b2, w2, e2) = seen
    assert k1 == k2 == 3 and kw1 == kw2 == {"normalize": True, "scale": 1.0, "scoring": "sigmoid"}
    assert np.array_equal(r1, r2) and np.array_equal(b1, b2)
    assert np.array_equal(e1, e2) and np.array_equal(w1, w2)
    assert len({tuple(sorted(row)) for row in e1}) > 1          # the picks are decided by the token


# -- the kernel ---------------------------------------------------------------------------

def gathered(kn, kr, vf, bt):
    """The pools' rows of each lane's block-table row, by head: keys (B, C, KV,
    dk) with the turning part first, values (B, C, KV, dv)."""
    kv, _, P, dn = kn.shape
    b, pps = bt.shape
    dr = kr.shape[3] // (kv // kr.shape[0])
    at = np.asarray(bt)
    kng = np.asarray(kn.astype(jnp.float32))[:, at].reshape(kv, b, pps * P, dn).transpose(1, 2, 0, 3)
    krg = np.asarray(kr.astype(jnp.float32))[:, at].reshape(kr.shape[0], b, pps * P, -1, dr)
    krg = krg.transpose(1, 2, 0, 3, 4).reshape(b, pps * P, kv, dr)
    vg = np.asarray(vf.astype(jnp.float32))[:, at].reshape(kv, b, pps * P, -1).transpose(1, 2, 0, 3)
    return np.concatenate([krg, kng], axis=-1), vg


KERNEL_CASES = {
    # last position a lane attends (0 with live False: a lane that is not live), pages a cell
    "lanes-of-unequal-length": ([70, 5, 33, 95], [True] * 4, 2),
    "a-lane-that-is-not-live-between-two-that-are": ([40, 0, 17], [True, False, True], 2),
    "a-context-of-one-position": ([0, 64, 0], [True, True, True], 2),
    "contexts-that-end-on-a-blocks-last-row": ([31, 63, 95, 32], [True] * 4, 2),
    "a-cell-of-one-page-and-a-cell-of-the-whole-row": ([70, 5, 33, 95], [True] * 4, 1),
    "a-cell-wider-than-what-any-lane-holds": ([20, 3], [True, True], 6),
}


def walk_case(case: str):
    """`KERNEL_CASES[case]`'s pools, block table, queries and work list, at the
    cell's global heads over pages of 16 positions."""
    last, live, kb = KERNEL_CASES[case]
    P, pps, kv, h = 16, 6, 4, 64
    b = len(last)
    rng = np.random.default_rng(sum(last))
    bf = jnp.bfloat16
    n_pages = b * pps + 1
    kn = jnp.asarray(rng.standard_normal((kv, n_pages, P, 128)), bf)
    kr = jnp.asarray(rng.standard_normal((kv // 2, n_pages, P, 128)), bf)
    vf = jnp.asarray(rng.standard_normal((kv, n_pages, P, 128)), bf)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(b, pps), jnp.int32)
    q = jnp.asarray(2.0 * rng.standard_normal((b, h, 192)), bf)
    pos = jnp.asarray(last, jnp.int32)
    work = la.work_list(jnp.where(jnp.asarray(live), pos, 0), bt, P, kb)
    return q, (kn, kr, vf), bt, pos, work


def walked(model, q, pools, work, kv=None, **more):
    """`head_walk` of pools by head, or (``kv``: their KV heads) of rings whose
    rows hold the heads side by side, with the queries parted as the family
    parts them."""
    heads = kv or pools[0].shape[0]
    return la.head_walk(q[..., 64:], model._pad_queries(q[..., :64], heads, 2), *pools, work,
                        scale=model._scale(), kv=kv, interpret=True, **more)


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_grouped_walk_in_the_interpreter_is_plain_attention_on_gathered_rows(wide, case):
    """`head_walk` at the cell's heads (64 query rows on 4 KV heads, keys in a
    passing part of 128 and a turning part of 64 two heads a row, values of 128
    in a pool of their own) over pages of 16 positions, against `_attend` on
    each lane's gathered rows: bfloat16 products with float32 sums both ways,
    so what differs is the order of a lane's blocks and the context's rounding
    to bfloat16 (2 ** -8 of values of a few units)."""
    last, live, kb = KERNEL_CASES[case]
    q, (kn, kr, vf), bt, pos, work = walk_case(case)
    b, (P, pps), h, bf = len(last), (16, 6), 64, jnp.bfloat16
    blocks = [p // (kb * P) + 1 if on else 1 for p, on in zip(last, live)]
    assert int(work["items"]) == sum(blocks)                    # each lane as far as IT needs
    got = walked(wide, q, (kn, kr, vf), work)
    assert got.shape == (b, h, 128) and got.dtype == bf
    k, v = gathered(kn, kr, vf, bt)
    mask = (jnp.arange(pps * P)[None, :] <= pos[:, None])[:, None, :]
    want = wide._attend(q[:, None], jnp.asarray(k, bf), jnp.asarray(v, bf), mask)[:, 0]
    rows = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32))[rows], np.asarray(want)[rows],
                               atol=2e-2)
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()  # a discarded row is finite
    # and the program's own gather of the padded table says the same
    xla = wide._decode_gather(q, (kn, kr, vf), bt, pos, wide._heads(0))
    np.testing.assert_allclose(np.asarray(xla)[rows], np.asarray(want)[rows], atol=1e-5)


def walked_sha256(case: str, model) -> str:
    q, pools, _, _, work = walk_case(case)
    return hashlib.sha256(np.asarray(walked(model, q, pools, work)).tobytes()).hexdigest()


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_walk_without_a_sink_is_to_the_bit_what_it_was(wide, case):
    """The sink is an operand only where there is one, and groups of 16 rows
    take the path they took: `head_walk`'s output for the global layers'
    cases, every byte, is what PR 49's kernel (26a6ef3) gave for the same
    pools (`walked_sha256` on a `git archive` of that commit, in the
    interpreter on the CPU)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                           "head_walk_pr49.json"), encoding="utf-8") as f:
        want = json.load(f)
    if want["jax"] != jax.__version__:
        pytest.skip(f"the fixture was written under jax {want['jax']}")
    assert walked_sha256(case, wide) == want["sha256"][case]


# -- the window layers' rings, read in place (ISSUE 50) --------------------------------------

RING_CASES = {
    # the position each lane's step is at (its row is in the ring already), live or not
    "rings-partly-filled": ([5, 0, 60, 126], [True] * 4),
    "rings-full-for-the-first-time": ([127, 128, 129], [True] * 3),
    "rings-wrapped-many-times": ([1000, 2047, 4095, 255], [True] * 4),
    "a-lane-that-is-not-live-between-two-that-are": ([40, 300, 700], [True, False, True]),
}


@pytest.mark.parametrize("sunk", [True, False], ids=["with-the-sink", "without-a-sink"])
@pytest.mark.parametrize("kv", [8, 4], ids=["groups-of-8-rows", "groups-of-16-rows"])
@pytest.mark.parametrize("case", list(RING_CASES))
def test_the_ring_read_in_place_is_the_gathered_ring_attended_in_xla(wide, case, kv, sunk):
    """`head_walk` over rings as they lie (a slot's ring a page of 128 places,
    a place a row with its heads side by side, a key in its two parts)
    through `ring_work`, one block a lane, against
    `_attend` on each lane's gathered ring under the step plan's own mask
    (place r holds the newest position <= pos that is r modulo 128, or
    nothing yet): before the ring is full, as it fills, wrapped many times,
    and a lane that is not live (it reads the sentinel ring and comes back
    finite); 64 query rows on 8 KV heads (every row over each head's keys, a
    row keeping its own head's) and on 4 (a head's 16 rows apart); the sink
    as one more term of the denominator, or none."""
    pos, live = (np.asarray(x) for x in RING_CASES[case])
    b, W, h, bf = len(pos), 128, 64, jnp.bfloat16
    rng = np.random.default_rng(int(pos.sum()) + kv)
    rings = tuple(jnp.asarray(rng.standard_normal((b + 1, W, kv * width)), bf)
                  for width in (128, 64, 128))
    q = jnp.asarray(2.0 * rng.standard_normal((b, h, 192)), bf)
    # sinks that hold a visible part of a row's mass: about the logarithm of the keys' sum
    sink = jnp.asarray(rng.uniform(4.0, 9.0, h), jnp.float32) if sunk else None
    ring = jnp.asarray(np.where(live, rng.permutation(np.arange(1, b + 1)), 0), jnp.int32)
    work = la.ring_work(ring, jnp.asarray(np.where(live, np.minimum(pos, W - 1), 0)))
    assert int(work["items"]) == b and work["pages"].shape == (b,)   # one block a lane
    got = np.asarray(walked(wide, q, rings, work, kv=kv, sink=sink).astype(jnp.float32))
    assert got.shape == (b, h, 128) and np.isfinite(got).all()
    kn, kr, v = (np.asarray(x.astype(jnp.float32))[np.asarray(ring)].reshape(b, W, kv, -1)
                 for x in rings)
    k = np.concatenate([kr, kn], axis=-1)
    rpos = pos[:, None] - ((pos[:, None] - np.arange(W)[None, :]) % W)   # `_step_plan`'s
    want = np.asarray(wide._attend(q[:, None], jnp.asarray(k, bf), jnp.asarray(v, bf),
                                   jnp.asarray(rpos >= 0)[:, None, :], sink)[:, 0])
    np.testing.assert_allclose(got[live], want[live], atol=2e-2)
    if sunk:   # and the sink is seen: without it the rows are another answer
        plain = np.asarray(walked(wide, q, rings, work, kv=kv).astype(jnp.float32))
        assert np.abs(plain[live] - got[live]).max() > 0.1
    # the program's own step in XLA (the rows written at each lane's place, then the rings
    # gathered a place a row) says the same
    m = {"t": None, "ring_walk": "xla", "w_ring": ring, "roff": jnp.asarray(pos % W),
         "mask_win": jnp.asarray(rpos >= 0)[:, None, :]}
    xla, _ = wide._attend_ring(q, jnp.asarray(k[np.arange(b), pos % W], bf),
                                 jnp.asarray(v[np.arange(b), pos % W], bf), rings, m, sink,
                                 Heads(kv, 192, 128))
    np.testing.assert_allclose(np.asarray(xla)[live], want[live], atol=1e-5)


def test_head_fits_takes_groups_of_8_rows_together_and_of_16_apart():
    bf = jnp.bfloat16
    assert la.head_fits(128, 64, 8, 128, 128, 128, bf) and not la._split(64, 8)
    assert la.head_fits(128, 64, 4, 128, 128, 128, bf) and la._split(64, 4)
    assert la.head_fits(16, 32, 2, 128, 128, 128, bf) and la._split(32, 2)
    assert la.head_fits(128, 32, 32, 128, 0, 128, bf) and not la._split(32, 32)   # a key in one part
    for refused in ((128, 8, 4, 128, 128, 128, bf),       # 8 query rows in all: half a tile
                    (128, 64, 8, 128, 128, 128, jnp.float32), (128, 64, 8, 64, 128, 128, bf),
                    (8, 64, 8, 128, 128, 128, bf), (128, 60, 8, 128, 128, 128, bf)):
        assert not la.head_fits(*refused)


def test_a_step_steered_to_the_kernel_is_the_step_in_xla(wide):
    """The whole step both ways from one state: prefill in XLA, then a step
    whose seven attention layers run in the kernel (the backend named ``tpu``
    for this family's module alone, the kernel interpreted: the two global
    layers over their pages, the five window layers over their rings in place
    with their sinks) against the step that gathers: the same
    log-probabilities to bfloat16's rounding, the same tokens' ids named, and
    the walk's columns of ``acc`` say which ran."""
    params = wide.init_params(jax.random.key(0))
    # a draw in which no router's pick turns on bfloat16's rounding (with seeds 9 and 10 one
    # does: an expert swapped moves a row's log-probabilities by tenths; in float32 the two
    # steps agree to 2e-6 under every draw)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (137, 5)]   # a full ring; a start
    _, _, state = serve(wide, params, prompts, [6, 6], chunk=16, page=16, steps=0)
    plain_state, plain = jax.jit(wide.step)(params, state)
    calls = []
    with in_the_kernel(calls):
        steered_state, steered = jax.jit(lambda p, s: wide.step(p, s))(params, state)
    # one call a layer; the window layers' with their sinks
    assert [sink is not None for sink in calls] == [False, True, True, True, True, False, True]
    np.testing.assert_allclose(np.asarray(steered_state["lp"][:2, 1]),
                               np.asarray(plain_state["lp"][:2, 1]), atol=5e-2)
    acc_x, acc_k = np.asarray(plain["acc"]).astype(int), np.asarray(steered["acc"]).astype(int)
    assert acc_x[1, 10] == 0 and acc_x[1, 11] == 14 and acc_k[1, 10] == 14 and acc_k[1, 11] == 0
    assert acc_k[1, 8] == acc_x[1, 8] == 2 * (138 + 6)           # attended: the live rows
    kb = max(1, wide.step_keys // 16)
    # walked: a cell a lane (the one that is not live walks one too), not the padded table
    assert acc_k[1, 9] == 2 * SLOTS * kb * 16 < acc_x[1, 9] == 2 * SLOTS * 192 * 16


# -- through the engine ---------------------------------------------------------------------

def test_through_the_engine_the_ledger_counts_the_leaves_and_the_counters_move_by_phase(tmp_path):
    """The family on `decoder`'s entry points: the engine's loop, its page
    ledger with rings, `/stats`' bytes from the signature's shapes (three page
    leaves a global layer whose widths differ from the rings'), and the new
    counters on `/metrics` in both phases."""
    import asyncio

    from tpuserve.config import GenserveConfig
    from tpuserve.genserve import GenEngine
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime

    model = make_model(str(tmp_path), name="eng")
    rt = build_runtime(model, compile_forward=False)
    metrics = Metrics()
    eng = GenEngine(model, rt, metrics, GenserveConfig(
        slots=SLOTS, kv_paging=True, kv_page_tokens=PAGE, prefill_chunk=CHUNK))
    eng.compile()
    model.bind_metrics(metrics)
    assert eng.pages.rings == SLOTS + 1
    # two global layers x 2 KV heads x (12 + 8) values x 4 B a token; pages of 4 tokens
    assert eng.kv_row_bytes() == 2 * 2 * 20 * 4 == 320
    assert eng.kv_cache_bytes() == eng.pages.pages * PAGE * 320
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 96, n).tolist() for n in (19, 3, 24, 10)]
    max_news = [12, 5, 7, 3]

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
    assert [r["n_tokens"] for r in results] == max_news
    want = reference_log_probs(ARCH, [np.asarray(p) for p in prompts], [
        {"tokens": np.asarray(r["tokens"]), "n_new": r["n_tokens"]} for r in results])
    for r, lp in zip(results, want):
        got = np.asarray(r["logprobs"]["values"])
        at_ids = np.take_along_axis(lp, np.asarray(r["logprobs"]["ids"], np.int64), axis=-1)
        np.testing.assert_allclose(got, at_ids, atol=ATOL)
    c = metrics.counter_values()
    attended = {ph: c[f"attn_rows_attended_total{{model=eng,phase={ph}}}"]
                for ph in ("prefill", "decode")}
    assert attended["decode"] == 2 * sum(sum(range(len(p) + 1, len(p) + m))
                                          for p, m in zip(prompts, max_news))
    assert attended["prefill"] >= 2 * sum(len(p) for p in prompts)
    for ph in ("prefill", "decode"):
        assert c[f"attn_rows_walked_total{{model=eng,phase={ph}}}"] >= attended[ph]
        assert c[f"attn_walks_total{{model=eng,phase={ph},walk=xla}}"] > 0
        assert c.get(f"attn_walks_total{{model=eng,phase={ph},walk=kernel}}", 0) == 0
    # a live lane an attention layer a step: two global layers and five window layers
    steps = sum(m - 1 for m in max_news)
    assert c["attn_walks_total{model=eng,phase=decode,walk=xla}"] == 7 * steps


# -- the other families' programs, and the two copies ------------------------------------------

def test_xings_programs_lower_to_the_parents_text(tmp_path):
    """`tests/test_mla_hc.py` holds the five older families' toy programs to
    the text they lowered to at PR 45; `paged_lm`'s geometry changed under the
    sixth too (ISSUE 49): `mla_hc`'s hashes are of PR 48 (bd78f0a), written by
    `tests.test_mla_hc.lowered_sha256` on a `git archive` of that commit."""
    from tests import test_mla_hc

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                           "lowered_toys_pr45.json"), encoding="utf-8") as f:
        want = json.load(f)
    if want["jax"] != jax.__version__:
        pytest.skip(f"the fixture was written under jax {want['jax']}")
    assert test_mla_hc.lowered_sha256("mla_hc", str(tmp_path)) == want["sha256"]["mla_hc"]


def test_the_repo_and_the_benchmark_copies_of_the_reference_agree():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "reference", "decoder_sink.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference_decoder_sink_for_test", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    seqs = [np.random.default_rng(6).integers(0, 96, n) for n in (17, 5)]
    a = ref.log_probs(ref.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    b = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0])
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    low = theirs.log_probs(theirs.Model(ARCH, SEED, "bfloat16"), seqs, [9, 0], True)
    assert float(np.abs(low[0] - a[0]).max()) > 1e-3  # the control's rounding is seen
    # the two files' bodies are one text up to what the harness calls
    with open(path, encoding="utf-8") as f:
        bench = f.read()
    with open(ref.__file__, encoding="utf-8") as f:
        mine = f.read()
    body = bench[bench.index("BELL_STD ="):bench.index("# -- what the harness calls")].rstrip()
    assert hashlib.sha256(body.encode()).hexdigest() == hashlib.sha256(
        mine[mine.index("BELL_STD ="):].rstrip().encode()).hexdigest()
