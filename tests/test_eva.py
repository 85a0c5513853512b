"""The `eva` family (ISSUE 55) against its plain reference at a small size on the
CPU: packed chunked prefill and decode through a ring a slot and pages of
summary rows in one pool a layer, against the reference's full pass (windows
that close in decode, inside a piece, at a piece's last row; a prompt of
exactly a window; a padded tail); each wrong reading of the layer failing; the
virtual block table's walk (`head_walk` with a key in one part, ISSUE 56, in
the Pallas interpreter: the toy's step against the gather's step, and at the
cell's heads against a plain float32 softmax); a launch's attention in ONE
kernel call a layer (`ops/launch_attention.py` `launch_walk`, ISSUE 58, in the
interpreter: against `_tile` in XLA, a plain float32 softmax over each row's
visible keys, and the work list against the masks); a lane that is
not live keeping ring, pages and lanes to the bit; the cache's geometry and what
`/stats` says of it; the weights recipe; the counters; and the two copies of
the reference. THE POOLS HOLD A POSITION AS ONE ROW, its KV heads side by side
(ISSUE 63): the flat pools against the parent's by-head pools and by-head
kernel (`tests/fixtures/eva_by_head_pr62.npz`), and the lowered programs'
scatters counted."""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import eva_reference as ref
from tpuserve.config import GenserveConfig, ModelConfig
from tpuserve.genserve.model import PrefillPiece
from tpuserve.models import build
from tpuserve.models import decoder as dec
from tpuserve.models import eva
from tpuserve.models import seeded
from tpuserve.ops import lane_attention as la
from tpuserve.ops import launch_attention as lat

# Two layers; 4 query heads of 16 on 2 KV heads (the cell has no grouping; the
# walk is written for any); a window of 16 in chunks of 4, so a page is 4
# summary rows and stands for 16 positions; two prediction blocks of 40 ids.
ARCH = {"model_type": "evabyte", "attention_class": "eva", "attention_bias": False,
        "chunk_size": 4, "window_size": 16, "num_chunks": None, "fp32_ln": False,
        "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 96, "mixedp_attn": True, "norm_add_unit_offset": True,
        "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
        "num_pred_heads": 2, "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 40}
SEED = 11
MAX_PROMPT, MAX_NEW, PAGE, CHUNK, SLOTS = 40, 24, 4, 8, 3
# float32 program against a float32 reference: what differs is the order of
# sums (a running softmax over the exact part and key blocks of summary pages,
# a ring read in ring order, rows pooled from a cache): 1e-6 on
# log-probabilities of a few units; 2e-4 leaves room for a longer toy.
ATOL = 2e-4
# A wrong reading must move some served log-probability by at least this: two
# hundred tolerances, a tenth of a nat.
WRONG_BY = 0.04


def make_model(tmp_path, arch=ARCH, name="eva", dtype="float32", **options):
    path = os.path.join(tmp_path, f"{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(arch, f)
    cfg = ModelConfig(name=name, family="eva", dtype=dtype, batch_buckets=[1],
                      options={"config_file": path, "draw_weights_seed": SEED,
                               "max_prompt_tokens": MAX_PROMPT, "max_new_tokens": MAX_NEW,
                               **options})
    return build(cfg)


def zeros(struct):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def side_by_side(pool):
    """A pool by head (KV, pages, P, hd) -> the family's: a position one row,
    its heads side by side, (pages, P, KV x hd)."""
    kv, pages, P, hd = pool.shape
    return pool.transpose(1, 2, 0, 3).reshape(pages, P, kv * hd)


@pytest.fixture(scope="module")
def by_head():
    """What the PARENT's tree (a9b7e16: pools by head, `launch_walk` over blocks
    by head) gave on this file's values: `pools/<leaf>/<layer>` the toy's pools
    after the packed case's launches and six steps, laid side by side;
    `launch/<kind>/<case>` its kernel's output in the interpreter on a launch
    case's values (the toy's whole, the cell heads' by sha256)."""
    with np.load(os.path.join(os.path.dirname(__file__), "fixtures",
                              "eva_by_head_pr62.npz")) as f:
        return dict(f)


def serve(model, params, prompts, max_news, chunk=CHUNK, launches=None, slots=SLOTS, page=PAGE,
          steps=None, state=None, steer=None, steer_launch=None):
    """What the engine does, by hand: the prompts' pieces through the prefill
    program (``launches``: lists of (slot, start, length); else a prompt alone,
    a chunk a launch), then steps until every lane is done -> (extract() a
    slot, the last step's out-block, the state). ``steer`` and ``steer_launch``:
    a context the steps, the launches are traced and run in."""
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

    def piece(slot, start, length):
        ids = np.zeros((model.max_prompt,), np.int32)
        ids[: len(prompts[slot])] = prompts[slot]
        item = (ids, np.int32(len(prompts[slot])), np.int32(3), np.int32(max_news[slot]),
                np.float32(0.0), np.int32(dec.LOGPROBS))
        cache = {"pages": np.arange(1 + slot * pps, 1 + (slot + 1) * pps, dtype=np.int32),
                 "ring": np.int32(slot + 1)}
        return PrefillPiece(slot, item, start, length, cache)

    with (steer_launch or contextlib.nullcontext)():
        for pieces in launches:
            state = prefill(params, state, model.pack_prefill([piece(*p) for p in pieces], chunk,
                                                              k), chunk=chunk)
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
    model = make_model(str(tmp_path_factory.mktemp("eva")))
    return model, model.init_params(jax.random.key(0))


def prompts_of(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, ARCH["vocab_size"], n).astype(np.int32) for n in lengths]


def gaps(got, prompts, news, arch=ARCH, wrong="", low=False):
    """Per request: |served - reference| over the served top-8 log-probabilities
    of every generated position, the reference teacher-forced on the served
    tokens in one full pass."""
    m = ref.Model(arch, SEED, "float32", wrong=wrong)
    seqs = [np.concatenate([p, g["tokens"][:n - 1]]) for p, g, n in zip(prompts, got, news)]
    lps = ref.log_probs(m, seqs, [len(p) - 1 for p in prompts], low)
    return [np.abs(np.take_along_axis(lp, g["lp_ids"][:n], axis=-1) - g["lp"][:n])
            for lp, g, n in zip(lps, got, news)]


# Each case: prompt lengths, tokens asked for, the launches (None: a prompt
# alone, a chunk of 8 a launch, so a window's edge at 16 or 32 is a piece's
# LAST row). Decode: 14 + 24 closes windows at steps 2 and 18; 16 + 5 begins
# its ring again at the first step.
CASES = {
    "alone: edges in decode, at a piece's last row, a padded tail":
        ((14, 37, 3), [24, 12, 7], None),
    "a prompt of exactly a window, and one a row short of it":
        ((16, 15, 32), [5, 5, 5], None),
    "packed: edges INSIDE a piece, pieces of three prompts in a launch":
        ((14, 37, 3), [24, 12, 7],
         [[(1, 0, 8)], [(1, 8, 4), (0, 0, 4)], [(1, 12, 8)], [(0, 4, 8)], [(1, 20, 8)],
          [(0, 12, 2), (2, 0, 3)], [(1, 28, 8)], [(1, 36, 1)]]),
}


@pytest.mark.parametrize("path", eva.TILE_PATHS[::-1])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prefill_then_decode_through_ring_and_pages_is_the_reference_full_pass(whole, case, path):
    """``path``: the launches' attention a tile at a time in XLA, and in ONE
    kernel call a layer (ISSUE 58) through the Pallas interpreter."""
    model, params = whole
    lengths, news, launches = CASES[case]
    prompts = prompts_of(lengths)
    got, out, _ = serve(model, params, prompts, news, launches=launches,
                        steer_launch=in_the_launch if path == "tile_kernel" else None)
    for g, gap, n in zip(got, gaps(got, prompts, news), news):
        assert int(g["n_new"]) == n
        assert float(gap.max()) < ATOL, case
    # the device's sums: every prompt token and every step's live token, by the rows its
    # index sets hold
    acc = np.asarray(out["acc"]).astype(int)
    W, P = model.window, model.rows
    pos_p = np.concatenate([np.arange(n) for n in lengths])
    pos_d = np.concatenate([np.arange(n, n + new - 1) for n, new in zip(lengths, news)])
    for row, pos in zip(acc, (pos_p, pos_d)):
        exact, summary = int(np.sum(pos % W + 1)), int(np.sum(pos // W * P))
        assert list(row[:3]) == [exact + summary, exact, summary]
        assert row[3] == model.n_layers * int(np.sum(pos % model.chunk == model.chunk - 1))
        assert row[4] == int(np.sum(pos % W == W - 1))
    # a step's lanes and a launch's tiles, times the layers, by the path each took
    T = CHUNK // model.kv_prefill_pieces(CHUNK, PAGE)
    tiles = model.n_layers * sum(-(-n // T) for of in launches or [[(0, 0, min(CHUNK, n - s))]
                                 for n in lengths for s in range(0, n, CHUNK)] for _, _, n in of)
    assert list(acc[0, 5:9]) == [0, 0] + [tiles * (path == p) for p in eva.TILE_PATHS]
    assert list(acc[1, 5:9]) == [0, model.n_layers * len(pos_d), 0, 0]


@pytest.fixture(scope="module")
def answers(whole):
    """The first case's served answers: what every wrong reading is held against."""
    model, params = whole
    lengths, news, _ = CASES[sorted(CASES)[1]]
    prompts = prompts_of(lengths)
    got, _, _ = serve(model, params, prompts, news)
    return prompts, news, got


@pytest.mark.parametrize("wrong", ref.WRONG + ("chunk_of_2", "chunk_of_8", "lowp"))
def test_each_wrong_reading_of_the_layer_is_told_from_the_served_one(answers, wrong):
    prompts, news, got = answers
    assert max(float(g.max()) for g in gaps(got, prompts, news)) < ATOL
    if wrong.startswith("chunk_of_"):
        gap = gaps(got, prompts, news, arch={**ARCH, "chunk_size": int(wrong[-1])})
    elif wrong == "lowp":   # the check's control: inputs at 3 mantissa bits, the stream in bfloat16
        gap = gaps(got, prompts, news, low=True)
    else:
        gap = gaps(got, prompts, news, wrong=wrong)
    # two layers of a toy: a stream in bfloat16 alone moves a log-probability by 0.01, fifty
    # tolerances (the cell's control rounds the products' inputs too)
    assert max(float(g.max()) for g in gap) > (0.005 if wrong == "bf16_stream" else WRONG_BY), wrong


def test_the_summary_rows_hold_a_visible_part_of_a_softmaxs_mass(whole):
    """What a lane reads of its past through summaries matters: with the
    summaries left out, positions past the first window move by far more than
    the tolerance, and positions inside it not at all."""
    model, params = whole
    prompts, news = prompts_of((14,)), [24]
    got, _, _ = serve(model, params, prompts, news)
    gap = gaps(got, prompts, news, wrong="no_summaries")[0]
    assert float(gap[:2].max()) < ATOL          # positions 13, 14, 15: the first window
    assert float(np.median(gap[3:].max(axis=-1))) > WRONG_BY


# -- a step's walk of the virtual block table --------------------------------------------

class NamedTpu:
    """``jax`` as ``eva`` sees it with the backend named ``tpu``: the family's
    trace-time choice takes its TPU branch, and nothing else does."""

    default_backend = staticmethod(lambda: "tpu")

    def __getattr__(self, name):
        return getattr(jax, name)


@contextlib.contextmanager
def in_the_walk(seen=None):
    """What is traced inside takes ``eva``'s TPU branch at the toy's shapes
    (which the interpreter takes and ``head_fits`` would refuse), ``head_walk``
    in the Pallas interpreter (``seen`` gets each call's operands and work
    list)."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(eva, "jax", NamedTpu())
        m.setattr(eva.EvaServing, "_walks", lambda self, P: True)

        def walk(*a, f=la.head_walk, **k):
            if seen is not None:
                seen.append(a)
            return f(*a, interpret=True, **k)

        m.setattr(la, "head_walk", walk)
        yield


def test_a_steps_walk_of_the_virtual_table_is_the_gather_and_reads_live_rows_only(whole):
    """Every step in the kernel (``head_walk`` with a key in one part, the
    interpreter): the same answers as on the gather's path, ONE work list a
    step shared by the layers, a table of summary pages then ring pages,
    lengths the rows the index sets hold, a lane that is not live one row of
    the sentinel, and the counter."""
    model, params = whole
    prompts, news = prompts_of((14, 37, 3)), [24, 12, 7]
    plain, _, _ = serve(model, params, prompts, news)
    seen = []
    got, out, _ = serve(model, params, prompts, news, steer=lambda: in_the_walk(seen))
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_allclose(a["lp"], b["lp"], atol=ATOL)
    # one trace: a call a layer, a key in one part, every layer the same work list
    assert len(seen) == model.n_layers and all(a[1] is None and a[3] is None for a in seen)
    assert all(a[5] is seen[0][5] for a in seen)
    acc = np.asarray(out["acc"]).astype(int)
    assert acc[1, 5] == model.n_layers * sum(n - 1 for n in news) and acc[1, 6] == 0
    # the plan of one step, as arrays: lane 0 live at position 21, lane 1 at 37, lane 2 free
    pps, c, P = model.kv_plan(1, PAGE).pages_per_slot, model.chunk, model.rows
    state = zeros(model.kv_plan(SLOTS, PAGE).state)
    bt = np.arange(1, 1 + SLOTS * pps).reshape(SLOTS, pps).astype(np.int32)
    state = dict(state, bt=jnp.asarray(bt), ring=jnp.asarray([1, 2, 3], jnp.int32))
    with in_the_walk():
        m = model._step_plan(state, jnp.asarray([True, True, False]),
                             jnp.asarray([21, 37, 9], jnp.int32))
    first = c * (SLOTS + 1)
    table, rows = np.asarray(m["table"]), np.asarray(m["rows_seen"])
    assert m["path"] == "head_walk" and table.shape[1] == pps + c
    assert list(rows) == [1 * P + 6, 2 * P + 6, 1]
    assert list(table[0, :1 + c]) == [first + bt[0, 0]] + [c * 1 + i for i in range(c)]
    assert list(table[1, :2 + c]) == [first + bt[1, 0], first + bt[1, 1]] + [c * 2 + i
                                                                              for i in range(c)]
    assert not table[2].any() and not table[0, 1 + c:].any()
    # the work list: each lane's blocks as far as ITS rows go, the free lane's one
    work, kb = m["work"], model.walk_block
    need = [-(-int(r) // (kb * P)) for r in rows]
    assert int(work["items"]) == sum(need)
    assert list(np.asarray(work["lane"])[:sum(need)]) == [b for b, n in enumerate(need)
                                                          for _ in range(n)]
    assert list(np.asarray(work["last"])) == [int(r) - 1 for r in rows]
    # off the TPU the plan holds no work list and the gather reads the same table
    m = model._step_plan(state, jnp.asarray([True, True, False]),
                         jnp.asarray([23, 37, 9], jnp.int32))
    assert m["path"] == "gather" and m["work"] is None
    # position 37 does not end a chunk, 21 does not either; 23 does: its summary's place
    assert list(np.asarray(m["sum_page"])) == [first + bt[0, 1], first, first]
    assert list(np.asarray(m["sum_off"]))[:1] == [(23 % 16) // c]
    assert list(np.asarray(m["ring_page"])) == [c * 1 + 7 // P, c * 2 + 5 // P, 0 + 9 // P]


# A step's lanes at the cell's heads (32 query rows on 32 KV heads of 128) over a
# window of 64 in chunks of 4: a page of 16 rows, a ring of 4 pages. The position
# each lane's step is at (its row is in the ring already), live or not.
WALK_CASES = {
    "a-lane-in-its-first-window": ([37, 5, 0], [True] * 3),           # no summary page
    "a-lane-at-a-windows-last-place": ([63, 191, 127], [True] * 3),   # j = W - 1
    "lanes-at-three-and-a-half-windows": ([224, 225, 239], [True] * 3),
    "a-lane-that-is-not-live-beside-live-ones": ([100, 230, 40], [True, False, True]),
}


@pytest.fixture(scope="module")
def cell_heads(tmp_path_factory):
    arch = {**ARCH, "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 32,
            "window_size": 64, "chunk_size": 4}
    model = make_model(str(tmp_path_factory.mktemp("heads")), arch, name="heads",
                       dtype="bfloat16", max_prompt_tokens=232)
    assert (model.hd, model.rows, model.kv) == (128, 16, 32)
    return model


@pytest.mark.parametrize("block_pages", [1, 2], ids=["one-page-a-block", "two-pages-a-block"])
@pytest.mark.parametrize("case", list(WALK_CASES))
def test_the_walk_with_a_key_in_one_part_is_plain_float32_attention(cell_heads, monkeypatch, case,
                                                                    block_pages):
    """`head_walk` with NO second key part (ISSUE 56) in the Pallas
    interpreter, at EVA's shapes (32 query rows on 32 KV heads: every row over
    each head's keys, a row keeping its own head's), over the step plan's own
    virtual table and work list in a pool of rings and summary pages, a
    position ONE row with its heads side by side (``kv=``, ISSUE 63): against
    a plain float32 softmax over each lane's rows taken from the pool one by
    one. bfloat16 products with float32 sums in the kernel and a context
    rounded to bfloat16: 2 ** -8 of values near 1."""
    model = cell_heads
    pos, live = (np.asarray(x) for x in WALK_CASES[case])
    slots, (c, P, W), hd = len(pos), (model.chunk, model.rows, model.window), model.hd
    pps = model.kv_plan(1, P).pages_per_slot
    rng = np.random.default_rng(int(pos.sum()) + block_pages)
    n_pages = c * (slots + 1) + slots * pps + 1
    kp, vp = (jnp.asarray(rng.standard_normal((model.kv, n_pages, P, hd)), jnp.bfloat16)
              for _ in range(2))
    q = jnp.asarray(2.0 * rng.standard_normal((slots, model.kv, hd)), jnp.bfloat16)
    state = {"bt": jnp.asarray(rng.permutation(np.arange(1, 1 + slots * pps))
                               .reshape(slots, pps), jnp.int32),
             "ring": jnp.asarray(rng.permutation(np.arange(1, slots + 1)), jnp.int32),
             "pos": jnp.zeros((slots,), jnp.int32), "kf": [side_by_side(kp)]}
    monkeypatch.setattr(eva, "jax", NamedTpu())
    monkeypatch.setattr(model, "walk_block", block_pages)
    assert model._walks(P)                      # the cell's shapes fit the kernel as they are
    m = model._step_plan(state, jnp.asarray(live), jnp.asarray(pos, jnp.int32))
    rows = np.where(live, pos // W * P + pos % W + 1, 1)
    assert m["path"] == "head_walk" and list(np.asarray(m["rows_seen"])) == list(rows)
    assert int(m["work"]["items"]) == sum(-(-int(r) // (block_pages * P)) for r in rows)
    flat = side_by_side(kp), side_by_side(vp)   # the pools as the family keeps them
    got = np.asarray(la.head_walk(q, None, flat[0], None, flat[1], m["work"], scale=model._scale(),
                                  kv=model.kv, interpret=True).astype(jnp.float32))
    assert got.shape == (slots, model.kv, hd) and np.isfinite(got).all()   # a free lane's too
    table = np.asarray(m["table"])
    k32, v32, q32 = (np.asarray(x.astype(jnp.float32)) for x in (kp, vp, q))
    for b in np.flatnonzero(live):
        keys, values = (x[:, table[b]].reshape(model.kv, -1, hd)[:, :rows[b]] for x in (k32, v32))
        s = np.einsum("hd,hcd->hc", q32[b], keys) * hd ** -0.5
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want = np.einsum("hc,hcd->hd", p / p.sum(axis=-1, keepdims=True), values)
        np.testing.assert_allclose(got[b], want, atol=2e-2)
        assert np.abs(want).max() > 0.3
    # and the program's own gather of the same table says the same
    xla = np.asarray(model._decode_gather(q, flat, m["table"], m["rows_seen"] - 1, model._heads()))
    np.testing.assert_allclose(got[live], xla[live], atol=2e-2)


# -- a launch's attention in one kernel call a layer --------------------------------------------

@contextlib.contextmanager
def in_the_launch(seen=None):
    """What is traced inside takes ``eva``'s TPU branch for a LAUNCH at the
    toy's shapes (which the interpreter takes and ``fits`` would refuse),
    ``launch_walk`` in the Pallas interpreter (``seen`` gets each call's
    operands and work list); a step stays on the gather."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(eva, "jax", NamedTpu())
        m.setattr(eva.EvaServing, "_tiles_fit", lambda self, T: True)
        m.setattr(eva.EvaServing, "_walks", lambda self, P: False)

        def walk(*a, f=lat.launch_walk, **k):
            if seen is not None:
                seen.append(a)
            return f(*a, interpret=True, **k)

        m.setattr(lat, "launch_walk", walk)
        yield


# One launch of 4 tiles over a synthetic pool (every ring place, every page full of
# random rows: a row wrongly seen moves the answer): its pieces (slot, start,
# length) in WINDOWS, each at the next free tile. A window is 4 pages.
LAUNCH_CASES = {
    "a-prompts-first-tiles: no ring, no summary": [(0, 0, 1)],
    "mid-window: ring rows behind, up to the window's end": [(1, 0.5, 0.5)],
    "across-a-windows-edge: the window the launch closed through its summary": [(2, 0.5, 1)],
    "deep: three closed windows, the ring's pages of the fourth": [(1, 3.5, 0.5)],
    "two-pieces-of-two-prompts, one shorter than a page": [(0, 1.5, 0.5), (2, 0.5, 0.125)],
    "a-padded-tail-and-a-tile-of-no-piece": [(1, 1.5, 0.375), (0, 0, 0.0625)],
}
LAUNCH_MODELS = {"toy-float32-grouped": (1, 1e-4), "cell-heads-bfloat16": (2, 2e-2),
                 "cell-heads-tiles-of-two-pages": (2, 2e-2)}


@pytest.fixture(scope="module")
def launched(whole, cell_heads):
    """(case, kind) -> ``launch_case``'s, made once for the tests that share it."""
    return functools.cache(lambda case, kind: launch_case(
        whole[0] if kind.startswith("toy") else cell_heads, case, kind))


def launch_case(model, case: str, kind: str) -> dict:
    """One launch of ``LAUNCH_CASES`` at a model of ``LAUNCH_MODELS``: the
    values (the pools drawn by head, as the parent's tree drew them, and laid
    side by side), the plan off the TPU and in the kernel, and the kernel's
    output in the interpreter."""
    slots, (c, P, W), hd = 3, (model.chunk, model.rows, model.window), model.hd
    T = P * (2 if kind.endswith("two-pages") else 1)
    K, pps, dtype = 4, 4, model.dtype
    C = K * T
    pieces = [(slot, int(start * W), int(n * W)) for slot, start, n in LAUNCH_CASES[case]]
    rng = np.random.default_rng(len(case) + T)
    n_pages = c * (slots + 1) + slots * pps + 1
    kh, vh = (jnp.asarray(rng.standard_normal((model.kv, n_pages, P, hd)), dtype) for _ in range(2))
    kp, vp = side_by_side(kh), side_by_side(vh)
    q = jnp.asarray(2.0 * rng.standard_normal((C, model.heads[0], hd)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((C, model.kv, hd)), dtype) for _ in range(2))
    bt = rng.permutation(np.arange(1, 1 + slots * pps)).reshape(slots, pps).astype(np.int32)
    rings = rng.permutation(np.arange(1, slots + 1)).astype(np.int32)
    state = {"bt": jnp.asarray(bt), "pos": jnp.zeros((slots,), jnp.int32), "kf": [kp]}
    launch = {f: np.zeros((K,), np.int32) for f in ("slot", "start", "length", "ring")}
    launch["pages"] = np.zeros((K, pps), np.int32)
    for j, (slot, start, length) in enumerate(pieces):
        launch["slot"][j], launch["start"][j], launch["length"][j] = slot, start, length
        launch["ring"][j], launch["pages"][j] = rings[slot], bt[slot]
    launch = {f: jnp.asarray(x) for f, x in launch.items()}
    plain = model._prefill_plan(state, launch, model._tiles(launch, C))
    with in_the_launch():
        m = model._prefill_plan(state, launch, model._tiles(launch, C))
        got = np.asarray(model._attend_tiles(q, k, v, kp, vp, m))
    assert (plain["tile_path"], plain["work"], m["tile_path"]) == ("xla", None, "tile_kernel")
    assert got.shape == q.shape and got.dtype == np.float32 and np.isfinite(got).all()
    return SimpleNamespace(model=model, T=T, K=K, slots=slots, pieces=pieces, bt=bt, rings=rings,
                           q=q, k=k, v=v, kp=kp, vp=vp, plain=plain, m=m, got=got)


@pytest.mark.parametrize("kind", list(LAUNCH_MODELS))
@pytest.mark.parametrize("case", list(LAUNCH_CASES))
def test_the_kernel_over_whole_rows_is_the_parents_kernel_by_head_to_the_bit(
        launched, by_head, case, kind):
    """``launch_walk`` over pools and own rows that hold a position as ONE row
    (ISSUE 63: a page one block, a head's columns cut out of it) against what
    the parent's kernel gave over the same values by head (a page a block of
    heads): the work list, the masks, the running softmax and the order of
    every sum are the parent's, so every bit is."""
    got = launched(case, kind).got
    want = by_head[f"launch/{kind}/{case.split(':')[0]}"]
    if want.dtype == np.uint8:   # the cell heads' MiB a case: its digest
        got = np.frombuffer(hashlib.sha256(got.tobytes()).digest(), np.uint8)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", list(LAUNCH_MODELS))
@pytest.mark.parametrize("case", list(LAUNCH_CASES))
def test_a_launchs_tiles_in_one_kernel_call_are_the_tile_in_xla_and_plain_attention(
        launched, case, kind):
    """``launch_walk`` (ISSUE 58) in the Pallas interpreter over the launch's
    own plan and work list, against ``_tile`` in XLA on the same plan and
    against a plain float32 softmax over each live row's visible keys taken
    from the pool and the launch one by one (its own window's positions below
    its piece's start from the ring, the launch's own up to itself, every row
    of its prompt's earlier windows' summary pages). And the work list: exactly
    the pages that hold a key some row of the tile sees, none twice, the
    sentinel for a tile of no piece. The toy in float32 with 2 query heads a KV
    head; the cell's heads in bfloat16 (2 ** -8 of values near 1), a tile one
    page and two."""
    lc = launched(case, kind)
    model, T, K, slots, pieces = lc.model, lc.T, lc.K, lc.slots, lc.pieces
    bt, rings = lc.bt, lc.rings
    q, k, v, m, got = lc.q, lc.k, lc.v, lc.m, lc.got
    kp, vp = (pool.reshape(pool.shape[:2] + (model.kv, -1)).transpose(2, 0, 1, 3)
              for pool in (lc.kp, lc.vp))        # by head: what the keys one by one are taken from
    (c, P, W), hd, tol = (model.chunk, model.rows, model.window), model.hd, LAUNCH_MODELS[kind][1]
    xla = np.asarray(model._attend_tiles(q, k, v, lc.kp, lc.vp, lc.plain))

    # each live row's visible keys, one by one: (operand, page, row) of the pools or the launch
    first, g = c * (slots + 1), model.heads[0] // model.kv
    k32, v32, q32, ko32, vo32 = (np.asarray(x.astype(jnp.float32)) for x in (kp, vp, q, k, v))
    at, seen_pages, live = 0, {}, []
    for slot, start, length in pieces:
        for i in range(length):
            pos, tile = start + i, (at + i) // T
            w0 = pos // W * W
            keys = [(0, c * rings[slot] + (t % W) // P, t % P) for t in range(w0, start)] \
                + [(1, (at + t - start) // P, (at + t - start) % P)
                   for t in range(max(w0, start), pos + 1)] \
                + [(0, first + bt[slot, n], r) for n in range(pos // W) for r in range(P)]
            seen_pages.setdefault(tile, set()).update((src, pg) for src, pg, _ in keys)
            kk = np.stack([(ko32[pg * P + r] if src else k32[:, pg, r]) for src, pg, r in keys], 1)
            vv = np.stack([(vo32[pg * P + r] if src else v32[:, pg, r]) for src, pg, r in keys], 1)
            sc = np.einsum("kgd,kcd->kgc", q32[at + i].reshape(model.kv, g, hd), kk) * hd ** -0.5
            pr = np.exp(sc - sc.max(axis=-1, keepdims=True))
            want = np.einsum("kgc,kcd->kgd", pr / pr.sum(axis=-1, keepdims=True), vv)
            np.testing.assert_allclose(got[at + i], want.reshape(-1, hd), atol=tol)
            live.append(at + i)
        at += -(-length // T) * T
    np.testing.assert_allclose(got[live], xla[live], atol=tol)

    # the work list: tile after tile, exactly the pages seen, none twice
    work = {f: np.asarray(x) for f, x in m["work"].items()}
    n = int(work["items"])
    has = np.asarray(m["t"]["has"])
    assert n == sum(len(seen_pages[t]) if has[t] else 1 for t in range(K))
    items = list(zip(work["tile"][:n], work["own"][:n],
                     np.where(work["own"][:n] == 1, work["page"][:n], work["pool"][:n])))
    assert len(set(items)) == n and list(work["tile"][:n]) == sorted(work["tile"][:n])
    for t in range(K):
        mine = {(int(own), int(pg)) for tile, own, pg in items if tile == t}
        assert mine == (seen_pages[t] if has[t] else {(0, 0)}), (t, mine)
    firsts = [i for i in range(n) if work["step"][i] == 0]
    assert [work["tile"][i] for i in firsts] == list(range(K))                  # a tile's first
    assert [int(i) - 1 for i in firsts[1:]] + [n - 1] == list(np.flatnonzero(work["left"][:n] == 0))


def test_the_cells_launch_fits_the_kernel_and_off_the_tpu_the_plan_holds_no_list(cell_heads):
    """The cell's shapes (32 heads on 32 KV heads of 128, pages of 128 rows,
    tiles of 128 in a window of 2,048) fit ``launch_walk`` as they are; what
    does not (float32, a tile that is no whole 128 rows or straddles windows)
    stays in XLA; chosen by the backend and the shapes, nothing else."""
    assert lat.fits(128, 128, 2048, 32, 32, 128, jnp.bfloat16)
    assert lat.fits(256, 128, 2048, 32, 8, 128, jnp.bfloat16)
    for bad in ((128, 128, 2048, 32, 32, 128, jnp.float32), (64, 64, 2048, 32, 32, 128, jnp.bfloat16),
                (384, 128, 2048, 32, 32, 128, jnp.bfloat16), (128, 128, 2048, 32, 32, 64, jnp.bfloat16),
                (128, 128, 2048, 32, 5, 128, jnp.bfloat16)):
        assert not lat.fits(*bad), bad
    assert not cell_heads._tiles_fit(16)       # the test's pages of 16 rows: the interpreter's alone


# -- free and frozen lanes, a slot's next tenant ----------------------------------------------

def test_a_lane_that_is_not_live_keeps_ring_pages_and_lanes_to_the_bit(whole):
    model, params = whole
    prompts, news = prompts_of((14, 37, 3)), [24, 12, 7]
    _, _, state = serve(model, params, prompts, news)
    again, _ = jax.jit(model.step)(params, state)   # every lane is done
    c = model.chunk
    live_rings = slice(c, None)                     # ring 0 and page 0 are the sentinels
    for leaf in ("kf", "vf"):
        for a, b in zip(state[leaf], again[leaf]):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_array_equal(a[live_rings][:c * SLOTS], b[live_rings][:c * SLOTS])
            np.testing.assert_array_equal(a[c * (SLOTS + 1) + 1:], b[c * (SLOTS + 1) + 1:])
    for leaf in ("pos", "n_new", "tokens", "lp", "last", "ring", "bt"):
        np.testing.assert_array_equal(np.asarray(state[leaf]), np.asarray(again[leaf]))
    # a shorter tenant in a slot whose ring and pages hold the last one's rows
    short = prompts_of((5, 9, 20), seed=8)
    reused, _, _ = serve(model, params, short, [6, 6, 6], state=again)
    fresh, _, _ = serve(model, params, short, [6, 6, 6])
    for a, b in zip(reused, fresh):
        np.testing.assert_array_equal(a["tokens"][:6], b["tokens"][:6])
        np.testing.assert_array_equal(a["lp"][:6], b["lp"][:6])


# -- a position is ONE row of a pool ------------------------------------------------------------

PACKED = "packed: edges INSIDE a piece, pieces of three prompts in a launch"


@pytest.fixture(scope="module")
def packed_state(whole):
    """The state after the packed case's launches (an edge inside a piece, a
    padded tail) and six steps, of which positions 15, 39 and 3 end a chunk."""
    model, params = whole
    lengths, news, launches = CASES[PACKED]
    return serve(model, params, prompts_of(lengths), news, launches=launches, steps=6)[2]


@pytest.mark.parametrize("layer", range(ARCH["num_hidden_layers"]))
@pytest.mark.parametrize("leaf", ("kf", "vf"))
def test_the_pools_hold_the_parents_rows_by_head_side_by_side(packed_state, by_head, leaf, layer):
    """Row for row what the parent's pools by head held after the same launches
    and steps (rings written as slabs, summaries by a launch and by a step,
    rows by a step), a position's heads laid side by side; the sentinels
    apart, which lanes that are not live write in no stated order."""
    got, want = np.asarray(packed_state[leaf][layer]), by_head[f"pools/{leaf}/{layer}"]
    c = ARCH["chunk_size"]
    first = c * (SLOTS + 1)
    assert got.shape == want.shape == (first + SLOTS * 4 + 1, PAGE, 2 * 16)
    written = np.r_[c:first, first + 1:got.shape[0]]
    np.testing.assert_array_equal(got[written], want[written])
    assert np.abs(want[written]).max() > 0.1 and np.any(want[first + 1:])


def scatters(text: str) -> list:
    """(operand, updates) dimensions of every scatter of a StableHLO text."""
    dims = lambda t: tuple(int(n) for n in t.split("x")[:-1])  # noqa: E731
    return [(dims(a), dims(u)) for a, u in re.findall(
        r"\}\) : \(tensor<([^>]+)>, tensor<[^>]+>, tensor<([^>]+)>\) -> tensor", text)]


@pytest.mark.parametrize("path", ["kernel", "xla"])
@pytest.mark.parametrize("program", ["step", "launch"])
def test_a_lowered_program_writes_a_token_as_one_row_and_moves_no_pool(whole, program, path):
    """THE GUARD OF ISSUE 63's mechanism, in the lowered text of the toy's two
    programs on either path: a layer writes each pool TWICE and no more (a
    step: the ring's rows and the summaries', ``B`` whole rows each; a launch:
    ``C / c`` summary rows, then the rings' ``C / P`` pages as slabs), every
    update a position's ``KV x hd`` values in one piece, and no transpose
    takes or gives anything of a pool's size (by head a token was ``KV``
    pieces a scatter and a launch's rows were transposed to be laid as pages)."""
    model, _ = whole
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    pps = model.kv_plan(1, PAGE).pages_per_slot
    state = model.kv_plan(SLOTS, PAGE).state
    pages, P, row = state["kf"][0].shape
    if program == "step":
        with in_the_walk() if path == "kernel" else contextlib.nullcontext():
            text = jax.jit(model.step).lower(params, state).as_text()
        want = [((pages * P, row), (SLOTS, row))] * 4
    else:
        k = model.kv_prefill_pieces(CHUNK, PAGE)
        launch = {"ids": (CHUNK,), "pages": (k, pps), "temp": (k,),
                  **{f: (k,) for f in ("slot", "start", "length", "n", "seed", "max_new", "ring")}}
        launch = {f: jax.ShapeDtypeStruct(dims, jnp.float32 if f == "temp" else jnp.int32)
                  for f, dims in launch.items()}
        with in_the_launch() if path == "kernel" else contextlib.nullcontext():
            text = jax.jit(lambda p, s, ln: model.prefill_chunk(p, s, ln, chunk=CHUNK)).lower(
                params, state, launch).as_text()
        want = [((pages * P, row), (CHUNK // model.chunk, row))] * 2 \
            + [((pages, P, row), (CHUNK // P, P, row))] * 2
    of_pools = [s for s in scatters(text) if np.prod(s[0]) == pages * P * row]
    assert of_pools == want * model.n_layers, of_pools
    moved = [ln for ln in text.split("\n") if "stablehlo.transpose" in ln
             and any(np.prod([int(n) for n in t.split("x")[:-1]] or [1]) >= pages * P * row
                     for t in re.findall(r"tensor<([^>]+)>", ln))]
    assert not moved, moved


# -- geometry, /stats, the recipe ----------------------------------------------------------

def test_the_caches_geometry_a_page_stands_for_a_window(whole, tmp_path):
    from tpuserve.genserve.engine import GenEngine
    from tpuserve.obs import Metrics
    from tpuserve.runtime import build_runtime

    model, _ = whole
    plan = model.kv_plan(SLOTS, PAGE, 9)
    assert plan.ring_tokens == 16 and model.rows == 4 and model._leaves() == ("kf", "vf")
    assert plan.page_positions == 16 and plan.ring_pages == 4
    assert plan.pages_per_slot == 4                                 # ceil((40 + 24) / 16)
    item = lambda n, new: (None, np.int32(n), None, np.int32(new))  # noqa: E731
    assert [plan.pages_for(model.context_tokens(item(n, new)))
            for n, new in ((1, 1), (10, 6), (10, 7), (40, 24))] == [1, 1, 2, 4]
    sig = plan.state
    assert [s.shape for s in sig["kf"]] == [(4 * (SLOTS + 1) + 9, 4, 2 * 16)] * 2   # a row: 2 heads
    assert sig["bt"].shape == (SLOTS, 4) and sig["ring"].shape == (SLOTS,)
    with pytest.raises(ValueError, match="kv_page_tokens"):
        model.kv_plan(SLOTS, 8, 9).state
    with pytest.raises(ValueError, match="at most a window"):
        model.kv_prefill_pieces(32, PAGE)
    with pytest.raises(NotImplementedError, match="num_chunks"):
        make_model(str(tmp_path), {**ARCH, "num_chunks": 8}, name="n")
    # what the engine says of it: a position's bytes in the pages are a page's over the
    # window it stands for; the rings' part of the pool apart from the pages'
    rt = build_runtime(model, compile_forward=False)
    eng = GenEngine(model, rt, Metrics(), GenserveConfig(
        slots=SLOTS, kv_paging=True, kv_page_tokens=PAGE, kv_pages=9, prefill_chunk=CHUNK))
    eng.compile()
    page = 2 * 2 * 2 * 4 * 16 * 4          # layers x (K, V) x KV heads x rows x hd x float32
    kv = eng.pipeline_stats()["kv"]
    assert kv["row_bytes_per_token"] == page // 16 and kv["page_positions"] == 16
    assert kv["page_bytes"] == 9 * page and kv["ring_bytes"] == (SLOTS + 1) * 4 * page
    assert kv["kv_bytes"] == kv["page_bytes"] + kv["ring_bytes"]
    assert kv["rings"] == SLOTS + 1 and kv["page_tokens"] == PAGE


def test_the_weights_are_the_recipes_and_the_references(whole):
    model, params = whole
    m = ref.Model(ARCH, SEED, "float32")
    w = m.layer(1)
    lp = params["layer1"]
    for name in ("wq", "wk", "wv", "wo", "phi", "mu", "w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(lp[name]), w[name], err_msg=name)
    np.testing.assert_array_equal(np.asarray(lp["norm1"]), w["g1"])
    np.testing.assert_array_equal(np.asarray(lp["norm2"]), w["g2"])
    np.testing.assert_array_equal(np.asarray(params["norm_f"]), m.gain("norm_f"))
    assert float(np.abs(w["g1"]).max()) <= 0.25 and float(np.abs(w["g1"]).max()) > 0.1
    # the head holds every prediction block; block 0 is what is served
    assert params["head"].shape == (64, 2 * 40)
    np.testing.assert_array_equal(np.asarray(params["head"][:, :40]), m.head())
    np.testing.assert_array_equal(np.asarray(params["embed"]), m.embed())
    assert np.asarray(seeded.draw(SEED, "layer1/phi", (4, 16), 0.18, jnp.float32)).std() \
        == pytest.approx(0.18, rel=0.2)
    # phi decides a chunk's weights: the largest of four is well above a quarter
    u = np.random.default_rng(1).standard_normal((64, 64)).astype(np.float32)
    k = np.einsum("td,dhk->thk", u, w["wk"]).reshape(16, 4, 2, 16)
    wt = jax.nn.softmax(np.einsum("mchd,hd->mch", k, w["phi"]), axis=1)
    assert 0.4 < float(np.mean(np.max(wt, axis=1))) < 0.8


def test_the_benchmarks_copy_of_the_reference_gives_the_same_numbers():
    from benchmark import spec

    bench = spec.load_module("reference", "eva")
    assert bench.WRONG == ref.WRONG and bench.DEFAULT_SCALES == ref.DEFAULT_SCALES
    seqs = prompts_of((37, 5), seed=2)
    for wrong, low in (("", False), ("", True), ("split_softmax", False)):
        a = ref.log_probs(ref.Model(ARCH, SEED, "float32", wrong=wrong), seqs, [0, 0], low)
        b = bench.log_probs(bench.Model(ARCH, SEED, "float32", wrong=wrong), seqs, [0, 0], low)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
