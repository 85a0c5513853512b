"""A picked prefill tile's block scores as one kernel call (`ops/block_scores.py`,
ISSUE 69), in the Pallas interpreter on the CPU at the family's sizes cut small
(2 KV groups of 16 heads of 128; windows of 32 keys at stride 16, blocks of 64):
against the plain form (`mixers.BlockSelectAttention._block_scores`, which a
step, every backend but the TPU and every shape the kernel does not take run)
to float32 rounding, `+inf` and `-inf` in the same places; a tile whose last
position needs 1, 2 and all window blocks, one that straddles `dense_len`, a row
at the first position past the forced blocks, a context that ends inside a
window, a tile with rows past its prompt's end; `_tile_keep`'s mask through the
kernel EQUAL to the plain form's, and the tie rule on a constructed exact tie;
which path a launch takes, and that a launch off the TPU lowers to the text it
had before the kernel came."""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_hybrid_blk as hb
from tpuserve.models import mixers
from tpuserve.ops import block_scores as bs

H, KV, HD, PAGE = 32, 2, 128, 64
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 8, "init_blocks": 1,
          "window_size": 128, "dense_len": 512}
# The same products and sums in another order: a score is a sum of sixteen
# softmax values (at most 16), read to a few float32 places.
CLOSE = 2e-6


class Plain(mixers.BlockSelectAttention):
    name = "plain"

    def __init__(self, sparse: dict = SPARSE, dtype: str = "float32"):
        self.heads, self.kv, self.hd, self.dtype = H, KV, HD, jnp.dtype(dtype)
        self._blk_setup(self.name, sparse)

    def _scale(self):
        return self.hd ** -0.5


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(bs, "block_scores", functools.partial(bs.block_scores, interpret=True))


def tile(pages: int, rows: int, first: int, seed: int = 0, dtype: str = "float32"):
    """A made-up tile: q at ``first .. first + rows - 1``, a pool of pooled keys
    behind a shuffled block-table row of ``pages`` pages."""
    rng = np.random.default_rng(seed)
    per = PAGE // SPARSE["kernel_stride"]
    kc = jnp.asarray(rng.standard_normal(((pages + 5) * per, KV * HD)), dtype)
    row = jnp.asarray(rng.permutation(np.arange(1, pages + 5))[:pages], jnp.int32)
    q = jnp.asarray(2 * rng.standard_normal((rows, H, HD)), dtype)
    return q, kc, row, first + jnp.arange(rows, dtype=jnp.int32)


def through_the_kernel(model, q, kc, row, qpos, last, spans):
    return bs.block_scores(q, kc, row, qpos, jnp.int32(last), spans=spans, page=PAGE,
                           kernel=model.b_kernel, stride=model.b_stride, block=model.b_block,
                           init=model.b_init, local=model.b_local, scale=model._scale())


# (pages of the table, rows, the tile's first position, its last LIVE position or None: its last
# row's). A window block is 512 windows = 8,192 positions = 128 blocks.
CASES = {
    "one-window-block": (300, 32, 700, None),
    "two-window-blocks": (300, 32, 9000, None),
    "all-three-window-blocks-of-the-table": (300, 32, 19168, None),
    "the-last-row-opens-a-window-block": (300, 32, 8161, None),     # last = 8,192: block 128
    "straddles-dense-len": (300, 32, 500, None),
    "the-first-row-past-the-forced-blocks": (300, 16, 192, None),   # init 1 + local 2 blocks
    "a-context-that-ends-inside-a-window": (300, 32, 1000, 1017),   # rows past the prompt's end
    "rows-before-any-whole-window": (300, 16, 16, None),            # positions 16..30 see none
    "a-table-of-no-whole-lane-tile": (131, 16, 8368, None),         # 524 windows, 2 blocks
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_plain_form(case, interpreted):
    pages, rows, first, last = CASES[case]
    model = Plain()
    q, kc, row, qpos = tile(pages, rows, first, seed=sum(map(ord, case)))
    last = first + rows - 1 if last is None else last
    spans = pages + 3
    want = np.asarray(model._block_scores(q, kc, row, qpos, spans, PAGE))
    got = np.asarray(through_the_kernel(model, q, kc, row, qpos, last, spans))
    assert got.shape == want.shape == (KV, rows, spans) and got.dtype == np.float32
    # every window block past the one that holds the tile's last position: -inf for every row
    assert np.isneginf(got[:, :, (last // 64 // 128 + 1) * 128:]).all()
    live = np.asarray(qpos) <= last
    want, got = want[:, live], got[:, live]
    np.testing.assert_array_equal(np.isposinf(got), np.isposinf(want))
    np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
    fin = np.isfinite(want)
    assert fin.any() or first < 64
    np.testing.assert_allclose(got[fin], want[fin], rtol=0, atol=CLOSE)
    # neighbouring blocks whose shared window is the largest of both tie EXACTLY, in both
    both = fin[..., 1:] & fin[..., :-1]
    np.testing.assert_array_equal((got[..., 1:] == got[..., :-1]) & both,
                                  (want[..., 1:] == want[..., :-1]) & both)
    # a forced block reads +inf, a block past the row's own -inf
    own = np.asarray(qpos)[live] // 64
    for r, b in enumerate(own):
        assert np.isposinf(got[:, r, max(b - 1, 0):b + 1]).all() and np.isposinf(got[:, r, 0]).all()
        assert np.isneginf(got[:, r, b + 1:]).all()


def test_a_group_whose_rows_are_no_whole_chunk(interpreted):
    """Twelve heads a group are 192 rows a cell, one and a half of `CHUNK`: every
    row still gets its maximum and its normaliser."""
    model = Plain()
    model.heads = 24
    q, kc, row, qpos = tile(300, 32, 9000, seed=12)
    q = q[:, :24]
    want = np.asarray(model._block_scores(q, kc, row, qpos, 303, PAGE))
    got = np.asarray(through_the_kernel(model, q, kc, row, qpos, 9031, 303))
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=0, atol=CLOSE)


def test_the_served_type_rounds_the_products_operands_alike(interpreted):
    model = Plain(dtype="bfloat16")
    q, kc, row, qpos = tile(300, 32, 9000, seed=3, dtype="bfloat16")
    want = np.asarray(model._block_scores(q, kc, row, qpos, 303, PAGE))
    got = np.asarray(through_the_kernel(model, q, kc, row, qpos, 9031, 303))
    fin = np.isfinite(want)
    np.testing.assert_array_equal(np.isfinite(got), fin)
    np.testing.assert_allclose(got[fin], want[fin], rtol=0, atol=CLOSE)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_tiles_picks_through_the_kernel_are_the_plain_forms(seed, interpreted):
    """On seeds whose ``topk``-th and next scores differ by more than 1e-5 in
    every (row, KV group): the mask equal, bit for bit."""
    model = Plain()
    q, kc, row, qpos = tile(300, 32, 8800 + 700 * seed, seed=seed)
    last, spans = int(qpos[-1]), 304
    score = np.asarray(model._block_scores(q, kc, row, qpos, spans, PAGE))
    # the least score kept, and its distance from the nearest OTHER value on either
    # side (neighbouring blocks tie exactly, in the kernel as in the plain form)
    fin = np.where(np.isfinite(score), score, -1.0)
    k = model.b_topk - model.b_init - model.b_local       # the picks the scores decide
    least = -np.sort(-fin, axis=-1)[..., k - 1:k]
    away = np.abs(np.where(fin == least, np.inf, fin) - least).min(axis=-1)
    assert (away > 1e-5).all()
    want = np.asarray(model._tile_keep(q, kc, row, qpos, spans, PAGE))
    got = np.asarray(model._tile_keep(q, kc, row, qpos, spans, PAGE, jnp.int32(last), "kernel"))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == model.b_topk).all()


def test_an_exact_tie_goes_to_the_lower_index(interpreted, monkeypatch):
    """Two blocks whose scores are EQUAL at the threshold: the kernel's scores
    feed the same rule, and the lower index is kept."""
    model = Plain()
    q, kc, row, qpos = tile(300, 16, 2000, seed=5)
    last, spans = int(qpos[-1]), 304
    real = bs.block_scores

    def tied(*a, **kw):
        """The kernel's scores with blocks 3 and 9 both at 8.0 (over any sum of
        sixteen softmax values that leaves room) and five others at 9.0: the
        ``topk`` of 8 = init 1 + local 2 + those five, no room for a tied one."""
        s = real(*a, **kw)
        s = s.at[:, :, (11, 13, 15, 17, 19)].set(9.0)
        return s.at[:, :, (3, 9)].set(8.0)

    monkeypatch.setattr(bs, "block_scores", tied)
    kept = np.asarray(model._tile_keep(q, kc, row, qpos, spans, PAGE, jnp.int32(last), "kernel"))
    assert (kept.sum(-1) == model.b_topk).all()
    assert kept[:, :, (11, 13, 15, 17, 19)].all() and not kept[:, :, (3, 9)].any()

    def tied_with_room(*a, **kw):
        s = real(*a, **kw)
        s = s.at[:, :, (11, 13, 15, 17)].set(9.0)
        return s.at[:, :, (3, 9)].set(8.0)

    monkeypatch.setattr(bs, "block_scores", tied_with_room)
    kept = np.asarray(model._tile_keep(q, kc, row, qpos, spans, PAGE, jnp.int32(last), "kernel"))
    assert (kept.sum(-1) == model.b_topk).all()
    assert kept[:, :, 3].all() and not kept[:, :, 9].any()      # one place left: the lower index


@pytest.mark.parametrize("shape,says", [
    ((512, 32, 2, 128, 4116, 4, 1, "bfloat16"), True),      # the cell's
    ((512, 32, 2, 128, 4116, 4, 1, "float32"), True),
    ((16, 32, 2, 128, 524, 4, 1, "float32"), True),
    ((8, 4, 2, 16, 52, 4, 1, "float32"), False),            # the toy's tile of 8 rows, heads of 16
    ((512, 32, 2, 64, 4116, 4, 1, "bfloat16"), False),      # heads of half a register
    ((512, 32, 3, 128, 4116, 4, 1, "bfloat16"), False),     # heads in no whole groups
    ((520, 32, 2, 128, 4116, 4, 1, "bfloat16"), False),     # no whole sub-tiles
    ((512, 32, 2, 128, 4116, 4, 5, "bfloat16"), False),     # a window over three blocks
    ((512, 32, 2, 128, 4116, 4, 1, "float16"), False),
    ((512, 32, 2, 128, 65536, 4, 1, "bfloat16"), False),    # a table whose scores VMEM cannot hold
])
def test_the_shapes_the_kernel_takes(shape, says):
    assert bs.supported(*shape) is says


def test_a_launch_takes_the_kernel_on_the_tpu_alone_and_at_its_shapes_alone(monkeypatch):
    cell = Plain({**SPARSE, "topk": 64, "window_size": 2048, "dense_len": 8192}, "bfloat16")
    assert cell._select_path(512, 1029, PAGE) == "xla"               # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cell._select_path(512, 1029, PAGE) == "kernel"
    assert cell._select_path(8, 1029, PAGE) == "xla"                 # a tile of 8 rows
    monkeypatch.setattr(cell, "hd", 64)
    assert cell._select_path(512, 1029, PAGE) == "xla"


def _launch_text(model, params, steer=None) -> str:
    """The prefill program's lowered text for a launch of one piece."""
    pps = model.kv_plan(1, hb.PAGE).pages_per_slot
    state = hb.zeros(model.kv_plan(hb.SLOTS, hb.PAGE).state)
    k = model.kv_prefill_pieces(hb.CHUNK, hb.PAGE)
    prompts = hb.prompts_of(90)
    launch = model.pack_prefill([hb.piece_of(model, prompts, [4], 0, 64, 26)], hb.CHUNK, k)
    return jax.jit(model.prefill_chunk, static_argnames=("chunk",)).lower(
        params, state, launch, chunk=hb.CHUNK).as_text()


def test_off_the_tpu_and_at_a_refused_shape_the_launch_is_the_plain_forms_program(
        tmp_path, monkeypatch):
    """No switch chooses the path: off the TPU, and on it at a shape ``supported``
    refuses (the toy's heads of 16), the launch's text holds no kernel call and
    is the same text; steered to the kernel it holds one."""
    model = hb.make_model(str(tmp_path))
    params = model.init_params(jax.random.key(0))
    plain = _launch_text(model, params)
    assert "block_scores" not in plain and "tpu_custom_call" not in plain
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model._select_path(8, 13, hb.PAGE) == "xla"
    refused = _launch_text(model, params)
    sha = lambda text: hashlib.sha256(text.encode()).hexdigest()  # noqa: E731
    assert sha(refused) == sha(plain)
