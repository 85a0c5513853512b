"""The routed expert layer over a share (`tpuserve.ops.moe` `topk_route`,
`held_experts`, `_grouped_dot`'s tiles), generalised in ISSUE 32: softmax or
sigmoid scores with a selection bias, an expert's body of two kernels or of
three, one sort and one grouped product for both. Tier-1 (`tests/test_moe.py`
is marked slow as a whole: the Switch layer's mesh tests)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# -- the routed layer over a share, generalised (ISSUE 32) ---------------------------------------

def _held_experts_swiglu_pr31(x, weights, experts, first, w_gate, w_up, w_down, live=None):
    """The function as PR 31 had it, kept here as the oracle of bit-equality."""
    from tpuserve.ops.moe import _grouped_dot

    t, k = experts.shape
    count = w_gate.shape[0]
    local = experts - jnp.int32(first)
    held = (local >= 0) & (local < count)
    held_live = held & live[:, None] if live is not None else held
    key = jnp.where(held_live, local, count).T.reshape(k * t)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    xs = jnp.take(x, order % t, axis=0)
    dot = _grouped_dot(t * k, x.dtype, w_gate.shape, w_down.shape)
    h = (jax.nn.silu(dot(xs, w_gate, sizes)) * dot(xs, w_up, sizes)).astype(x.dtype)
    out = dot(h, w_down, sizes)
    out = jnp.take(out, jnp.argsort(order), axis=0).reshape(k, t, -1)
    return jnp.sum(jnp.where(held_live.T[:, :, None], out * weights.T[:, :, None], 0.0), axis=0)


@pytest.mark.parametrize("dtype,first,count,masked", [
    ("float32", 0, 8, False), ("float32", 4, 4, True), ("bfloat16", 2, 5, True),
    ("bfloat16", 0, 8, False)])
def test_the_swiglu_path_through_the_general_function_is_bit_equal(dtype, first, count, masked):
    from tpuserve.ops.moe import held_experts, held_experts_swiglu, swiglu, topk_route

    rng = np.random.default_rng(4)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((40, 6)), dt)
    w, e = topk_route(jnp.asarray(rng.standard_normal((40, 8)), jnp.float32), 3, scale=2.5)
    wg, wu = (jnp.asarray(rng.standard_normal((count, 6, 5)), dt) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((count, 5, 6)), dt)
    live = jnp.arange(40) % 3 != 0 if masked else None
    want = _held_experts_swiglu_pr31(x, w, e, first, wg, wu, wd, live)
    got, stats = held_experts_swiglu(x, w, e, first, wg, wu, wd, live=live)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    same, _ = held_experts(x, w, e, first, (wg, wu), wd, swiglu, live=live)
    assert np.array_equal(np.asarray(same), np.asarray(want))
    assert int(stats["routed_held"]) + int(stats["routed_absent"]) == \
        3 * (40 if live is None else int(np.sum(live)))


def test_softmax_routing_is_unchanged_by_the_new_arguments():
    from tpuserve.ops.moe import topk_route

    logits = jnp.asarray(np.random.default_rng(1).standard_normal((30, 16)), jnp.float32)
    w, e = topk_route(logits, 4, normalize=True, scale=2.5)
    p = jax.nn.softmax(logits, axis=-1)
    w0, e0 = jax.lax.top_k(p, 4)
    assert np.array_equal(np.asarray(e), np.asarray(e0))
    assert np.array_equal(np.asarray(w), np.asarray(w0 / jnp.sum(w0, -1, keepdims=True) * 2.5))
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        topk_route(logits, 4, scoring="tanh")


def test_sigmoid_routing_with_a_bias_moves_picks_and_not_weights():
    from tpuserve.ops.moe import held_experts, relu2, topk_route

    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))
    w, e = topk_route(logits, 22, normalize=True, scale=5.0, scoring="sigmoid")
    # the 22 largest scores, weighted by score over their own sum, times 5
    assert np.array_equal(np.sort(e, -1), np.sort(np.argsort(-s, -1)[:, :22], -1))
    np.testing.assert_allclose(np.sum(w, -1), 5.0, rtol=1e-6)
    picked = np.take_along_axis(s, np.asarray(e), -1)
    np.testing.assert_allclose(w, 5.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # a bias on expert 7 puts it among every token's picks; its weight is its score's
    bias = jnp.zeros((64,), jnp.float32).at[7].set(10.0)
    wb, eb = topk_route(logits, 22, normalize=True, scale=5.0, scoring="sigmoid",
                        select_bias=bias)
    assert np.all(np.any(np.asarray(eb) == 7, axis=-1))
    assert not np.all(np.any(np.asarray(e) == 7, axis=-1))       # it moved picks
    pb = np.take_along_axis(s, np.asarray(eb), -1)                # scores, without the bias
    np.testing.assert_allclose(wb, 5.0 * pb / pb.sum(-1, keepdims=True), rtol=1e-6)
    assert float(np.max(wb)) < 5.0 * 0.2                          # no weight of 10
    # a zero bias is no bias
    w0, e0 = topk_route(logits, 22, normalize=True, scale=5.0, scoring="sigmoid",
                        select_bias=jnp.zeros((64,)))
    assert np.array_equal(np.asarray(e0), np.asarray(e))
    np.testing.assert_allclose(w0, w, rtol=1e-6)
    # un-gated experts in a latent: picks on absent experts add nothing
    x = jnp.asarray(rng.standard_normal((50, 6)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((64, 6, 5)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((64, 5, 6)), jnp.float32)
    whole, st = held_experts(x, w, e, 0, (w1,), w2, relu2)
    want = sum(np.asarray(w)[:, j:j + 1] * np.einsum(
        "tf,tfd->td", np.square(np.maximum(np.einsum("td,tdf->tf", x, w1[e[:, j]]), 0)),
        w2[e[:, j]]) for j in range(22))
    np.testing.assert_allclose(whole, want, rtol=2e-4, atol=2e-4)
    parts = [held_experts(x, w, e, f, (w1[f:f + 16],), w2[f:f + 16], relu2)
             for f in (0, 16, 32, 48)]
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, rtol=2e-4, atol=2e-4)
    assert sum(int(s_["routed_held"]) for _, s_ in parts) == 50 * 22 == int(st["routed_held"])
    assert all(int(s_["routed_held"]) + int(s_["routed_absent"]) == 50 * 22 for _, s_ in parts)


@pytest.mark.parametrize("n,tile", [(1024, 1024), (3072, 1024), (2688, 896), (5376, 896),
                                    (128, 128), (64, 0), (1000, 0)])
def test_the_grouped_products_tile_divides_the_width_it_is_given(n, tile):
    from tpuserve.ops.moe import _tile

    assert _tile(n) == tile
    if tile:
        assert n % tile == 0 and tile % 128 == 0 and tile <= 1024


def test_the_tiles_of_both_expert_layers_on_the_chip():
    """What `_grouped_dot` hands megablox: the sparse decoder's kernels
    (3072 x 1024, 1024 x 3072) keep (128 / 256, 1024, 1024); the latent
    experts' published width 2688 = 21 x 128 gets 896 in place of `ragged_dot`."""
    from tpuserve.ops.moe import _tile

    assert [(_tile(k), _tile(n)) for k, n in ((3072, 1024), (1024, 3072))] == [(1024, 1024)] * 2
    assert [(_tile(k), _tile(n)) for k, n in ((1024, 2688), (2688, 1024))] == \
        [(1024, 896), (896, 1024)]


# -- the dispatch carries only the held picks (ISSUE 37) ------------------------------------------

def _topk_route_pr60(logits, k, *, normalize=True, scale=1.0, scoring="softmax",
                     select_bias=None, eps=0.0):
    """The router as PR 60 had it (the picks' scores by ``take_along_axis``, a
    gather of scalars), kept here as the oracle of bit-equality."""
    x = logits.astype(jnp.float32)
    p = jax.nn.softmax(x, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(x)
    _, e = jax.lax.top_k(p + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(p, e, axis=-1)
    if normalize:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + jnp.float32(eps) if eps else total)
    return w * jnp.float32(scale), e.astype(jnp.int32)


@pytest.mark.parametrize("eps", [0.0, 1e-6])
@pytest.mark.parametrize("normalize", [True, False])
@pytest.mark.parametrize("of,k", [(of, k) for of in (16, 64, 512) for k in (2, 4, 8, 22)
                                  if k <= of])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_the_picks_weights_by_comparison_are_the_gathers_bit_for_bit(scoring, of, k, normalize,
                                                                     eps):
    """With a selection bias the picks' weights are the scores at the picks
    (ISSUE 61): read by comparison (the maximum over a row of the score at
    the pick and nothing elsewhere), they are what ``take_along_axis`` read to
    the last bit, normalised or not, and the program holds no gather."""
    from tpuserve.ops.moe import topk_route

    rng = np.random.default_rng(of * 100 + k)
    logits = jnp.asarray(2.0 * rng.standard_normal((96, of)), jnp.float32).at[:, 3].set(-9.0)
    p = jax.nn.softmax(logits, -1) if scoring == "softmax" else jax.nn.sigmoid(logits)
    # a bias as wide as the scores themselves, and one that lifts every row's last expert
    # among its picks: at least one pick of every row moves
    bias = jnp.asarray(rng.uniform(-1, 1, of) * 2 * float(jnp.std(p)), jnp.float32).at[3].set(4.0)
    kw = dict(normalize=normalize, scale=2.5, scoring=scoring, select_bias=bias, eps=eps)
    new = jax.jit(lambda x: topk_route(x, k, **kw))
    old = jax.jit(lambda x: _topk_route_pr60(x, k, **kw))
    (w, e), (w0, e0) = new(logits), old(logits)
    assert w.dtype == jnp.float32 and e.dtype == jnp.int32 and w.shape == e.shape == (96, k)
    assert np.array_equal(np.asarray(e), np.asarray(e0))
    assert np.array_equal(_bits(w), _bits(w0))
    _, unbiased = jax.lax.top_k(p, k)
    assert np.all(np.any(np.sort(np.asarray(e), -1) != np.sort(np.asarray(unbiased), -1), -1))
    assert "gather" not in new.lower(logits).as_text()
    assert "gather" in old.lower(logits).as_text()


def _layer(seed, first, count, of, k, t, dtype, body, masked, d=32, f=24, bias=None):
    """A routed layer's inputs: (x, weights, experts, w_in, w_out, live)."""
    from tpuserve.ops.moe import topk_route

    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((t, d)), dt)
    w, e = topk_route(jnp.asarray(rng.standard_normal((t, of)), jnp.float32), k,
                      scoring="sigmoid", select_bias=bias, scale=2.5)
    w_in = tuple(jnp.asarray(rng.standard_normal((count, d, f)) / 4, dt)
                 for _ in range(2 if body == "swiglu" else 1))
    w_out = jnp.asarray(rng.standard_normal((count, f, d)) / 4, dt)
    live = jnp.asarray(rng.random(t) < 0.9) if masked else None
    return x, w, e, w_in, w_out, live


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


# (first, count, of, k, t, masked, dtype, body, (d, f)): sizes at which the row bound is under t * k
# and the launch's held picks fit it. The last two have kernel widths in whole tiles of 128 and rows
# in whole row tiles: on the chip those go through megablox, at 128 rows a tile in `compact`
# and in `wide` (1,024 picks), and at 128 against 256 (8,192 picks).
COMPACT_CASES = [
    (0, 2, 8, 4, 96, False, "float32", "relu2", (32, 24)),
    (4, 4, 16, 4, 256, True, "float32", "swiglu", (32, 24)),
    (8, 8, 16, 2, 300, True, "bfloat16", "relu2", (32, 24)),
    (6, 2, 8, 3, 128, False, "bfloat16", "swiglu", (16, 8)),
    (96, 32, 128, 6, 200, True, "float32", "relu2", (8, 8)),
    (0, 1, 4, 1, 1024, False, "float32", "swiglu", (8, 8)),
    (4, 4, 16, 4, 256, True, "bfloat16", "relu2", (256, 128)),
    (0, 4, 16, 8, 1024, True, "bfloat16", "swiglu", (128, 128)),
]


def _both_branches(monkeypatch, layer, first, fn, of, n_real=None):
    """ONE compiled program of the layer, run twice: its ``cond`` taking the
    branch the test names (the layer's own predicate is computed and reported
    as ever). What serving needs: which branch a launch takes depends on who
    else is in it, and the program is the same either way."""
    from tpuserve.ops import moe

    x, w, e, w_in, w_out, live = layer
    real = jax.lax.cond

    def run(x, w, e, take_compact):
        own = []

        def cond(pred, *branches):   # the layer's own is the first traced; a kernel's inner ones stay
            own.append(pred)
            return real(take_compact if len(own) == 1 else pred, *branches)

        monkeypatch.setattr(jax.lax, "cond", cond)
        try:
            return moe.held_experts(x, w, e, first, w_in, w_out, fn, live=live, of=of,
                                    real=n_real)
        finally:
            monkeypatch.setattr(jax.lax, "cond", real)

    program = jax.jit(run)
    return program(x, w, e, True), program(x, w, e, False)


@pytest.mark.parametrize("first,count,of,k,t,masked,dtype,body,widths", COMPACT_CASES)
def test_the_compact_branch_is_bit_identical_to_wide(first, count, of, k, t, masked, dtype, body,
                                                     widths, monkeypatch):
    from tpuserve.ops import moe

    layer = _layer(7, first, count, of, k, t, dtype, body, masked, *widths)
    x, w, e, w_in, w_out, live = layer
    fn = moe.swiglu if body == "swiglu" else moe.relu2
    bound = moe._row_bound(t * k, count, of)
    assert bound < t * k and bound % 128 == 0
    (got, stats), (wide, wide_stats) = _both_branches(monkeypatch, layer, first, fn, of)
    assert 0 < int(stats["routed_held"]) <= bound            # the held picks fit: both are valid
    assert np.array_equal(_bits(got), _bits(wide))
    assert float(np.abs(np.asarray(got, np.float32)).max()) > 0
    # ... and the program without the second branch (no width given) counts the same and answers
    # the same to the last bits of a float32 sum (another program: its sum over k may associate
    # otherwise, as seen on the chip)
    alone, alone_stats = jax.jit(lambda *a: moe.held_experts(
        *a, first, w_in, w_out, fn, live=live))(x, w, e)
    assert int(stats["compact"]) == int(wide_stats["compact"]) == 1    # what the layer itself chose
    assert int(alone_stats["compact"]) == 0
    for name in ("routed_held", "routed_absent", "experts_hit"):
        assert int(stats[name]) == int(wide_stats[name]) == int(alone_stats[name])
    assert int(stats["routed_held"]) + int(stats["routed_absent"]) == \
        k * (t if live is None else int(np.sum(live)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(alone), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,masked", [("float32", False), ("float32", True),
                                          ("bfloat16", True)])
def test_held_picks_over_the_bound_take_wide_and_lose_nothing(dtype, masked, monkeypatch):
    """A selection bias pulls every pick onto the held range: the launch's
    held picks exceed the compact rows, `wide` runs, and the answer is the
    per-token sum in float32 of the picks' experts."""
    from tpuserve.ops import moe

    first, count, of, k, t = 4, 4, 16, 3, 128
    bias = jnp.zeros((of,), jnp.float32).at[first:first + count].set(10.0)
    x, w, e, (w1,), w2, live = _layer(9, first, count, of, k, t, dtype, "relu2", masked, bias=bias)
    assert np.all((np.asarray(e) >= first) & (np.asarray(e) < first + count))
    bound = moe._row_bound(t * k, count, of)
    got, stats = jax.jit(lambda *a: moe.held_experts(
        *a, first, (w1,), w2, moe.relu2, live=live, of=of))(x, w, e)
    n_live = t if live is None else int(np.sum(live))
    assert int(stats["routed_held"]) == k * n_live > bound
    assert int(stats["compact"]) == 0 and int(stats["routed_absent"]) == 0
    # the same program made to take `compact` here would leave picks behind: the lever of the
    # test above moves something, and the layer's own predicate is what keeps it from happening
    (short, _), (whole, _) = _both_branches(monkeypatch, (x, w, e, (w1,), w2, live), first,
                                            moe.relu2, of)
    assert np.array_equal(_bits(got), _bits(whole)) and not np.array_equal(_bits(short), _bits(whole))
    f32 = [np.asarray(a, np.float32) for a in (x, w1, w2)]
    el = np.asarray(e) - first
    want = sum(np.asarray(w)[:, j:j + 1] * np.einsum("tf,tfd->td", np.square(np.maximum(
        np.einsum("td,tdf->tf", f32[0], f32[1][el[:, j]]), 0)).astype(x.dtype).astype(np.float32),
        f32[2][el[:, j]]) for j in range(k))
    if live is not None:
        want = np.where(np.asarray(live)[:, None], want, 0.0)
    # float32 products take the TPU's default precision (one bfloat16 pass) where a test runs there
    tol = 3e-2 if dtype == "bfloat16" else 1e-1 if jax.default_backend() == "tpu" else 2e-4
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


@pytest.mark.parametrize("first,count,of,k,t,cond", [
    (0, 16, 16, 4, 256, False),      # every expert held: no absent pick to leave behind
    (4, 4, 16, 4, 8, False),         # a launch so small that the bound rounds up to t * k
    (4, 4, None, 4, 256, False),     # a caller that gives no router width
    (4, 4, 16, 4, 256, True)])
def test_where_nothing_can_be_left_behind_the_program_has_no_cond(first, count, of, k, t, cond):
    from tpuserve.ops import moe

    x, w, e, w_in, w_out, _ = _layer(3, first, count, of or 16, k, t, "float32", "relu2", False)
    text = str(jax.make_jaxpr(lambda *a: moe.held_experts(
        *a, first, w_in, w_out, moe.relu2, of=of))(x, w, e))
    assert ("cond[" in text) == cond
    _, stats = moe.held_experts(x, w, e, first, w_in, w_out, moe.relu2, of=of)
    assert int(stats["compact"]) == int(cond)


def _held_experts_pr60(x, weights, experts, first, w_in, w_out, body, live=None, of=None):
    """``held_experts``' ``y`` as PR 60 had it: jax's own ``fill`` on both takes
    (a bounds compare and a select over the block), ``clip`` on ``compact``'s
    way back alone. The oracle of bit-equality for ISSUE 61."""
    from tpuserve.ops.moe import _grouped_dot, _row_bound

    t, k = experts.shape
    count = w_out.shape[0]
    bound = _row_bound(k * t, count, of)
    local = experts - jnp.int32(first)
    held = (local >= 0) & (local < count)
    held_live = held & live[:, None] if live is not None else held
    key = jnp.where(held_live, local, count).T.reshape(k * t)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :], axis=0,
                    dtype=jnp.int32)

    def rows_of(picks, mode=None):
        xs = jnp.take(x, picks % t, axis=0)
        dot = _grouped_dot(picks.shape[0], x.dtype, w_in[0].shape, w_out.shape,
                           expects=k * t / (of or count))
        h = body(*(dot(xs, w, sizes) for w in w_in)).astype(x.dtype)
        return jnp.take(dot(h, w_out, sizes), jnp.argsort(order), axis=0, mode=mode)

    if bound < k * t:
        out = jax.lax.cond(jnp.sum(held_live, dtype=jnp.int32) <= bound,
                           lambda: rows_of(order[:bound], mode="clip"), lambda: rows_of(order))
    else:
        out = rows_of(order)
    out = out.reshape(k, t, -1)
    return jnp.sum(jnp.where(held_live.T[:, :, None], out * weights.T[:, :, None], 0.0), axis=0)


def _fills(text, rows, width):
    """What jax's ``fill`` leaves in a lowered text: the NaN it fills with, and a
    select over a whole (rows, width) block."""
    return len(re.findall(r"dense<0x7FC0(0000)?> : tensor<(f32|bf16)>", text)), len(re.findall(
        rf"stablehlo\.select [^\n]*, tensor<{rows}x{width}x(f32|bf16)>\n", text))


# (first, count, of, k, t, masked, dtype, body, branches): every expert held and a caller without a
# width trace ONE branch (JoyAI's, Xing's and LFM2's programs), a share of the experts two
@pytest.mark.parametrize("first,count,of,k,t,masked,dtype,body,branches", [
    (0, 16, 16, 4, 256, True, "bfloat16", "swiglu", 1),
    (0, 16, 16, 4, 256, False, "float32", "relu2", 1),
    (4, 4, None, 4, 256, True, "float32", "swiglu", 1),
    (4, 4, None, 2, 300, True, "bfloat16", "relu2", 1),
    (4, 4, 16, 4, 256, True, "float32", "swiglu", 2),
    (8, 8, 16, 2, 300, True, "bfloat16", "relu2", 2),
    (4, 4, 16, 4, 256, False, "bfloat16", "swiglu", 2)])
def test_no_take_checks_an_index_the_layer_made_and_y_is_the_parents(first, count, of, k, t, masked,
                                                                      dtype, body, branches):
    """Both takes of ``held_experts`` are the gather alone (ISSUE 61):
    ``picks % t`` and the way back's permutation are in range by construction,
    so ``y`` is what the checked takes gave to the last bit, in the program of
    one branch and in the one of two, and the program of one branch holds no
    fill: no NaN to fill with and no select over the (k t, D) block between
    the gather and the weighted sum (whose own select is over (k, t, D))."""
    from tpuserve.ops import moe

    d, f = 32, 24
    x, w, e, w_in, w_out, live = _layer(5, first, count, of or 16, k, t, dtype, body, masked, d, f)
    fn = moe.swiglu if body == "swiglu" else moe.relu2
    new = jax.jit(lambda *a: moe.held_experts(*a, first, w_in, w_out, fn, live=live, of=of)[0])
    old = jax.jit(lambda *a: _held_experts_pr60(*a, first, w_in, w_out, fn, live=live, of=of))
    got = new(x, w, e)
    assert got.dtype == jnp.float32 and float(np.abs(np.asarray(got)).max()) > 0
    assert np.array_equal(_bits(got), _bits(old(x, w, e)))
    text, parents = new.lower(x, w, e).as_text(), old.lower(x, w, e).as_text()
    assert ("stablehlo.case" in text or "stablehlo.if" in text) == (branches == 2)
    assert _fills(text, k * t, d) == (0, 0)
    # the lever moves something: x's and the way back's (and x's again in `compact`, of fewer rows)
    assert _fills(parents, k * t, d) == (branches + 1, 2)
    assert text.count('"stablehlo.gather"(') == parents.count('"stablehlo.gather"(') == 2 * branches


@pytest.mark.parametrize("picks,count,of,rows", [
    (22528, 128, 512, 7040), (5632, 128, 512, 1792),       # Nemotron's launch and step
    (10240, 128, 256, 6400), (1280, 128, 256, 896),        # Laguna's
    (16384, 256, 256, 16384), (128, 256, 256, 128),        # JoyAI's: every expert held
    (176, 128, 512, 128), (40, 2, 8, 40), (4096 * 4, 1, 4, 5120),
    # 16 held of a router 768 wide (512 real experts, then 256 zero-compute outputs), 12 picks:
    # a step of 256 lanes and a prefill launch of 1,024 rows (ISSUE 42)
    (3072, 16, 768, 128), (12288, 16, 768, 384)])
def test_the_row_bound_is_the_expected_held_picks_with_slack_in_whole_row_tiles(picks, count, of,
                                                                               rows):
    from tpuserve.ops.moe import COMPACT_SLACK, _row_bound, _row_tile

    assert _row_bound(picks, count, of) == rows
    if rows < picks:
        tm = _row_tile(picks / of)
        assert rows % tm == 0 and rows >= picks * count / of * COMPACT_SLACK
        assert rows - tm < picks * count / of * COMPACT_SLACK


# -- zero-compute picks (ISSUE 42) ------------------------------------------------------------------

# (first, count, real, zero, k, t, masked, dtype): the router is real + zero wide; the first case's
# row bound is under t * k (two branches), the second holds every real expert (no cond), the last
# has more zero-compute outputs than real ones.
ZERO_CASES = [
    (4, 4, 16, 8, 4, 256, True, "float32"),
    (0, 16, 16, 8, 4, 64, False, "float32"),
    (2, 2, 8, 4, 3, 40, True, "bfloat16"),
    (0, 4, 8, 24, 6, 128, True, "float32"),
]


@pytest.mark.parametrize("first,count,real,zero,k,t,masked,dtype", ZERO_CASES)
def test_zero_compute_picks_add_their_weight_times_the_token_and_are_neither_held_nor_absent(
        first, count, real, zero, k, t, masked, dtype, monkeypatch):
    from tpuserve.ops import moe

    of = real + zero
    x, w, e, w_in, w_out, live = _layer(13, first, count, of, k, t, dtype, "swiglu", masked)
    got, st = jax.jit(lambda *a: moe.held_experts(
        *a, first, w_in, w_out, moe.swiglu, live=live, of=of, real=real))(x, w, e)
    plain, st0 = jax.jit(lambda *a: moe.held_experts(
        *a, first, w_in, w_out, moe.swiglu, live=live, of=of))(x, w, e)
    # the held experts' part is the program's that was told of no zero-compute width (to a float32
    # multiply-add's rounding: the compiler may fuse the term's product and its sum) ...
    alive = np.ones((t,), bool) if live is None else np.asarray(live)
    is_zero = (np.asarray(e) >= real) & alive[:, None]
    w_zero = np.sum(np.where(is_zero, np.asarray(w), 0.0), axis=1, dtype=np.float32)
    want = np.asarray(plain) + w_zero[:, None] * np.asarray(x, np.float32)
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)
    # ... and a zero-compute pick is counted once, as its own kind
    n_zero = int(is_zero.sum())
    assert 0 < n_zero == int(st["routed_zero"]) and "routed_zero" not in st0
    assert int(st["routed_held"]) == int(st0["routed_held"])
    assert int(st["routed_absent"]) == int(st0["routed_absent"]) - n_zero
    assert int(st["routed_held"]) + int(st["routed_absent"]) + n_zero == k * int(alive.sum())
    assert int(st["experts_hit"]) == int(st0["experts_hit"])
    assert int(st["compact"]) == int(st0["compact"]) == int(moe._row_bound(t * k, count, of) < t * k)
    if int(st["compact"]):
        (a, _), (b, _) = _both_branches(monkeypatch, (x, w, e, w_in, w_out, live), first,
                                        moe.swiglu, of, n_real=real)
        assert np.array_equal(_bits(a), _bits(b)) and np.array_equal(_bits(a), _bits(got))


@pytest.mark.parametrize("real", [None, 16])
def test_without_a_zero_compute_output_the_layer_is_the_one_it_always_was(real):
    """A caller that passes no zero-compute width traces to a program with no
    `moe_zero` scope and no third count; one whose router has no zero-compute
    output (`real` = the router's width) answers the same bits."""
    from tpuserve.ops import moe

    x, w, e, w_in, w_out, live = _layer(5, 4, 4, 16, 4, 256, "float32", "swiglu", True)
    fn = lambda *a: moe.held_experts(  # noqa: E731
        *a, 4, w_in, w_out, moe.swiglu, live=live, of=16, real=real)
    text = str(jax.make_jaxpr(fn)(x, w, e))
    got, st = jax.jit(fn)(x, w, e)
    old = _held_experts_swiglu_pr31(x, w, e, 4, *w_in, w_out, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(old), rtol=1e-5, atol=1e-5)
    assert ("routed_zero" in st) == (real is not None)
    if real is None:
        before = str(jax.make_jaxpr(lambda *a: moe.held_experts(
            *a, 4, w_in, w_out, moe.swiglu, live=live, of=16))(x, w, e))
        assert text == before and "moe_zero" not in text
    else:
        assert int(st["routed_zero"]) == 0


def test_a_token_with_every_pick_zero_compute_and_one_with_none():
    from tpuserve.ops import moe

    real, zero, k, d = 8, 4, 3, 6
    rng = np.random.default_rng(21)
    logits = np.full((2, real + zero), -5.0, np.float32)
    logits[0, [8, 9, 11]] = [3.0, 2.0, 1.0]     # token 0: three zero-compute outputs
    logits[1, [0, 5, 6]] = [3.0, 2.0, 1.0]      # token 1: three real experts, of which 5 and 6 held
    w, e = moe.topk_route(jnp.asarray(logits), k, normalize=False, scale=6.0)
    x = jnp.asarray(rng.standard_normal((2, d)), jnp.float32)
    w_in = tuple(jnp.asarray(rng.standard_normal((4, d, 5)), jnp.float32) for _ in range(2))
    w_out = jnp.asarray(rng.standard_normal((4, 5, d)), jnp.float32)
    y, st = moe.held_experts(x, w, e, 4, w_in, w_out, moe.swiglu, of=real + zero, real=real)
    assert (int(st["routed_zero"]), int(st["routed_held"]), int(st["routed_absent"])) == (3, 2, 1)
    np.testing.assert_allclose(y[0], np.sum(w[0]) * np.asarray(x[0]), rtol=1e-6)
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    want = sum(6.0 * p[1, g] * (np.asarray(jax.nn.silu(x[1] @ w_in[0][g - 4])) *
                                np.asarray(x[1] @ w_in[1][g - 4])) @ np.asarray(w_out[g - 4])
               for g in (5, 6))
    np.testing.assert_allclose(y[1], want, rtol=2e-4, atol=2e-4)


# -- the grouped product's tiles follow what an expert gets (ISSUE 48) ----------------------------

# (launch rows, step rows, k, count, of, D, F): the five routed cells (`benchmark/configs/*.json`:
# Laguna, Nemotron's latent experts, JoyAI, LongCat's 16 of 512 + 256 zero-compute, Xing)
CELL_SHAPES = {
    "laguna": (1024, 128, 10, 128, 256, 3072, 1024),
    "nemotron": (1024, 256, 22, 128, 512, 1024, 2688),
    "joyai": (4096, 16, 8, 256, 256, 2048, 768),
    "longcat": (1024, 256, 12, 16, 768, 6144, 2048),
    "xing": (4096, 64, 4, 64, 64, 3584, 1024),
}


# (launch, step) -> ((tm, tk, tn) of the in-product, of the out-product): what the sweep on the
# chip led to (PERF.md section 6, PR 48). K is whole but for LongCat's 6144-deep and Xing's
# 3584-deep in-kernels, whose blocks fit at no N tile of 512 or more (those keep the cut tiles:
# Xing's two programs are the parent's); only Xing's launch gives an expert a tile of 256 rows.
CELL_TILES = {
    "laguna": (((128, 3072, 512), (128, 1024, 1536)),) * 2,
    "nemotron": (((128, 1024, 896), (128, 2688, 512)),) * 2,
    "joyai": (((128, 2048, 768), (128, 768, 2048)),) * 2,
    "longcat": (((128, 1024, 1024), (128, 2048, 768)),) * 2,
    "xing": (((256, 896, 1024), (256, 1024, 896)), ((128, 896, 1024), (128, 1024, 896))),
}


@pytest.mark.parametrize("phase", ["launch", "step"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_rules_tiles_at_a_cells_shapes(cell, phase):
    """At each cell's launch and step: the kernel's tiles divide K and N in
    multiples of 128 and fit the stated fast-memory budget beside the row
    tile's blocks, no wider N tile would, K is whole wherever an N tile of
    `TN_LEAST` leaves it room, the row tile follows the rows an expert expects
    and the compact branch carries a whole number of them."""
    from tpuserve.ops import moe

    launch, step, k, count, of, d, f = CELL_SHAPES[cell]
    picks = (launch if phase == "launch" else step) * k
    expects = picks / of
    bound = moe._row_bound(picks, count, of)
    assert bound <= picks and (bound == picks or bound % moe._row_tile(expects) == 0)
    assert bound == picks or bound >= picks * count / of * moe.COMPACT_SLACK
    for (kk, n), want in zip(((d, f), (f, d)), CELL_TILES[cell][phase == "step"]):
        tk, tn = moe._kernel_tiles(kk, n)
        tm = moe._row_tile(expects, tk, tn)
        assert (tm, tk, tn) == want
        assert kk % tk == 0 and n % tn == 0 and tn % 128 == 0 and bound % tm == 0
        assert tm == (256 if expects >= 256 else 128)
        assert moe._tile_bytes(tm, tk, tn) <= moe.TILE_BUDGET < 16 * 2 ** 20
        whole = moe._tile_bytes(moe.ROW_TILE, kk, moe.TN_LEAST) <= moe.TILE_BUDGET
        assert (tk == kk) == whole, "K whole wherever it fits: an expert's kernel is fetched once"
        if whole:
            wider = [t for t in range(tn + 128, n + 1, 128) if n % t == 0]
            assert all(moe._tile_bytes(moe.ROW_TILE, tk, t) > moe.TILE_BUDGET for t in wider)


@pytest.mark.parametrize("expects,tiles,tm", [
    (0.5, (2048, 768), 128), (4, (1024, 896), 128), (128, (2048, 768), 128),
    (255, (1024, 896), 128), (256, (1024, 896), 256), (4096, (2048, 512), 256),
    (4096, (2048, 768), 128),    # the blocks of 256 rows would not fit beside this kernel block
    (256, (0, 0), 256), (40, (0, 0), 128)])   # what `_row_bound` rounds to: no kernel named
def test_the_row_tile_follows_the_rows_an_expert_expects(expects, tiles, tm):
    from tpuserve.ops import moe

    assert moe._row_tile(expects, *tiles) == tm
    assert moe._tile_bytes(tm, *tiles) <= moe.TILE_BUDGET


@pytest.mark.parametrize("rows", [128, 896, 4096, 6400, 32768])
@pytest.mark.parametrize("k,n", [(3072, 1024), (2048, 768), (2688, 1024), (3584, 1024)])
def test_the_kernels_tiles_are_the_kernels_own(monkeypatch, rows, k, n):
    """(tk, tn) handed to megablox for a kernel do not change with the rows
    carried nor with the rows an expert expects: a row's sum over K is the same
    whoever shares its launch."""
    from tpuserve.ops import moe

    tilings = _recorded_gmm(monkeypatch, run=False)
    for expects in (1.0, 255.0, 4096.0 if rows % 256 == 0 else 40.0):
        moe._grouped_dot(rows, jnp.bfloat16, (64, k, n), (64, n, k), expects=expects)(
            jax.ShapeDtypeStruct((rows, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16), None)
    assert {t[2:] for t in tilings} == {moe._kernel_tiles(k, n)} and len(tilings) == 3
    assert all(rows % t[1] == 0 for t in tilings)


@pytest.mark.parametrize("k,n,tiles", [
    (16384, 1024, (1024, 1024)),   # no N tile leaves room for a K this deep: the old rule stands
    (6144, 2048, (1024, 1024)),    # LongCat's in-kernels: K whole fits only under `TN_LEAST`
    (3584, 1024, (896, 1024)),     # Xing's: at an N tile of 256, and the step lost what the launch won
    (1000, 1024, (0, 1024)),       # K in no whole tiles: `ragged_dot`
    (2048, 64, (1024, 0)), (128, 128, (128, 128)), (256, 512, (256, 512)), (512, 256, (512, 256))])
def test_where_k_whole_does_not_fit_the_cut_tiles_stand(k, n, tiles):
    from tpuserve.ops.moe import _kernel_tiles, _tile

    assert _kernel_tiles(k, n) == tiles
    if tiles[0] != k or n < 512:
        assert tiles == (_tile(k), _tile(n))


def _recorded_gmm(monkeypatch, run=True):
    """The backend named `tpu` and megablox run in the interpreter (or, with
    `run` false, not at all): -> the list of (rows, tm, tk, tn)
    `_grouped_dot` handed it, one a product traced."""
    import functools

    from jax.experimental.pallas.ops.tpu import megablox

    tilings = []
    real = megablox.gmm

    def gmm(lhs, rhs, sizes, *, tiling, **kw):
        tilings.append((lhs.shape[0], *tiling))
        return real(lhs, rhs, sizes, tiling=tiling, interpret=True, **kw) if run else None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(megablox, "gmm", functools.wraps(real)(gmm))
    return tilings


@pytest.mark.parametrize("first,count,of,k,t,body,dims", [
    (4, 4, 16, 4, 256, "relu2", (256, 128)), (0, 4, 16, 8, 1024, "swiglu", (128, 128)),
    (0, 8, 8, 2, 128, "swiglu", (128, 256))])
def test_the_kernels_tiles_do_not_change_with_the_rows_nor_the_rows_bits(monkeypatch, first, count,
                                                                        of, k, t, body, dims):
    """`compact` and `wide` hand megablox the same (tk, tn) for a kernel and
    the same row tile, whatever rows each carries, and a held pick's row is the
    same bits in both (the interpreter stands in for the chip)."""
    from tpuserve.ops import moe

    tilings = _recorded_gmm(monkeypatch)
    layer = _layer(11, first, count, of, k, t, "bfloat16", body, True, *dims)
    x, w, e, w_in, w_out, live = layer
    fn = moe.swiglu if body == "swiglu" else moe.relu2
    bound = moe._row_bound(k * t, count, of)
    (compact, cs), (wide, ws) = _both_branches(monkeypatch, layer, first, fn, of) \
        if bound < k * t else [jax.jit(lambda *a: moe.held_experts(
            *a, first, w_in, w_out, fn, live=live, of=of))(x, w, e)] * 2
    assert (_bits(compact) == _bits(wide)).all()
    d, f = dims
    by_kernel = {}
    for rows, tm, tk, tn in tilings:
        assert rows in (bound, k * t) and rows % tm == 0
        assert tm == moe._row_tile(k * t / of, tk, tn)
        by_kernel.setdefault(tk, set()).add((tk, tn))
    assert {rows for rows, *_ in tilings} == {bound, k * t}
    assert by_kernel == {d: {moe._kernel_tiles(d, f)}, f: {moe._kernel_tiles(f, d)}}
    plain = jax.jit(lambda *a: moe.held_experts(
        *a, first, w_in, w_out, fn, live=live))(x, w, e)[0]   # no `of`: one branch
    np.testing.assert_allclose(np.asarray(compact), np.asarray(plain), rtol=2e-2, atol=2e-2)


# (K, N, sizes, rows): K whole at the rule's tiles, through megablox in the interpreter.
GMM_CASES = {
    "a tile straddles three experts": (256, 384, [40, 50, 166, 128], 384),
    "an empty expert": (384, 256, [100, 0, 28, 128], 256),
    "rows past the groups' sum": (256, 256, [30, 60, 20], 256),
    "an expert of several tiles": (128, 384, [300, 10, 74], 384),
    "every expert empty but one": (256, 128, [0, 0, 128, 0], 128),
}


@pytest.mark.parametrize("case", sorted(GMM_CASES))
def test_megablox_at_the_rules_tiles_is_the_ragged_dot(case):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from tpuserve.ops import moe

    k, n, sizes, rows = GMM_CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)) / 16, jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    tiles = (moe._row_tile(rows / len(sizes), *moe._kernel_tiles(k, n)), *moe._kernel_tiles(k, n))
    assert tiles[1] == k and rows % tiles[0] == 0
    got = gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32, tiling=tiles, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)
    live = int(sizes.sum())
    assert got.dtype == jnp.float32 and got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got[:live]), np.asarray(want[:live]), rtol=1e-5,
                               atol=1e-5)
