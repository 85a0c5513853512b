"""The routed expert layer over a share (`tpuserve.ops.moe` `topk_route`,
`held_experts`, `_grouped_dot`'s tiles), generalised in ISSUE 32: softmax or
sigmoid scores with a selection bias, an expert's body of two kernels or of
three, one sort and one grouped product for both. Tier-1 (`tests/test_moe.py`
is marked slow as a whole: the Switch layer's mesh tests)."""

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
