"""The hyper-connection's two kernels (ISSUE 47) in the Pallas interpreter on
the CPU, against the plain functions they stand in for on the TPU
(`hyper.maps`, `mix_in`, `mix_out`): the maps to 1e-5, `u` and `X'` to one unit
in bfloat16's last place; `H_res` doubly stochastic as the XLA path's is held
to be; a float32 `Phi` through its three terms; and which launches `fits`
takes."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_mla_hc import sums_off
from tpuserve.ops import hyper

ARGS = (1e-6, 20, 1e-6, (-30.0, 30.0))   # rms_norm_eps, hc_sinkhorn_iters, hc_eps, the clamp
TILE = hyper.ROW_TILE


def case(rows: int, n: int, d: int, live: int | None = None, phi=jnp.bfloat16, seed: int = 0):
    """A launch of ``rows`` rows of unit deviation (rows from ``live`` on are
    dead: zeros, as a launch's padding is) and one sublayer's tensors at the
    family's drawn scales (`models/mla_hc.py`)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, n * d))
    if live is not None:
        x[live:] = 0.0
    hp = {"phi": jnp.asarray(rng.standard_normal((n * d, 2 * n + n * n)) / np.sqrt(n * d), phi),
          "alpha": jnp.asarray(rng.uniform(1.5, 4.5, 3) * [1, 1, 0.15], jnp.float32),
          "b_pre": jnp.asarray(rng.uniform(-0.3, 0.3, n), jnp.float32),
          "b_post": jnp.asarray(rng.uniform(-3.3, -2.7, n), jnp.float32),
          "b_res": jnp.asarray(rng.uniform(-0.3, 0.3, (n, n)) + 1.25 * np.eye(n), jnp.float32)}
    return jnp.asarray(x, jnp.bfloat16), hp, jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)


def within_one_ulp(got, want) -> bool:
    """Of bfloat16 (8 bits: a unit in the last place of `want` is at most
    2^-7 of it), beside a float32 sum's own rounding."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return bool(np.all(np.abs(got - want) <= np.abs(want) * 2.0 ** -7 + 1e-6))


@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("rows,live", [(TILE, None), (3 * TILE, None), (2 * TILE, TILE + 37)],
                         ids=["one-row-tile", "three-row-tiles", "dead-rows"])
def test_enter_and_leave_are_the_maps_and_the_mixes(rows, live, n, d):
    x, hp, y = case(rows, n, d, live)
    want = hyper.maps(x, hp, n, *ARGS)
    u, h = hyper.enter(x, hp, n, *ARGS, interpret=True)
    got = hyper.unpack(h, n)
    assert u.shape == (rows, d) and u.dtype == x.dtype and h.dtype == jnp.float32
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)
    assert sums_off(got[2]) < 1e-4   # what the XLA path's twenty iterations are held to
    assert within_one_ulp(u, hyper.mix_in(x, want[0]))
    out = hyper.leave(x, y, h, n, interpret=True)
    assert out.shape == x.shape and out.dtype == x.dtype
    assert within_one_ulp(out, hyper.mix_out(x, want[2], want[1], y))
    # the mixes alone, given the kernel's own maps: mix_in's and mix_out's sums in their order
    assert within_one_ulp(u, hyper.mix_in(x, got[0]))
    assert within_one_ulp(out, hyper.mix_out(x, got[2], got[1], y))
    if live is not None:   # a dead row is a row of zeros in, finite maps, zeros and H_post y out
        assert bool(jnp.all(jnp.isfinite(h))) and not bool(jnp.any(u[live:]))


def test_a_float32_phi_goes_through_its_three_terms():
    """`Phi` drawn in float32 beside a bfloat16 stream: the kernel's product
    takes three bfloat16 terms of it (one trip), XLA's HIGHEST product six
    passes; the maps agree as closely as with a bfloat16 `Phi`, where rounding
    `Phi` to bfloat16 first moves every one of them over ten times the tolerance."""
    x, hp, _ = case(TILE, 4, 256, phi=jnp.float32)
    want = hyper.maps(x, hp, 4, *ARGS)
    got = hyper.unpack(hyper.enter(x, hp, 4, *ARGS, interpret=True)[1], 4)
    rounded = hyper.maps(x, dict(hp, phi=hp["phi"].astype(jnp.bfloat16)), 4, *ARGS)
    for g, w, r in zip(got, want, rounded):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)
        assert float(jnp.abs(r - w).max()) > 1e-4
    assert [t.dtype for t in hyper._terms(hp["phi"], jnp.bfloat16)] == [jnp.bfloat16] * 3
    total = sum(t.astype(jnp.float32) for t in hyper._terms(hp["phi"], jnp.bfloat16))
    assert bool(jnp.array_equal(total, hp["phi"]))


def test_the_maps_lie_in_whole_sublane_groups():
    """`H_pre` and `H_post` in the first group of eight rows, a row of `H_res`
    in each group after; `unpack` reads them back from where `_places` put
    `Phi`'s columns."""
    assert hyper._layout(4) == (8, 8, 48) and hyper._layout(1) == (8, 8, 16)
    assert hyper._places(4) == [*range(8), *range(8, 12), *range(16, 20), *range(24, 28),
                                *range(32, 36)]
    assert hyper._places(1) == [0, 1, 8]
    h = jnp.zeros((5, 128)).at[:, jnp.asarray(hyper._places(4))].set(jnp.arange(24.0))
    h_pre, h_post, h_res = hyper.unpack(h, 4)
    assert h_pre[:, 0].tolist() == [0, 1, 2, 3] and h_post[:, 0].tolist() == [4, 5, 6, 7]
    assert h_res[:, :, 0].tolist() == [[8, 9, 10, 11], [12, 13, 14, 15], [16, 17, 18, 19],
                                       [20, 21, 22, 23]]


@pytest.mark.parametrize("rows,n,d,dtype,takes", [
    (4096, 4, 3584, jnp.bfloat16, True),      # the cell's launch
    (TILE, 1, 128, jnp.bfloat16, True),
    (4096, 4, 3584, jnp.float32, False),      # the product's identity needs a bfloat16 stream
    (4096, 4, 3584 + 64, jnp.bfloat16, False),   # a stream that is not whole lane tiles
    (4096 + TILE // 2, 4, 3584, jnp.bfloat16, False),  # rows that are not whole row tiles
    (64, 4, 3584, jnp.bfloat16, False),       # a step's lanes
    (4096, 16, 128, jnp.bfloat16, False),     # more maps a token than one lane tile holds
], ids=["the-cell", "one-stream", "float32", "half-a-lane-tile", "half-a-row-tile", "a-step",
        "sixteen-streams"])
def test_fits_takes_whole_bfloat16_tiles_alone(rows, n, d, dtype, takes):
    assert hyper.fits(rows, n, d, dtype) is takes
