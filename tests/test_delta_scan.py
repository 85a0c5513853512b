"""The launch's chunked delta rule as one kernel call (`ops/delta_scan.py`, ISSUE
54), in the Pallas interpreter on the CPU at toy shapes: against the plain form
(`mixers.DeltaMixer._delta_chunks`) AND against the recurrence token by token
(`ops/delta_update.delta_step`): o, the pieces' ending states and the
convolution's rows; pieces that open mid-launch, a piece over several tiles,
rows that are not live, steps past 1, decays strong enough that a naive
``e^(-G)`` would overflow; which path a launch takes, and what counts it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_hybrid_delta import ARCH, make_model
from tpuserve.models import mixers
from tpuserve.ops import delta_scan as ds
from tpuserve.ops import delta_update as du

D = 128
# The tolerances `tests/test_hybrid_delta.py` holds the chunked form to against
# the recurrence (2e-4 of the largest value); kernel against plain form, the same
# float32 products in another order, is held ten times closer.
RECURRENCE, PLAIN = 2e-4, 2e-5


class Plain(mixers.DeltaMixer):
    kd = D

    def __init__(self, heads: int = 0):
        self.kh = heads


# (tiles, rows a tile, heads, heads a cell, tiles by piece, live rows by piece, what is special)
CASES = {
    "one-piece-three-tiles": (3, 32, 2, 2, [3], [96], {}),
    "opens-mid-launch": (3, 32, 4, 2, [1, 2], [32, 64], {}),
    "padded-tails": (3, 64, 2, 2, [2, 1], [70, 9], {}),
    "a-tile-of-no-piece": (3, 32, 2, 2, [1, 1], [20, 32], {}),
    "eight-heads-two-cells": (2, 32, 8, 4, [2], [61], {}),
    "overflowing-decay": (2, 64, 2, 2, [2], [128], {"fast": -5.0}),
    "slow-decay": (2, 32, 2, 1, [1, 1], [32, 31], {"fast": -0.01}),
    "steps-to-one-only": (2, 32, 2, 2, [2], [50], {"beta": 1.0}),
    "first-piece-empty": (3, 32, 2, 2, [0, 2, 1], [0, 64, 30], {}),
}


def launch(case: str):
    """The tiles' inputs of one made-up launch: what a convolution might give and,
    from it, q of length 1 / sqrt(D), k of length 1 and v; beta up to 2 (negative eigenvalues allowed) or up to ``beta``, a
    quarter of the channels decaying by ``fast`` a token, g and beta zero at rows
    that are not live."""
    K, T, H, hb, n_tiles, n_live, special = CASES[case]
    n_tiles, n_live = (x + [0] * (K - len(x)) for x in (n_tiles, n_live))   # a launch has K pieces
    rng = np.random.default_rng(sum(map(ord, case)))
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    conv = jnp.asarray(f(K, T, 3 * H * D))
    g = -0.05 * np.abs(f(K, H, T, D))
    if "fast" in special:
        g[..., ::4] = special["fast"]
    beta = rng.uniform(0.0, special.get("beta", 2.0), (K, H, T)).astype(np.float32)
    first = np.cumsum([0] + n_tiles[:-1])
    piece = np.minimum(np.searchsorted(np.cumsum(n_tiles), np.arange(K), side="right"), K - 1)
    opens = np.arange(K) == first[piece]
    live = np.zeros((K, T), bool)
    for p, (at, n) in enumerate(zip(first, n_live)):
        live.reshape(-1)[at * T:at * T + n] = True
        assert n <= n_tiles[p] * T
    g, beta = g * live[:, None, :, None], beta * live[:, None, :]
    q, k, v = (x.transpose(0, 2, 1, 3) for x in Plain(H)._delta_heads(conv))     # (K, H, T, D)
    arrays = [q, k, v, *(jnp.asarray(x) for x in (g, beta, f(K, H, D, D)))]
    return conv, arrays, jnp.asarray(opens), jnp.asarray(piece.astype(np.int32)), first, \
        n_tiles, n_live, hb


def scan(conv, g, beta, s0, opens, piece, heads_block):
    """The kernel in the interpreter: it takes what the convolution gives, (K, T,
    3 H D), and g and beta by row, and gives o by row; here g, beta and o are
    by head, as the plain form has them."""
    o, s_end = ds.delta_scan(conv, g.transpose(0, 2, 1, 3), beta.transpose(0, 2, 1), s0, opens,
                             piece, l2_eps=Plain.L2_EPS, heads_block=heads_block, interpret=True)
    return o.transpose(0, 2, 1, 3), s_end


def recurrence(q, k, v, g, beta, s0, first, n_live):
    """Token by token, a piece at a time -> (o by piece, the state it ends with)."""
    K, H, T, _ = q.shape
    rows = lambda x: np.asarray(x).transpose(0, 2, 1, 3).reshape(K * T, H, -1)  # noqa: E731
    q, k, v, g = (rows(x) for x in (q, k, v, g))
    beta = np.asarray(beta).transpose(0, 2, 1).reshape(K * T, H)
    step = jax.jit(du.delta_step)
    out = []
    for p, (at, n) in enumerate(zip(first, n_live)):
        S, o = s0[p][None], []
        for i in range(at * T, at * T + n):
            o_i, S = step(S, q[i][None], k[i][None], v[i][None], np.exp(g[i])[None],
                          beta[i][None], jnp.asarray([True]))
            o.append(np.asarray(o_i[0]))
        out.append((np.stack(o) if o else np.zeros((0, H, D), np.float32), np.asarray(S[0])))
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_plain_form_and_the_recurrence(case):
    conv, arrays, opens, piece, first, n_tiles, n_live, hb = launch(case)
    q, k, v, g, beta, s0 = arrays
    K, H, T, _ = q.shape
    o, s_end = scan(conv, g, beta, s0, opens, piece, heads_block=hb)
    o_plain, s_out = jax.jit(Plain()._delta_chunks)(q, k, v, g, beta, opens, s0[piece])
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s_end)).all()
    scale = float(jnp.max(jnp.abs(o_plain)))
    assert float(jnp.max(jnp.abs(o - o_plain))) < PLAIN * scale
    by_rows = np.asarray(o).transpose(0, 2, 1, 3).reshape(K * T, H, D)
    for p, (o_want, s_want) in enumerate(recurrence(*arrays, first, n_live)):
        if not n_tiles[p]:
            np.testing.assert_array_equal(np.asarray(s_end[p]), np.asarray(s0[p]))
            continue
        last = first[p] + n_tiles[p] - 1
        s_scale = float(np.abs(s_want).max())
        assert float(jnp.max(jnp.abs(s_end[p] - s_out[last]))) < PLAIN * s_scale
        assert float(np.abs(np.asarray(s_end[p]) - s_want).max()) < RECURRENCE * s_scale
        got = by_rows[first[p] * T:first[p] * T + n_live[p]]
        assert float(np.abs(got - o_want).max()) < RECURRENCE * float(np.abs(o_want).max())


def test_a_naive_exponent_would_overflow_where_the_kernel_stays_finite():
    """The case's decay is e^-5 a token: over a tile of 64 rows ``e^(-G)`` is
    e^320, past float32 (e^88) after 18 rows; the kernel takes no exponent above
    0, so no entry of a pair table is past the product of two lengths."""
    conv, (q, k, _v, g, beta, s0), opens, piece, *_ = launch("overflowing-decay")
    G = jnp.cumsum(g[0, 0], axis=0)
    assert not np.isfinite(np.asarray(jnp.exp(-G))).all()
    kk, qk = Plain()._pair_tables(q[0, 0] * D ** 0.5, k[0, 0], G)
    assert float(jnp.max(jnp.abs(kk))) <= 1.0 + 1e-5 and float(jnp.max(jnp.abs(qk))) <= 1.0 + 1e-5
    o, s_end = scan(conv, g, beta, s0, opens, piece, heads_block=2)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s_end)).all()


@pytest.mark.parametrize("tile", [32, 128])
def test_the_inverse_by_blocks_is_the_mixers(tile):
    """The kernel's inverse alone (in a call of its own, the interpreter): the
    diagonal blocks as diagonals by their series, spread, and pairs joined,
    against `mixers.unit_lower_inverse` and against the definition."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(tile)
    a = np.tril(rng.uniform(-0.2, 0.2, (tile, tile)), -1).astype(np.float32)
    block = np.arange(tile) // ds.SUB
    diagonals = np.zeros((ds.SUB, tile), np.float32)
    for d in range(1, ds.SUB):
        at = np.arange(tile - d)
        diagonals[d, :tile - d] = np.where(block[at + d] == block[at], a[at + d, at], 0.0)
    under = np.where(block[:, None] > block[None, :], a, 0.0)

    def inverse(on_ref, under_ref, out_ref):
        out_ref[...] = ds._join(ds._spread(ds._block_inverses(on_ref[...])), under_ref[...],
                                ds.SUB)

    got = pl.pallas_call(inverse, out_shape=jax.ShapeDtypeStruct((tile, tile), jnp.float32),
                         interpret=True)(jnp.asarray(diagonals), jnp.asarray(under))
    want = mixers.unit_lower_inverse(jnp.asarray(a), ds.SUB)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    eye = (np.eye(tile) + a.astype(np.float64)) @ np.asarray(got, np.float64)
    assert np.abs(eye - np.eye(tile)).max() < 1e-5 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("tile,heads,dim,takes", [
    (128, 64, 128, True), (256, 8, 128, True), (128, 4, 128, True), (128, 12, 128, False),
    (64, 64, 128, False), (384, 64, 128, False), (128, 64, 64, False), (24, 8, 16, False)])
def test_the_shapes_the_kernel_takes(tile, heads, dim, takes):
    assert ds.supported(tile, heads, dim) is takes


def test_a_launch_off_the_tpu_or_at_a_shape_the_kernel_refuses_takes_the_plain_form(
        tmp_path, monkeypatch):
    """The path is chosen when the launch is traced, from the backend and the
    static shapes: the CPU takes the plain form whatever the shape; a TPU takes
    the plain form at the toy's tiles of 4 rows and heads of 16 channels."""
    model = make_model(str(tmp_path))
    assert model._scan_path({"T": 128}) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model._scan_path({"T": 4}) == "xla" and model._scan_path({"T": 128}) == "xla"
    monkeypatch.setattr(model, "kd", 128)
    assert model._scan_path({"T": 128}) == "kernel" and model._scan_path({"T": 4}) == "xla"


def _tiles_inputs(model, T, K=3, seed=3):
    H, Dm = model.kh, model.kd
    rng = np.random.default_rng(seed)
    C, lengths = K * T, [T + T // 2, T - 3]                            # two tiles, then one
    qkv = jnp.asarray(rng.standard_normal((C, 3 * H * Dm)), jnp.float32)
    live = np.concatenate([np.arange(2 * T) < lengths[0], np.arange(T) < lengths[1]])
    g = -np.abs(rng.standard_normal((C, H, Dm))).astype(np.float32) * 0.05 * live[:, None, None]
    beta = rng.uniform(0.0, 2.0, (C, H)).astype(np.float32) * live[:, None]
    lp = {"conv_w": jnp.asarray(rng.standard_normal((4, 3 * H * Dm)), jnp.float32) * 0.5}
    s0 = jnp.asarray(rng.standard_normal((K, H, Dm, Dm)), jnp.float32)
    c0 = jnp.asarray(rng.standard_normal((K, 3, 3 * H * Dm)), jnp.float32)
    launch_ = {"slot": jnp.asarray([0, 1, 2]), "start": jnp.asarray([5, 0, 0]),
               "length": jnp.asarray(lengths + [0]), "pages": jnp.zeros((K, 1), jnp.int32)}
    return lp, qkv, jnp.asarray(g), jnp.asarray(beta), model._tiles(launch_, C), s0, c0


def test_a_launchs_tiles_through_the_kernel_are_the_plain_forms(tmp_path, monkeypatch):
    """`_delta_tiles` whole, both paths: o, the pieces' states and the
    convolution's rows (which no path touches) of two pieces, the first over two
    tiles, at heads of 128 channels."""
    arch = dict(ARCH, linear_attn_config={**ARCH["linear_attn_config"], "head_dim": 128,
                                          "num_heads": 2})
    model = make_model(str(tmp_path), arch, name="wide")
    monkeypatch.setattr(ds, "delta_scan", functools.partial(ds.delta_scan, interpret=True))
    lp, qkv, g, beta, t, s0, c0 = _tiles_inputs(model, 32)
    got = model._delta_tiles(lp, qkv, g, beta, t, s0, c0, "kernel")
    want = jax.jit(lambda *a: model._delta_tiles(lp, *a, t, s0, c0, "xla"))(qkv, g, beta)
    for a, b, rows in zip(got, want, (slice(None), slice(0, 2), slice(0, 2))):
        a, b = np.asarray(a)[rows], np.asarray(b)[rows]
        assert np.abs(a - b).max() < PLAIN * np.abs(b).max()
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def test_the_counter_says_where_a_launchs_chunked_rules_ran(tmp_path):
    """`delta_scans_total{phase=prefill,path=}`: the delta-rule layers a
    launch, by the path its plan chose; a step adds nothing to it."""
    model = make_model(str(tmp_path))

    class Names:
        @staticmethod
        def counter(name):
            return name

    names = [c.counter(model, Names(), "prefill") for c in model.COLUMNS]
    at = {p: names.index(f"delta_scans_total{{model=hd,phase=prefill,path={p}}}")
          for p in mixers.PATHS}
    assert all(model.COLUMNS[i].counter(model, Names(), "decode") is None for i in at.values())
    base = {"tokens": 5, "rows": 1, "zero": 1, "carried": 0, "context": 15,
            "sample": {"greedy": 0, "drawn": 0}}
    for path in mixers.PATHS:
        counts = {**base, "paths": dict.fromkeys(mixers.PATHS, 0),
                  "scans": {p: int(p == path) for p in mixers.PATHS}}
        sums = [col.sums(model, [], counts) for col in model.COLUMNS]
        assert sums[at[path]] == len(model.m_layers) == 6
        assert sums[at[next(p for p in mixers.PATHS if p != path)]] == 0
