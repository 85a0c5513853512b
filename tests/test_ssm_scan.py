"""A launch's chunked Mamba-2 scan as one kernel call (`ops/ssm_scan.py`, ISSUE
67), in the Pallas interpreter on the CPU at toy shapes: against the plain form
(`mixers.Mamba2Mixer._scan_pieces` and `_scan_tiles`, which every backend but the
TPU runs): y of every live row, every slot's state and convolution rows after
the launch; one group and several, a piece over several tiles, two pieces in
one launch, a piece from a stored state and one from zeros, a piece of no
tokens, a launch of none, a tile with no live row, a last tile partly live, a
slot that no piece names; which path a launch takes, what counts
it, and a toy `hybrid_ffn` model served with every launch's scans in the kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_hybrid_ffn as hf
from tpuserve.models import mixers
from tpuserve.models.paged_lm import PagedLM
from tpuserve.ops import ssm_scan as ss

# Kernel against plain form in float32: the same products summed in another
# order. In bfloat16 a state that differs in its last float32 place may round
# the other way where it becomes an operand: one part in 2 ** 9 of an entry.
CLOSE = {"float32": 2e-5, "bfloat16": 4e-3}
SLOTS, TAPS = 5, 4


class Plain(mixers.Mamba2Mixer):
    name, conv_k = "plain", TAPS

    def __init__(self, heads: int, head_dim: int, groups: int, state: int, dtype: str):
        self.mh, self.mp, self.mg, self.mn = heads, head_dim, groups, state
        self.dtype = jnp.dtype(dtype)
        self.conv_ch = heads * head_dim + 2 * groups * state


# (rows a tile, heads, head_dim, groups, state, type; by piece: slot, start, length)
CASES = {
    "one-piece-three-tiles-partly-live": (16, 4, 16, 1, 16, "float32", [(2, 0, 43), (0, 0, 0),
                                                                         (1, 0, 0)]),
    "two-pieces-one-stored-one-fresh": (16, 4, 16, 1, 16, "float32", [(3, 7, 19), (0, 0, 16),
                                                                       (1, 0, 0)]),
    "two-groups": (16, 8, 16, 2, 16, "float32", [(1, 0, 20), (4, 3, 9), (0, 0, 0)]),
    "a-piece-of-no-tokens-between": (16, 4, 16, 1, 16, "float32", [(0, 5, 16), (2, 9, 0),
                                                                    (3, 0, 7)]),
    "tiles-with-no-live-row": (16, 4, 16, 1, 16, "float32", [(4, 2, 17), (1, 0, 3), (0, 0, 0),
                                                              (2, 0, 0), (3, 0, 0)]),
    "sixteen-heads-two-blocks-a-group": (16, 32, 8, 2, 16, "float32", [(0, 4, 30), (1, 0, 2),
                                                                        (3, 0, 0)]),
    "heads-of-a-whole-register": (16, 2, 128, 1, 16, "float32", [(0, 4, 30), (1, 0, 2),
                                                                 (3, 0, 0)]),
    "served-type-the-cells-tile": (128, 16, 64, 2, 128, "bfloat16", [(1, 100, 130), (0, 0, 0)]),
    "a-launch-of-no-tokens": (16, 4, 16, 1, 16, "float32", [(2, 0, 0), (1, 3, 0)]),
}


def launch(case: str):
    """One made-up launch of a layer: the projections' rows, the layer's
    vectors, the slots' block as earlier launches might have left it."""
    T, H, P, G, N, dtype, pieces = CASES[case]
    model = Plain(H, P, G, N, dtype)
    K, C, ch = len(pieces), len(pieces) * T, model.conv_ch
    rng = np.random.default_rng(sum(map(ord, case)))
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    lp = {"conv_w": jnp.asarray(0.5 * f(TAPS, ch), model.dtype),
          "conv_b": jnp.asarray(0.1 * f(ch), model.dtype),
          "dt_bias": jnp.asarray(rng.uniform(-4.0, -1.0, H), jnp.float32),
          "A_log": jnp.asarray(rng.uniform(0.0, np.log(16.0), H), jnp.float32),
          "D": jnp.asarray(1.0 + 0.3 * f(H))}
    xbc, dt = jnp.asarray(f(C, ch), model.dtype), jnp.asarray(f(C, H))
    ssm, conv = jnp.asarray(f(SLOTS, H, P, N)), jnp.asarray(f(SLOTS, TAPS - 1, ch), model.dtype)
    slot, start, length = (jnp.asarray(x, jnp.int32) for x in zip(*pieces))
    t = PagedLM._tiles({"slot": slot, "start": start, "length": length,
                        "pages": jnp.zeros((K, 1), jnp.int32)}, C)
    return model, lp, xbc, dt, t, ssm, conv, slot, start, length


def scan(model, path, *launch):
    """What `_mamba_prefill` does between the projections and the gated norm
    -> (y, the slots' states, the slots' convolution rows)."""
    return (model._scan_slots if path == "kernel" else model._scan_pieces)(*launch)


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setattr(ss, "ssm_scan", functools.partial(ss.ssm_scan, interpret=True))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_plain_form(case, interpreted):
    model, *args = launch(case)
    t, ssm0, slot, length = args[3], np.asarray(args[4]), np.asarray(args[6]), np.asarray(args[8])
    y, ssm, conv = scan(model, "kernel", *args)
    y_want, ssm_want, conv_want = jax.jit(
        lambda *a: scan(model, "xla", *a[:3], t, *a[3:]))(*args[:3], *args[4:])
    tol = CLOSE[model.dtype.name]
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(ssm)).all()
    live = np.asarray(t["valid"])
    assert live.sum() == length.sum()
    if live.any():
        got, want = np.asarray(y)[live], np.asarray(y_want)[live]
        assert np.abs(got - want).max() < tol * np.abs(want).max()
    # every slot's state after the launch: a piece's end where it belongs ...
    for p in np.flatnonzero(length > 0):
        end = np.asarray(ssm_want[slot[p]])
        assert np.abs(np.asarray(ssm[slot[p]]) - end).max() < tol * np.abs(end).max()
        assert not np.array_equal(end, ssm0[slot[p]])
    # ... and every other slot, a piece of no tokens' too, what it held, bit for bit
    rest = np.setdiff1d(np.arange(SLOTS), slot[length > 0])
    np.testing.assert_array_equal(np.asarray(ssm)[rest], ssm0[rest])
    np.testing.assert_array_equal(np.asarray(ssm_want)[rest], ssm0[rest])
    np.testing.assert_array_equal(np.asarray(conv, np.float32), np.asarray(conv_want, np.float32))
    # a tile with no live row did no product: its rows are zeros
    alive = live.reshape(t["K"], t["T"]).any(axis=1)
    assert not np.asarray(y).reshape(t["K"], t["T"], -1)[~alive].any()


def test_tiles_of_no_live_row_change_no_live_row(interpreted):
    """The same prompts with two more (empty) pieces in the launch, so two more
    tiles of no live row: every live row's y and every slot's state are the
    launch's without them, bit for bit."""
    model, lp, xbc, dt, t, ssm, conv, slot, start, length = launch("two-groups")
    T, K = t["T"], t["K"]
    more = lambda x, v: jnp.concatenate([x, jnp.full((2,), v, x.dtype)])  # noqa: E731
    slot2, start2, length2 = more(slot, 0), more(start, 0), more(length, 0)
    pad = lambda x: jnp.concatenate([x, jnp.ones((2 * T,) + x.shape[1:], x.dtype)])  # noqa: E731
    t2 = PagedLM._tiles({"slot": slot2, "start": start2, "length": length2,
                         "pages": jnp.zeros((K + 2, 1), jnp.int32)}, (K + 2) * T)
    y, ssm1, _ = scan(model, "kernel", lp, xbc, dt, t, ssm, conv, slot, start, length)
    y2, ssm2, _ = scan(model, "kernel", lp, pad(xbc), pad(dt), t2, ssm, conv, slot2, start2,
                       length2)
    live = np.asarray(t["valid"])
    np.testing.assert_array_equal(np.asarray(y)[live], np.asarray(y2)[:K * T][live])
    np.testing.assert_array_equal(np.asarray(ssm1), np.asarray(ssm2))


@pytest.mark.parametrize("tile,heads,dim,state,groups,held,takes", [
    (128, 64, 64, 128, 1, "float32", True), (128, 128, 64, 128, 1, "float32", True),
    (128, 32, 64, 128, 2, "float32", True), (256, 16, 128, 128, 2, "float32", True),
    (128, 64, 64, 128, 1, "bfloat16", False), (64, 64, 64, 128, 1, "float32", False),
    (1024, 64, 64, 128, 1, "float32", False), (128, 8, 64, 128, 2, "float32", False),
    (128, 64, 48, 128, 1, "float32", False), (128, 64, 64, 64, 1, "float32", False),
    (4, 8, 32, 8, 1, "float32", False)])
def test_the_shapes_the_kernel_takes(tile, heads, dim, state, groups, held, takes):
    assert ss.supported(tile, heads, dim, state, groups, held) is takes


def test_a_launch_off_the_tpu_or_at_a_shape_the_kernel_refuses_takes_the_plain_form(
        tmp_path, monkeypatch):
    """The path is chosen when the launch is traced, from the backend and the
    static shapes: the CPU takes the plain form whatever the shape; a TPU takes
    the plain form at the toy's tiles of 4 rows and state of 8."""
    model, held = hf.make_model(str(tmp_path)), jnp.zeros((), jnp.float32)
    assert model._scan_path({"T": 128}, held) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model._scan_path({"T": 4}, held) == "xla" and model._scan_path({"T": 128}, held) == "xla"
    for key, value in (("mh", 64), ("mp", 64), ("mn", 128)):
        monkeypatch.setattr(model, key, value)
    assert model._scan_path({"T": 128}, held) == "kernel"
    assert model._scan_path({"T": 4}, held) == "xla"
    assert model._scan_path({"T": 128}, held.astype(jnp.bfloat16)) == "xla"   # a state in bfloat16


def test_the_counter_says_where_a_launchs_scans_ran(tmp_path):
    """`ssm_scans_total{phase=prefill,path=}`: the Mamba-2 layers a launch, by the
    path its plan chose; a step adds nothing to it."""
    model = hf.make_model(str(tmp_path))

    class Names:
        @staticmethod
        def counter(name):
            return name

    names = [c.counter(model, Names(), "prefill") for c in model.COLUMNS]
    at = {p: names.index(f"ssm_scans_total{{model=hf,phase=prefill,path={p}}}")
          for p in mixers.PATHS}
    assert all(model.COLUMNS[i].counter(model, Names(), "decode") is None for i in at.values())
    base = {"tokens": 5, "rows": 1, "zero": 1, "carried": 0, "context": 15,
            "sample": {"greedy": 0, "drawn": 0}}
    for path in mixers.PATHS:
        counts = {**base, "scans": {p: int(p == path) for p in mixers.PATHS}}
        sums = [col.sums(model, [], counts) for col in model.COLUMNS]
        assert sums[at[path]] == len(model.m_layers) == 4
        assert sums[at[next(p for p in mixers.PATHS if p != path)]] == 0


def test_a_toy_model_served_with_every_launchs_scans_in_the_kernel_is_the_reference(
        tmp_path, monkeypatch, interpreted):
    """Packed, chunked prefill and then decode of `tests/test_hybrid_ffn.py`'s toy
    with `_scan_path` steered to the kernel (in the interpreter): the reference's
    one full pass within that file's tolerance, and the launches counted."""
    model = hf.make_model(str(tmp_path), name="kern")
    monkeypatch.setattr(mixers.Mamba2Mixer, "_scan_path", lambda self, t, ssm: "kernel")
    served, out, _ = hf.serve(model, model.init_params(jax.random.key(0)), hf.PROMPTS,
                              hf.MAX_NEWS, launches=hf.PACKED)
    assert [int(s["n_new"]) for s in served] == hf.MAX_NEWS
    assert hf.worst(hf.ARCH, hf.PROMPTS, served) < hf.TOL
    names = [c.counter(model, type("N", (), {"counter": staticmethod(str)}), "prefill")
             for c in model.COLUMNS]
    acc = np.asarray(out["acc"])
    kernel, xla = (names.index(f"ssm_scans_total{{model=kern,phase=prefill,path={p}}}")
                   for p in mixers.PATHS)
    assert acc[0, kernel] == len(hf.PACKED) * len(model.m_layers) and acc[0, xla] == 0
    assert not acc[1, kernel] and not acc[1, xla]
