"""Runtime AOT compilation and execution (C5) on fake CPU devices."""

import jax
import numpy as np
import pytest

from tpuserve.config import ModelConfig
from tpuserve.models import build
from tpuserve.runtime import build_runtime


@pytest.fixture(scope="module")
def toy_runtime():
    cfg = ModelConfig(name="toy", family="toy", batch_buckets=[1, 2, 4],
                      dtype="float32", num_classes=10, parallelism="single")
    model = build(cfg)
    return model, build_runtime(model)


def test_compiles_all_buckets(toy_runtime):
    _, rt = toy_runtime
    assert sorted(rt.executables) == [(1,), (2,), (4,)]


def test_sharded_buckets_mesh_aligned():
    """Sharded mode rounds buckets up to data-axis multiples (8 fake devs)."""
    cfg = ModelConfig(name="toys", family="toy", batch_buckets=[1, 2, 4, 16],
                      dtype="float32", num_classes=10, parallelism="sharded")
    rt = build_runtime(build(cfg))
    assert sorted(rt.executables) == [(8,), (16,)]


def test_run_and_fetch(toy_runtime):
    model, rt = toy_runtime
    batch = np.random.default_rng(0).integers(0, 255, size=(4, 8, 8, 3), dtype=np.uint8)
    out = rt.fetch(rt.run((4,), batch))
    assert out["probs"].shape == (4, 3)
    assert out["indices"].shape == (4, 3)
    np.testing.assert_allclose(out["probs"].sum(axis=-1) <= 1.0, True)


def test_deterministic(toy_runtime):
    model, rt = toy_runtime
    batch = np.full((2, 8, 8, 3), 17, dtype=np.uint8)
    a = rt.fetch(rt.run((2,), batch))
    b = rt.fetch(rt.run((2,), batch))
    np.testing.assert_array_equal(a["indices"], b["indices"])
    np.testing.assert_allclose(a["probs"], b["probs"], rtol=1e-6)


def test_sharded_batch_across_mesh():
    """Batch dim sharded over the data axis of the 8-device mesh runs + matches."""
    cfg = ModelConfig(name="toy8", family="toy", batch_buckets=[8],
                      dtype="float32", num_classes=10, parallelism="sharded")
    model = build(cfg)
    rt8 = build_runtime(model)
    assert rt8.meshes[0].shape["data"] == 8
    batch = np.random.default_rng(2).integers(0, 255, (8, 8, 8, 3), dtype=np.uint8)
    out = rt8.fetch(rt8.run((8,), batch.copy()))
    assert out["probs"].shape == (8, 3)

    # sharded result == single-device result on identical params/batch
    cfg1 = ModelConfig(name="toy1", family="toy", batch_buckets=[8],
                       dtype="float32", num_classes=10, parallelism="single")
    rt1 = build_runtime(build(cfg1))
    out1 = rt1.fetch(rt1.run((8,), batch.copy()))
    np.testing.assert_allclose(out["probs"], out1["probs"], rtol=1e-5)
    np.testing.assert_array_equal(out["indices"], out1["indices"])


def test_replica_mode():
    cfg = ModelConfig(name="toyr", family="toy", batch_buckets=[1],
                      dtype="float32", num_classes=10, parallelism="replica")
    rt = build_runtime(build(cfg))
    assert len(rt.meshes) == len(jax.devices())
    batch = np.zeros((1, 8, 8, 3), dtype=np.uint8)
    outs = [rt.fetch(rt.run((1,), batch)) for _ in range(3)]
    for o in outs[1:]:
        np.testing.assert_allclose(o["probs"], outs[0]["probs"], rtol=1e-6)


def test_padding_lanes_do_not_affect_real_lanes(toy_runtime):
    """Core static-shape invariant (SURVEY.md §4-1)."""
    model, rt = toy_runtime
    item = np.random.default_rng(1).integers(0, 255, size=(8, 8, 3), dtype=np.uint8)
    solo = model.assemble([item], (1,))
    padded = model.assemble([item], (4,))
    out1 = rt.fetch(rt.run((1,), solo))
    out4 = rt.fetch(rt.run((4,), padded))
    np.testing.assert_allclose(out1["probs"][0], out4["probs"][0], rtol=1e-5)
    np.testing.assert_array_equal(out1["indices"][0], out4["indices"][0])


def test_hot_reload_swaps_weights_without_recompile(tmp_path):
    """Write ckpt A, serve, overwrite with ckpt B at the same path, reload:
    outputs change, no recompilation (executable objects identical)."""
    from tpuserve.savedmodel import save_orbax

    ckpt = str(tmp_path / "ckpt")
    cfg = ModelConfig(name="toy", family="toy", batch_buckets=[2],
                      dtype="float32", num_classes=10, parallelism="single",
                      weights=ckpt)
    model = build(cfg)
    params_a = model.init_params(jax.random.key(1))
    save_orbax(ckpt, params_a)
    rt = build_runtime(model)
    exe_before = rt.executables[(2,)][0].compiled

    batch = np.full((2, 8, 8, 3), 50, dtype=np.uint8)
    out_a = rt.fetch(rt.run((2,), batch))

    params_b = jax.tree_util.tree_map(lambda x: x + 0.5, params_a)
    import shutil

    shutil.rmtree(ckpt)
    save_orbax(ckpt, params_b)
    info = rt.reload_params()
    assert info["reload_ms"] > 0

    out_b = rt.fetch(rt.run((2,), batch))
    assert rt.executables[(2,)][0].compiled is exe_before  # no recompile
    assert not np.allclose(out_a["probs"], out_b["probs"])


def test_hot_reload_rejects_mismatched_tree(toy_runtime):
    model, rt = toy_runtime
    before = rt.params_per_mesh
    orig = model.load_params
    model.load_params = lambda: {"w1": np.zeros((4, 4), np.float32)}
    try:
        with pytest.raises(ValueError, match="old params kept"):
            rt.reload_params()
    finally:
        model.load_params = orig
    assert rt.params_per_mesh is before  # still serving the old weights
    batch = np.full((2, 8, 8, 3), 9, dtype=np.uint8)
    assert rt.fetch(rt.run((2,), batch))["probs"].shape == (2, 3)


# -- start-up rules: the device guard and the compile-cache placement ---------

def test_device_guard_refuses_a_cpu_backend_nobody_asked_for():
    """jax falls back to the CPU when the accelerator cannot be opened; the
    serve path refuses that unless JAX_PLATFORMS names cpu."""
    from tpuserve.runtime import check_backend

    for requested in ("", "tpu"):
        with pytest.raises(RuntimeError, match="'cpu' platform.*JAX_PLATFORMS"):
            check_backend("cpu", requested)
    check_backend("cpu", "cpu")
    check_backend("cpu", "tpu,cpu")
    check_backend("tpu", "")


@pytest.mark.parametrize("placed", [None, "/somewhere/else"])
def test_compile_cache_rule(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR is the one way to move the cache: set, no
    code names another directory; unset, it is <checkout>/.jaxcache."""
    import os

    from tpuserve import runtime

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if placed is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    got = runtime.configure_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed is None:
        assert got == os.path.join(checkout, ".jaxcache")
        assert updates["jax_compilation_cache_dir"] == got
    else:
        assert got == placed
        assert "jax_compilation_cache_dir" not in updates


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver reads the smoke's last stdout line and refuses anything but
    {"ok", "device": {"platform", "kind", "count"}}; the per-phase report
    goes on the line before it."""
    import importlib.util
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(repo, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    line = smoke.result_line(True, {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4,
        "jax_version": "0.9.0", "devices": [], "mesh": {}})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_chip_smoke_fails_on_cpu_in_its_device_phase():
    """The smoke must not pass by accident: on a CPU backend it exits
    non-zero naming the platform, prints no result line, and has compiled
    nothing (it never gets past ``describe``)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "platform 'cpu'" in proc.stderr
    assert "native build: start" not in proc.stderr
