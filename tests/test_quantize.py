"""Weight-only int8 quantization (tpuserve/quantize.py): numerics, spec
mirroring for tensor parallelism, and the end-to-end serving path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpuserve import quantize as qz
from tpuserve.config import ModelConfig
from tpuserve.models import build
from tpuserve.runtime import build_runtime


def test_roundtrip_error_bounded_per_channel():
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.3, (64, 96)).astype(np.float32)
    q = qz.quantize_leaf(w)
    assert q[qz.QKEY].dtype == np.int8 and q[qz.QKEY].shape == w.shape
    assert q[qz.SKEY].shape == (1, 96)
    deq = q[qz.QKEY].astype(np.float32) * q[qz.SKEY]
    # Symmetric rounding: error <= scale/2 per element, channel-wise.
    assert (np.abs(deq - w) <= q[qz.SKEY] / 2 + 1e-7).all()


def test_depthwise_uses_second_to_last_axis():
    w = np.random.default_rng(1).normal(size=(3, 3, 512, 1)).astype(np.float32)
    q = qz.quantize_leaf(w)
    assert q[qz.SKEY].shape == (1, 1, 512, 1)


def test_small_int_and_1d_leaves_untouched():
    tree = {
        "kernel": np.zeros((128, 64), np.float32),
        "bias": np.zeros((64,), np.float32),
        "small": np.zeros((4, 4), np.float32),
        "table": np.zeros((128, 64), np.int32),
    }
    out = qz.quantize_tree(tree, min_size=1024)
    assert qz.is_quantized(out["kernel"])
    assert out["bias"] is tree["bias"]
    assert out["small"] is tree["small"]
    assert out["table"] is tree["table"]


def test_zero_weight_channel_dequantizes_to_zero():
    w = np.zeros((64, 64), np.float32)
    q = qz.quantize_leaf(w)
    assert (q[qz.QKEY] == 0).all() and (q[qz.SKEY] == 1.0).all()


def test_specs_for_tree_mirror_tp_sharding():
    params = qz.quantize_tree({
        "up": np.zeros((256, 128), np.float32),    # TP on last axis
        "down": np.zeros((128, 256), np.float32),  # TP on first axis
        "bias": np.zeros((128,), np.float32),
    }, min_size=1024)
    rules = [("up", P(None, "model")), ("down", P("model", None)), (".*", P())]
    out = qz.specs_for_tree(rules, params)
    assert out["up"] == {qz.QKEY: P(None, "model"), qz.SKEY: P(None, "model")}
    # down's channel axis is the last (unsharded) one; its scale replicates.
    assert out["down"] == {qz.QKEY: P("model", None), qz.SKEY: P(None, None)}
    assert out["bias"] == P()


def test_dequantize_tree_matches_numpy():
    rng = np.random.default_rng(2)
    tree = {"k": rng.normal(size=(64, 80)).astype(np.float32),
            "b": rng.normal(size=(80,)).astype(np.float32)}
    qtree = qz.quantize_tree(tree, min_size=1024)
    deq = jax.jit(lambda t: qz.dequantize_tree(t, np.float32))(qtree)
    ref = qtree["k"][qz.QKEY].astype(np.float32) * qtree["k"][qz.SKEY]
    np.testing.assert_allclose(np.asarray(deq["k"]), ref, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(deq["b"]), tree["b"], rtol=1e-6)


def _toy_cfg(**kw) -> ModelConfig:
    return ModelConfig(name="toy", family="toy", batch_buckets=[2],
                       dtype="float32", num_classes=10, parallelism="single",
                       **kw)


def test_end_to_end_toy_matches_fp_serving():
    """Quantized serving agrees with full-precision serving on the same
    weights, and the compiled params really are int8."""
    img = np.random.default_rng(3).integers(0, 255, (8, 8, 3), np.uint8)

    def run(cfg):
        model = build(cfg)
        rt = build_runtime(model)
        bucket = model.buckets()[0]
        batch = model.assemble([img], bucket)
        return rt, rt.fetch(rt.run(bucket, batch))

    rt_fp, out_fp = run(_toy_cfg())
    rt_q, out_q = run(_toy_cfg(quantize="int8", quantize_min_size=1024))

    leaves = jax.tree_util.tree_leaves(rt_q.params_per_mesh[0])
    assert any(x.dtype == np.int8 for x in leaves), "nothing was quantized"
    np.testing.assert_allclose(out_q["probs"], out_fp["probs"], atol=5e-3)
    # Top-1 agreement.
    assert out_q["indices"][0][0] == out_fp["indices"][0][0]


@pytest.mark.parametrize("mode", ["int8", "int8c"])
def test_tp_sharded_quantized_bert_runs(mode):
    """Quantized weights + TP: scales shard with their weights over the
    model axis and the forward stays finite (8 fake CPU devices). The
    int8c variant additionally proves the int8 dot_general partitions
    under GSPMD with the FFN kernels kept quantized (the int8-compute x
    tensor-parallel composition)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device mesh")
    from tpuserve.parallel import make_mesh
    from tpuserve.parallel.mesh import MeshPlan

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:4])
    cfg = ModelConfig(
        name="bert", family="bert", parallelism="sharded", tp=2,
        batch_buckets=[2], seq_buckets=[16], dtype="float32", num_classes=4,
        quantize=mode, quantize_min_size=256,
        options={"layers": 1, "d_model": 32, "heads": 2, "d_ff": 64,
                 "vocab_size": 512},
    )
    model = build(cfg)
    rt = build_runtime(model, mesh=mesh)
    (bucket,) = rt.executables
    item = model.host_decode(b'{"text": "quantized tensor parallel"}',
                             "application/json")
    out = rt.fetch(rt.run(bucket, model.assemble([item, item], bucket)))
    assert np.isfinite(out["probs"]).all()
    if mode == "int8c":
        # The kept-quantized FFN kernels really are sharded int8 on device.
        q8 = rt.params_per_mesh[0]["params"]["layer0"]["mlp_up"]["kernel"]["q8"]
        assert q8.dtype == np.int8
        assert len(q8.addressable_shards) >= 2


def test_int8_matmul_matches_dequant_dense():
    """int8 x int8 -> int32 with dynamic activation scales tracks the
    dequantize-then-dense product to quantization tolerance."""
    from tpuserve.quantize import int8_matmul, quantize_leaf

    rng = np.random.default_rng(7)
    x = rng.standard_normal((4, 96)).astype(np.float32)
    w = rng.standard_normal((96, 128)).astype(np.float32)
    q = quantize_leaf(w)
    ref = x @ (q["q8"].astype(np.float32) * q["q8_scale"])
    got = np.asarray(int8_matmul(jnp.asarray(x), jnp.asarray(q["q8"]),
                                 jnp.asarray(q["q8_scale"]), jnp.float32))
    # int8c adds only activation rounding on top of the weight rounding;
    # bound the error against the output scale (elementwise relative error
    # is meaningless where the dot products cancel to ~0).
    assert np.isfinite(got).all()
    assert np.abs(got - ref).max() < 0.02 * np.abs(ref).max()


def test_int8c_bert_serves_with_bounded_drift():
    """quantize='int8c' (FFN matmuls on the MXU's int8 path) serves with
    top-1 agreement and bounded prob drift vs full precision, and the
    unsupported-family config fails with guidance."""
    def bert_cfg(**over):
        base = dict(
            name="b", family="bert", parallelism="single",
            batch_buckets=[2], seq_buckets=[16], dtype="float32",
            num_classes=4, quantize_min_size=256,
            options={"layers": 2, "d_model": 32, "heads": 2, "d_ff": 64,
                     "vocab_size": 512},
        )
        base.update(over)
        return ModelConfig(**base)

    def run(cfg):
        model = build(cfg)
        rt = build_runtime(model)
        (bucket,) = rt.executables
        item = model.host_decode(b'{"text": "int8 compute on the mxu"}',
                                 "application/json")
        return rt.fetch(rt.run(bucket, model.assemble([item, item], bucket)))

    out_fp = run(bert_cfg())
    out_c = run(bert_cfg(quantize="int8c"))
    assert out_c["indices"][0][0] == out_fp["indices"][0][0]
    # d_model=32 random net with unit-scale init: quantization noise is
    # proportionally larger than at real widths (per-head-dim scales over
    # 16-wide heads); with FFN + attention projections both int8 the
    # observed drift is ~4e-2 with stable top-1. This IS the binding
    # accuracy bound for the full int8c path — the imported-weight gate in
    # test_tf_parity uses 0.05-scale weights whose drift (~3e-5) sits far
    # under its 3e-2 assert, so it checks wiring, not noise margins.
    np.testing.assert_allclose(out_c["probs"], out_fp["probs"], atol=6e-2)

    with pytest.raises(ValueError, match="int8c.*not.*supported|weight-only"):
        build_runtime(build(_toy_cfg(quantize="int8c")))


@pytest.mark.slow  # two full ResNet-50 AOT compiles
def test_int8c_resnet_serves_with_bounded_drift():
    """ResNet-50's int8c site (bottleneck 1x1 convs via Int8Conv1x1,
    including the strided v1-downsample and projection variants): top-1
    agreement and bounded prob drift vs full precision through the
    production runtime."""
    def rn_cfg(**over):
        base = dict(
            name="rn", family="resnet50", parallelism="single",
            batch_buckets=[2], dtype="float32", num_classes=10,
            image_size=32, wire_size=32, quantize_min_size=256,
            options={"v1_downsample": True},
        )
        base.update(over)
        return ModelConfig(**base)

    img = np.random.default_rng(5).integers(0, 255, (32, 32, 3), np.uint8)

    def run(cfg):
        model = build(cfg)
        rt = build_runtime(model)
        (bucket,) = rt.executables
        return rt.fetch(rt.run(bucket, model.assemble([img, img], bucket)))

    out_fp = run(rn_cfg())
    out_c = run(rn_cfg(quantize="int8c"))
    assert out_c["indices"][0][0] == out_fp["indices"][0][0]
    np.testing.assert_allclose(out_c["probs"], out_fp["probs"], atol=3e-2)


def test_int8_conv1x1_matches_dense_conv():
    """Int8Conv1x1's strided matmul == nn.Conv 1x1 with the same
    (dequantized) kernel, on both stride variants."""
    import flax.linen as nn

    from tpuserve.quantize import Int8Conv1x1, quantize_leaf

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)).astype(np.float32))
    w = rng.standard_normal((1, 1, 16, 24)).astype(np.float32)
    for strides in ((1, 1), (2, 2)):
        conv = nn.Conv(24, (1, 1), strides=strides, use_bias=False,
                       dtype=jnp.float32)
        q = quantize_leaf(w)
        wdq = q["q8"].astype(np.float32) * q["q8_scale"]
        ref = conv.apply({"params": {"kernel": jnp.asarray(wdq)}}, x)
        mod = Int8Conv1x1(24, strides=strides, dtype=jnp.float32)
        got = mod.apply({"params": {"kernel": {"q8": jnp.asarray(q["q8"]),
                                               "q8_scale": jnp.asarray(q["q8_scale"])}}}, x)
        assert got.shape == ref.shape
        assert np.abs(np.asarray(got) - np.asarray(ref)).max() \
            < 0.02 * np.abs(np.asarray(ref)).max()


def test_quantize_tree_is_idempotent():
    tree = {"k": np.random.default_rng(6).normal(size=(4096, 8)).astype(np.float32)}
    once = qz.quantize_tree(tree, min_size=1024)
    twice = qz.quantize_tree(once, min_size=1)  # would re-quantize any leaf
    assert qz.is_quantized(twice["k"])
    np.testing.assert_array_equal(twice["k"][qz.QKEY], once["k"][qz.QKEY])
    np.testing.assert_array_equal(twice["k"][qz.SKEY], once["k"][qz.SKEY])


@pytest.mark.slow
def test_quantized_orbax_checkpoint_roundtrip(tmp_path):
    """An int8 orbax checkpoint restores and serves; its outputs match
    quantize-at-load serving exactly (same scheme, same weights)."""
    from tpuserve import savedmodel

    img = np.random.default_rng(7).integers(0, 255, (8, 8, 3), np.uint8)

    # Reference: quantize-at-load serving from raw init weights.
    model_ref = build(_toy_cfg(quantize="int8", quantize_min_size=1024))
    rt_ref = build_runtime(model_ref)
    bucket = model_ref.buckets()[0]
    out_ref = rt_ref.fetch(rt_ref.run(bucket, model_ref.assemble([img], bucket)))

    # Write the quantized checkpoint (what import-model --quantize emits).
    raw = build(_toy_cfg()).load_params()
    ckpt = tmp_path / "toy_q8"
    savedmodel.save_orbax(str(ckpt),
                          qz.quantize_tree(jax.device_get(raw), 1024))

    model_q = build(_toy_cfg(weights=str(ckpt), quantize="int8",
                             quantize_min_size=1024))
    rt_q = build_runtime(model_q)
    out_q = rt_q.fetch(rt_q.run(bucket, model_q.assemble([img], bucket)))
    np.testing.assert_allclose(out_q["probs"], out_ref["probs"], rtol=1e-6)

    leaves = jax.tree_util.tree_leaves(rt_q.params_per_mesh[0])
    assert any(x.dtype == np.int8 for x in leaves)


def test_quantized_checkpoint_without_flag_gives_guidance(tmp_path):
    from tpuserve import savedmodel

    raw = build(_toy_cfg()).load_params()
    ckpt = tmp_path / "toy_q8"
    savedmodel.save_orbax(str(ckpt),
                          qz.quantize_tree(jax.device_get(raw), 1024))
    model = build(_toy_cfg(weights=str(ckpt)))
    with pytest.raises(ValueError, match='quantize = "int8"'):
        model.load_params()


def test_unquantized_checkpoint_serves_with_int8_flag(tmp_path):
    """quantize="int8" over a raw checkpoint quantizes at load (the
    documented fallback)."""
    from tpuserve import savedmodel

    raw = build(_toy_cfg()).load_params()
    ckpt = tmp_path / "toy_raw"
    savedmodel.save_orbax(str(ckpt), jax.device_get(raw))
    model = build(_toy_cfg(weights=str(ckpt), quantize="int8",
                           quantize_min_size=1024))
    rt = build_runtime(model)
    leaves = jax.tree_util.tree_leaves(rt.params_per_mesh[0])
    assert any(x.dtype == np.int8 for x in leaves)


def test_checkpoint_metadata_bridges_min_size_mismatch(tmp_path):
    """A checkpoint quantized at min_size=1024 serves under the default
    quantize_min_size: the restore target comes from checkpoint metadata,
    not from the serving config's quantization settings."""
    from tpuserve import savedmodel

    raw = build(_toy_cfg()).load_params()
    ckpt = tmp_path / "toy_q8"
    savedmodel.save_orbax(str(ckpt),
                          qz.quantize_tree(jax.device_get(raw), 1024))

    model = build(_toy_cfg(weights=str(ckpt), quantize="int8"))  # default 4096
    rt = build_runtime(model)
    leaves = jax.tree_util.tree_leaves(rt.params_per_mesh[0])
    assert any(x.dtype == np.int8 for x in leaves)


def test_mismatched_checkpoint_gives_guidance(tmp_path):
    """A checkpoint from a different model shape fails with guidance, not an
    opaque downstream compile error."""
    from tpuserve import savedmodel

    raw = build(_toy_cfg(options={"hidden": 16})).load_params()
    ckpt = tmp_path / "toy16"
    savedmodel.save_orbax(str(ckpt), jax.device_get(raw))
    with pytest.raises(ValueError, match="does not match"):
        build(_toy_cfg(weights=str(ckpt))).load_params()  # hidden=32 default
