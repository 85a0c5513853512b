"""Mesh construction + partition rules on the 8-fake-device CPU mesh (C7)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpuserve.parallel import make_mesh, match_partition_rules, shard_pytree
from tpuserve.parallel.mesh import MeshPlan, pad_batch_to_mesh


def test_fake_devices_present():
    assert len(jax.devices()) == 8, "conftest must provide 8 fake CPU devices"


def test_make_mesh_default_dp():
    mesh = make_mesh()
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1
    assert mesh.shape["seq"] == 1


def test_make_mesh_tp():
    mesh = make_mesh(MeshPlan(tp=2))
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2


def test_mesh_plan_invalid():
    with pytest.raises(ValueError):
        MeshPlan(tp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshPlan(dp=3, tp=2).resolve(8)


def test_match_partition_rules():
    params = {
        "layer1": {"kernel": np.zeros((4, 8)), "bias": np.zeros((8,))},
        "head": {"kernel": np.zeros((8, 16))},
        "scalar": np.float32(1.0),
    }
    rules = [
        (r"head/kernel", P(None, "model")),
        (r".*bias", P()),
        (r".*kernel", P("model", None)),
        (r".*", P()),
    ]
    specs = match_partition_rules(rules, params)
    assert specs["head"]["kernel"] == P(None, "model")
    assert specs["layer1"]["kernel"] == P("model", None)
    assert specs["layer1"]["bias"] == P()
    assert specs["scalar"] == P()  # scalars never partitioned


def test_match_partition_rules_unmatched_raises():
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules([(r"xyz", P())], {"a": np.zeros((2, 2))})


def test_shard_pytree_places_on_mesh():
    mesh = make_mesh(MeshPlan(tp=2))
    params = {"w": np.ones((16, 4), np.float32), "b": np.zeros((4,), np.float32)}
    rules = [(r"w", P("model", None)), (r".*", P())]
    sharded = shard_pytree(params, rules, mesh)
    assert sharded["w"].sharding.spec == P("model", None)
    # value integrity after sharding
    np.testing.assert_array_equal(np.asarray(sharded["w"]), params["w"])


def test_sharded_matmul_matches_single_device():
    """DP+TP sharded execution must be numerically identical to unsharded."""
    mesh = make_mesh(MeshPlan(tp=2))
    x = np.random.default_rng(0).normal(size=(16, 32)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(32, 64)).astype(np.float32)

    from jax.sharding import NamedSharding

    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
    f = jax.jit(lambda a, b: a @ b, out_shardings=NamedSharding(mesh, P("data", "model")))
    out = np.asarray(f(xs, ws))
    np.testing.assert_allclose(out, x @ w, rtol=1e-5)


def test_gspmd_partitions_a_sequence_sharded_block_itself():
    """What the train step rests on since its ring / Ulysses arm went: the
    training block attends densely, and under a (data, model, seq) = (2, 2,
    2) mesh with the activation sharded on "seq" (and its kernels by the
    train step's own rules) it answers as on one device."""
    from jax.sharding import NamedSharding

    from tpuserve.train import TRAIN_PARTITION_RULES, Block, TrainConfig

    mesh = make_mesh(MeshPlan(tp=2, sp=2))
    assert dict(mesh.shape) == {"data": 2, "model": 2, "seq": 2}
    block = Block(TrainConfig(d_model=16, n_heads=2, d_ff=32, max_seq=8))
    x = np.random.default_rng(0).normal(size=(4, 8, 16)).astype(np.float32)
    params = block.init(jax.random.key(0), x)
    want = np.asarray(jax.jit(block.apply)(params, x))

    act = NamedSharding(mesh, P("data", "seq", None))
    f = jax.jit(block.apply, out_shardings=act)
    got = f(shard_pytree(params, TRAIN_PARTITION_RULES, mesh),
            jax.device_put(x, act))
    assert got.sharding.spec == P("data", "seq", None)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_pad_batch_to_mesh():
    mesh = make_mesh()
    assert pad_batch_to_mesh(1, mesh) == 8
    assert pad_batch_to_mesh(8, mesh) == 8
    assert pad_batch_to_mesh(9, mesh) == 16
