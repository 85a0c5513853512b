"""Sharded train step (dp/tp/sp) on the 8-fake-device mesh; graft entries."""

import jax
import numpy as np

from tpuserve.parallel import make_mesh
import pytest

from tpuserve.train import (
    TrainConfig,
    dryrun,
    make_train_state,
    make_train_step,
    mesh_plan_for,
    restore_train_state,
    save_train_state,
    synthetic_batch,
)

pytestmark = pytest.mark.slow


def test_mesh_plan_factors():
    assert mesh_plan_for(8).resolve(8) == (2, 2, 2)
    assert mesh_plan_for(2).resolve(2) == (1, 2, 1)
    assert mesh_plan_for(1).resolve(1) == (1, 1, 1)


def test_dryrun_8dev():
    loss = dryrun(jax.devices(), steps=1)
    assert np.isfinite(loss)


def test_loss_decreases():
    mesh = make_mesh(mesh_plan_for(len(jax.devices())))
    cfg = TrainConfig(n_layers=1, d_model=32, d_ff=64, vocab=64, max_seq=16)
    model, params, tx, opt_state, shardings = make_train_state(mesh, cfg)
    step, _ = make_train_step(model, tx, mesh, shardings)
    batch = synthetic_batch(cfg, 8, seed=0)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, dict(batch))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_tp_params_actually_sharded():
    mesh = make_mesh(mesh_plan_for(8))
    cfg = TrainConfig()
    _, params, _, _, _ = make_train_state(mesh, cfg)
    from jax.sharding import PartitionSpec as P

    assert params["block0"]["up"]["kernel"].sharding.spec == P(None, "model")


def test_checkpoint_resume_is_bitwise_continuation(tmp_path):
    """Save at step 2, restore into the sharded mesh, and the next step must
    equal the uninterrupted run: params, opt state, and loss (SURVEY.md §5
    checkpoint/resume, training side)."""
    mesh = make_mesh(mesh_plan_for(8))
    cfg = TrainConfig(n_layers=1, d_model=32, d_ff=64, vocab=64, max_seq=16)
    model, params, tx, opt_state, shardings = make_train_state(mesh, cfg)
    step, _ = make_train_step(model, tx, mesh, shardings)
    for i in range(2):
        params, opt_state, _ = step(params, opt_state, synthetic_batch(cfg, 8, seed=i))

    path = str(tmp_path / "ckpt")
    save_train_state(path, params, opt_state, step=1)  # periodic-loop shape:
    save_train_state(path, params, opt_state, step=2)  # overwrite must work
    loss_cont = step(params, opt_state, synthetic_batch(cfg, 8, seed=2))[2]

    model_r, params_r, tx_r, opt_r, shardings_r, at = restore_train_state(
        path, mesh, cfg)
    assert at == 2
    # Restored leaves land with their original shardings (no host gather) —
    # including the optimizer moments, which mirror the param tree.
    from jax.sharding import PartitionSpec as P

    assert params_r["block0"]["up"]["kernel"].sharding.spec == P(None, "model")
    assert opt_r[0].mu["block0"]["up"]["kernel"].sharding.spec == P(None, "model")
    step_r, _ = make_train_step(model_r, tx_r, mesh, shardings_r)
    loss_resumed = step_r(params_r, opt_r, synthetic_batch(cfg, 8, seed=2))[2]
    np.testing.assert_array_equal(np.asarray(loss_cont), np.asarray(loss_resumed))


def test_graft_entry_single_chip():
    import __graft_entry__ as g

    fn, (params, batch) = g.entry()
    out = jax.jit(fn)(params, batch)
    assert out["indices"].shape == (8, 5)


def test_graft_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)
