"""Training step over a (data, model, seq) mesh.

The reference is an inference server, but tpuserve ships a first-class
training path for fine-tuning served models, and it is the surface the
multi-chip dry run validates: one jitted train step whose shardings exercise
DP (batch on "data"), TP (attention/MLP kernels on "model"), and SP
(activation sequence dim on "seq") simultaneously, with XLA inserting the
collectives (psum for grads across data, all-gather/reduce-scatter around TP
matmuls) over ICI.

The model is a compact pre-LN transformer encoder LM trained with masked-token
cross-entropy via optax.adamw. Everything is shape-static and scans-free at
this size; jax.checkpoint on the block stack trades FLOPs for HBM when
layers/seq grow. Attention is dense: GSPMD partitions an activation that is
sharded on "seq" itself (tests/test_parallel.py holds it to one device's
answer).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuserve.parallel import make_mesh, match_partition_rules
from tpuserve.parallel.mesh import MeshPlan
from tpuserve.parallel.partition import specs_to_shardings


@dataclass
class TrainConfig:
    vocab: int = 512
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 128
    max_seq: int = 32
    lr: float = 1e-3
    remat: bool = False
    # Mixture-of-experts FFN: 0 = dense MLP; N > 0 = Switch top-1 routing
    # over N experts (tpuserve.ops.moe), expert dim sharded on "model" (EP).
    moe_experts: int = 0
    moe_capacity: float = 1.25
    moe_aux_weight: float = 0.01


class Block(nn.Module):
    cfg: TrainConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None):
        c = self.cfg
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        h = nn.MultiHeadDotProductAttention(num_heads=c.n_heads, dtype=self.dtype,
                                            deterministic=True, name="attn")(h)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if c.moe_experts:
            from tpuserve.ops.moe import SwitchFFN

            # mask: pad tokens must not claim expert capacity or drive the
            # balance loss.
            h, aux = SwitchFFN(c.moe_experts, c.d_ff,
                               capacity_factor=c.moe_capacity,
                               dtype=self.dtype, name="moe")(h, mask)
            self.sow("losses", "moe_aux", aux)
        else:
            h = nn.Dense(c.d_ff, dtype=self.dtype, name="up")(h)
            h = nn.gelu(h)
            h = nn.Dense(c.d_model, dtype=self.dtype, name="down")(h)
        return x + h


class TransformerLM(nn.Module):
    cfg: TrainConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, mask=None):
        c = self.cfg
        x = nn.Embed(c.vocab, c.d_model, dtype=self.dtype, name="embed")(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02), (c.max_seq, c.d_model))
        x = x + pos[None, : tokens.shape[1], :].astype(self.dtype)
        block = Block
        if c.remat:
            block = nn.remat(Block)
        for i in range(c.n_layers):
            x = block(c, dtype=self.dtype, name=f"block{i}")(x, mask)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        return nn.Dense(c.vocab, dtype=jnp.float32, name="lm_head")(x)


# Tensor-parallel rules: attention QKV/out and MLP kernels split on "model";
# embeddings split on the vocab dim; MoE expert dims split on "model" (EP:
# each device holds E/tp experts, XLA inserts the token all-to-alls);
# everything else replicated.
TRAIN_PARTITION_RULES: list[tuple[str, P]] = [
    (r"embed/embedding", P("model", None)),
    (r"attn/(query|key|value)/kernel", P(None, "model", None)),
    (r"attn/out/kernel", P("model", None, None)),
    (r"moe/w_(up|down)", P("model", None, None)),
    (r"up/kernel", P(None, "model")),
    (r"down/kernel", P("model", None)),
    (r"lm_head/kernel", P(None, "model")),
    (r".*", P()),
]


def make_train_state(mesh: Mesh, cfg: TrainConfig, rng: jax.Array | None = None):
    """Init params + opt state, sharded by the TP rules over `mesh`."""
    model = TransformerLM(cfg)
    rng = rng if rng is not None else jax.random.key(0)
    tokens = jnp.zeros((mesh.shape["data"], cfg.max_seq), jnp.int32)
    params = model.init(rng, tokens)["params"]

    specs = match_partition_rules(TRAIN_PARTITION_RULES, params)
    shardings = specs_to_shardings(specs, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, shardings)

    tx = optax.adamw(cfg.lr)
    opt_state = tx.init(params)  # mirrors param shardings via GSPMD on first use
    return model, params, tx, opt_state, shardings


def loss_fn(model, params, tokens, targets, mask):
    logits, mods = model.apply({"params": params}, tokens, mask,
                               mutable=["losses"])
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    loss = (losses * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    # MoE load-balancing aux (zero-leaved when no MoE blocks sowed).
    aux = sum(jnp.sum(v) for v in
              jax.tree_util.tree_leaves(mods.get("losses", {})))
    return loss + model.cfg.moe_aux_weight * aux


def make_train_step(model, tx, mesh: Mesh, param_shardings):
    """Build the jitted train step with dp/tp/sp in/out shardings."""
    batch_sharding = {
        "tokens": NamedSharding(mesh, P("data", "seq")),
        "targets": NamedSharding(mesh, P("data", "seq")),
        "mask": NamedSharding(mesh, P("data", "seq")),
    }

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(partial(loss_fn, model))(
            params, batch["tokens"], batch["targets"], batch["mask"]
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(  # tps-ok[TPS501,TPS505]: setup-time factory, jitted once per run
        step,
        in_shardings=(param_shardings, None, batch_sharding),
        out_shardings=(param_shardings, None, None),
        donate_argnums=(0, 1),
    ), batch_sharding


def save_train_state(path: str, params: Any, opt_state: Any, step: int) -> None:
    """Checkpoint the full train state (params + optimizer + step) with orbax.

    Arrays are saved from wherever they live — on a sharded mesh each host
    writes its own shards (orbax is multi-host-aware), so no host ever
    gathers the full state.
    """
    import os

    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        # force=True: a periodic-checkpoint loop overwrites its stable path.
        ckptr.save(os.path.abspath(path),
                   {"params": params, "opt_state": opt_state, "step": step},
                   force=True)
        ckptr.wait_until_finished()


def restore_train_state(path: str, mesh: Mesh, cfg: TrainConfig):
    """Resume: restore directly into the mesh's shardings (no host staging).

    The abstract restore target comes from ``jax.eval_shape`` — nothing is
    materialized on device before the restore, so peak memory is one train
    state, not two. Each abstract leaf carries its NamedSharding (params from
    the partition rules; optimizer moments inherit the matching param's
    sharding by tree-suffix, scalars replicate), so every device reads
    exactly its own shard from disk. Returns
    ``(model, params, tx, opt_state, shardings, step)`` ready for
    ``make_train_step``.
    """
    import os

    import orbax.checkpoint as ocp

    model = TransformerLM(cfg)
    tokens = jnp.zeros((mesh.shape["data"], cfg.max_seq), jnp.int32)
    params_shape = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    specs = match_partition_rules(TRAIN_PARTITION_RULES, params_shape)
    shardings = specs_to_shardings(specs, mesh)
    tx = optax.adamw(cfg.lr)
    opt_shape = jax.eval_shape(tx.init, params_shape)

    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    by_suffix = {tuple(str(k) for k in p): s for p, s in flat}
    replicated = NamedSharding(mesh, P())

    def opt_sharding(path, leaf):
        """Adam's mu/nu mirror the param tree: match by path suffix."""
        keys = tuple(str(k) for k in path)
        for i in range(len(keys)):
            s = by_suffix.get(keys[i:])
            if s is not None:
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=s)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=replicated)

    target = {
        "params": jax.tree_util.tree_map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            params_shape, shardings),
        "opt_state": jax.tree_util.tree_map_with_path(opt_sharding, opt_shape),
        "step": 0,
    }
    with ocp.StandardCheckpointer() as ckptr:
        restored = ckptr.restore(os.path.abspath(path), target)
    return (model, restored["params"], tx, restored["opt_state"], shardings,
            int(restored["step"]))


def synthetic_batch(cfg: TrainConfig, batch_size: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (batch_size, cfg.max_seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    mask = np.ones((batch_size, cfg.max_seq), np.float32)
    return {"tokens": tokens, "targets": targets, "mask": mask}


def mesh_plan_for(n_devices: int) -> MeshPlan:
    """Factor n into dp*tp*sp, exercising every axis that fits."""
    tp = 2 if n_devices % 2 == 0 else 1
    sp = 2 if n_devices % 4 == 0 else 1
    return MeshPlan(tp=tp, sp=sp)


def dryrun(devices: list, steps: int = 1) -> float:
    """One (or more) real sharded train step(s) on the given devices.

    When the mesh has a real "seq" axis (sp > 1), the batch is sharded on
    it and GSPMD partitions the dense attention, alongside DP and TP. When
    the "model" axis is real (tp > 1), the FFN runs as a Switch MoE with
    the expert dim sharded over it — expert parallelism in the same step.
    """
    n = len(devices)
    plan = mesh_plan_for(n)
    mesh = make_mesh(plan, devices=devices)
    cfg = TrainConfig(moe_experts=2 * plan.tp if plan.tp > 1 else 0)
    model, params, tx, opt_state, shardings = make_train_state(mesh, cfg)
    step, _ = make_train_step(model, tx, mesh, shardings)
    batch_size = max(4, 2 * mesh.shape["data"])
    loss = None
    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, synthetic_batch(cfg, batch_size, seed=i))
    return float(loss)
