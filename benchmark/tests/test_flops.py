"""benchmark/flops/bert.py against XLA's own count for the family's forward.
The file counts matrix multiplications only, so it must come out a little
UNDER XLA's `cost_analysis()["flops"]` (which adds softmax, LayerNorm, GELU
and the like) and never over: margin 0 above, 15% below at this small shape
(about 2% at the cells' (256, 512))."""

import jax
import jax.numpy as jnp

from benchmark import spec
from tpuserve.config import ModelConfig
from tpuserve.models import bert as program_bert

flops = spec.load_module("flops", "bert")
SZ = {"layers": 2, "d_model": 128, "heads": 4, "d_ff": 512, "vocab_size": 1000,
      "positions": 128, "num_classes": 5}


def test_ops_agree_with_xla_cost_analysis():
    cfg = ModelConfig(name="m", family="bert", dtype="float32", batch_buckets=[8],
                      seq_buckets=[128], num_classes=5, parallelism="single",
                      options={"layers": 2, "d_model": 128, "heads": 4, "d_ff": 512,
                               "vocab_size": 1000})
    model = program_bert.create(cfg)
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    batch = model.input_signature((8, 128))
    compiled = jax.jit(model.forward).lower(params, batch).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    xla = float(cost["flops"])
    mine, nbytes = flops.ops_and_bytes(SZ, 8, 128)
    assert 0.85 * xla <= mine <= xla, (mine, xla)
    assert nbytes > 0


def test_published_sizes_give_the_known_counts():
    base = {"layers": 12, "d_model": 768, "heads": 12, "d_ff": 3072, "vocab_size": 30522,
            "positions": 512, "num_classes": 5}
    ops, nbytes = flops.ops_and_bytes(base, 256, 512)
    # 12 x (8 BS d^2 + 4 B S^2 d + 4 BS d d_ff) by hand: 24.74e12
    assert abs(ops - 24.74e12) < 0.02e12
    # every weight once (85.6 M outside the word table, bf16) is the floor
    assert nbytes > 2 * 85e6
    assert ops / 197e12 > nbytes / 819e9, "the (256, 512) bucket is compute bound"
