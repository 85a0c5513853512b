"""Device-level ops that go beyond plain jnp calls.

- ``fused_attention`` (the module): one Pallas TPU kernel that keeps the
  score matrix out of HBM. It takes a whole sequence of up to 512 a step and
  is what BERT's (x, 512) buckets run on one TPU chip, chosen from the shape
  by ``attention_path`` (v5e, 2026-09-28: 4.0 ms a layer against the XLA
  pair's 12.5 at (256, 512, 16, 64)).
- ``moe`` — Switch-style mixture-of-experts FFN: static top-1 routing with
  fixed capacity (all einsums, no dynamic shapes), expert dim sharded on
  "model" for expert parallelism (XLA inserts the token all-to-alls).
"""

from tpuserve.ops.moe import SwitchFFN, switch_route  # noqa: F401
