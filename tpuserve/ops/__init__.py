"""Device-level ops that go beyond plain jnp calls (SURVEY.md §5 long-context).

- ``ring_attention`` — sequence-parallel blockwise attention: the sequence is
  sharded over a mesh axis and K/V blocks rotate around the ICI ring via
  ``jax.lax.ppermute`` while each device accumulates its queries' output with
  an online (streaming) softmax. Memory per device is O(seq/devices), enabling
  contexts far beyond one chip's HBM.
- ``ulysses_attention`` — the alternative sequence-parallel scheme: one
  all-to-all deals heads across the seq axis so each device dense-attends its
  head slice over the full sequence, then an inverse all-to-all restores seq
  sharding. Lower step latency than the ring for short/medium sequences; the
  ring wins on memory for very long ones.
- ``flash_attention`` (the module): two Pallas TPU kernels that keep the
  score matrix out of HBM. ``fused_attention`` takes a whole sequence of up
  to 512 a step and is what BERT's (x, 512) buckets run on one TPU chip,
  chosen from the shape by ``attention_path`` (v5e, 2026-09-28: 4.0 ms a
  layer against the XLA pair's 12.5 at (256, 512, 16, 64)); the tiled
  ``flash_attention`` streams K/V in blocks with an online softmax, serves
  any length and ring/Ulysses's per-device step, and is 3x slower than
  XLA at serving shapes: ``options.attention = "flash"`` opts in.
- ``moe`` — Switch-style mixture-of-experts FFN: static top-1 routing with
  fixed capacity (all einsums, no dynamic shapes), expert dim sharded on
  "model" for expert parallelism (XLA inserts the token all-to-alls).
"""

from tpuserve.ops.flash_attention import flash_attention  # noqa: F401
from tpuserve.ops.moe import SwitchFFN, switch_route  # noqa: F401
from tpuserve.ops.ring_attention import dense_attention, ring_attention  # noqa: F401
from tpuserve.ops.ulysses import ulysses_attention  # noqa: F401
