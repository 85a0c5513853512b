"""Device time of the global layers' decode walk in one step, in ms: the
operations of the step program (`jit_step`) that carry the program's
`attn_full_walk` scope (`tpuserve/models/decoder_sink.py`: a global layer's
rows written into its three page pools and the walk of every live lane's own
key blocks, `ops/lane_attention.py` `head_walk` on the chip; both global
layers), as the union of their intervals a launch, median over the launches
that lie whole inside the traced window (benchmark/ssm_window.py). None where
the program has no such scope (another family, the parent of the PR that
added it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_full_walk")
    return m["launch_s"] * 1e3 if m else None
