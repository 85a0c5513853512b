"""Device time of the window layers' ring attention in one decode step, in ms:
the operations of the step program (`jit_step`) that carry the program's
`attn_ring` scope (`tpuserve/models/decoder_sink.py`: a window layer's row
written into its slot's ring, every live lane's whole ring gathered, the
scores with the sink beside them, the softmax and the context, plain XLA; all
window layers), as the union of their intervals a launch, median over the
launches that lie whole inside the traced window (benchmark/ssm_window.py).
None where the program has no such scope (another family, the parent of the
PR that added it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_ring")
    return m["launch_s"] * 1e3 if m else None
