"""Device time of attention by head in one decode step, in ms: the operations
of the step program (`jit_step`) that carry the program's `attn_decode` scope
(every attention layer's mixer from its three projections to `W_o`'s product:
the rows' write into the pages and the walk of each lane's live pages), as the
union of their intervals a launch, median over the launches that lie whole
inside the traced window (benchmark/ssm_window.py, a reader of any
`jax.named_scope`)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "attn_decode")
    return m["launch_s"] * 1e3 if m else None
