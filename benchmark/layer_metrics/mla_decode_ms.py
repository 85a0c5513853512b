"""Device time of latent attention in one decode step, in ms: the operations
of the step program (`jit_step`) that carry the program's `mla_decode` scope
(every layer's mixer from its two down-projections to `W_o`'s product: the
projections, the row's write, the walk of each lane's pages in the absorbed
form), as the union of their intervals a launch, median over the launches that
lie whole inside the traced window (benchmark/ssm_window.py, a reader of any
`jax.named_scope`)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "mla_decode")
    return m["launch_s"] * 1e3 if m else None
