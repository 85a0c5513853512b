"""Device time of the sampler in one decode step, in ms: the operations of the
step program (`jit_step`) that carry the program's `sample` scope (from the
logits to the tokens, the top 8 ids and their log-probabilities: the group
maxima, the two small `top_k`s, the sum of exponentials, and the Gumbel draw on
a step that makes one; the head's product stays outside), as the union of their
intervals a launch, median over the launches that lie whole inside the traced
window (benchmark/ssm_window.py, a reader of any `jax.named_scope`). None where
the program has no such scope (a tree older than PR 60, whose draw carries the
function's name, `PagedLM._sample.<locals>.one`, and no `/sample/`)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "/sample/")
    return m["launch_s"] * 1e3 if m else None
