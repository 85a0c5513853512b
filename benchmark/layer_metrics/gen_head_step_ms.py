"""Device time of the head in one decode step, in ms: the self time of every chain
of scopes of `jit_step` that ends in `head` (the vocabulary product and a family's
logit scaling; the last norm is `head>norm`, which `gen_glue_step_ms` counts; the
sampler is `sample`, `gen_sample_ms`) (benchmark/launch_scopes.py: one parse of
the trace a run for both programs, the union of the operations' intervals a
launch, median over the launches that lie whole inside the traced window). None
where the trace holds no such program or the program no such scope (a tree older
than ISSUE 66, or a program the compile cache served from such a tree's entry)."""

from benchmark import gen_window, launch_scopes


def read(run: dict):
    return launch_scopes.ends_in_ms(run, gen_window.STEP_MODULE, ("head",))
