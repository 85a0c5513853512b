"""Device time of a prefill launch's EVA attention and pooling, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`eva_prefill` scope (each tile over its ring as the launch found it, the
launch's own rows and its prompt's summary pages, one running softmax) or its
`eva_summarise` scope (the pooling of the chunks that end inside the launch),
the layers together, as the union of their intervals a launch, median over the
launches that lie whole inside the traced window (benchmark/ssm_window.py).
None where the program has no such scope."""

from benchmark import gen_window, ssm_window

SCOPES = "eva_"   # both scopes of a launch: the step's `eva_decode` is another program's


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, SCOPES)
    return m["launch_s"] * 1e3 if m else None
