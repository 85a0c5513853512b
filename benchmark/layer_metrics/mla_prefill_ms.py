"""Device time of latent attention in one prefill launch, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`mla_prefill` scope (every layer's mixer from its two down-projections to
`W_o`'s product: the projections, the rows' write, each tile's walk of its
prompt's pages in the expanded form), as the union of their intervals a
launch, median over the launches that lie whole inside the traced window
(benchmark/ssm_window.py)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "mla_prefill")
    return m["launch_s"] * 1e3 if m else None
