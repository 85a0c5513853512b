"""Device time of the hyper-connection in one prefill launch, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`hc_mix` scope (every sublayer's three maps from the flattened norm to the
Sinkhorn's last pass, the mix the sublayer reads and the mix it leaves: two
scopes a sublayer, sixteen sublayers at eight layers; outside `mla_prefill`),
as the union of their intervals a launch, median over the launches that lie
whole inside the traced window (benchmark/ssm_window.py). None where the
program has no such scope (another family, the parent of the PR that added
it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "hc_mix")
    return m["launch_s"] * 1e3 if m else None
