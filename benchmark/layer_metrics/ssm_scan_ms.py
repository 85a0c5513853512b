"""Device time of the chunked scans in one prefill launch, in ms: the
operations of the prefill program (`jit_prefill_fn`) that carry the program's
`ssm_scan` scope (every Mamba-2 layer from the convolution to the gated norm:
the quadratic form inside a tile, the state passed between a piece's tiles),
as the union of their intervals a launch, median over the launches that lie
whole inside the traced window (benchmark/ssm_window.py)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "ssm_scan")
    return m["launch_s"] * 1e3 if m else None
