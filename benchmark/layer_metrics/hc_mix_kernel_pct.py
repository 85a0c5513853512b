"""Sublayers of prefill launches whose hyper-connection ran in the kernels
(`tpuserve/ops/hyper.py` `enter` and `leave`: two calls a sublayer, the stream
read twice and written once, no float32 copy of it in device memory), over all
sublayers prefill launches mapped, weighted by live tokens, in percent:
`hc_maps_total{phase=prefill,path=kernel}` over every `path`, from the two
scrapes. The program chooses the path when it is traced (on the TPU, a
bfloat16 launch of whole row tiles), so this says what a launch cost, never
what it answered. 0 where the counter has no such label (the parent of the PR
that added it: every sublayer in XLA); None where no sublayer was mapped or
the program has no such counter (another family)."""

from benchmark import gen_window


def read(run: dict):
    maps = gen_window.total(run, "hc_maps_total", phase="prefill")
    if maps <= 0:
        return None
    return 100.0 * gen_window.total(run, "hc_maps_total", phase="prefill", path="kernel") / maps
