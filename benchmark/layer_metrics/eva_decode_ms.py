"""Device time of a decode step's EVA attention, in ms: the operations of the
step program (`jit_step`) that carry the program's `eva_decode` scope
(`tpuserve/models/eva.py` `_walk`: the walk of every lane's virtual block
table, its summary pages and its ring in place; the ring's and the summary's
writes are outside it), the layers together, as the union of their intervals a
launch, median over the launches that lie whole inside the traced window
(benchmark/ssm_window.py). None where the program has no such scope (another
family, the parent of the PR that added it)."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "eva_decode")
    return m["launch_s"] * 1e3 if m else None
