"""Idle device time, in percent of the traced window, that lies inside
`tpuserve.gen_step` or `tpuserve.gen_prefill`: the worker thread is in the
compiled call and the chip has not begun the program yet (dispatch).
`benchmark/gen_loop.py` has the rule; with the other six `idle_gen_*_pct` it
sums to `device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "launch")
