"""Idle device time, in percent of the traced window, that lies inside
`tpuserve.gen_fetch`: the step has ended on the chip and the host does not
hold its out-block yet (the copy back, and the worker thread's wake-up).
`benchmark/gen_loop.py` has the rule; with the other six `idle_gen_*_pct` it
sums to `device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "fetch")
