"""Idle device time, in percent of the traced window, that lies in the loop's
own Python: its phases `sweep`, `admit`, `account` and `emit`, and
`tpuserve.gen_pack` (a launch's arrays packed in the worker thread).
`benchmark/gen_loop.py` has the rule; with the other six `idle_gen_*_pct` it
sums to `device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "host")
