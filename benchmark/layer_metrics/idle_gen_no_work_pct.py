"""Idle device time, in percent of the traced window, that lies in the loop's
phase `wait`: no request is queued and no slot is active.
`benchmark/gen_loop.py` has the rule; with the other six `idle_gen_*_pct` it
sums to `device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "no_work")
