"""Idle device time, in percent of the traced window, that lies in the loop's
phase `retire`: a finished request's extract, fetch and finalize, one request
at a time, with nothing queued on the chip (the run's notes split it:
`gen_extract`, `gen_finalize`, the hand-overs between them).
`benchmark/gen_loop.py` has the rule; with the other six `idle_gen_*_pct` it
sums to `device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "retire")
