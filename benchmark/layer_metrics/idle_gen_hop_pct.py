"""Idle device time, in percent of the traced window, that lies in the loop's
phases `prefill` and `step` outside every worker's span: the hand-over from
the event loop to the executor thread and back (the executor's queue, the GIL,
an event loop that runs late), with the loop's gathering of pieces before a
launch. `benchmark/gen_loop.py` has the rule, and its notes say on which side
of which span the time lay; with the other six `idle_gen_*_pct` it sums to
`device_idle_share`."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "hop")
