"""Idle device time of a generating cell that no span names, in percent of the
traced window: gaps under 1 ms, the window's edges beyond the device's events,
and whatever neither a worker's span nor a phase of the engine's loop covers.
It is what is left of `device_idle_share` after the other six `idle_gen_*_pct`
(`benchmark/gen_loop.py` has the rule), so the seven sum to it. All of the
idle time is here when the clock check finds no pairing that holds."""

from benchmark import gen_loop


def read(run: dict):
    return gen_loop.idle_pct(run, "unknown")
