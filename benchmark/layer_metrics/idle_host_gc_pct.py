"""Idle device time, in percent of the traced window, that lies under a
`tpuserve.gc` mark: a collection of 1 ms or more, written by the program from
the thread that collected. A VIEW of `device_idle_share` and not one more part
of it: the `idle_gen_*_pct` and `idle_*_pct` readers charge the same gaps to
what the loop or the batch was in, as before. The note lists every pause of 20
ms or more (start, length, generation, objects collected, the thread's line
and what else that line writes, the loop's phase and the span it fell in) and
every device gap of 20 ms or more with the part of it a mark covers.
`benchmark/host_time.py` has the rule. None where the program has no
`host_gc_seconds_total` or the run no device trace."""

from benchmark import host_time


def read(run: dict):
    return host_time.idle_host_gc_pct(run)
