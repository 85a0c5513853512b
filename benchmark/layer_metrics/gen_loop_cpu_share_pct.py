"""CPU time of the generation engine's loop thread over its wall time, in
percent, in the phases `sweep` and `account`, which hold no await in the
benchmark's traffic: `gen_loop_cpu_seconds_total` over
`gen_loop_seconds_total`, from the two scrapes. What is missing from 100 is
time the loop's thread wanted to run and did not: the GIL held by another
thread, a collection in another thread, the scheduler. The note gives CPU and
wall ms an iteration for all eight phases (in a phase with an await the
thread's CPU is also whatever else the event loop ran meanwhile). None where
the program has no such counter."""

from benchmark import host_time


def read(run: dict):
    return host_time.loop_cpu_share_pct(run)
