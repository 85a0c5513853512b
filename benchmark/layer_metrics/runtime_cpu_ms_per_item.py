"""CPU time (user + system) of the server's threads of role `runtime` over the
items answered in the window: `host_thread_cpu_seconds_total{role=runtime}` as
the difference of the two scrapes. The program walks `/proc/self/task` when it
is scraped and names each thread by what it started it as; `runtime` is every
thread that Python did not start: XLA's, the TPU driver's, the profiler's. A
count of host work; it says nothing of the device. None where the program has
no such counter."""

from benchmark import host_time


def read(run: dict):
    return host_time.role_cpu_ms_per_item(run, "runtime")
