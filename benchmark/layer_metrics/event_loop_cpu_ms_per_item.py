"""CPU time (user + system) of the server's threads of role `event_loop` over the
items answered in the window: `host_thread_cpu_seconds_total{role=event_loop}`
as the difference of the two scrapes. The program walks `/proc/self/task` when
it is scraped and names each thread by what it started it as; `event_loop` is
the main thread and the ingest loops: the event loops, and with them the
generation engine's loop, the handlers' JSON and the batcher. This reader's
note prints all six roles (`compile` and `other` too) and their sum beside
`server_cpu_ms_per_item`, the same quantity taken from outside. A count of host
work; it says nothing of the device. None where the program has no such
counter."""

from benchmark import host_time


def read(run: dict):
    return host_time.role_cpu_ms_per_item(run, "event_loop", with_note=True)
