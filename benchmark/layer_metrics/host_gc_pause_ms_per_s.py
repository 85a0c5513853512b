"""Time the server's process spent inside Python's cyclic collector, in ms a
second of the window: `host_gc_seconds_total`, every generation, as the
difference of the two scrapes over the window's seconds. The program counts it
in a `gc.callbacks` entry, so it is every collection of every thread; while a
collection runs no other Python of the process does. The note gives
collections, seconds and the mean pause by generation. None where the program
has no such counter (the parent of the PR that added it)."""

from benchmark import host_time


def read(run: dict):
    return host_time.gc_pause_ms_per_s(run)
