"""Median time a batch's h2d stage took apart from the compiled call: each
`tpuserve.h2d` span of the traced window (the stage thread: `device_put` of
the ids and the mask, the wait for the transfer, the launch) less the
`tpuserve.launch` span nested in it, on the profiler's clock
(benchmark/host_spans.py). `latency_ms{phase=h2d}` is the event loop's clock
around the same stage and holds the hop to the thread and back."""

import statistics

from benchmark import host_spans


def read(run: dict):
    hs = host_spans.for_run(run)
    if not hs or not hs["h2d_ms"]:
        return None
    return statistics.median(hs["h2d_ms"])
