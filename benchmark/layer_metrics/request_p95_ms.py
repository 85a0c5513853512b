"""The 95th percentile of request latency, by the load generator's clock.
Per layer and with no bound: on a shared host a stall of a second or two in
one run of six moves the few dozen requests beyond it and leaves the median
alone, so its runs spread by more than half of the widest bound there is
(PERF.md, section 2). It is read beside `latency_p50_ms`, which it should
follow."""

from benchmark.loadgen import percentile


def read(run: dict):
    lat = run["load"].latencies_ms if run.get("load") else None
    return percentile(lat, 0.95) if lat else None
