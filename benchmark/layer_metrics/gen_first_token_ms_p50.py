"""Median time from a request's arrival in the engine's queue to the iteration
whose out-block, on the host, holds its first generated token:
`gen_first_unit_ms` at 0.5, from the difference of the two scrapes around the
window. The histogram's buckets are 1, 2, ... 9 times a power of ten, so a
reading is linear inside a bucket a ninth to a half of its value wide."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "gen_first_unit_ms", 0.5,
                                   model=run.get("model_name"))
