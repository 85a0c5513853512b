"""The 95th percentile of the time between two decoding iterations' out-blocks
reaching the host (the gap between tokens; a prefill launch between two steps
is inside it): `gen_token_gap_ms` at 0.95, from the difference of the two
scrapes around the window. The histogram's buckets are 1, 2, ... 9 times a power of ten, so a
reading is linear inside a bucket a ninth to a half of its value wide."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "gen_token_gap_ms", 0.95,
                                   model=run.get("model_name"))
