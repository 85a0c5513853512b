"""Median time between two decoding iterations' out-blocks reaching the host,
the gap between tokens a streaming caller of any decoding lane would feel:
`gen_token_gap_ms` at 0.5, from the difference of the two scrapes around the
window. The histogram's buckets are 1, 2, ... 9 times a power of ten, so a
reading is linear inside a bucket a ninth to a half of its value wide."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "gen_token_gap_ms", 0.5,
                                   model=run.get("model_name"))
