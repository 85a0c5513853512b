"""Median time a formed batch waited for admission into the pipeline:
`latency_ms{phase=slot_wait}`, one observation per batch, from the flush
decision in `_group_loop` to `_inflight` acquired, as the difference of the
two scrapes. `queue_ms_p50` is per item and holds this wait and the
accumulation before it together."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "latency_ms", 0.5,
                                   model=run.get("model_name"), phase="slot_wait")
