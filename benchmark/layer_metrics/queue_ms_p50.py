"""Median wait of an item in the batcher's queue: `latency_ms{phase=queue}`
(`batcher.py`), as the difference of the two scrapes around the window."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "latency_ms", 0.5,
                                   model=run.get("model_name"), phase="queue")
