"""Share of the window's flushes that the timer forced, in percent:
`batcher_flushes_total{reason=timer}` over all reasons (`target`: the group
reached the batch size it was accumulating towards; `timer`: `deadline_ms`
or a request's deadline headroom ran out first), as differences of the two
scrapes."""

from benchmark import prom


def read(run: dict):
    flushes = prom.select(run.get("metrics_delta") or {}, "batcher_flushes_total",
                          model=run.get("model_name"))
    total = sum(flushes.values())
    if total <= 0:
        return None
    timer = sum(v for k, v in flushes.items() if 'reason="timer"' in k)
    return 100.0 * timer / total
