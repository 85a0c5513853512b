"""Share of the window's items that joined their batch at its close, in
percent: `batcher_batch_items_total{joined=close}` over both values of
`joined` (`accumulate`: the item was in the batch when it was flushed;
`close`: it arrived while the flushed batch waited for the device and was
taken in when the batch closed; the two sum to `items_total`), as differences
of the two scrapes. It says how much of a launch's fill the late close buys:
97-99% in the closed-loop cells (chip, PR 26). None where the program has no
such family (a program before PR 26, or a run that the batcher does not
serve)."""

from benchmark import prom


def read(run: dict):
    joined = prom.select(run.get("metrics_delta") or {}, "batcher_batch_items_total",
                         model=run.get("model_name"))
    total = sum(joined.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in joined.items() if 'joined="close"' in k) / total
