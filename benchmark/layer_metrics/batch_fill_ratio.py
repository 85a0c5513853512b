"""Valid items over bucket slots dispatched in the window, in percent, from
the program's counters: `items_total` over the sum, by variant, of
`runtime_variant_batches_total` times that variant's batch size (the first
number of its label, "<batch>x<seq>/...")."""

import re

from benchmark import prom


def read(run: dict):
    d, model = run.get("metrics_delta") or {}, run.get("model_name")
    items = sum(prom.select(d, "items_total", model=model).values())
    slots = 0.0
    for key, n in prom.select(d, "runtime_variant_batches_total", model=model).items():
        m = re.search(r'variant="(\d+)x', key)
        if m:
            slots += n * int(m.group(1))
    return 100.0 * items / slots if slots > 0 else None
