"""Tokens in over token slots launched, in percent: `ingest_tokens_total`
(ids the tokenizer produced, [CLS] and [SEP] included) over the sum, by
variant, of `runtime_variant_batches_total` times batch times sequence of the
variant's label ("<batch>x<seq>/..."), as differences of the two scrapes.
`batch_fill_ratio` counts rows; this counts positions, so padding along the
sequence shows too.

Tokens are counted when a request is parsed and slots when its batch is
launched, so documents in flight at the window's edges are counted on one
side only: at most the 512 outstanding documents of about 21,000 a window, at
each edge, and the two edges nearly cancel."""

import re

from benchmark import prom


def read(run: dict):
    d, model = run.get("metrics_delta") or {}, run.get("model_name")
    tokens = prom.select(d, "ingest_tokens_total", model=model)
    slots = 0.0
    for key, n in prom.select(d, "runtime_variant_batches_total", model=model).items():
        m = re.search(r'variant="(\d+)x(\d+)', key)
        if m:
            slots += n * int(m.group(1)) * int(m.group(2))
    if not tokens or slots <= 0:
        return None
    return 100.0 * sum(tokens.values()) / slots
