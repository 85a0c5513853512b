"""Idle device time, in percent of the traced window, during which the server
held nothing it could run: the documents of the batch that ended the gap had
not reached the batcher and no tokenizer was running, so callers were waiting
for answers or had not sent (benchmark/host_spans.py has the rule)."""

from benchmark import host_spans


def read(run: dict):
    return host_spans.idle_pct(run, ("no_request",))
