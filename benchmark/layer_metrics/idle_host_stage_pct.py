"""Idle device time, in percent of the traced window, during which the batch
whose launch ended the gap was in a host stage: its documents in the
tokenizer (`tokenize`), or the batch in `assemble` or `h2d`
(benchmark/host_spans.py has the rule)."""

from benchmark import host_spans


def read(run: dict):
    return host_spans.idle_pct(run, ("tokenize", "assemble", "h2d"))
