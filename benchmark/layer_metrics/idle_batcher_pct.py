"""Idle device time, in percent of the traced window, during which the batch
whose launch ended the gap was held by the batcher: `accumulate` +
`slot_wait` + `staging_wait` (benchmark/host_spans.py has the rule). With
`idle_host_stage_pct`, `idle_no_request_pct` and `idle_unknown_pct` it sums to
`device_idle_share`."""

from benchmark import host_spans


def read(run: dict):
    return host_spans.idle_pct(run, ("accumulate", "slot_wait", "staging_wait"))
