"""The share of the loop's `account` phase that writes the request trees'
`gen_step` events (one span a riding lane a step), in percent:
`gen_account_seconds_total{part=trees}` over the three parts (`finish`: handing
the read extracts to their tasks; `trees`; `sums`: histograms, counters,
`_count_step`, the family's `observe_step`), from the two scrapes. Wall time.
The note gives each part in ms an iteration. None where the program has no
such counter."""

from benchmark import host_time


def read(run: dict):
    return host_time.account_trees_pct(run)
