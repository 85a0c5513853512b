"""Summary rows over all rows the window's decode steps attended, in percent:
`eva_rows_attended_total{phase=decode,kind=summary}` over both kinds, from the
two scrapes (what the live tokens' index sets hold, not what a block read). 0
means the traffic never left a first window. None where no step ran or the
program has no such counter (another family, the parent of the PR that added
it)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "eva_rows_attended_total", phase="decode")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "eva_rows_attended_total", phase="decode",
                                    kind="summary") / n
