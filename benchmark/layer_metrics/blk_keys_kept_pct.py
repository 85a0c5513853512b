"""Keys the picked queries attended over the keys they could see, in percent:
`blk_keys_attended_total` over `blk_keys_visible_total`, both phases, from the
two scrapes (live queries at or past `dense_len` only: a query under it attends
every key and picks nothing). 100% less this is the share of a query's keys the
attention need not read. None where no query picked or the program has no such
counter."""

from benchmark import gen_window


def read(run: dict):
    visible = gen_window.total(run, "blk_keys_visible_total")
    if visible <= 0:
        return None
    return 100.0 * gen_window.total(run, "blk_keys_attended_total") / visible
