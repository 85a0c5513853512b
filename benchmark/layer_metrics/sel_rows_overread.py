"""Cache rows the walks read and scored for the picked queries, over the rows
those queries kept: `sel_rows_walked_total` over `sel_pairs_kept_total`, both
phases, from the two scrapes. 1.0 is a walk that reads its picks alone; a walk
of every key block under a mask reads the context over `index_topk`. None where
no query was past `index_topk` or the program has no such counter."""

from benchmark import gen_window


def read(run: dict):
    kept = gen_window.total(run, "sel_pairs_kept_total")
    if kept <= 0:
        return None
    return gen_window.total(run, "sel_rows_walked_total") / kept
