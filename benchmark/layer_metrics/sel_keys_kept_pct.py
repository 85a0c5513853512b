"""Keys the indexer's queries kept over the keys they were scored against, in
percent: `sel_pairs_kept_total` over `sel_pairs_scored_total`, both phases, from
the two scrapes (live queries past `index_topk` only: a query under it keeps
every key and is not scored). 100% less this is the share of a query's keys the
attention need not read. None where nothing was scored or the program has no
such counter."""

from benchmark import gen_window


def read(run: dict):
    scored = gen_window.total(run, "sel_pairs_scored_total")
    if scored <= 0:
        return None
    return 100.0 * gen_window.total(run, "sel_pairs_kept_total") / scored
