"""Key rows the walks fetched for the picked queries, over the keys those
queries attended: `blk_rows_read_total` over `blk_keys_attended_total`, both
phases, from the two scrapes. 1.0 is a walk that reads its picked blocks alone
(a step's); a launch's walk of every key block under a mask reads the context
over `topk` blocks. None where no query picked or the program has no such
counter."""

from benchmark import gen_window


def read(run: dict):
    attended = gen_window.total(run, "blk_keys_attended_total")
    if attended <= 0:
        return None
    return gen_window.total(run, "blk_rows_read_total") / attended
