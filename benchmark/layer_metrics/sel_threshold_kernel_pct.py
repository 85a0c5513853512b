"""Picked prefill tiles of attention over picks whose rows' thresholds (each
row's `index_topk`-th largest index score) were found by the kernel that made
the scores, in fast memory (`tpuserve/ops/index_select.py` `tile_scores`), over
all picked prefill tiles, in percent:
`sel_threshold_tiles_total{phase=prefill,path=kernel}` over both paths, from
the two scrapes. The program chooses the path when it is traced (both the
indexer's and the walk's kernels take the shapes: the walk then compares the
scores against the thresholds and no mask is made in device memory), so this
says what a launch cost, never what it answered. None where no picked tile ran
or the program has no such counter (the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    tiles = gen_window.total(run, "sel_threshold_tiles_total", phase="prefill")
    if tiles <= 0:
        return None
    return 100.0 * gen_window.total(run, "sel_threshold_tiles_total", phase="prefill",
                                    path="kernel") / tiles
