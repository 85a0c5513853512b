"""Attention layers of prefill launches with a picked tile whose block scores
were made by the kernel (`tpuserve/ops/block_scores.py`: one call a tile, the
softmax a head over the windows, the group sum and the block maximum in fast
memory, over the window blocks the tile can see), over all of the window's, in
percent: `blk_selects_total{phase=prefill,path=kernel}` over both paths, from the
two scrapes. The program chooses the path when the launch is traced (the TPU,
at shapes the kernel takes), so this says what a launch cost, never what it
answered: anything under 100 on the chip means launches fell back to the plain
form, a float32 array of every (head, row, window) score written to device
memory and read back five to seven times a tile. None where no launch had a
picked tile or the program has no such counter (another family, the parent of
the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "blk_selects_total", phase="prefill")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "blk_selects_total", phase="prefill", path="kernel") / n
