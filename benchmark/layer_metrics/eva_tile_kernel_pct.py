"""Tiles x layers of the window's prefill launches whose attention ran in the
repo's own kernel (`tpuserve/ops/launch_attention.py` `launch_walk`: one call a
layer over one flat work list a launch of the (tile, key page) items that exist,
ring, own rows and summary pages read in place), over all of the window's, in
percent: `eva_prefill_tiles_total{phase=prefill,path=tile_kernel}` over every
path, from the two scrapes. The program chooses the path when the launch is
traced (the TPU, at shapes the kernel takes), so this says what a launch cost,
never what it answered: on the chip 100 since ISSUE 58, and 0 at a program of
this family from before it (the series is not there, but the family's
`eva_rows_attended_total{phase=prefill}` is and moved: its tiles ran one by one
in XLA); anything between means launches fell back to `_tile` in XLA. None where
no launch of this family ran (another family, or a window without a launch)."""

from benchmark import gen_window


def read(run: dict):
    if gen_window.total(run, "eva_rows_attended_total", phase="prefill") <= 0:
        return None
    n = gen_window.total(run, "eva_prefill_tiles_total", phase="prefill")
    if n <= 0:
        return 0.0
    return 100.0 * gen_window.total(run, "eva_prefill_tiles_total", phase="prefill",
                                    path="tile_kernel") / n
