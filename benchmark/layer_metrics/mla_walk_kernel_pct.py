"""Prefill tiles of latent attention whose walk over their key blocks ran in
the kernel (`tpuserve/ops/tile_attention.py`: one call a tile, nothing of a
block in device memory), over all prefill tiles that walked, in percent:
`mla_tiles_total{phase=prefill,walk=kernel}` over both walks, from the two
scrapes. The program chooses the walk when it is traced (an expanded tile on
the TPU at shapes the kernel takes), so this says what a launch cost, never
what it answered. None where no tile walked or the program has no such
counter (the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    tiles = gen_window.total(run, "mla_tiles_total", phase="prefill")
    if tiles <= 0:
        return None
    return 100.0 * gen_window.total(run, "mla_tiles_total", phase="prefill", walk="kernel") / tiles
