"""Decode lanes of latent attention whose walk over their key blocks ran in
the kernel (`tpuserve/ops/lane_attention.py`: one call an attention, every
lane over its own key blocks, nothing of a lane in device memory), over all
decode lanes that walked, in percent:
`mla_tiles_total{phase=decode,walk=kernel}` over both walks, from the two
scrapes. The program chooses the walk when it is traced (a step's absorbed
tiles of one query on the TPU at shapes the kernel takes), so this says what
a step cost, never what it answered. 0 on a program whose steps walk in XLA
(the parent of the PR that added the kernel); None where no lane walked or
the program has no such counter."""

from benchmark import gen_window


def read(run: dict):
    lanes = gen_window.total(run, "mla_tiles_total", phase="decode")
    if lanes <= 0:
        return None
    return 100.0 * gen_window.total(run, "mla_tiles_total", phase="decode", walk="kernel") / lanes
