"""Decode lanes of the global layers whose walk over their key blocks ran in
the kernel (`tpuserve/ops/lane_attention.py` `head_walk`: one call a global
layer, every lane over its own key blocks, nothing of a lane in device memory
but its pages), over all such lanes that walked, in percent:
`attn_walks_total{phase=decode,walk=kernel}` over both walks, from the two
scrapes. The program chooses the walk when the step is traced (on the TPU in
bfloat16 at shapes the kernel takes), so this says what a step cost, never
what it answered: anything under 100 on the chip means steps fell back to the
gather of the padded block table. None where no lane walked or the program has
no such counter (another family, the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    lanes = gen_window.total(run, "attn_walks_total", phase="decode")
    if lanes <= 0:
        return None
    return 100.0 * gen_window.total(run, "attn_walks_total", phase="decode", walk="kernel") / lanes
