"""Delta-rule layers of prefill launches whose chunked rule ran in the kernel
(`tpuserve/ops/delta_scan.py`: one call a layer a launch, a tile's tables,
inverse and products in fast memory, the state passed from tile to tile inside
it), over all of the window's, in percent:
`delta_scans_total{phase=prefill,path=kernel}` over both paths, from the two
scrapes. The program chooses the path when the launch is traced (the TPU, at
shapes the kernel takes), so this says what a launch cost, never what it
answered: anything under 100 on the chip means launches fell back to the plain
form, some 140 device operations a layer. None where no launch ran or the
program has no such counter (another family, the parent of the PR that added
it)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "delta_scans_total", phase="prefill")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "delta_scans_total", phase="prefill", path="kernel") / n
