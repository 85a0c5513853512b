"""Mamba-2 layers of prefill launches whose chunked scan ran in the kernel
(`tpuserve/ops/ssm_scan.py`: one call a layer a launch, a tile's quadratic form
in fast memory, the state passed from tile to tile inside it), over all of the
window's, in percent: `ssm_scans_total{phase=prefill,path=kernel}` over both
paths, from the two scrapes. The program chooses the path when the launch is
traced (the TPU, at shapes the kernel takes), so this says what a launch cost,
never what it answered: anything under 100 on the chip means launches fell back
to the plain form, thousands of device operations a launch and a table of
decays a layer through device memory. None where no launch ran or the program
has no such counter (another family, the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "ssm_scans_total", phase="prefill")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "ssm_scans_total", phase="prefill", path="kernel") / n
