"""Delta-rule updates of decode steps (live lanes x layers) that ran in the
kernel (`tpuserve/ops/delta_update.py`: one call a layer a step, the state in
place), over all of the window's, in percent:
`delta_steps_total{phase=decode,path=kernel}` over both paths, from the two
scrapes. The program chooses the path when the step is traced (the TPU, at
shapes the kernel takes), so this says what a step cost, never what it
answered: anything under 100 on the chip means steps fell back to the plain
form, which reads the state twice. None where no update ran or the program has
no such counter (another family, the parent of the PR that added it)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "delta_steps_total", phase="decode")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "delta_steps_total", phase="decode", path="kernel") / n
