"""Live lanes x layers of the window's decode steps whose walk of the virtual
block table ran in the repo's own kernel (`tpuserve/ops/lane_attention.py`
`head_walk` with a key in one part: one work list a step of the (lane, key
block) items that exist, a cell an item for all heads), over all of the
window's, in percent: `eva_decode_steps_total{phase=decode,path=head_walk}` over
every path, from the two scrapes. The program chooses the path when the step is
traced (the TPU, at shapes the kernel takes), so this says what a step cost,
never what it answered: on the chip 100 since ISSUE 56, and 0 at a program from
before it, whose steps count under `path=walk` (jax's `paged_attention`);
anything between means steps fell back to the gather of the padded table. None
where no step ran or the program has no such counter (another family)."""

from benchmark import gen_window


def read(run: dict):
    n = gen_window.total(run, "eva_decode_steps_total", phase="decode")
    if n <= 0:
        return None
    return 100.0 * gen_window.total(run, "eva_decode_steps_total", phase="decode",
                                    path="head_walk") / n
