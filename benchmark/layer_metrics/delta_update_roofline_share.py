"""The least time the chip could take for the delta-rule state's update and
read of the window's mean decode step (the live lanes from
`ssm_tokens_total{phase=decode}`: each one's state once in and once out a
layer, the step's vectors beside it; the family's `flops/<family>.py`
`delta_update`) over `delta_update_ms`, in percent: the kernel's share of its
roofline. A lane that is not live is read and written back too: the share says
what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "delta_update")
    lanes = ssm_window.tokens_per_launch(run, "decode")
    fn = getattr(run.get("flops"), "delta_update", None)
    if not m or not lanes or fn is None:
        return None
    return ssm_window.roofline_share(run, f"delta_update ({lanes:.1f} live lanes)",
                                     fn(run["sizes"], lanes), m["launch_s"])
