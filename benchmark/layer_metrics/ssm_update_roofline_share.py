"""The least time the chip could take for the state updates of the window's
mean decode step (the live lanes from `ssm_tokens_total{phase=decode}`: each
one's state once read and once written, `W_in` and `W_out` once a layer;
benchmark/flops/hybrid.py `update`) over `ssm_update_ms`, in percent. The
program reads and writes the state of EVERY lane, live or not: the share says
what that costs."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.STEP_MODULE, "ssm_update")
    lanes = ssm_window.tokens_per_launch(run, "decode")
    fn = getattr(run.get("flops"), "update", None)
    if not m or not lanes or fn is None:
        return None
    return ssm_window.roofline_share(run, f"ssm_update ({lanes:.1f} live lanes)",
                                     fn(run["sizes"], lanes), m["launch_s"])
