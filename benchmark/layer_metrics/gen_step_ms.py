"""Device time of one decode step of the generation engine, in ms: the median
over the launches of the step program (`jit_step`) that lie whole inside the
traced window."""

from benchmark import gen_window


def read(run: dict):
    m = gen_window.module(run, gen_window.STEP_MODULE)
    if not m:
        return None
    run.setdefault("notes", []).append(
        f"gen_step_ms: {m['launches']} launches in the traced window, {m['whole_launches']} whole")
    return m["launch_s"] * 1e3
