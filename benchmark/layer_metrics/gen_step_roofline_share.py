"""The least time the chip could take for the window's mean decode step (the
lanes that were live, the context they attended from, the held experts that
were hit: the window's own counters; benchmark/flops/decoder.py counts the
least operations and bytes) over the time a step took in the trace
(`gen_step_ms`), in percent."""

from benchmark import gen_window


def read(run: dict):
    m = gen_window.module(run, gen_window.STEP_MODULE)
    return gen_window.roofline_share(run, "decode", m["launch_s"] if m else None)
