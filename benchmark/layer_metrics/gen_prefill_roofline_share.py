"""The least time the chip could take for the window's mean prefill chunk (its
prompt tokens, not the chunk's padded width; the window's own counters;
benchmark/flops/decoder.py) over the time a chunk took in the trace
(`gen_prefill_chunk_ms`), in percent."""

from benchmark import gen_window


def read(run: dict):
    m = gen_window.module(run, gen_window.PREFILL_MODULE)
    return gen_window.roofline_share(run, "prefill", m["launch_s"] if m else None)
