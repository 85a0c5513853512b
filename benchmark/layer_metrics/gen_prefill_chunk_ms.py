"""Device time of one prefill chunk of the generation engine, in ms: the
median over the launches of the prefill program (`jit_prefill_fn`) that lie
whole inside the traced window. Every chunk is one launch of the same static
width, whatever part of it is prompt."""

from benchmark import gen_window


def read(run: dict):
    m = gen_window.module(run, gen_window.PREFILL_MODULE)
    if not m:
        return None
    run.setdefault("notes", []).append(
        f"gen_prefill_chunk_ms: {m['launches']} launches in the traced window, "
        f"{m['whole_launches']} whole")
    return m["launch_s"] * 1e3
