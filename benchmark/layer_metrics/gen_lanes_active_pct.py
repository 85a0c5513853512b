"""Lanes that decoded a token, over the lanes a step carries, in percent:
`gen_decode_tokens_total` over `gen_iterations_total` times the slots the
configuration gives the engine (a lane that is free, or still in prefill,
rides every step for nothing)."""

from benchmark import gen_window


def read(run: dict):
    steps = gen_window.total(run, "gen_iterations_total")
    slots = (run.get("sizes") or {}).get("slots")
    tokens = gen_window.total(run, "gen_decode_tokens_total")
    if steps <= 0 or not slots or tokens <= 0:
        return None
    return 100.0 * tokens / (steps * slots)
