"""Tokens an expert that was hit got in a decode step, as the mean over the
window's steps and routed layers: picks of live lanes on held experts
(`moe_tokens_routed_total{phase=decode,held=yes}`) over held experts hit
(`moe_experts_hit_total{phase=decode}`), from the two scrapes. It is the rows a
grouped product has to spread an expert's matrices over: at fixed traffic a
fall means lanes stood empty. None where no expert was hit or the program has
no such counter (a family with no routed layer)."""

from benchmark import gen_window


def read(run: dict):
    hit = gen_window.total(run, "moe_experts_hit_total", phase="decode")
    if hit <= 0:
        return None
    return gen_window.total(run, "moe_tokens_routed_total", phase="decode", held="yes") / hit
