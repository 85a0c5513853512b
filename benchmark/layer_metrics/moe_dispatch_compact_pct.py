"""Expert layers run whose dispatch carried only the compact row bound, over
expert layers run, both phases together, in percent:
`moe_layers_compact_total` over `moe_layers_total`. The layer's two branches
are bit-identical (`tpuserve/ops/moe.py` `held_experts`), so this says what a
launch cost, never what it answered: a launch whose held picks pass the
bound takes the branch that carries every pick. None where no expert layer
ran or the program has no such counter (the parent of the PR that added it),
and where the program has no second branch at all (every expert held)."""

from benchmark import gen_window, prom


def read(run: dict):
    ran = gen_window.total(run, "moe_layers_total")
    if ran <= 0:
        return None
    compact = prom.select(run.get("metrics_delta") or {}, "moe_layers_compact_total",
                          model=run.get("model_name"))
    if not compact:
        return None
    return 100.0 * sum(compact.values()) / ran
