"""Decode steps that the generation engine dispatched while the step before
them was still unread on the host, over the steps it accounted for, in
percent: `gen_steps_ahead_total` over `gen_iterations_total`, from the two
scrapes. A step counted there was queued on the chip behind its predecessor,
so the device went from one to the next without waiting for the host's pass
between them; one that was not began a busy stretch, or followed a failure.
None where no step ran or the program has no such counter (the parent of the
PR that added it)."""

from benchmark import gen_window, prom


def read(run: dict):
    steps = gen_window.total(run, "gen_iterations_total")
    if steps <= 0:
        return None
    ahead = prom.select(run.get("metrics_delta") or {}, "gen_steps_ahead_total",
                        model=run.get("model_name"))
    if not ahead:
        return None
    return 100.0 * sum(ahead.values()) / steps
