"""Idle device time that no span names, in percent of the traced window: gaps
under 1 ms, the parts of a gap that none of its batch's spans covers, and gaps
whose launch is not in the trace. It is what is left of `device_idle_share`
after `idle_batcher_pct`, `idle_host_stage_pct` and `idle_no_request_pct`, so
the four sum to it. This reader also prints the run's clock check and its ten
longest gaps with every part of each (`breakdown.idle_gaps` carries each gap's
largest part as its name: run.py `named_idle_gaps`)."""

from benchmark import host_spans


def read(run: dict):
    hs = host_spans.for_run(run)
    trace = run.get("trace")
    if hs is None or not trace or not trace["window_s"]:
        return None
    run.setdefault("notes", []).extend(host_spans.notes(hs))
    idle = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    named = sum(host_spans.idle_pct(run, states) for states in (
        ("accumulate", "slot_wait", "staging_wait"), ("tokenize", "assemble", "h2d"),
        ("no_request",)))
    return idle - named
