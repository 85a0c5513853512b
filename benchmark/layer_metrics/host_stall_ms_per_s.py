"""Time the server's process was held back as a whole, in ms a second of the
window: `host_stall_seconds_total`, as the difference of the two scrapes over the
window's seconds. The program counts it in its telemetry sampler's thread
(`tpuserve/telemetry/store.py` `MetricSampler.run`): what each wait of
`sample_interval_s` took beyond that, so it is how late a thread that needs
nothing but the processor woke. A quiet machine reads the scheduler's own
lateness (under a millisecond a tick); a window in which the machine or the
whole process stood still for seconds reads those seconds, whatever held it, and
says that the machine and not the program lost the window. None where the
program has no such counter (the parent of the PR that added it)."""

from benchmark import prom


def read(run: dict):
    found = prom.select(run.get("metrics_delta") or {}, "host_stall_seconds_total")
    seconds = getattr(run.get("load"), "seconds", None)
    if not found or not seconds:
        return None
    return 1e3 * sum(found.values()) / seconds
