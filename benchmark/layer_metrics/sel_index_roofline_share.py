"""The least time the chip could take for the indexer's scores in the window's
mean prefill launch (the (query, key) pairs the program says it scored,
`sel_pairs_scored_total{phase=prefill}`: 2 x heads x width operations a pair;
the index keys of the rows its pieces attend over read once, in the scored
queries' share of them; benchmark/flops/mla_sel.py `index`) over `sel_index_ms`,
in percent. The scope also holds each row's threshold (sixteen passes of three
counts over its scores), which the least counts nothing for: the share says
what that, the ReLU and the sum over heads cost."""

from benchmark import gen_window, ssm_window


def launch_share(run: dict, scope: str, pairs_counter: str, least: str):
    """The share of a prefill launch's scope `scope`: the flops file's `least`
    over the window's mean launch (`pairs_counter` pairs a layer, and the rows
    its pieces attend over in the picked queries' share) against the scope's
    time. None where the trace, the counters or the flops file have nothing."""
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, scope)
    mean = gen_window.per_launch(run, "prefill")
    fn = getattr(run.get("flops"), least, None)
    pairs = gen_window.total(run, pairs_counter, phase="prefill")
    if not m or not mean or fn is None or pairs <= 0:
        return None
    picked = gen_window.total(run, "sel_queries_total", phase="prefill", path="picked")
    rows = gen_window.total(run, "mla_rows_attended_total", phase="prefill") \
        * picked / (mean["tokens"] * mean["launches"])
    return ssm_window.roofline_share(
        run, f"{scope} in a launch ({pairs / mean['launches']:.4g} pairs a layer)",
        fn(run["sizes"], pairs / mean["launches"], rows / mean["launches"]), m["launch_s"])


def read(run: dict):
    return launch_share(run, "sel_index", "sel_pairs_scored_total", "index")
