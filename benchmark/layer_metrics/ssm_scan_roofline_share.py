"""The least time the chip could take for the chunked scans of the window's
mean prefill launch (its live prompt tokens from
`ssm_tokens_total{phase=prefill}`, its pieces from
`ssm_state_rows_total{phase=prefill}`; benchmark/flops/hybrid.py `scan`) over
`ssm_scan_ms`, in percent."""

from benchmark import gen_window, ssm_window


def read(run: dict):
    m = ssm_window.scoped_launch_s(run, gen_window.PREFILL_MODULE, "ssm_scan")
    tokens = ssm_window.tokens_per_launch(run, "prefill")
    fn = getattr(run.get("flops"), "scan", None)
    if not m or not tokens or fn is None:
        return None
    launches = gen_window.total(run, "gen_prefill_chunks_total")
    pieces = gen_window.total(run, "ssm_state_rows_total", phase="prefill") \
        / run["sizes"]["n_mamba"] / launches
    return ssm_window.roofline_share(
        run, f"ssm_scan ({tokens:.1f} live tokens in {pieces:.2f} pieces)",
        fn(run["sizes"], tokens, pieces), m["launch_s"])
