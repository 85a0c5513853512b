"""The least time the chip could take for one launch of the program the
device spent most of the traced window in (the larger of operations over the
peak rate and bytes over the peak bandwidth; benchmark/flops/<family>.py counts
the whole padded bucket, whose batch and sequence are read from the program's
own operations in the trace; benchmark/peaks.json holds the peaks) over the
time a launch took in the trace (`exec_ms_per_batch`). No peaks for the device
or no bucket to be read: no number."""

from benchmark.trace_reduce import bucket_of


def read(run: dict):
    top = (run.get("trace") or {}).get("top_module")
    peaks = run.get("peaks")
    bucket = bucket_of(top, run["sizes"]["d_model"]) if top else None
    if not peaks or not bucket:
        return None
    batch, seq = bucket
    ops, nbytes = run["flops"].ops_and_bytes(run["sizes"], batch, seq)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"exec_roofline_share: bucket ({batch}, {seq}) bound by "
        f"{'compute' if t_ops >= t_bytes else 'memory'} "
        f"(ops {ops:.4g} -> {t_ops * 1e3:.3f} ms, bytes {nbytes:.4g} -> "
        f"{t_bytes * 1e3:.3f} ms)")
    return 100.0 * max(t_ops, t_bytes) / top["launch_s"]
