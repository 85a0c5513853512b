"""How late the benchmark's own load generator ran: send time less due time,
95th percentile over the window's requests. A starved generator must not be
read as a fast server."""

from benchmark.loadgen import percentile


def read(run: dict):
    late = run["load"].late_ms if run.get("load") else None
    return percentile(late, 0.95) if late else None
