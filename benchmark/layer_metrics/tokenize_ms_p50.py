"""Median wall time of the tokenizer alone for one request (a frame of 16
documents in the closed-loop cells): `latency_ms{phase=tokenize}`, taken by
`time.perf_counter()` in the decode thread around the WordPiece calls only,
as the difference of the two scrapes. Unlike `ingest_parse_ms_p50` it holds
neither the JSON parse nor the wait for a decode thread; the wait for the
GIL is still inside it (`tokenize_cpu_ms_per_item` is the CPU time). None
where the program has no such phase."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "latency_ms", 0.5,
                                   model=run.get("model_name"), phase="tokenize")
