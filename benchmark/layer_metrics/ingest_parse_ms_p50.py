"""Median time the server spent turning a request body into token ids:
`latency_ms{phase=parse}` wraps `host_decode_items` (JSON parse and the
WordPiece tokenizer, `server.py` handle_predict), as the difference of the
two scrapes around the window."""

from benchmark import prom


def read(run: dict):
    return prom.histogram_quantile(run.get("metrics_delta") or {}, "latency_ms", 0.5,
                                   model=run.get("model_name"), phase="parse")
