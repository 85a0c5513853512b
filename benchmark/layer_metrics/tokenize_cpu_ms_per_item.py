"""CPU time of the tokenizer per document answered: the decode threads'
`time.thread_time()` around the WordPiece calls,
`ingest_tokenize_cpu_seconds_total`, over `items_total`, both as differences
of the two scrapes. Read beside `server_cpu_ms_per_item` (the whole process,
from /proc): their ratio is the tokenizer's share of the host's work. A
count of host work; it says nothing of the device."""

from benchmark import prom


def read(run: dict):
    d, model = run.get("metrics_delta") or {}, run.get("model_name")
    cpu = prom.select(d, "ingest_tokenize_cpu_seconds_total", model=model)
    items = sum(prom.select(d, "items_total", model=model).values())
    if not cpu or items <= 0:
        return None
    return sum(cpu.values()) * 1e3 / items
