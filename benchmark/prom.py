"""Reading the program's `/metrics` (Prometheus text): counters, gauges and
histogram buckets, and the difference of two scrapes."""

from __future__ import annotations

import math
import re

_LINE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)")


def parse(text: str) -> dict[str, float]:
    """`name{labels}` (labels as printed, order kept) -> value."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.split(" # ")[0])
        if not m:
            continue
        name, labels, value = m.groups()
        key = f"{name}{{{labels}}}" if labels else name
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def delta(after: dict[str, float], before: dict[str, float]) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def select(metrics: dict[str, float], name: str, **labels: str) -> dict[str, float]:
    """Entries of family `name` whose labels include every given pair."""
    out = {}
    for k, v in metrics.items():
        base, _, rest = k.partition("{")
        if base != name:
            continue
        if all(f'{lk}={lv}' in rest.replace('"', "") for lk, lv in labels.items()):
            out[k] = v
    return out


def histogram_quantile(metrics: dict[str, float], name: str, q: float,
                       **labels: str) -> float | None:
    """Quantile of a histogram (or of the difference of two scrapes), linear
    inside the bucket as Prometheus does. None where nothing was observed."""
    buckets = []
    for k, v in select(metrics, name + "_bucket", **labels).items():
        le = re.search(r'le="([^"]+)"', k).group(1)
        buckets.append((math.inf if le == "+Inf" else float(le), v))
    buckets.sort()
    if not buckets or buckets[-1][1] <= 0:
        return None
    rank = q * buckets[-1][1]
    lo, prev = 0.0, 0.0
    for le, acc in buckets:
        if acc >= rank and acc > prev:
            if math.isinf(le):
                return lo
            return lo + (le - lo) * (rank - prev) / (acc - prev)
        lo, prev = (le if not math.isinf(le) else lo), acc
    return None
