"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one mix, one traffic kind, one family or one per-layer metric
is a file of its own, found here by its name; nothing in the harness lists
them."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def find(items: list[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_config(bench: dict, name: str) -> dict:
    """A configuration's file, by the path BENCHMARK.json gives for it; a
    name it does not list (a rehearsal configuration) is looked up in
    benchmark/configs/."""
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(REPO, c["file"]), encoding="utf-8") as f:
                return json.load(f)
    cfg = load_json("configs", f"{name}.json")
    if "base" in cfg:  # a control: another configuration with a few keys changed
        merged = load_config(bench, cfg["base"])
        _deep_update(merged, cfg["override"])
        merged.update({k: v for k, v in cfg.items() if k not in ("base", "override")})
        return merged
    return cfg


def _deep_update(into: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _deep_update(into[k], v)
        else:
            into[k] = v


def load_mix(name: str) -> dict:
    return load_json("mixes", f"{name}.json")


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module (names may hold '-' and '.')."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: {kind}/{name}.py does not exist")
    modname = f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, group: str, workload: str | None) -> list[dict]:
    """The metrics of `group` ("end_to_end" or "per_layer") that this cell
    reports: those without a `workloads` key, and those that list it. A run
    that is no cell (`workload` None: a control, a rehearsal) reports all."""
    return [m for m in bench[group]
            if workload is None or "workloads" not in m or workload in m["workloads"]]
