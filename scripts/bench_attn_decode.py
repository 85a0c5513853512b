#!/usr/bin/env python
"""Decode attention over packed pages alone, on the chip: the four attention
layers' `_decode_full` of a cell whose KV heads are narrower than the 128 lanes,
at the cell's lanes and a FIXED live context, timed by the host's clock around
a dependent read, (1) through the paged-attention kernel at several sizes of
its compute block, (2) at two `max_ctx` (block tables of the cell's width and
of 4,096 positions: the time may not grow with it), (3) through the gather of
the padded block table that every backend but the TPU takes.

    chiprun -- python scripts/bench_attn_decode.py
    python scripts/bench_attn_decode.py --rehearse --config benchmark/configs/rehearsal-hybrid_ffn-tiny.json

This is where `paged_lm.PagedLM.PACKED_DECODE_BLOCK` comes from (PERF.md
section 6, PR 40). One JSON line a case on stdout and in
`chiprun_out/bench_attn_decode/`. Off the TPU it walks the path (`--rehearse`)
and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import spec  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.models import build  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "granite-4.0-h-micro.json"))
    ap.add_argument("--context", type=int, default=300, help="live positions a lane")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_attn_decode: no TPU here; a time from another backend is no device number")
    out_dir = os.path.join(REPO, "chiprun_out", "bench_attn_decode")
    os.makedirs(out_dir, exist_ok=True)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    sz = spec.load_module("reference", cfg["family"]).sizes_from_config(cfg)
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)
    model = build(ModelConfig(
        name="m", family=cfg["family"], dtype=cfg["serve"]["model"]["dtype"], batch_buckets=[1],
        options={"config_file": arch_path, "draw_weights_seed": 1,
                 "max_prompt_tokens": sz["max_prompt"], "max_new_tokens": sz["max_new"]}))
    lanes, P, n_layers = sz["slots"], sz["page_tokens"], sz["n_attn"]
    context = min(args.context, sz["max_ctx"])
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((lanes, model.heads, model.hd)), model.dtype)
    pos = jnp.full((lanes,), context - 1, jnp.int32)
    live_pages = -(-context // P)
    lines = []

    def case(name: str, pps: int, block: int | None, gather: bool) -> None:
        pages = lanes * live_pages + 1
        shape = model._page_shape(pages, P)
        pools = [(jnp.asarray(rng.standard_normal(shape), model.dtype),
                  jnp.asarray(rng.standard_normal(shape), model.dtype)) for _ in range(n_layers)]
        bt = np.zeros((lanes, pps), np.int32)
        bt[:, :live_pages] = 1 + np.arange(lanes * live_pages).reshape(lanes, live_pages)
        bt = jnp.asarray(bt)
        if block is not None:
            model.PACKED_DECODE_BLOCK = block
        backend = jax.default_backend
        if gather:  # steer the trace-time branch, here in the script
            jax.default_backend = lambda: "cpu"
        try:
            fn = jax.jit(lambda q, pools, bt, pos: sum(
                model._decode_full(q, kp, vp, bt, pos) for kp, vp in pools))
            jax.block_until_ready(fn(q, pools, bt, pos))
        finally:
            jax.default_backend = backend
        line = {"case": name, "lanes": lanes, "context": context, "layers": n_layers,
                "max_ctx": pps * P, "block_positions": None if gather else block}
        if on_tpu:
            times = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, pools, bt, pos))
                times.append(time.perf_counter() - t0)
            line["ms"] = statistics.median(times) * 1e3
            line["ms_min"] = min(times) * 1e3
        lines.append(line)
        print(json.dumps(line), flush=True)

    pps = sz["pages_per_slot"]
    for block in (P, 2 * P, 3 * P, 4 * P, 6 * P, pps * P):
        case(f"kernel, block {block}", pps, block, False)
    case("kernel, block 512, max_ctx 4096", 4096 // P, 512, False)
    case("gather of the padded table", pps, None, True)
    case("gather of the padded table, max_ctx 4096", 4096 // P, None, True)
    with open(os.path.join(out_dir, "cases.jsonl"), "w", encoding="utf-8") as f:
        f.write("".join(json.dumps(line) + "\n" for line in lines))


if __name__ == "__main__":
    main()
