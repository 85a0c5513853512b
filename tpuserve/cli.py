"""Command-line entry points (SURVEY.md §2 C10).

Usage::

    python -m tpuserve serve  --config serve.toml [--set port=9000 ...]
        ([router] enabled = true starts the router tier + worker processes)
    python -m tpuserve bench  --url http://127.0.0.1:8000 --model resnet50 ...
    python -m tpuserve chaos  --config chaos.toml --min-availability 0.99 \
                              [--drill reload | --drill worker_kill]
    python -m tpuserve import-model --saved-model DIR --family resnet50 --out CKPT
    python -m tpuserve warmup --config serve.toml   (compile + persist XLA cache)
    python -m tpuserve lint                          (concurrency/drift analysis)
    python -m tpuserve describe                      (device/mesh inventory)
"""

from __future__ import annotations

import argparse
import json
import sys


def _add_config_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", default=None, help="TOML config path")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   help="dot-path override, e.g. --set model.resnet50.deadline_ms=2")


def _parse_opt_args(parser: argparse.ArgumentParser, items: list[str]) -> dict:
    """--opt KEY=VALUE pairs -> {key: TOML-parsed value} (import-model and
    finetune-det share this)."""
    from tpuserve.config import _parse_toml_value

    options = {}
    for item in items:
        if "=" not in item:
            parser.error(f"--opt must look like key=value, got {item!r}")
        key, _, text = item.partition("=")
        options[key.strip()] = _parse_toml_value(text.strip())
    return options


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="tpuserve")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_serve = sub.add_parser("serve", help="start the inference server")
    _add_config_args(p_serve)

    p_bench = sub.add_parser("bench", help="run the HTTP load generator")
    p_bench.add_argument("--url", default="http://127.0.0.1:8000")
    p_bench.add_argument("--model", default="resnet50")
    p_bench.add_argument("--verb", default="predict")
    p_bench.add_argument("--duration", type=float, default=10.0)
    p_bench.add_argument("--warmup", type=float, default=2.0)
    p_bench.add_argument("--concurrency", type=int, default=64,
                         help="closed-loop workers (ignored with --rate)")
    p_bench.add_argument("--rate", type=float, default=None,
                         help="open-loop offered rate (req/s); switches to open-loop mode")
    p_bench.add_argument("--payload", default=None, help="file to POST; default synthetic image")
    p_bench.add_argument("--content-type", default="application/x-npy")
    p_bench.add_argument("--batch", type=int, default=0,
                         help="client-side batch: POST (N,H,W,3) npy bodies; "
                              "throughput counts items")
    p_bench.add_argument("--distinct", type=int, default=0,
                         help="cycle N distinct synthetic payloads — a "
                              "miss-only workload for the result cache when "
                              "N exceeds its capacity; 0/1 repeats one "
                              "payload (hit-heavy once the cache is warm)")
    p_bench.add_argument("--synthetic",
                         choices=["npy", "jpeg", "prompt", "sd-prompt"],
                         default="npy",
                         help="synthetic payload kind for --distinct pools: "
                              "npy/jpeg images, or JSON prompt bodies for "
                              "the generative families (prompt = textgen "
                              "with mixed max_new_tokens, sd-prompt = "
                              "fixed-steps txt2img)")
    p_bench.add_argument("--edge", type=int, default=256,
                         help="synthetic payload image edge for --distinct")
    p_bench.add_argument("--max-new", default="2,32",
                         help="lo,hi range of max_new_tokens for "
                              "--synthetic prompt pools (mixed output "
                              "lengths; ISSUE 9)")
    p_bench.add_argument("--wire", choices=["npy", "frame"], default="npy",
                         help="client wire: npy bodies, or framed binary "
                              "multi-item bodies (application/"
                              "x-tpuserve-frame — zero-copy server parse; "
                              "--batch sets items per frame, --frame-kind "
                              "the pixel layout)")
    p_bench.add_argument("--frame-kind", choices=["yuv420", "rgb8"],
                         default="yuv420",
                         help="--wire frame item layout; must match the "
                              "served model's wire_format")
    p_bench.add_argument("--procs", type=int, default=1,
                         help="load-worker processes; > 1 splits "
                              "--concurrency (and --rate) across workers "
                              "with disjoint synthetic seed ranges and "
                              "merges exact percentiles — so the measured "
                              "bottleneck is the server, not one client "
                              "process's event loop")
    p_bench.add_argument("--seed-base", type=int, default=0,
                         help="first synthetic seed (multi-process workers "
                              "take disjoint ranges automatically)")
    p_bench.add_argument("--dump-latencies", default=None,
                         help="write raw latency samples as JSON to this "
                              "path (the multi-process merge reads them)")
    p_bench.add_argument("--stream", action="store_true",
                         help="closed-loop STREAMING mode (?stream=true, "
                              "SSE): reports first-token p50/p99, "
                              "inter-token-gap p50/p99/max + histogram, "
                              "and exact tokens/s from token event "
                              "timestamps; use with --synthetic prompt "
                              "against a generative model (--rate/--procs "
                              "don't apply)")
    p_bench.add_argument("--long-every", type=int, default=0,
                         help="skew the --synthetic prompt pool: every "
                              "Nth body is a --long-words-word prompt at "
                              "the top of --max-new (0 = uniform pool)")
    p_bench.add_argument("--long-words", type=int, default=16,
                         help="prompt length (words) of the injected "
                              "long bodies for --long-every")

    p_imp = sub.add_parser("import-model", help="convert TF SavedModel -> orbax checkpoint")
    p_imp.add_argument("--saved-model", required=True)
    p_imp.add_argument("--family", required=True)
    p_imp.add_argument("--out", required=True)
    p_imp.add_argument("--opt", action="append", default=[], metavar="KEY=VALUE",
                       help="model option for the import (TOML-parsed value), "
                            "e.g. --opt vocab_file=vocab.txt --opt layers=24")
    p_imp.add_argument("--quantize", choices=["int8"], default=None,
                       help="write a weight-only int8 checkpoint (half the "
                            "bytes); serve it with quantize = \"int8\"")

    p_ft = sub.add_parser(
        "finetune-det",
        help="fine-tune EfficientDet -> full orbax detector checkpoint")
    p_ft.add_argument("--out", required=True)
    p_ft.add_argument("--steps", type=int, default=50)
    p_ft.add_argument("--batch", type=int, default=8)
    p_ft.add_argument("--data", default=None,
                      help=".npz with images/boxes/classes/valid; default "
                           "synthetic rectangles")
    p_ft.add_argument("--weights", default=None,
                      help="EfficientNet-B0 backbone checkpoint to transfer "
                           "from (SavedModel or orbax)")
    p_ft.add_argument("--lr", type=float, default=1e-3)
    p_ft.add_argument("--opt", action="append", default=[], metavar="KEY=VALUE",
                      help="model option/field (TOML-parsed), e.g. "
                           "--opt image_size=512 --opt det_classes=90")

    p_chaos = sub.add_parser(
        "chaos",
        help="serve a fault-injected config on an ephemeral port, drive the "
             "load generator at it, and report availability (staging drills)")
    _add_config_args(p_chaos)
    p_chaos.add_argument("--model", default=None,
                         help="model to load test (default: first configured)")
    p_chaos.add_argument("--duration", type=float, default=10.0)
    p_chaos.add_argument("--warmup", type=float, default=1.0)
    p_chaos.add_argument("--concurrency", type=int, default=16)
    p_chaos.add_argument("--rate", type=float, default=None,
                         help="open-loop offered rate (req/s); default closed loop")
    p_chaos.add_argument("--min-availability", type=float, default=0.0,
                         help="exit non-zero when n_ok/(n_ok+n_err) falls below this")
    p_chaos.add_argument("--drill",
                         choices=["reload", "worker_kill", "host_kill",
                                  "stream_kill", "fleet", "autopilot"],
                         default=None,
                         help="additionally drive a drill during the run: "
                              "'reload' POSTs :reload on an interval so "
                              "reload_* fault rules prove the lifecycle "
                              "gates hold availability; 'worker_kill' "
                              "serves a real router + worker fleet and "
                              "SIGKILLs one worker mid-load; 'host_kill' "
                              "serves >= 2 host failure domains x >= 2 "
                              "workers and SIGKILLs one ENTIRE host's "
                              "process group mid-load (agent + workers — "
                              "a machine death), gating availability on "
                              "the survivors plus a torn/duplicate audit "
                              "and the re-absorb time; 'fleet' loads "
                              "every configured model (>= 3), poisons "
                              "--model with device_error @ 100%, and "
                              "reports per-model isolation — the victim's "
                              "breaker must open while every survivor "
                              "holds its SLO (docs/ROBUSTNESS.md); "
                              "'stream_kill' serves a router + worker "
                              "fleet with a generative model, drives "
                              "mixed streaming + unary load, SIGKILLs "
                              "one worker mid-stream, and byte-audits "
                              "the fail-safe stream semantics: every "
                              "started stream ends in a terminal event "
                              "(zero torn streams, zero duplicate or "
                              "reordered tokens vs a seeded reference) "
                              "while un-started streams retry "
                              "transparently; "
                              "'autopilot' serves a tenant-fenced fleet "
                              "with the self-healing controller engaged, "
                              "turns one tenant hostile mid-load while a "
                              "seeded latency fault fires on one host, and "
                              "gates on unattended containment: hostile "
                              "overage 429'd, victims green, every "
                              "controller action audited "
                              "(docs/OPERATIONS.md)")
    p_chaos.add_argument("--drill-interval", type=float, default=0.5,
                         help="seconds between drill operations")
    p_chaos.add_argument("--kill-after", type=float, default=None,
                         help="worker_kill: seconds after warmup before the "
                              "SIGKILL (default: 25%% of the run)")
    p_chaos.add_argument("--respawn-budget", type=float, default=120.0,
                         help="worker_kill: seconds the killed worker has "
                              "to come back healthy (backoff + boot)")

    p_warm = sub.add_parser("warmup", help="AOT-compile all buckets, persist XLA cache")
    _add_config_args(p_warm)

    p_lint = sub.add_parser(
        "lint",
        help="concurrency + drift static analysis over tpuserve/ "
             "(docs/ANALYSIS.md); fails on findings not in the checked-in "
             "baseline")
    from tpuserve.analysis.cli import add_lint_args

    add_lint_args(p_lint)

    sub.add_parser("describe", help="print device / mesh inventory")

    args = parser.parse_args(argv)

    if args.cmd == "serve":
        from tpuserve.config import default_config, load_config

        if args.config:
            cfg = load_config(args.config, args.overrides)
        else:
            cfg = default_config()
            for ov in args.overrides:
                from tpuserve.config import _apply_override

                _apply_override(cfg, ov)
        if cfg.router.enabled:
            # Router/worker split (docs/ROBUSTNESS.md "Process failure
            # domains"): this process is the device-free front tier; the
            # supervisor spawns the worker processes that build models.
            from tpuserve.workerproc import serve_router

            serve_router(cfg)
        else:
            from tpuserve.server import serve

            serve(cfg)
        return 0

    if args.cmd == "bench":
        from tpuserve.bench.loadgen import run_loadgen_cli

        return run_loadgen_cli(args)

    if args.cmd == "chaos":
        import asyncio

        from tpuserve.config import default_config, load_config
        from tpuserve.server import configure_logging

        cfg = load_config(args.config, args.overrides) if args.config else default_config()
        configure_logging(cfg)
        model = args.model or cfg.models[0].name
        if args.drill == "worker_kill":
            # Multi-process drill: this process stays device-free (the
            # router never touches a chip); the fleet builds the models.
            from tpuserve.workerproc.drill import run_worker_kill_drill

            summary = asyncio.run(run_worker_kill_drill(
                cfg, model, duration_s=args.duration, warmup_s=args.warmup,
                concurrency=args.concurrency, kill_after_s=args.kill_after,
                respawn_budget_s=args.respawn_budget))
        elif args.drill == "host_kill":
            # Host-domain drill (ISSUE 13): SIGKILL one entire host's
            # process group (agent + its workers) mid-load; the surviving
            # hosts must hold availability while the dead domain respawns.
            from tpuserve.workerproc.drill import run_host_kill_drill

            summary = asyncio.run(run_host_kill_drill(
                cfg, model, duration_s=args.duration, warmup_s=args.warmup,
                concurrency=args.concurrency, kill_after_s=args.kill_after,
                reabsorb_budget_s=args.respawn_budget))
        elif args.drill == "stream_kill":
            # Mid-stream chaos drill (ISSUE 17): SIGKILL one worker while
            # streams are in flight; gated availability is the unary
            # load's, and the stream audit (torn/duplicates/byte-diff vs
            # a seeded reference) is asserted by scripts/stream_drill.sh.
            from tpuserve.workerproc.drill import run_stream_kill_drill

            summary = asyncio.run(run_stream_kill_drill(
                cfg, model, duration_s=args.duration, warmup_s=args.warmup,
                concurrency=args.concurrency, kill_after_s=args.kill_after,
                respawn_budget_s=args.respawn_budget))
        elif args.drill == "autopilot":
            # Hostile-tenant drill (ISSUE 16): one tenant floods past its
            # quota while a seeded [faults] latency rule fires mid-load on
            # one host; the gated availability is the WORST VICTIM's —
            # containment must hold without an operator in the loop.
            from tpuserve.workerproc.drill import run_autopilot_drill

            summary = asyncio.run(run_autopilot_drill(
                cfg, model, duration_s=args.duration, warmup_s=args.warmup,
                concurrency=args.concurrency))
        elif args.drill == "fleet":
            # Isolation drill (Clipper P1): --model names the VICTIM; the
            # gated availability is the WORST SURVIVOR's.
            from tpuserve.parallel import init_distributed
            from tpuserve.scheduler import run_fleet_drill

            init_distributed(cfg.distributed)
            summary = asyncio.run(run_fleet_drill(
                cfg, victim=model, duration_s=args.duration,
                warmup_s=args.warmup, concurrency=args.concurrency))
        else:
            from tpuserve.faults import run_chaos
            from tpuserve.parallel import init_distributed
            from tpuserve.server import ServerState

            init_distributed(cfg.distributed)
            state = ServerState(cfg)
            state.build()
            summary = asyncio.run(run_chaos(
                state, model, duration_s=args.duration, warmup_s=args.warmup,
                concurrency=args.concurrency, rate_per_s=args.rate,
                edge=cfg.model(model).wire_size, drill=args.drill,
                drill_interval_s=args.drill_interval))
        print(json.dumps(summary, indent=2))
        return 0 if summary["availability"] >= args.min_availability else 1

    if args.cmd == "import-model":
        from tpuserve import savedmodel

        options = _parse_opt_args(parser, args.opt)
        savedmodel.convert_cli(args.saved_model, args.family, args.out, options,
                               quantize=args.quantize)
        return 0

    if args.cmd == "finetune-det":
        import dataclasses

        from tpuserve.config import ModelConfig
        from tpuserve.train_det import DetTrainConfig, finetune_detector

        opts = _parse_opt_args(parser, args.opt)
        settable = {f.name for f in dataclasses.fields(ModelConfig)} - {
            "name", "family", "weights", "options"}
        fields = {k: opts.pop(k) for k in list(opts) if k in settable}
        cfg = ModelConfig(name="efficientdet", family="efficientdet",
                          weights=args.weights, options=opts, **fields)
        loss = finetune_detector(cfg, args.out, steps=args.steps,
                                 batch_size=args.batch,
                                 tcfg=DetTrainConfig(lr=args.lr),
                                 dataset=args.data)
        print(json.dumps({"final_loss": loss, "checkpoint": args.out}))
        return 0

    if args.cmd == "lint":
        from tpuserve.analysis.cli import run_lint

        return run_lint(args)

    if args.cmd == "warmup":
        from tpuserve.config import default_config, load_config
        from tpuserve.parallel import init_distributed
        from tpuserve.server import ServerState

        cfg = load_config(args.config, args.overrides) if args.config else default_config()
        # Same ordering rule as serve(): on a pod, the cache entries are only
        # useful if they're compiled against the global topology.
        init_distributed(cfg.distributed)
        state = ServerState(cfg)
        state.build()
        print(json.dumps({n: rt.describe() for n, rt in state.runtimes.items()}, indent=2))
        return 0

    if args.cmd == "describe":
        import jax

        from tpuserve.parallel import make_mesh

        mesh = make_mesh()
        devs = jax.devices()
        print(json.dumps({
            "devices": [str(d) for d in devs],
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "jax_version": jax.__version__,
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
        }, indent=2))
        return 0

    return 1


if __name__ == "__main__":
    sys.exit(main())
