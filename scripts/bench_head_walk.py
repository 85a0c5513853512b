#!/usr/bin/env python
"""The grouped decode walk over pages alone, on the chip: ONE global layer's
`ops/lane_attention.py` `head_walk` at a cell's lanes, heads and page size
over synthetic pools, timed ON THE DEVICE (the kernel's own line in a trace,
not the host's clock: a call of a millisecond hides under the dispatch), at
several sizes of its cell (`block_pages`), under the mix's spread of contexts
and under one fixed context, beside (1) a plain copy of the pages the walk had
to read, (2) the least time by `benchmark/flops/decoder_sink.py` `full_walk`,
and (3) the gather of the padded block table that every backend but the TPU
takes (`paged_lm._decode_gather`), at a few lanes, because at the cell's it
does not fit.

    chiprun -- python scripts/bench_head_walk.py
    python scripts/bench_head_walk.py --rehearse

And ONE window layer's read of every lane's full ring (ISSUE 50, `ring_cases`;
`--only ring`): the same kernel over the rings in place, beside the gather in
XLA and a plain pass over the rings.

This is where `decoder_sink.SinkDecoderServing.step_keys` comes from (PERF.md
section 6, PR 49). One JSON line a case on stdout and in
`chiprun_out/bench_head_walk/`. Off the TPU it walks a toy shape in the
interpreter (`--rehearse`) and prints no time.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import spec  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.models import build  # noqa: E402
from tpuserve.ops import lane_attention as la  # noqa: E402


def device_ms(fn, args: tuple, name: str, out_dir: str, iters: int) -> float | None:
    """Median device time of the operations whose name holds `name` in a
    trace of `iters` calls (all of them where `name` is None). Off the TPU:
    one call, and no time."""
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    if jax.default_backend() != "tpu":
        return None
    trace = os.path.join(out_dir, "trace")
    shutil.rmtree(trace, ignore_errors=True)
    with jax.profiler.trace(trace):
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
    found = glob.glob(os.path.join(trace, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    per_call = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules" and name is None:
                per_call = [e.duration_ns / 1e6 for e in line.events]
            elif line.name == "XLA Ops" and name is not None:
                per_call = [e.duration_ns / 1e6 for e in line.events if name in e.name]
    shutil.rmtree(trace, ignore_errors=True)
    return float(np.median(per_call)) if per_call else None


def ring_cases(model, sz: dict, flops, peaks, out_dir: str, iters: int, on_tpu: bool, rng) -> list:
    """ONE window layer's read of every lane's FULL ring (ISSUE 50): `head_walk`
    in place through the ring index with the sink as its operand, the query
    rows as they lie (8 a KV head: every row over each head's keys) and padded
    to 16 a KV head by the caller (the kernel's other path), beside the gather
    and softmax in XLA that every other backend takes, a plain pass over the
    rings, and the least time by `flops.ring_read`."""
    w = sz["by_kind"]["window"]
    lanes, W = sz["slots"], sz["win_tokens"]
    h, kv, dk, dv, dr = w["heads"], w["kv_heads"], w["dk"], w["dv"], 64
    if not on_tpu:
        lanes = 4
    bf = jnp.bfloat16
    rings = tuple(jnp.asarray(rng.standard_normal((lanes + 1, W, kv * width)), bf)
                  for width in (dk - dr, dr, dv))
    q = jnp.asarray(2.0 * rng.standard_normal((lanes, h, dk)), bf)
    sink = jnp.asarray(rng.uniform(8, 12, h), jnp.float32)
    ring = jnp.arange(1, lanes + 1, dtype=jnp.int32)
    pos = jnp.asarray(rng.integers(W, 3000, lanes), jnp.int32)
    ops, nbytes = flops.ring_read(dict(sz, kinds=["window"]), float(lanes), float(pos.sum()))
    base = {"spread": "full rings", "lanes": lanes, "least_ms": None if not peaks else 1e3 * max(
        ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])}
    lines = []

    def emit(case: str, ms) -> None:
        lines.append({**base, "case": case, "ms": ms})
        print(json.dumps(lines[-1]), flush=True)

    def walk(q, rings, ring, pos, sink, rows: int):
        g = h // kv
        if rows > g:   # a KV head's group padded with rows of zeros, dropped after
            q = jnp.pad(q.reshape(lanes, kv, g, dk), ((0, 0), (0, 0), (0, rows - g), (0, 0))) \
                .reshape(lanes, kv * rows, dk)
            sink = jnp.pad(sink.reshape(kv, g), ((0, 0), (0, rows - g))).reshape(-1)
        o = la.head_walk(q[..., dr:], model._pad_queries(q[..., :dr], kv, 2), *rings,
                         la.ring_work(ring, jnp.minimum(pos, W - 1)), scale=dk ** -0.5, kv=kv,
                         sink=sink, interpret=not on_tpu)
        return o.reshape(lanes, kv, rows, dv)[:, :, :g].reshape(lanes, h, dv)

    for rows in (h // kv, 16):
        fn = jax.jit(lambda *a, rows=rows: walk(*a, rows))
        emit(f"head_walk of the rings in place, {rows} query rows a KV head",
             device_ms(fn, (q, rings, ring, pos, sink), "head_walk", out_dir, iters))

    def xla(q, rings, ring, pos, sink):
        kn, kr, v = (jnp.take(x, ring, axis=0).reshape(lanes, W, kv, -1) for x in rings)
        return model._attend(q[:, None], jnp.concatenate([kr, kn], axis=-1), v,
                             jnp.ones((lanes, 1, W), bool), sink)[:, 0]

    if not on_tpu:   # the CPU's backend has no bfloat16 product of these shapes
        q, rings = q.astype(jnp.float32), tuple(x.astype(jnp.float32) for x in rings)
    emit("the rings gathered and attended in XLA",
         device_ms(jax.jit(xla), (q, rings, ring, pos, sink), None, out_dir, iters))
    if on_tpu:
        emit("a pass over the rings (read and written)",
             device_ms(jax.jit(lambda r: tuple(x + 1 for x in r)), (rings,), None, out_dir, iters))
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "mimo-v2.5-e16-l7.json"))
    ap.add_argument("--context", type=int, default=700, help="the fixed case's live positions")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", choices=("global", "ring"), help="one kind's cases alone")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_head_walk: no TPU here; a time from another backend is no device number")
    out_dir = os.path.join(REPO, "chiprun_out", "bench_head_walk")
    os.makedirs(out_dir, exist_ok=True)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    sz = spec.load_module("reference", cfg["family"]).sizes_from_config(cfg)
    flops = spec.load_module("flops", cfg["family"])
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)
    model = build(ModelConfig(   # for its gather and its queries' padding; no weight is drawn
        name="m", family=cfg["family"], dtype=cfg["serve"]["model"]["dtype"], batch_buckets=[1],
        options={"config_file": arch_path, "max_prompt_tokens": sz["max_prompt"],
                 "max_new_tokens": sz["max_new"]}))
    with open(os.path.join(REPO, "benchmark", "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)["devices"].get(jax.devices()[0].device_kind)
    g = sz["by_kind"]["global"]
    lanes, P, pps = sz["slots"], sz["page_tokens"], sz["pages_per_slot"]
    h, kv, dk, dv, dr = g["heads"], g["kv_heads"], g["dk"], g["dv"], 64
    if args.rehearse and not on_tpu:   # a toy walk in the interpreter
        lanes, P, pps = 4, 16, 6
    bf, rng = jnp.bfloat16, np.random.default_rng(0)
    one = dict(sz, kinds=["global"])          # full_walk of ONE global layer
    mix = spec.load_mix("reason-closed-384")["classes"][0]
    drawn = np.clip(np.exp(rng.normal(np.log(mix["prompt_tokens"]["median"]),
                                      mix["prompt_tokens"]["sigma"], lanes)), 32, 2048) \
        + rng.uniform(0, 1, lanes) * np.clip(np.exp(rng.normal(
            np.log(mix["max_new_tokens"]["median"]), mix["max_new_tokens"]["sigma"], lanes)),
            128, 1024)
    contexts = {"fixed": np.full(lanes, min(args.context, pps * P)),
                "mix": np.minimum(drawn.astype(np.int64), pps * P)}
    lines = []
    for spread, ctx in contexts.items() if args.only != "ring" else ():
        live = -(-ctx // P)
        n_pages = int(live.sum()) + 1
        kn = jnp.asarray(rng.standard_normal((kv, n_pages, P, dk - dr)), bf)
        kr = jnp.asarray(rng.standard_normal((kv // 2, n_pages, P, 2 * dr)), bf)
        vf = jnp.asarray(rng.standard_normal((kv, n_pages, P, dv)), bf)
        bt = np.zeros((lanes, pps), np.int32)
        at = 1
        for lane, n in enumerate(live):
            bt[lane, :n] = np.arange(at, at + n)
            at += n
        bt, last = jnp.asarray(bt), jnp.asarray(ctx - 1, jnp.int32)
        q = jnp.asarray(2.0 * rng.standard_normal((lanes, h, dk)), bf)
        ops, nbytes = flops.full_walk(one, float(lanes), float(ctx.sum()))
        base = {"spread": spread, "lanes": lanes, "mean_context": float(ctx.mean()),
                "least_ms": None if not peaks else 1e3 * max(
                    ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])}

        def emit(case: str, ms, **more) -> None:
            lines.append({**base, "case": case, **more, "ms": ms})
            print(json.dumps(lines[-1]), flush=True)

        for kb in (1, 2, 4, 8) if on_tpu else (2,):
            def walk(q, kn, kr, vf, bt, last, kb=kb):
                work = la.work_list(last, bt, P, kb)
                return la.head_walk(q[..., dr:], model._pad_queries(q[..., :dr], kv, 2), kn, kr, vf,
                                    work, scale=dk ** -0.5, interpret=not on_tpu)
            fn = jax.jit(walk)
            cells = int(la.work_list(last, bt, P, kb)["items"])
            ms = device_ms(fn, (q, kn, kr, vf, bt, last), "head_walk", out_dir, args.iters)
            emit(f"head_walk, cells of {kb} pages", ms, cells=cells,
                 rows_walked=cells * kb * P, rows_attended=int(ctx.sum()))
        if on_tpu:   # what a plain copy of the pages the walk had to read takes
            copy = jax.jit(lambda kn, kr, vf: (kn + 1, kr + 1, vf + 1))
            emit("a pass over the live pages (read and written)",
                 device_ms(copy, (kn, kr, vf), None, out_dir, args.iters))
        few = 8      # the gather: (KV, lanes, pps x P, width) of keys and of values
        gather = jax.jit(lambda q, kn, kr, vf, bt, pos: model._decode_gather(
            q, (kn, kr, vf), bt, pos, model._heads()))
        gargs = (q[:few], kn, kr, vf, bt[:few], last[:few])
        emit(f"the gather of the padded table, {few} lanes",
             device_ms(gather, gargs, None, out_dir, args.iters), lanes=few)
    if args.only != "global":
        lines += ring_cases(model, sz, flops, peaks, out_dir, args.iters, on_tpu, rng)
    with open(os.path.join(out_dir, "cases.jsonl"), "w", encoding="utf-8") as f:
        f.write("".join(json.dumps(line) + "\n" for line in lines))


if __name__ == "__main__":
    main()
