#!/usr/bin/env python
"""Attention alone, on the chip: the XLA pair that `_masked_attention` lowers
to against the kernels that could replace it, at the serving buckets' shapes.

This is the go / no-go measurement behind `tpuserve.ops.fused_attention`'s
shape rule (`attention_path`): one jitted call a candidate, inputs in the
`(B, H*D, S)` layout the projections write on the TPU, every reshape or
transpose a candidate needs inside its jit, median of `--iters` timed calls after two
warm-up calls, and the largest difference from a float64 reference.

    chiprun -- python scripts/bench_flash.py            # every shape
    python scripts/bench_flash.py --shape 256,512,16,64

One JSON line a (shape, candidate) on stdout and in
`chiprun_out/bench_flash.jsonl`. It refuses to run off the TPU: a time from
the interpreter is no device number.
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

from tpuserve.models.bert import _masked_attention  # noqa: E402
from tpuserve.ops.fused_attention import fused_attention  # noqa: E402

SHAPES = [(256, 512, 16, 64), (256, 512, 12, 64), (32, 512, 16, 64),
          (256, 256, 16, 64), (256, 128, 16, 64), (256, 128, 12, 64),
          (32, 128, 16, 64)]


def candidates(shape: tuple, only: "list[str] | None") -> dict:
    """name -> f(q3, k3, v3, bias) -> o3, all `(B, H*D, S)`: the layout XLA
    gives the projections' outputs on the TPU (sequence-minor)."""
    b, s, h, d = shape
    four = lambda x: x.reshape(b, h, d, s).transpose(0, 3, 1, 2)  # noqa: E731
    three = lambda o: o.transpose(0, 2, 3, 1).reshape(b, h * d, s)  # noqa: E731
    out: dict = {}

    def dense(q, k, v, bias):
        return three(_masked_attention(four(q), four(k), four(v),
                                       bias[:, None, None, :]))
    out["dense"] = dense

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, SegmentIds)
    from jax.experimental.pallas.ops.tpu.flash_attention import \
        flash_attention as jax_flash

    for bb in (1, 4):
        def jaxfa(q, k, v, bias, bb=bb):
            live = (bias == 0).astype(jnp.int32)
            t = lambda x: x.reshape(b, h, d, s).transpose(0, 1, 3, 2)  # noqa: E731
            o = jax_flash(t(q), t(k), t(v),
                          segment_ids=SegmentIds(q=jnp.ones_like(live), kv=live),
                          sm_scale=d ** -0.5,
                          block_sizes=BlockSizes(block_q=s, block_k_major=s,
                                                 block_k=s, block_b=bb))
            return o.transpose(0, 1, 3, 2).reshape(b, h * d, s)
        out[f"jax_flash/b{bb}"] = jaxfa

    for bh in sorted({2, 4, h // 2, h}):
        def fused(q, k, v, bias, bh=bh):
            return three(fused_attention(four(q), four(k), four(v), bias == 0,
                                         block_h=bh))
        out[f"fused/h{bh}"] = fused
    if only:
        out = {n: f for n, f in out.items()
               if any(n.startswith(o) for o in only)}
    return out


def timed_ms(f, args: tuple, iters: int) -> dict:
    """Median and least of `iters` calls, after two that warm up."""
    for _ in range(2):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return {"ms": round(statistics.median(times), 3),
            "ms_min": round(min(times), 3)}


def forward_times(which: str, iters: int) -> list[dict]:
    """The serving forward alone at the cells' four buckets, each attention
    path: the program BERT's runtime compiles, random weights, no server."""
    from tpuserve.config import ModelConfig
    from tpuserve.models import bert

    dims = {"large": dict(layers=24, d_model=1024, heads=16, d_ff=4096),
            "base": dict(layers=12, d_model=768, heads=12, d_ff=3072)}[which]
    cfg = ModelConfig(name=which, family="bert", dtype="bfloat16",
                      parallelism="single", batch_buckets=[32, 256],
                      seq_buckets=[128, 256, 512], num_classes=5,
                      options=dict(vocab_size=30522, **dims))
    model = bert.create(cfg)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        jax.jit(model.init_params)(jax.random.PRNGKey(0)))
    rows = []
    rng = np.random.default_rng(0)
    for bucket in model.buckets():
        b, s = bucket
        lens = np.clip(rng.lognormal(np.log(300), 0.6, size=b), 16, s).astype(int)
        mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None], jnp.int32)
        ids = jnp.asarray(rng.integers(0, 30000, size=(b, s)), jnp.int32)
        for path in ("dense", "fused"):
            module = model.module.clone(attention_impl=path)
            f = jax.jit(lambda p, i, m, module=module: module.apply(p, i, m))
            rows.append({"forward": which, "bucket": list(bucket), "attention": path,
                         **timed_ms(f, (params, ids, mask), iters),
                         "logits": np.asarray(f(params, ids, mask))[:2].tolist()})
    return rows


def reference(q, k, v, live, hd: tuple) -> np.ndarray:
    """Plain attention in float64, `(B, H*D, S)` in and out."""
    b, f, s = q.shape
    h, d = hd
    out = np.empty_like(q)
    for i in range(b):
        qi, ki, vi = (x[i].reshape(h, d, s) for x in (q, k, v))
        sc = np.einsum("hdq,hdk->hqk", qi, ki) * d ** -0.5
        sc = np.where(live[i][None, None, :], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hqk,hdk->hdq", p, vi).reshape(f, s)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", action="append",
                    help="B,S,H,D; may repeat (default: the serving buckets)")
    ap.add_argument("--only", action="append",
                    help="candidate name prefix; may repeat")
    ap.add_argument("--forward", action="append", choices=["base", "large"],
                    help="time the whole forward of this size instead")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_flash: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    shapes = ([tuple(int(x) for x in s.split(",")) for s in args.shape]
              if args.shape else SHAPES)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(REPO, "chiprun_out", "bench_flash.jsonl"), "a")

    def emit(row: dict) -> None:
        line = json.dumps({**row, "device": dev.device_kind})
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for which in args.forward or []:
        for row in forward_times(which, min(args.iters, 5)):
            emit(row)
    if args.forward and not args.shape:
        return 0
    rng = np.random.default_rng(args.seed)
    for shape in shapes:
        b, s, h, d = shape
        q, k, v = (jnp.asarray(rng.normal(size=(b, h * d, s)), jnp.bfloat16)
                   for _ in range(3))
        # The cells' lengths: lognormal, median 300, clipped to the bucket.
        lens = np.clip(rng.lognormal(np.log(300), 0.6, size=b), 1, s).astype(int)
        lens[0] = 1
        live = np.arange(s)[None, :] < lens[:, None]
        bias = jnp.asarray(np.where(live, 0.0, -1e9), jnp.float32)
        # float64 on the host, one row at a time: on the TPU a "float32"
        # product rounds its inputs to bfloat16 unless told otherwise.
        n_ref = min(b, 16)                  # rows compared: the host is slow
        want = reference(*(np.asarray(x[:n_ref], np.float64) for x in (q, k, v)),
                         live[:n_ref], (h, d))
        for name, fn in candidates(shape, args.only).items():
            row = {"shape": list(shape), "candidate": name}
            try:
                f = jax.jit(fn)
                row.update(timed_ms(f, (q, k, v, bias), args.iters))
                # Only live queries answer for anything downstream.
                err = np.abs(np.asarray(f(q, k, v, bias)[:n_ref], np.float64)
                             - want).transpose(0, 2, 1)[live[:n_ref]]
                row.update(max_err=float(err.max()),
                           rms_err=float(np.sqrt((err ** 2).mean())))
            except Exception as e:  # noqa: BLE001 - a refused candidate is a result
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
