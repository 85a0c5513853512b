#!/usr/bin/env python
"""The mla family's two programs alone, on the chip: packed prefill launches
that fill every slot to a context of `--context` tokens and decode steps of
every lane over those contexts, on the cell's drawn weights, timed by the
host's clock around a dependent read, then traced, with a table by operation
of one launch and one step (each operation under the program's `mla_prefill`
/ `mla_decode` scope or outside it, by what it is; the kernels that walk a
prefill tile's key blocks, `tile_walk`, and a step's lanes' key blocks,
`lane_walk`, in rows of their own). The decode step and ONE attention of it
alone are timed at BOTH walks, the kernel's and XLA's (`decode_walks`), each
with the cache rows it walked over the rows attended.

    chiprun -- python scripts/bench_mla.py [--context 9216] [--chunk 2048]
    python scripts/bench_mla.py --rehearse --config benchmark/configs/rehearsal-mla-tiny.json

One JSON line a case on stdout and in `chiprun_out/bench_mla/`; every
operation's event of the last launch of each program in `ops.jsonl` there.
Off the TPU it walks the path (`--rehearse`) and prints no time.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import spec, ssm_window  # noqa: E402
from benchmark.trace_reduce import op_name, union_s  # noqa: E402
from tpuserve.config import ModelConfig  # noqa: E402
from tpuserve.genserve.model import PrefillPiece  # noqa: E402
from tpuserve.models import build  # noqa: E402

KINDS = (("tile_walk", "the tiles' walks of their key blocks (kernel tile_walk, in mla_prefill)"),
         ("lane_walk", "the lanes' walks of their key blocks (kernel lane_walk, in mla_decode)"),
         ("mla_prefill", "latent attention (scope mla_prefill)"),
         ("mla_decode", "latent attention (scope mla_decode)"),
         ("gmm", "grouped products of the routed experts"), ("sort", "sort and un-sort of the picks"),
         ("top_k", "top-k"), ("scatter", "scatters outside the scope"),
         ("gather", "gathers outside the scope"), ("take", "gathers outside the scope"),
         ("dot_general", "dense products outside the scope"))


def kind_of(instruction: str, scoped: str) -> str:
    """What an operation's event is counted as: its scope, else the first
    word of `KINDS` that its `op_name` or its own name holds."""
    text = f"{scoped} {instruction}"
    return next((label for word, label in KINDS if word in text), "everything else")


def by_operation(path: str, f) -> list[dict]:
    """Every operation's event of the LAST launch of each program into `f`,
    and per program a table {kind: ms} (the union of a kind's intervals, so a
    loop and its body count once)."""
    from jax.profiler import ProfileData

    scopes = ssm_window.scope_map(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        last = {}
        for ev in lines["XLA Modules"].events:
            last[ev.name.split("(")[0]] = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
        for mod, (lo, hi) in last.items():
            under, spans = scopes.get(mod, {}), {}
            for ev in lines["XLA Ops"].events:
                if not lo <= ev.start_ns < hi:
                    continue
                name = op_name(ev.name)
                kind = kind_of(name, under.get(name, ""))
                spans.setdefault(kind, []).append(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                f.write(json.dumps({"in": mod, "name": ev.name[:300], "op_name": under.get(name, ""),
                                    "kind": kind, "ns": int(ev.duration_ns)}) + "\n")
            # `everything else` holds the loops that contain the others: what is left of the launch
            table = {k: union_s(v) * 1e3 for k, v in spans.items() if k != "everything else"}
            table["everything else"] = (hi - lo) / 1e6 - union_s(
                [iv for k, v in spans.items() if k != "everything else" for iv in v]) * 1e3
            out.append({"case": f"by operation, last launch of {mod}", "launch_ms": (hi - lo) / 1e6,
                        "ms": {k: round(v, 3) for k, v in sorted(table.items(), key=lambda kv: -kv[1])}})
    return out


ALONE_REPS = 8   # attentions chained in one program when one is timed alone


def xla_walk(cls):
    """`cls` with a step's walk left in XLA where the kernel would take it
    (steered here, in the script: the program has no such option)."""
    class XlaWalk(cls):
        def _walk(self, form, T, pools, pps):
            return "xla" if form == "absorbed" else super()._walk(form, T, pools, pps)

    return XlaWalk


def decode_walks(models: dict, params, lp, state, iters: int, emit, on_tpu: bool):
    """A decode step and ONE attention of it alone (the first attention's
    weights `lp` and pools, drawn queries, every lane at the context `state`
    holds), at each of `models` {what: model}: the family's own (the kernel
    on the TPU) beside the walk in XLA. A row a model: the step's median, the
    attention's (`ALONE_REPS` chained in one program; the kernel's with its
    work list built inside; XLA's lane by lane) and the rows the step walked
    over the rows it attended, from the device's own sums. Every model steps from the
    SAME lanes (positions, counts and flags are put back; the pools keep what
    the steps wrote) -> (state, {what: jitted step})."""
    steps = {}
    lanes = {k: np.asarray(v) for k, v in state.items() if k not in ("ckv", "kr")}
    for what, m in models.items():
        state = dict(state, **{k: jnp.asarray(v) for k, v in lanes.items()})
        live = state["armed"] & ~state["done"]
        pos = jnp.clip(state["pos"], 0, m.max_ctx - 1)
        last = jnp.where(live, pos, 0)
        bt, pools = state["bt"], (state["ckv"][0], state["kr"][0])
        b = pos.shape[0]
        qn = jax.random.normal(jax.random.key(1), (b, m.heads, m.dn), m.dtype)
        qr = jax.random.normal(jax.random.key(2), (b, m.heads, m.dr), m.dtype)
        walk = m._step_walk(pools, bt, last)[0]
        if walk == "kernel":
            one = lambda lp, qn, qr, pools, bt, pos, last, m=m: m._walk_lanes(  # noqa: E731
                lp, qn, qr, pools, m._step_walk(pools, bt, last)[1])
        else:
            one = lambda lp, qn, qr, pools, bt, pos, last, m=m: jax.lax.map(  # noqa: E731
                lambda a: m._attend_tile(lp, *a[:2], pools, *a[2:], "absorbed"),
                (qn[:, None], qr[:, None], bt, pos[:, None], last))

        def alone(lp, qn, *rest, one=one):
            """ALONE_REPS attentions in one program, each waiting for the one
            before it: a single dispatch is longer than the attention."""
            o = one(lp, qn, *rest)
            for _ in range(ALONE_REPS - 1):
                o = one(lp, qn + (jnp.sum(o) * 0).astype(qn.dtype), *rest)
            return o

        alone, att = jax.jit(alone), []
        for _ in range(iters + 2):
            t0 = time.perf_counter()
            jax.block_until_ready(alone(lp, qn, qr, pools, bt, pos, last))
            att.append((time.perf_counter() - t0) / ALONE_REPS)
        fn = steps[what] = jax.jit(m.step, donate_argnums=(1,))
        times = []
        for _ in range(iters + 2):
            t0 = time.perf_counter()
            state, out = fn(params, state)
            np.asarray(out["n_new"])
            times.append(time.perf_counter() - t0)
        moved = np.asarray(state["acc"]).astype(np.int64)[1] - lanes["acc"].astype(np.int64)[1]
        row = {"case": f"decode step, {what}", "walk": walk, "lanes_live": int(jnp.sum(live)),
               "rows_walked_over_attended": float(moved[6]) / max(1.0, float(moved[5])),
               "lanes_by_walk": {"kernel": int(moved[10]), "xla": int(moved[11])}}
        if on_tpu:
            row.update(first_s=times[0], step_median_ms=statistics.median(times[2:]) * 1e3,
                       attention_alone_median_ms=statistics.median(att[2:]) * 1e3)
        emit(**row)
    return state, steps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "joyai-llm-flash-l5.json"))
    ap.add_argument("--context", type=int, default=9216, help="tokens every slot is filled to")
    ap.add_argument("--chunk", type=int, default=0, help="the launch's width (default: the cell's)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit("bench_mla: no TPU here; a time from another backend is no device number")
    out_dir = os.path.join(REPO, "chiprun_out", "bench_mla")
    os.makedirs(out_dir, exist_ok=True)
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    sz = spec.load_module("reference", "mla").sizes_from_config(cfg)
    arch_path = os.path.join(out_dir, "arch.json")
    with open(arch_path, "w", encoding="utf-8") as f:
        json.dump(sz["arch"], f)
    model = build(ModelConfig(
        name="m", family="mla", dtype=cfg["serve"]["model"]["dtype"], batch_buckets=[1],
        options={"config_file": arch_path, "draw_weights_seed": args.seed,
                 "max_prompt_tokens": sz["max_prompt"], "max_new_tokens": sz["max_new"]}))
    slots, pages, P = sz["slots"], sz["kv_pages"], sz["page_tokens"]
    chunk = args.chunk or sz["prefill_chunk"]
    plan = model.kv_plan(slots, P, pages)
    pps = plan.pages_per_slot
    k = model.kv_prefill_pieces(chunk, P)
    context = min(args.context, sz["max_prompt"]) // chunk * chunk or min(chunk, sz["max_prompt"])
    assert slots * -(-(context + sz["max_new"]) // P) < pages, "the pool holds every slot's context"
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init_params(None))
    print(f"weights drawn in {time.perf_counter() - t0:.1f} s; chunk {chunk} in {k} tiles of "
          f"{chunk // k} ({model._form(chunk // k)}), a step {model._form(1)}", flush=True)
    state = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), plan.state)
    prefill = jax.jit(lambda p, s, l: model.prefill_chunk(p, s, l, chunk=chunk),
                      donate_argnums=(1,))
    rng = np.random.default_rng(args.seed)
    need = -(-(context + sz["max_new"]) // P)
    items = []
    for slot in range(slots):
        ids = np.zeros((sz["max_prompt"],), np.int32)
        ids[:context] = rng.integers(0, sz["vocab"], context)
        items.append((ids, np.int32(context), np.int32(slot), np.int32(sz["max_new"]),
                      np.float32(0.0), np.int32(8)))

    def piece(slot: int, start: int) -> PrefillPiece:
        row = np.zeros((pps,), np.int32)
        row[:need] = 1 + slot * need + np.arange(need)
        return PrefillPiece(slot, items[slot], start, min(chunk, context - start), row)

    rows = []

    def emit(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    # every slot's prompt, a launch of one piece at a time: the n-th launch of a prompt reads
    # the n - 1 before it
    by_start: dict[int, list] = {}
    for slot in range(slots):
        for start in range(0, context, chunk):
            launch = model.pack_prefill([piece(slot, start)], chunk, k)
            t0 = time.perf_counter()
            state = prefill(params, state, launch)
            np.asarray(state["pos"])
            by_start.setdefault(start, []).append(time.perf_counter() - t0)
    if on_tpu:
        first = by_start[0][0]
        for start, times in sorted(by_start.items()):
            emit(case=f"prefill launch of {chunk} tokens at position {start}", launches=len(times),
                 median_ms=statistics.median(times[1:] if start == 0 else times) * 1e3,
                 **({"first_s": first} if start == 0 else {}))
    in_xla = xla_walk(type(model))(model.cfg)
    state, steps = decode_walks(
        {f"{slots} live lanes at context {context}, the family's own walk": model,
         f"{slots} live lanes at context {context}, the walk left in XLA": in_xla},
        params, params["layer0"], state, args.iters, emit, on_tpu)
    step = next(iter(steps.values()))
    assert int(np.sum(np.asarray(state["n_new"]) > 1)) == slots, "every lane decodes"
    if on_tpu:
        stats = jax.devices()[0].memory_stats() or {}
        emit(case="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             peak_bytes_reserved=stats.get("peak_bytes_reserved"))
    # the trace: three steps and two launches (a prompt's last launch again: it rewrites what it wrote)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    trace_dir = os.path.join(out_dir, "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for _ in range(3):
        state, out = step(params, state)
    np.asarray(out["n_new"])
    for slot in (0, 1):
        state = prefill(params, state, model.pack_prefill(
            [piece(slot, (context - 1) // chunk * chunk)], chunk, k))
    np.asarray(state["pos"])
    jax.profiler.stop_trace()
    with open(os.path.join(out_dir, "ops.jsonl"), "w", encoding="utf-8") as f:
        for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
            for row in by_operation(path, f):
                emit(**row)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with open(os.path.join(out_dir, "report.jsonl"), "w", encoding="utf-8") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    print(f"operation events written to {out_dir}/ops.jsonl", flush=True)


if __name__ == "__main__":
    main()
