"""Fresh-subprocess device probes for scripts/bench_configs.py.

A chip belongs to one process at a time, so every probe here is a child that
opens the device, measures, prints one JSON line and exits BEFORE the caller
builds its own server. A caller that already holds the chip must not start
one.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap


def probe_device_count(timeout: float = 300.0, cwd: str | None = None) -> int:
    """Visible accelerator count, measured in a fresh subprocess.

    The bench needs the chip count BEFORE it shapes load (connection count,
    offered rate scale with it — a v5e-8 driven with a single-chip load
    profile is demand-starved and under-reports by design), but touching
    ``jax.devices()`` in the calling process would take the accelerator
    before the chip probes run in their own subprocesses. Raises when the
    child cannot report: a guessed count of 1 would shape the whole run
    around a device nobody looked at."""
    src = ("import json, jax; "
           "print(json.dumps({'n': len(jax.devices())}))")
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, timeout=timeout, cwd=cwd)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device-count probe exited {proc.returncode}: "
            f"{proc.stderr.strip()[-300:]}")
    return int(json.loads(proc.stdout.strip().splitlines()[-1])["n"])


# Device-resident serving-forward rate: a dependency-chained fori_loop of N
# full forwards (wire inputs -> on-device preproc -> model -> on-device
# postproc), inputs already on device, one scalar read at the end, so no
# per-batch dispatch or readback appears in the window. Read by
# scripts/bench_configs.py (the per-family MFU table, VERDICT r4 missing 1).
#
# Inputs come from the family's own input_signature (token ids for BERT,
# YUV/RGB wire planes for vision, prompt ids + seeds for SD) — the r4 probe
# hard-coded an image tensor and crashed for any non-vision family.
# FLOPs come from XLA's own HloCostAnalysis on the compiled forward; for
# sd15 the denoise fori_loop body is counted once by XLA (verified on this
# jax), so the probe adds the remaining (steps - 1) UNet calls explicitly.
CHIP_PROBE_SRC = textwrap.dedent("""
    import time, json, sys, numpy as np, jax, jax.numpy as jnp
    sys.path.insert(0, %(repo)r)
    from tpuserve.config import ModelConfig
    from tpuserve.models import build
    from tpuserve.runtime import configure_backend
    # The server's own start-up rules: its persistent XLA cache (per-bucket
    # roofline probes then cost one compile per cache, not one per bench
    # run) and no CPU backend nobody asked for.
    configure_backend()
    mcfg = dict(%(mcfg)r)
    bucket = tuple(%(bucket)r)
    N = %(iters)d
    cfg = ModelConfig(**{"name": "m", "dtype": "bfloat16",
                         "batch_buckets": [bucket[0]],
                         "parallelism": "single", **mcfg})
    m = build(cfg)
    if cfg.quantize:
        # Quantized probes go through the runtime's forward (quantize_tree
        # + the mode's dequant layer) — exactly what serving compiles.
        from tpuserve.runtime import ModelRuntime
        rt = ModelRuntime(m)
        rt.load_and_shard_params()
        params = rt.params_per_mesh[0]
        fwd = rt._forward_fn()
    else:
        params = m.init_params(jax.random.key(0))
        fwd = m.forward

    rng = np.random.default_rng(0)
    def rand_for(l):
        dt = np.dtype(l.dtype)
        if np.issubdtype(dt, np.unsignedinteger):   # image wire planes
            return rng.integers(0, 255, l.shape, dt)
        if np.issubdtype(dt, np.integer):           # token ids / masks / seeds
            return np.ones(l.shape, dt)             # valid for any vocab/mask
        return rng.standard_normal(l.shape).astype(dt)

    x = jax.tree_util.tree_map(rand_for, m.input_signature(bucket))

    @jax.jit
    def many(params, x):
        def body(i, carry):
            x, acc = carry
            out = fwd(params, x)
            s = jax.tree_util.tree_leaves(out)[0].reshape(-1)[0]
            s = s.astype(jnp.float32)
            leaves, treedef = jax.tree_util.tree_flatten(x)
            leaves[0] = leaves[0] + (s * 0).astype(leaves[0].dtype)  # dep chain
            return (jax.tree_util.tree_unflatten(treedef, leaves), acc + s)
        _, acc = jax.lax.fori_loop(0, N, body, (x, jnp.float32(0)))
        return acc

    def flops_from(compiled):
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            return float(ca.get("flops", 0.0)) if ca else 0.0
        except Exception:
            return 0.0

    x = jax.device_put(x)
    many_c = many.lower(params, x).compile()  # the ONE compile
    # HloCostAnalysis visits a while body once (verified on this jax), so
    # the N-iteration loop's count ~= ONE forward's flops; no second
    # standalone compile of the forward is needed (for sd15 that compile
    # is the whole 20-step denoise — minutes of wall time saved).
    flops = flops_from(many_c)
    if cfg.family == "sd15" and flops:
        if cfg.quantize:
            # m.unet.apply cannot consume quantized {"q8","q8_scale"}
            # leaves; report no FLOPs rather than a silently ~steps-x
            # understated MFU.
            flops = 0.0
        else:
            b2 = 2 * bucket[0]  # CFG runs cond + uncond lanes per step
            lat2 = jnp.zeros((b2, m.latent, m.latent, 4), jnp.float32)
            t2 = jnp.zeros((b2,), jnp.int32)
            ctx2 = jnp.zeros((b2, 77, m.text_encoder.d_model), m.dtype)
            step_c = (jax.jit(m.unet.apply)
                      .lower(params["unet"], lat2, t2, ctx2).compile())
            flops += (m.steps - 1) * flops_from(step_c)

    float(many_c(params, x))  # warm (H2D + first dispatch)
    t0 = time.perf_counter()
    float(many_c(params, x))
    dur = time.perf_counter() - t0
    batch = bucket[0]
    tflops_s = flops * N / dur / 1e12 if flops else None
    print(json.dumps({
        "img_s": round(batch * N / dur, 1),
        "ms_per_batch": round(dur / N * 1e3, 3),
        "batch": batch, "bucket": list(bucket),
        "gflops_per_item": round(flops / batch / 1e9, 2) if flops else None,
        "achieved_tflops_s": round(tflops_s, 2) if tflops_s else None,
        "device": jax.devices()[0].device_kind,
    }))
""")

def chained_rate_ms(f, inputs, iters: int) -> float:
    """ms per call of ``f(*inputs)`` via a dependency-chained fori loop —
    the in-process twin of CHIP_PROBE_SRC's timing core (that template must
    stay self-contained for its fresh-subprocess discipline; any timing-
    method fix must land in BOTH — this module's one-source-of-truth rule).
    Used by scripts/bench_sd_profile.py for component-level splits where
    one process times several functions against shared params."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(inputs):
        def body(i, carry):
            inp, acc = carry
            out = f(*inp)
            s = jax.tree_util.tree_leaves(out)[0].reshape(-1)[0]
            s = s.astype(jnp.float32)
            leaves, td = jax.tree_util.tree_flatten(inp)
            leaves[-1] = leaves[-1] + (s * 0).astype(leaves[-1].dtype)
            return (jax.tree_util.tree_unflatten(td, leaves), acc + s)

        _, acc = jax.lax.fori_loop(0, iters, body, (inputs, jnp.float32(0)))
        return acc

    import time as _time

    c = many.lower(inputs).compile()
    float(c(inputs))  # warm
    t0 = _time.perf_counter()
    float(c(inputs))
    return (_time.perf_counter() - t0) / iters * 1e3


# Per-family probe presets: serving-shaped bucket + model options. `family`
# maps a preset name to the registry family when they differ (bert-moe).
CHIP_PROBE_FAMILIES: dict[str, dict] = {
    "resnet50": dict(mcfg={"family": "resnet50"}, bucket=(256,), iters=32),
    "mobilenetv3": dict(mcfg={"family": "mobilenetv3"}, bucket=(256,), iters=32),
    "bert": dict(mcfg={"family": "bert", "seq_buckets": [128]},
                 bucket=(32, 128), iters=64),
    "bert-moe": dict(mcfg={"family": "bert", "seq_buckets": [128],
                           "options": {"moe_experts": 8}},
                     bucket=(32, 128), iters=64),
    "efficientdet": dict(mcfg={"family": "efficientdet", "image_size": 512,
                               "wire_size": 512},
                         bucket=(8,), iters=16),
    "sd15": dict(mcfg={"family": "sd15", "image_size": 512,
                       "options": {"steps": 20}},
                 bucket=(1,), iters=2),
}

# v5e (TPU v5 lite) bf16 peak per chip; the MFU denominator for the chip
# table in BASELINE.md. Other device kinds report achieved TF/s with no MFU.
PEAK_TFLOPS_S = {"TPU v5 lite": 197.0, "TPU v5e": 197.0}


def measure_chip_img_s(batch: int | None = None, family: str = "resnet50",
                       iters: int | None = None, timeout: float = 1800.0,
                       repo: str | None = None,
                       bucket: tuple | None = None,
                       mcfg_extra: dict | None = None) -> dict:
    """Device-resident serving-forward rate + FLOP count, fresh subprocess.

    `family` must be a CHIP_PROBE_FAMILIES preset (the r4 foot-gun of
    accepting any family then crashing on image-only inputs is now a clear
    error up front). `batch`/`bucket`/`iters` override the preset;
    `mcfg_extra` shallow-merges over the preset's ModelConfig kwargs (e.g.
    {"seq_buckets": [512]} for a long-bucket sweep).

    Returns {"img_s", "ms_per_batch", "batch", "bucket", "gflops_per_item",
    "achieved_tflops_s", "mfu_pct"?, "device"} or {"error": str}.
    """
    import os

    if family not in CHIP_PROBE_FAMILIES:
        return {"error": f"no chip-probe preset for family {family!r}; "
                         f"known: {sorted(CHIP_PROBE_FAMILIES)}"}
    preset = CHIP_PROBE_FAMILIES[family]
    bkt = tuple(bucket) if bucket else preset["bucket"]
    if batch is not None:
        bkt = (batch,) + bkt[1:]
    mcfg = {**preset["mcfg"], **(mcfg_extra or {})}
    repo = repo or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    src = CHIP_PROBE_SRC % {"repo": repo, "mcfg": mcfg,
                            "bucket": bkt,
                            "iters": iters or preset["iters"]}
    try:
        proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                              text=True, timeout=timeout, cwd=repo)
    except subprocess.TimeoutExpired:
        return {"error": f"chip probe timed out after {timeout}s"}
    if proc.returncode != 0:
        return {"error": proc.stderr.strip()[-300:]}
    try:
        res = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001
        return {"error": f"unparseable probe output: {e}"}
    peak = PEAK_TFLOPS_S.get(res.get("device", ""))
    if peak and res.get("achieved_tflops_s"):
        res["mfu_pct"] = round(100.0 * res["achieved_tflops_s"] / peak, 1)
    return res
