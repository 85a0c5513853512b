"""What the per-layer readers of a generating cell share: the window's counters of the generation engine and of the
expert layer, as means a launch, and the device trace's programs by name.
Everything returns None, and never raises, where the program has no such
counter or the trace no such program (the parent of the PR that added them)."""

from benchmark import prom

STEP_MODULE, PREFILL_MODULE = "jit_step", "jit_prefill_fn"
PREFIX = "tpuserve."
GEN_SPANS = ("gen_admit", "gen_prefill", "gen_step", "gen_fetch", "gen_retire")


def total(run: dict, family: str, **labels) -> float:
    d, model = run.get("metrics_delta") or {}, run.get("model_name")
    return sum(prom.select(d, family, model=model, **labels).values())


def module(run: dict, prefix: str) -> dict | None:
    """The trace's program whose name starts with `prefix` (the fingerprint
    follows it), with `launch_s` (median over whole launches) and `device_s`."""
    mods = (run.get("trace") or {}).get("modules") or {}
    found = [m for name, m in mods.items() if name.startswith(prefix + "(") or name == prefix]
    if not found:
        return None
    best = max(found, key=lambda m: m["device_s"])
    return {**best, "device_s": sum(m["device_s"] for m in found)}


def per_launch(run: dict, phase: str) -> dict | None:
    """Means a launch of the window's counters for `phase` ("decode": steps;
    "prefill": chunks): live tokens, the context they attend from, picks on
    held experts and held experts hit (both summed over the sparse layers)."""
    launches = total(run, "gen_iterations_total" if phase == "decode" else "gen_prefill_chunks_total")
    tokens = total(run, f"gen_{phase}_tokens_total")
    if launches <= 0 or tokens <= 0:
        return None
    return {"launches": launches, "tokens": tokens / launches,
            "context": total(run, "gen_context_tokens_total", phase=phase) / launches,
            "held_picks": total(run, "moe_tokens_routed_total", phase=phase, held="yes") / launches,
            "experts_hit": total(run, "moe_experts_hit_total", phase=phase) / launches}


def roofline_share(run: dict, phase: str, launch_s: float | None) -> float | None:
    """Least time of the phase's mean launch (flops/decoder.py over the
    device's peaks) over the time a launch took in the trace, in percent."""
    peaks, mean, flops = run.get("peaks"), per_launch(run, phase), run.get("flops")
    fn = getattr(flops, "decode_step" if phase == "decode" else "prefill_chunk", None)
    if not peaks or not mean or not launch_s or fn is None:
        return None
    ops, nbytes = fn(run["sizes"], mean["tokens"], mean["context"], mean["held_picks"],
                     mean["experts_hit"])
    t_ops, t_bytes = ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"gen {phase} roofline: a mean launch of {mean['tokens']:.1f} live tokens at mean context "
        f"{mean['context'] / mean['tokens']:.0f}, {mean['experts_hit']:.1f} expert-layers hit, bound by "
        f"{'compute' if t_ops >= t_bytes else 'memory'} (ops {ops:.4g} -> {t_ops * 1e3:.3f} ms, "
        f"bytes {nbytes:.4g} -> {t_bytes * 1e3:.3f} ms) against {launch_s * 1e3:.3f} ms a launch")
    return 100.0 * max(t_ops, t_bytes) / launch_s
