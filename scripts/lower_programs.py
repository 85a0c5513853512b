#!/usr/bin/env python3
"""The lowered text of the paged families' two programs, for comparing two
trees (a refactoring of trace-time Python either lowers to the same program
text or it does not): ``prefill_chunk`` and ``step`` of each family, jitted as
the engine jits them (``jit_prefill_fn``, ``jit_step``; the state donated off
the CPU), at the geometry the engine would build.

    python scripts/lower_programs.py --root TREE --out DIR --toys
    python scripts/lower_programs.py --root TREE --out DIR [--v5e] --cells [NAME ...]

``--toys``: each family's toy model, the ``make_model`` of its test file in
TREE, at that file's SLOTS / PAGE / CHUNK. ``--cells``: the generating cells
of TREE's ``BENCHMARK.json`` (or the ones named), each built from the serve
file ``benchmark/run.py`` would write for it. TREE is a checkout or a ``git
archive`` of one; run once a tree, then compare the ``.sha256`` files (or
``diff`` the ``.txt.gz``). Writes, a program: ``<name>.<program>.txt.gz`` (the
text as ``as_text()`` gives it, which has no source locations of its own; a
Pallas kernel's serialized body does carry the files and lines of the Python
that called it, the tree's path among them, so each body is printed in its
place as its assembly WITHOUT locations) and
``<name>.<program>.scopes.txt.gz`` (every operation's name stack, in order,
from ``as_text(debug_info=True)``: ``jit(step)/mla_decode/mul``, the path of
``jax.named_scope``s that the trace's readers find programs' parts by; file
names, lines and the Python functions on the way are no part of it), and one
line each in ``sha256.txt``. ``--v5e`` (here, where there is no chip): the
programs are lowered for a DESCRIBED v5e and the families' TPU branches are
steered by the backend's name: a rehearsal of the chip's comparison, which
shows the kernels' calls; the comparison that counts is made on the chip.
"""

from __future__ import annotations

import argparse
import base64
import gzip
import hashlib
import importlib
import importlib.util
import os
import re
import sys
import tempfile
from types import SimpleNamespace

FAMILIES = ("decoder", "hybrid", "hybrid_ffn", "mla", "mla_sc", "mla_hc", "decoder_sink",
            "hybrid_delta", "eva", "hybrid_conv", "mla_sel", "hybrid_ffn_moe", "hybrid_blk")


def kernels_without_places(text: str) -> tuple[str, int]:
    """``text`` with every kernel's serialized body (``tpu_custom_call``'s
    ``custom_call_config.body``: MLIR bytecode in base64) replaced by the
    body's assembly printed without locations -> (the text, bodies found)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the body is in its versioned dialect

    def printed(found):
        with ctx:
            module = ir.Module.parse(base64.b64decode(found.group(2)))
            return found.group(1) + "\n" + module.operation.get_asm(enable_debug_info=False)

    return re.subn(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)', printed, text)


def name_stacks(debug_text: str) -> str:
    """The name stack of every operation of ``as_text(debug_info=True)``, a
    line each, in the text's order ("" where an operation has none)."""
    named = dict(re.findall(r'^(#loc\d+) = loc\("(jit\([^"]*)"', debug_text, re.M))
    return "\n".join(named.get(at, "") for at in
                     re.findall(r"loc\((#loc\d+)\)\s*$", debug_text, re.M)) + "\n"


def lower(model, slots: int, pages: int, page_tokens: int, prefill_chunk: int,
          sharding=None) -> dict:
    """{program: Lowered} at the engine's geometry (``GenEngine.__init__`` and
    ``compile``: the block table's width, the pages, the launch a lone canary
    packs). ``sharding``: where every argument lies (a described device)."""
    import jax
    import numpy as np

    from tpuserve.genserve.model import PrefillPiece

    plan = model.kv_plan(slots, page_tokens, pages)
    chunk = int(model.kv_prefill_chunk(prefill_chunk))
    k = int(model.kv_prefill_pieces(chunk, page_tokens))
    state = plan.state
    row = np.arange(1, plan.pages_per_slot + 1, dtype=np.int32)
    cache = {"pages": row, "ring": np.int32(1)} if plan.ring_tokens else row
    item = model.canary_item()
    launch = model.pack_prefill(
        [PrefillPiece(0, item, 0, min(chunk, model.prompt_tokens(item)), cache)], chunk, k)
    launch = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), launch)
    params = jax.eval_shape(lambda: model.draw_params(0))
    if sharding is not None:
        params, state, launch = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            (params, state, launch))
    donate = () if jax.default_backend() == "cpu" else (1,)

    def prefill_fn(params, state, launch):
        return model.prefill_chunk(params, state, launch, chunk=chunk)

    return {"prefill": jax.jit(prefill_fn, donate_argnums=donate).lower(params, state, launch),
            "step": jax.jit(model.step, donate_argnums=donate).lower(params, state)}


def toys(tmp: str):
    for family in FAMILIES:
        if importlib.util.find_spec(f"tests.test_{family}") is None:
            continue   # a tree from before the family: compared over what both have
        t = importlib.import_module(f"tests.test_{family}")
        yield family, t.make_model(tmp), (t.SLOTS, 0, t.PAGE, t.CHUNK)


def cells(tmp: str, names: list[str]):
    from benchmark import run as bench_run
    from benchmark import spec
    from tpuserve.config import load_config
    from tpuserve.models import build

    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cfg = spec.load_config(bench, w["config"])
        if not cfg["serve"].get("tables", {}).get("genserve", {}).get("kv_paging") \
                or (names and w["name"] not in names):
            continue
        cell = bench_run.load_cell(SimpleNamespace(workload=w["name"], config=None, traffic=None))
        work = os.path.join(tmp, w["name"])
        os.makedirs(work)
        _weights, options, _ref = cell.family.prepare(7, cell.sizes, cell.cfg, work)
        _vocab, extra = cell.traffic.prepare(work, cell.cfg)
        toml = os.path.join(work, "serve.toml")
        bench_run.write_serve_toml(toml, cell.cfg, 0, None, {**options, **extra})
        served = load_config(toml)
        g = served.genserve
        yield w["name"], build(served.models[0]), \
            (g.slots, g.kv_pages, g.kv_page_tokens, g.prefill_chunk)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True, help="the tree whose programs are lowered")
    ap.add_argument("--out", required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--toys", action="store_true")
    what.add_argument("--cells", nargs="*", metavar="NAME")
    ap.add_argument("--v5e", action="store_true", help="lower for a described v5e (no chip)")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    os.chdir(args.root)
    sys.path.insert(0, os.getcwd())

    import jax

    sharding = None
    if args.v5e:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        jax.default_backend = lambda: "tpu"
    lines = [f"# backend {jax.default_backend()}{' (described)' if args.v5e else ''}, "
             f"jax {jax.__version__}"]
    with tempfile.TemporaryDirectory() as tmp:
        for name, model, geometry in toys(tmp) if args.toys else cells(tmp, args.cells):
            for program, lowered in lower(model, *geometry, sharding).items():
                text, kernels = kernels_without_places(lowered.as_text())
                texts = {"txt": text, "scopes.txt": name_stacks(lowered.as_text(debug_info=True))}
                for kind, text in texts.items():
                    with gzip.open(os.path.join(out, f"{name}.{program}.{kind}.gz"), "wt",
                                   encoding="utf-8") as f:
                        f.write(text)
                    lines.append(f"{hashlib.sha256(text.encode()).hexdigest()}  "
                                 f"{name}.{program}.{kind}  {len(text)} bytes"
                                 + (f"  {kernels} kernel bodies" if kind == "txt" else ""))
                print(lines[-2], flush=True)
    with open(os.path.join(out, "sha256.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
