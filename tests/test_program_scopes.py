"""Every heavy operation of a launch and of a step carries a scope (ISSUE 66).

For each of the thirteen paged families, the toy model's two programs are lowered
as `scripts/lower_programs.py --toys` lowers them and the name stacks of
`as_text(debug_info=True)` (`jit(step)/mla_decode/proj/dot_general`) are held to
three rules: (i) no operation of a heavy kind stands outside every
`jax.named_scope` of the program; (ii) each of the eight names of
`paged_lm`'s vocabulary, and each older name the family's program had before
them, is in the program it belongs to; (iii) none of the eight holds more than
half of a program's operations (none wraps a layer or a program: a catch-all
would read 0% unnamed and say nothing). A scope is told from the tracer's own
words by `benchmark/launch_scopes.py`'s rule, the one the trace's reader goes by.
A family is lowered once for its cases.
"""

import importlib
import importlib.util
import os
import re

import pytest

from benchmark import launch_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAMS = ("prefill", "step")
NEW = ("embed", "norm", "proj", "ffn_dense", "cache_write", "head", "plan", "emit")
MOE = ("moe_dispatch", "moe_experts", "moe_route")
# The scopes each family's programs had at PR 65 (`--toys` on that tree): (both
# programs', the launch's alone, the step's alone).
OLD = {
    "decoder": (MOE, (), ()),
    "decoder_sink": (MOE + ("attn_full_walk", "attn_ring"), ("attn_prefill",), ("attn_decode",)),
    "eva": (("eva_summarise",), ("eva_prefill",), ("eva_decode",)),
    "hybrid": (MOE, ("ssm_scan",), ("ssm_update", "attn_decode")),
    "hybrid_conv": (MOE, ("ssm_scan",), ("ssm_update", "attn_decode")),
    "hybrid_delta": (MOE, ("ssm_scan",), ("ssm_update", "attn_decode", "delta_update")),
    "hybrid_ffn": ((), ("ssm_scan",), ("ssm_update", "attn_decode")),
    "hybrid_ffn_moe": (MOE + ("moe_layer", "moe_shared"), ("ssm_scan",),
                       ("ssm_update", "attn_decode")),
    # since PR 68, the family's own from the start: the pooled keys, the picks, the walk
    # (a launch's picks and walk lie in a branch inside the tiles' loop, which the
    # lowered text's name stacks do not reach: `mla_sel`'s `sel_*` likewise)
    "hybrid_blk": (("blk_pool",), ("ssm_scan", "attn_prefill"),
                   ("ssm_update", "attn_decode", "blk_select", "blk_attend")),
    "mla": (MOE, ("mla_prefill",), ("mla_decode",)),
    "mla_hc": (MOE + ("hc_mix",), ("mla_prefill",), ("mla_decode",)),
    "mla_sc": (MOE + ("moe_layer", "moe_zero"), ("mla_prefill",), ("mla_decode",)),
    "mla_sel": (MOE, ("mla_prefill",), ("mla_decode",)),
}
# What is heavy: a product, a gather or a scatter, a sort, a reduction, a scan of
# any kind, a loop or a branch, a kernel's call; by the primitive, or by the
# jitted `jax.numpy` function that wraps it (`jit(_take)`, `jit(cumsum)`).
HEAVY = re.compile(r"^(dot_general|ragged_dot_general|gather|_?take|take_along_axis|scatter.*|"
                   r"dynamic_update_slice|sort|argsort|searchsorted|top_k|reduce.*|argmax|argmin|"
                   r"cum.*|conv.*|while|cond|scan|pallas_call|.*custom_call)$")


def _lower_programs():
    spec = importlib.util.spec_from_file_location(
        "lower_programs_for_scopes", os.path.join(ROOT, "scripts", "lower_programs.py"))
    lp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lp)
    return lp


LP = _lower_programs()
_LOWERED: dict = {}


def name_stacks(family: str, program: str, tmp_factory) -> list[str]:
    """The name stack of every operation of the family's toy `program` that has
    one, the family lowered once for both programs."""
    if family not in _LOWERED:
        t = importlib.import_module(f"tests.test_{family}")
        model = t.make_model(str(tmp_factory.mktemp(family)))
        _LOWERED[family] = {
            prog: [s for s in LP.name_stacks(low.as_text(debug_info=True)).split("\n") if s]
            for prog, low in LP.lower(model, t.SLOTS, 0, t.PAGE, t.CHUNK).items()}
    return _LOWERED[family][program]


def test_the_table_of_older_names_covers_the_families_the_script_lowers():
    assert set(OLD) == set(LP.FAMILIES) and len(LP.FAMILIES) == 13
    assert not [(n, o) for n in NEW for both, launch, step in OLD.values()
                for o in both + launch + step if n in o or o in n]


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", LP.FAMILIES)
def test_every_heavy_operation_carries_a_scope(family, program, tmp_path_factory):
    stacks = name_stacks(family, program, tmp_path_factory)
    known = launch_scopes.scopes_of(stacks)
    chains = [launch_scopes.chain(s, known) for s in stacks]

    # (i) no heavy operation outside every scope
    def primitive(stack: str) -> str:
        last = stack.rsplit("/", 1)[-1]
        inner = re.fullmatch(r"(?:jit|vmap)\((.*)\)", last)
        return inner.group(1) if inner else last

    bare = sorted({s for s, c in zip(stacks, chains) if not c and HEAVY.match(primitive(s))})
    assert not bare, f"{family}.{program}: heavy operations under no scope: {bare}"
    assert sum(1 for s in stacks if HEAVY.match(primitive(s))) > 20   # the rule saw them

    # (ii) the eight names and the family's older ones, each in its program
    both, launch, step = OLD[family]
    want = set(NEW) | set(both) | set(launch if program == "prefill" else step)
    assert not want - known, f"{family}.{program}: no {sorted(want - known)} in {sorted(known)}"

    # (iii) none of the eight wraps a layer or a program
    for name in NEW:
        held = sum(1 for c in chains if name in c)
        assert 0 < held <= len(stacks) / 2, (family, program, name, held, len(stacks))
    # and what no scope names at all is a small part, by count
    assert sum(1 for c in chains if not c) < 0.15 * len(stacks)
