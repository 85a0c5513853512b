"""Device time of the dense feed-forwards and the shared experts in one prefill
launch, in ms: the self time of every chain of scopes of `jit_prefill_fn` that
ends in `ffn_dense` (`paged_lm._swiglu`, `hybrid._relu2`: a dense layer's feed-
forward, a shared expert) or in `moe_shared` (what of a shared expert stands
outside `_swiglu`); the routed experts are `moe_experts`'
(benchmark/launch_scopes.py: one parse of the trace a run for both programs, the
union of the operations' intervals a launch, median over the launches that lie
whole inside the traced window). None where the trace holds no such program or the
program no such scope (a tree older than ISSUE 66, or a program the compile cache
served from such a tree's entry)."""

from benchmark import gen_window, launch_scopes


def read(run: dict):
    return launch_scopes.ends_in_ms(run, gen_window.PREFILL_MODULE, ("ffn_dense", "moe_shared"))
