"""Device time of the mixers' dense in- and out-projections in one prefill launch,
in ms: the self time of every chain of scopes of `jit_prefill_fn` that ends in
`proj` (`_qkv`, `_attn_out`, the latent attention's down- and up-products, the
Mamba / delta / convolution mixers' `W_in` / `W_out`; `ssm_update>proj` and
`mla_decode>proj` among them, `proj>norm` not: a chain counts for its innermost
scope) (benchmark/launch_scopes.py: one parse of the trace a run for both
programs, the union of the operations' intervals a launch, median over the
launches that lie whole inside the traced window). None where the trace holds no
such program or the program no such scope (a tree older than ISSUE 66, or a
program the compile cache served from such a tree's entry)."""

from benchmark import gen_window, launch_scopes


def read(run: dict):
    return launch_scopes.ends_in_ms(run, gen_window.PREFILL_MODULE, ("proj",))
