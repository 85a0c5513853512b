"""Device time of what stands between the mixers and the feed-forwards in one
prefill launch, in ms: the self time of every chain of scopes of `jit_prefill_fn`
that ends in `norm` (wherever the norm stands: `head>norm`, `ssm_update>norm`),
`embed`, `plan` (the launch's index arithmetic), `emit` (the state's scatters
after the sampler, the row into `acc`) or `cache_write` (the pages' and rings'
scatters) (benchmark/launch_scopes.py: one parse of the trace a run for both
programs, the union of the operations' intervals a launch, median over the
launches that lie whole inside the traced window). None where the trace holds no
such program or the program no such scope (a tree older than ISSUE 66, or a
program the compile cache served from such a tree's entry)."""

from benchmark import gen_window, launch_scopes


def read(run: dict):
    return launch_scopes.ends_in_ms(run, gen_window.PREFILL_MODULE,
                                   ("norm", "embed", "plan", "emit", "cache_write"))
