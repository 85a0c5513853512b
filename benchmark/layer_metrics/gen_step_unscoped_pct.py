"""Share of one decode step's device time that NO scope of the program names, in
percent: the self time of the operations of `jit_step` whose `op_name` holds no
`jax.named_scope` of the program, over the sum of every chain of scopes' self
time. The run's `launch_scopes` note lists the five costliest of them by name.
Read only for a program that names its kinds of work (it has the scope `plan`)
(benchmark/launch_scopes.py: one parse of the trace a run for both programs, the
union of the operations' intervals a launch, median over the launches that lie
whole inside the traced window). None where the trace holds no such program or the
program no such scope (a tree older than ISSUE 66, or a program the compile cache
served from such a tree's entry)."""

from benchmark import gen_window, launch_scopes


def read(run: dict):
    return launch_scopes.unscoped_pct(run, gen_window.STEP_MODULE)
