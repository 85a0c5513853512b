"""Programs compiled inside the window: `runtime_compiles_total`, difference
of the two scrapes. The harness fails the run when it is above 0."""


def read(run: dict):
    n = run.get("compiles_in_window")
    return None if n is None else float(n)
