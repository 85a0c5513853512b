"""Programs compiled inside the window: `runtime_compiles_total`, difference
of the two scrapes. The harness fails the run when it is above 0."""


def read(run: dict):
    return float(run["compiles_in_window"])
