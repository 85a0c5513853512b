"""CPU time of the whole server process (user + system, every thread, from
/proc/<pid>/stat at the window's start and end) over the items it answered
in the window. A count of host work per item; it says nothing of the
device."""


def read(run: dict):
    items = run["load"].items_in_window if run.get("load") else 0
    if not items or run.get("server_cpu_s") is None:
        return None
    return run["server_cpu_s"] * 1e3 / items
