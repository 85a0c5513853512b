"""Share of the traced window in which no operation ran on the device: 1 less
the union of the device's operation intervals over the window, in percent."""


def read(run: dict):
    t = run.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
