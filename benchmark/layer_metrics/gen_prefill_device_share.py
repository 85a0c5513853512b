"""Prefill's share of the device's busy time in the traced window, in percent:
device time of the prefill program over that of the prefill and step programs."""

from benchmark import gen_window


def read(run: dict):
    pre = gen_window.module(run, gen_window.PREFILL_MODULE)
    step = gen_window.module(run, gen_window.STEP_MODULE)
    if not pre or not step:
        return None
    return 100.0 * pre["device_s"] / (pre["device_s"] + step["device_s"])
