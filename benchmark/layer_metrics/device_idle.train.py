"""``device_idle.train``: 1 - the device's busy union over the traced
window, in per cent; both from the trace, on the device's clock (the window
runs from the first step program's start to the last one's end)."""


def compute(trace, counters, run):
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
