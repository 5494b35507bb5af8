"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals over the window), averaged
over the chips used. Layer: device."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["planes"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / run["trace_window_s"])
