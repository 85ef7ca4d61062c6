"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the cell's chips (``bench/devtrace.py``).
Reads ``device_idle.tick`` and ``device_idle.replay``."""


def read(inputs):
    trace = inputs.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
