"""Share of the traced slice in which no operation ran on the device, in %."""


def read(trace: dict):
    if not trace.get("window_s") or trace.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
