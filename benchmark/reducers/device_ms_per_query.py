"""Device-busy milliseconds per request: the busy share of the traced
slice times the host's seconds per request answered in it. (A ratio
inside the trace and a rate on the host's clock, so the two clocks need
no common zero.)"""


def read(trace: dict):
    if not trace.get("busy_s") or not trace.get("slice_requests"):
        return None
    share = trace["busy_s"] / trace["window_s"]
    return 1000.0 * share * trace["slice_s"] / trace["slice_requests"]
