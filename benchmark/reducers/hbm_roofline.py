"""The least time the chip's memory could take to read the data the
slice's requests need (``benchmark/roofline.py``), over the time the
device was busy, in %. All served kernels together: the trace has no
name that tells them apart yet."""


def read(trace: dict):
    if not trace.get("busy_s") or not trace.get("slice_bytes"):
        return None
    busy_in_slice = trace["busy_s"] / trace["window_s"] * trace["slice_s"]
    return 100.0 * (trace["slice_bytes"] / trace["peak_hbm_bytes_per_s"]) / busy_in_slice
