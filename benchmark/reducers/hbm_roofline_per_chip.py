"""``hbm_roofline`` with the peak of every chip that was traced: the
least time the memories of all the slice's device planes together could
take to read the data the slice's requests need, over the time a device
was busy (the planes' average), in %. On one plane it is
``hbm_roofline``."""

from benchmark.reducers import hbm_roofline


def read(trace: dict):
    whole = hbm_roofline.read(trace)
    planes = len(trace.get("device_planes") or ())
    if whole is None or not planes:
        return None
    return whole / planes
