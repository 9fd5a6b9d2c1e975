"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy time.

Runs as a child with ``JAX_PLATFORMS=cpu`` once the server has exited:
``python benchmark/trace_reduce.py <trace dir>`` prints one JSON object.
Busy is the union of the intervals in which an operation ran on a
device, averaged over the device planes (``/device:TPU:<n>``). The window
is the extent of the device planes' own events: the host's planes run on
through the capture's start and stop calls, several seconds beyond the
span in which the device was traced (my chip run, PR 25: 14.7 s of host
events around a 5 s slice), so under steady load the first and the last
device operation mark the traced span to within one gap between requests.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = r"/device:TPU:\d+$"
NAME_CHARS = 160  # an HLO operation's text can run to a thousand characters
OPS_LINE = "XLA Ops"  # the line of single operations on a TPU plane
TOP = 10


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_planes(planes: list[dict], device_prefix: str = DEVICE_PLANE) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]. Kept apart from the file reader so a small
    recorded trace can check it."""
    devices = [p for p in planes if re.match(device_prefix, p["name"])]
    out = {
        "planes": [p["name"] for p in planes],
        "device_planes": [p["name"] for p in devices],
    }
    busy, by_op, by_gap = [], {}, {}
    lo = hi = None
    for p in devices:
        lines = [ln for ln in p["lines"] if ln["name"] == OPS_LINE] or p["lines"]
        events = sorted((s, s + d, n[:NAME_CHARS]) for ln in lines for n, s, d in ln["events"])
        if not events:
            continue
        union = merge([(s, e) for s, e, _ in events])
        lo = union[0][0] if lo is None else min(lo, union[0][0])
        hi = union[-1][1] if hi is None else max(hi, union[-1][1])
        busy.append(sum(e - s for s, e in union) / 1e9)
        for s, e, n in events:
            by_op[n] = by_op.get(n, 0.0) + (e - s) / 1e9
        starts = {s: n for s, _, n in reversed(events)}
        for (_, e0), (s1, _) in zip(union, union[1:]):
            key = "before:" + starts[s1]
            by_gap[key] = by_gap.get(key, 0.0) + (s1 - e0) / 1e9
    if not busy:
        return out  # no operation ran on a device: nothing to read
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:TOP]  # noqa: E731
    out["window_s"] = (hi - lo) / 1e9
    out["busy_s"] = sum(busy) / len(devices)
    out["device_ops"] = [[n, s / len(devices)] for n, s in top(by_op)]
    out["idle_gaps"] = [[n, s / len(devices)] for n, s in top(by_gap)]
    return out


def read_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        {
            "name": p.name,
            "lines": [
                {
                    "name": ln.name,
                    "events": [(e.name, int(e.start_ns), int(e.duration_ns)) for e in ln.events],
                }
                for ln in p.lines
            ],
        }
        for p in data.planes
    ]


def find_trace(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def main(argv: list[str]) -> int:
    path = find_trace(argv[1])
    if path is None:
        print(json.dumps({"error": f"no .xplane.pb under {argv[1]}"}))
        return 1
    planes = read_planes(path)
    prefix = argv[2] if len(argv) > 2 else DEVICE_PLANE
    out = reduce_planes(planes, prefix)
    out["trace_bytes"] = os.path.getsize(path)
    out["lines"] = {
        p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]} for p in planes
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
