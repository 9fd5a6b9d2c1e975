"""Per-layer metrics, each one file under ``benchmark/layer_metrics/``
found by the name ``BENCHMARK.json`` gives it.

``source: server_metrics``: the growth over the measured window of the
listed ``/metrics`` samples (``numerator``: metric name + labels to
match, summed over every other label), times ``scale``, ``per``
``request`` (over the requests the parent saw answered) or ``window``.
A sample the server has not published yet has not counted anything: it
reads 0.

``source: device_trace``: ``reducer`` names a module
``benchmark/reducers/<reducer>.py`` whose ``read(trace)`` takes the
traced slice's reduction (``benchmark/trace_reduce.py`` plus what the
parent counted in the slice) and returns a number, or None where there
is nothing to read; the metric is then left out of the line.
"""

from __future__ import annotations

import importlib
import json
import os

from benchmark.server import total

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layer_metrics")


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def evaluate(spec: dict, before, after, answered: int, trace: dict | None):
    """The metric's value, or None where there is nothing to read."""
    if spec["source"] == "server_metrics":
        grown = sum(
            total(after, s["metric"], **s.get("labels", {}))
            - total(before, s["metric"], **s.get("labels", {}))
            for s in spec["numerator"]
        )
        if spec["per"] == "request":
            if not answered:
                return None
            grown /= answered
        return grown * spec["scale"]
    if spec["source"] == "device_trace":
        if trace is None:
            return None
        return importlib.import_module(f"benchmark.reducers.{spec['reducer']}").read(trace)
    raise ValueError(f"{spec['name']}: unknown source {spec['source']!r}")
