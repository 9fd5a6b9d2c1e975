"""The program's GroupBy paths against the reference, in a process of
their own: an ``Executor`` imports JAX, and the harness refuses to start
a server from a process that has (``test_served.py``). For
``test_groupby.py`` only:

    JAX_PLATFORMS=cpu python -m benchmark.tests.groupby_program <dir>

prints one JSON line: for each path and case the program's answer, the
reference's and the device launches it took; and a tiny index with
nulls answered on the CPU path beside the reference's own sums."""

import json
import os
import sys

from benchmark import datagen, run, traffic

SEED = 3900000011
MONTH = ["Row", "pickup_month", 4]


def _file(*parts):
    with open(os.path.join(run.ROOT, *parts)) as f:
        return json.load(f)


TAXI = _file("benchmark", "configs", "taxi96.json")
SMALL = {**TAXI, "shards": 2}
MIX = traffic.load(os.path.join(run.ROOT, "benchmark", "traffic", "groupby.json"))
Q1, Q2, Q3, Q4 = [traffic._substitute(t["call"], {"m": 4}) for t in MIX["mix"]]
CASES = {
    "Q1": Q1, "Q2": Q2, "Q3": Q3, "Q4": Q4,
    "unfiltered_sum": ["GroupBy", [["Rows", "cab_type"], ["Rows", "pickup_year"]], None, {"sum": "total_amount"}],
    "ids_reordered_and_a_row_with_no_bits": ["GroupBy", [["Rows", "passenger_count", [9, 1, 11]], ["Rows", "cab_type", [1, 0]]],
                                             MONTH, {"sum": "total_amount"}],
    "limit": ["GroupBy", [["Rows", "passenger_count"], ["Rows", "pickup_year"]],
              ["Intersect", MONTH, ["Row", "cab_type", 1]], {"limit": 7}],
}
# the fused panel (a lone GroupBy fuses alone), the lone batched launch
# (where the chip's fusion admission sends a panel it will not fuse) and
# the CPU's map-reduce of ``analytics.groupby_shard``
PATHS = {"always": {"device_policy": "always"}, "unfused": {"device_policy": "always", "fusion_enabled": False},
         "never": {"device_policy": "never"}}
HOLES = {"name": "tmp_holes", "index": "holes", "shards": 2, "fields": [
    {"name": "c", "kind": "categorical", "rows": 3, "shares": [0.5, 0.3, 0.2]},
    {"name": "n", "kind": "int_uniform", "min": 0, "max": 99, "present": 0.5}]}


def _paths(data_dir: str, cfg: dict, calls: dict, paths: dict) -> dict:
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils import metrics

    key = metrics._flat_key(metrics.FUSION_GROUPBY_LAUNCHES, metrics._labels_key({}))
    out = {}
    h = Holder(data_dir)
    h.open()
    try:
        for path, kwargs in paths.items():
            ex = Executor(h, **kwargs)
            out[path] = {}
            for case, call in calls.items():
                before = metrics.snapshot().get(key, 0)
                (got,) = ex.execute(cfg["index"], traffic.pql(call))
                out[path][case] = {"got": got, "launched": metrics.snapshot().get(key, 0) - before}
            ex.close()
    finally:
        h.close()
    return out


def main(tmp: str) -> dict:
    ref, _ = datagen.build(SMALL, SEED, os.path.join(tmp, "taxi"))
    taxi = _paths(os.path.join(tmp, "taxi"), SMALL, CASES, PATHS)
    for case, call in CASES.items():
        want = ref.answer(call)
        for path in taxi:
            taxi[path][case]["want"] = want
    holes, _ = datagen.build(HOLES, 7, os.path.join(tmp, "holes"))
    call = ["GroupBy", [["Rows", "c"]], None, {"sum": "n"}]
    codes = holes.fields["c"].codes
    return {
        "taxi": taxi,
        "groups_without_limit": len(ref.answer([*CASES["limit"][:3], {}])),
        "holes": {
            "got": _paths(os.path.join(tmp, "holes"), HOLES, {"c": call}, {"never": PATHS["never"]})["never"]["c"]["got"],
            "want": holes.answer(call),
            "columns": [int((codes == r).sum()) for r in range(3)],
            "held": [holes.fields["n"].sum(holes.fields["c"].row(r)) for r in range(3)],
        },
    }


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])))
