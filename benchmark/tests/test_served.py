"""Whole runs on the CPU at 2 shards (the rehearsal): they skip the
harness's look for a chip (``--allow-cpu``) and drive the rest of a run
through ``python -m pilosa_tpu server`` as a child. Run by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import run

ROOT = run.ROOT
CELLS = ["tall64.topn", "taxi96.dashboard", "taxi96.groupby"]
# the GroupBy mix, which no cell of BENCHMARK.json sends yet: rehearsed
# as the cell it waits to be (PERF.md, Open questions)
GROUPBY = {"name": "taxi96.groupby", "config": "taxi96", "traffic": "groupby", "chips": 1}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def rehearse(cell, trace=0, seconds=4.0, **hooks):
    args = run.parse_args([
        "--workload", cell, "--seed", str(2**31 + 11), "--seconds", str(seconds),
        "--trace", str(trace), "--allow-cpu", "--shards", "2",
    ])
    manifest = run.read_json("BENCHMARK.json")
    if cell == GROUPBY["name"]:
        manifest["workloads"].append(GROUPBY)
    return run.run_cell(args, manifest=manifest, **hooks)


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_agrees_with_the_servers_cpu_path(cell):
    """A second witness: the roaring CPU path (``--device-policy
    never``) gives what the plain reference gives on every template
    (``taxi96.groupby``: the four GroupBy panels, the map-reduce of
    ``analytics.groupby_shard``)."""
    out = rehearse(cell, server_flags=["--device-policy", "never"])
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["compared"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_wrong_answer_makes_the_run_incorrect(cell):
    """An answer altered where it is produced: ``correct`` comes out
    false and the altered answers count as failed."""
    out = rehearse(cell, server_module="benchmark.tests.faulty_server")
    assert out["correct"] is False
    assert out["failed"] == out["checks"]["wrong_answers"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert list(out)[-1] == "checks"


def test_a_sound_traced_run_is_correct_and_reports_only_what_it_read(capsys):
    out = rehearse("taxi96.dashboard", trace=1, seconds=6.0)
    assert out["correct"] and out["failed"] == 0
    # on the CPU there is no device plane: the trace metrics are left out, never 0
    assert "kernels.hbm_roofline" not in out["metrics"] and "busy_s" not in out["device"]
    assert out["metrics"]["executor.fallbacks"]["value"] == 0
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device", "checks"}


def test_a_configuration_a_mix_a_cell_and_a_metric_are_new_files_alone(tmp_path):
    """Add a throw-away example of each as files, run the cell, remove them."""
    bench = os.path.join(ROOT, "benchmark")
    files = {
        os.path.join(bench, "configs", "tmp_tiny.json"): {
            "name": "tmp_tiny", "index": "tiny", "shards": 2, "server_flags": ["--device-policy", "always"],
            "fields": [
                {"name": "c", "kind": "categorical", "rows": 4, "shares": [0.4, 0.3, 0.2, 0.1]},
                {"name": "n", "kind": "int_uniform", "min": 0, "max": 99, "present": 0.5},
            ],
        },
        os.path.join(bench, "traffic", "tmp_counts.json"): {
            "loop": "closed", "clients": 2, "cache": False, "deck": 8,
            "mix": [
                {"weight": 1, "call": ["Count", ["Intersect", ["Row", "c", "$r"], ["Range", "n", ">", 50]]],
                 "draw": {"r": {"field": "c", "from": "rows", "dist": "uniform"}}},
                {"weight": 1, "call": ["Sum", "n", None]},
            ],
        },
        os.path.join(bench, "layer_metrics", "tmp.calls_per_query.json"): {
            "name": "tmp.calls_per_query", "source": "server_metrics",
            "numerator": [{"metric": "executor.calls", "labels": {}}], "scale": 1, "per": "request",
        },
    }
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tmp_tiny", "file": "benchmark/configs/tmp_tiny.json"})
    manifest["workloads"].append({"name": "tmp_tiny.counts", "config": "tmp_tiny", "traffic": "tmp_counts", "chips": 1})
    manifest["per_layer"].append({"name": "tmp.calls_per_query", "unit": "count", "workloads": ["tmp_tiny.counts"]})
    try:
        for path, content in files.items():
            with open(path, "w") as f:
                json.dump(content, f)
        args = run.parse_args(["--workload", "tmp_tiny.counts", "--seed", "5", "--seconds", "3",
                               "--trace", "1", "--allow-cpu"])
        out = run.run_cell(args, manifest=manifest)
    finally:
        for path in files:
            if os.path.exists(path):
                os.remove(path)
    assert out["correct"] and out["attempted"] > 0
    assert out["metrics"]["tmp.calls_per_query"]["value"] > 0  # a wave dedups equal calls
