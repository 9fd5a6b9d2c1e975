"""Bench artifact headline conventions (bench.py helpers): the
published value is the best measured closed-loop serving number, never
lowered by a degraded window below the sequential number the run
achieved, and the vs_baseline note always states which convention the
ratio uses. These lock the semantics the BENCH_r05 artifacts rely
on."""

import importlib.util
import os

import pytest


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    spec = importlib.util.spec_from_file_location("bench_module", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_headline_prefers_best_closed_loop(bench):
    t = {"topn_qps": 12.5, "topn_qps_c8": 39.0, "topn_qps_c32": 101.6,
         "topn_qps_c64": 132.9}
    assert bench.headline_mode(t) == ("64 closed-loop clients", 132.9)


def test_headline_never_below_sequential(bench):
    # a degraded concurrency window must not lower the published number
    t = {"topn_qps": 0.41, "topn_qps_c8": 0.39}
    assert bench.headline_mode(t) == ("sequential", 0.41)


def test_headline_sequential_only_run(bench):
    assert bench.headline_mode({"topn_qps": 12.5}) == ("sequential", 12.5)


def test_best_closed_loop_ignores_non_numeric_and_other_keys(bench):
    t = {"topn_qps": 5.0, "topn_qps_c8": 7.0, "topn_qps_c32": "err",
         "topn_queries_timed": 99, "chain_qps_c8": 1000.0}
    assert bench.best_closed_loop(t, "topn_qps_c") == ("topn_qps_c8", 7.0)
    assert bench.best_closed_loop({}, "topn_qps_c") == (None, None)


def test_vs_baseline_note_matches_mode(bench):
    serving = bench.vs_baseline_fields("32 closed-loop clients", 112.4, 0.4)
    assert serving["vs_baseline"] == round(112.4 / 0.4, 2)
    assert "serving" in serving["vs_baseline_note"]
    seq = bench.vs_baseline_fields("sequential", 12.5, 0.4)
    assert "sequential qps both sides" in seq["vs_baseline_note"]
    assert bench.vs_baseline_fields("sequential", 12.5, None) == {}


def test_vs_baseline_uses_measured_cpu_closed_loop_denominator(bench):
    # when a CPU closed-loop window was measured, the serving ratio
    # divides by the BEST measured CPU throughput, not the asserted
    # sequential ceiling — the denominator is backed by data
    out = bench.vs_baseline_fields(
        "32 closed-loop clients", 112.4, 0.4, cpu_closed_qps=0.5
    )
    assert out["vs_baseline"] == round(112.4 / 0.5, 2)
    assert out["baseline_cpu_closed_qps"] == 0.5
    assert "measured" in out["vs_baseline_note"]
    # a degraded closed-loop window never RAISES the ratio
    out = bench.vs_baseline_fields(
        "32 closed-loop clients", 112.4, 0.4, cpu_closed_qps=0.3
    )
    assert out["vs_baseline"] == round(112.4 / 0.4, 2)


def test_window_quality_derives_rtt_and_depth(bench):
    t = {
        "topn_qps": 12.5,
        "topn_qps_c64": 100.0,
        "profile": {"device_rtt_ms": 20.0},
    }
    wq = bench.window_quality(t)
    assert wq["sustained_rtt_ms"] == 20.0
    # 100 qps x 20 ms RTT = 2 concurrent round-trips in flight
    assert wq["pipelining_depth"] == 2.0
    assert wq["headline_qps"] == 100.0
    # no RTT profile measured -> no quality record
    assert bench.window_quality({"topn_qps": 12.5}) is None
    assert bench.window_quality({}) is None
    assert bench.window_quality(
        {"topn_qps": 1.0, "profile": {"error": "x"}}
    ) is None


def test_degraded_rtt_refuses_last_good_overwrite(bench):
    good = {"sustained_rtt_ms": 20.0, "pipelining_depth": 2.0}
    # mildly worse RTT: fine
    ok = {"sustained_rtt_ms": 30.0, "pipelining_depth": 2.0}
    assert bench.window_degraded(ok, good) == (False, None)
    # RTT past the degradation factor: refused, with the reason
    bad = {"sustained_rtt_ms": 20.0 * bench.DEGRADED_RTT_FACTOR + 1,
           "pipelining_depth": 2.0}
    degraded, why = bench.window_degraded(bad, good)
    assert degraded and "RTT" in why


def test_collapsed_pipelining_depth_refuses_overwrite(bench):
    good = {"sustained_rtt_ms": 20.0, "pipelining_depth": 10.0}
    bad = {"sustained_rtt_ms": 20.0,
           "pipelining_depth": 10.0 * bench.DEGRADED_DEPTH_FACTOR - 0.5}
    degraded, why = bench.window_degraded(bad, good)
    assert degraded and "depth" in why


def test_window_gating_bootstrap_and_unprovable_runs(bench):
    wq = {"sustained_rtt_ms": 20.0, "pipelining_depth": 2.0}
    # no prior quality record (pre-gating artifact): anything may seed
    assert bench.window_degraded(wq, None) == (False, None)
    assert bench.window_degraded(None, None) == (False, None)
    # a run that measured no quality must not displace one that did
    degraded, why = bench.window_degraded(None, wq)
    assert degraded and "window_quality" in why


def test_window_quality_carries_fused_rtt_fields(bench):
    t = {
        "topn_qps": 12.5,
        "profile": {
            "device_rtt_ms": 20.0,
            "fused_rtt": {
                "rtt_multiple": 1.3,
                "fused_launches_per_query": 1.0,
            },
        },
    }
    wq = bench.window_quality(t)
    assert wq["fused_rtt_multiple"] == 1.3
    assert wq["fused_launches_per_query"] == 1.0
    # no fused probe (or a bad value) -> fields simply absent
    wq = bench.window_quality({"topn_qps": 12.5, "profile": {"device_rtt_ms": 20.0}})
    assert "fused_rtt_multiple" not in wq
    t["profile"]["fused_rtt"] = {"rtt_multiple": 0}
    assert "fused_rtt_multiple" not in bench.window_quality(t)


def test_fused_window_regression_refuses_overwrite(bench):
    good = {"sustained_rtt_ms": 20.0, "pipelining_depth": 2.0,
            "fused_rtt_multiple": 1.3}
    # comparable fused window: fine
    ok = dict(good, fused_rtt_multiple=1.5)
    assert bench.window_degraded(ok, good) == (False, None)
    # fusion regressed to per-call round trips: refused, with the reason
    bad = dict(good, fused_rtt_multiple=1.3 * bench.DEGRADED_RTT_FACTOR + 0.1)
    degraded, why = bench.window_degraded(bad, good)
    assert degraded and "fused" in why
    # fused window not measured while last-good has one: refused
    degraded, why = bench.window_degraded(
        {"sustained_rtt_ms": 20.0, "pipelining_depth": 2.0}, good
    )
    assert degraded and "fused" in why
    # last-good PRE-fusion (no fused fields): new fused fields accepted
    old = {"sustained_rtt_ms": 20.0, "pipelining_depth": 2.0}
    assert bench.window_degraded(good, old) == (False, None)


def test_vs_baseline_seq_ratio_rides_alongside(bench):
    out = bench.vs_baseline_fields(
        "64 closed-loop clients", 132.9, 0.4, seq_qps=12.5
    )
    assert out["vs_baseline_seq"] == round(12.5 / 0.4, 2)
    # sequential mode: the headline IS the sequential ratio already
    out = bench.vs_baseline_fields("sequential", 12.5, 0.4, seq_qps=12.5)
    assert "vs_baseline_seq" not in out
