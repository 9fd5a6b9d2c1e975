"""Always-on performance attribution (ISSUE 12): the waterfall
taxonomy + attribution layer, the continuous stack sampler, compile
tracking with storm detection, HBM telemetry gating, SLO burn-rate
monitoring, and the server surfaces (/debug/latency, /debug/profile,
/debug/slo, profile=waterfall, uptime gauges, fleet scrape).

Server-level pieces run against a real in-process server on :0 under
JAX_PLATFORMS=cpu (the tier-1 environment)."""

import io
import json
import threading
import time
import urllib.request
from types import SimpleNamespace

import pytest

from pilosa_tpu.server import Config, Server
from pilosa_tpu.utils import (
    events,
    logger as logger_mod,
    metrics,
    profiler,
    slo,
    trace,
)
from pilosa_tpu.utils.profiler import (
    CompileTracker,
    DeviceTelemetry,
    StackSampler,
    WaterfallAggregator,
)
from pilosa_tpu.utils.slo import SLOMonitor, parse_objectives


@pytest.fixture()
def server(tmp_path):
    cfg = Config(
        data_dir=str(tmp_path / "data"),
        bind="127.0.0.1:0",
        metric="expvar",
        device_policy="always",
        device_timeout=0,
    )
    s = Server(cfg)
    s.open()
    yield s
    s.close()


@pytest.fixture(autouse=True)
def _clean_globals():
    """The profiler singletons and journal are process-global; every
    test starts and ends clean."""
    events.JOURNAL.clear()
    profiler.WATERFALL.clear()
    profiler.COMPILES.clear()
    slo.MONITOR.clear()
    yield
    events.JOURNAL.clear()
    profiler.WATERFALL.clear()
    profiler.COMPILES.clear()
    profiler.SAMPLER.stop()
    profiler.SAMPLER.clear()
    slo.MONITOR.configure(parse_objectives(slo.DEFAULT_OBJECTIVES))
    slo.MONITOR.clear()
    logger_mod.set_context_provider(None)


def req(server, method, path, body=None, raw=False):
    url = server.uri + path
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(r) as resp:
            payload = resp.read()
            return resp.status, payload if raw else json.loads(payload or b"{}")
    except urllib.error.HTTPError as e:
        payload = e.read()
        return e.code, payload if raw else json.loads(payload or b"{}")


def _seed(server, index="pf"):
    req(server, "POST", f"/index/{index}", {})
    req(server, "POST", f"/index/{index}/field/f", {})
    req(server, "POST", f"/index/{index}/query", b"Set(1, f=1)")


# -- taxonomy completeness ----------------------------------------------------


def test_waterfall_taxonomy_covers_every_span_stage():
    """The taxonomy's tables say the same thing: every stage has a
    ``WF_`` name of its own, is listed once in display order (the
    synthetic ``other`` last) and is documented for /debug/latency; the
    device-side stages of ``rtt_fraction`` are stages, the launch and
    the wait both (one leg became two: the gauge must not move), no
    host stage among them. (Until ISSUE 37 it held a span-stage →
    bucket mapping, which nothing read once the timers were folded into
    ``leg``, to the span stages; the mapping went.)"""
    stages = trace.WATERFALL_STAGES
    assert len(set(stages)) == len(stages) and stages[-1] == trace.WF_OTHER
    named = {v for k, v in vars(trace).items() if k.startswith("WF_")}
    assert named == set(stages), named ^ set(stages)
    assert set(trace.WATERFALL) == set(stages)
    assert all(isinstance(d, str) and d for d in trace.WATERFALL.values())
    device = WaterfallAggregator.DEVICE_STAGES
    assert len(set(device)) == len(device) and set(device) < set(stages)
    assert set(device) == {
        trace.WF_DEVICE_LAUNCH,
        trace.WF_DEVICE_COMPUTE,
        trace.WF_TRANSFER_DECODE,
        trace.WF_MESH_FETCH,
    }
    # launch + wait read what the one leg read
    both = WaterfallAggregator.summarize(
        {trace.WF_DEVICE_LAUNCH: 0.002, trace.WF_DEVICE_COMPUTE: 0.003}, 0.010
    )
    one = WaterfallAggregator.summarize({trace.WF_DEVICE_COMPUTE: 0.005}, 0.010)
    assert both["rtt_fraction"] == one["rtt_fraction"] == 0.5


# -- attribution layer --------------------------------------------------------


def test_attrib_activate_reenters_on_worker_thread():
    """Pool submitters capture the dict once and re-enter it in the
    worker — legs measured on the worker land in the submitter's
    waterfall."""
    wf: dict = {}
    seen = []
    with trace.attrib_activate(wf):
        captured = trace.attrib_current()

        def worker():
            seen.append(trace.attrib_current())
            with trace.attrib_activate(captured):
                seen.append(trace.attrib_current())

        t = threading.Thread(target=worker)
        t.start()
        t.join(5)
    assert seen[0] is None and seen[1] is wf
    # activation nests and restores: no ctx leaks out of the with
    assert trace.attrib_current() is None


def test_waterfall_summarize_sums_to_total():
    """The rendered stages (including the synthetic `other`) partition
    the end-to-end latency exactly, and device+transfer legs set
    rtt_fraction."""
    wf = {
        trace.WF_PLAN_CANON: 0.010,
        trace.WF_DEVICE_COMPUTE: 0.060,
        trace.WF_TRANSFER_DECODE: 0.010,
        "_wave": 7,
    }
    s = WaterfallAggregator.summarize(wf, 0.100)
    assert s["total_ms"] == 100.0
    assert abs(sum(s["stages"].values()) - s["total_ms"]) < 1e-6
    assert s["stages"]["other"] == pytest.approx(20.0, abs=1e-6)
    assert s["rtt_fraction"] == pytest.approx(0.7)
    assert s["wave"] == 7
    # stage order follows the taxonomy, zero stages are skipped
    order = [st for st in trace.WATERFALL_STAGES if st in s["stages"]]
    assert list(s["stages"]) == order
    # degenerate total: no division blow-ups
    z = WaterfallAggregator.summarize({}, 0.0)
    assert z["rtt_fraction"] == 0.0 and z["stages"] == {}


def test_waterfall_aggregator_ring_ema_and_metrics():
    agg = WaterfallAggregator(ring_size=3)
    for i in range(5):
        agg.record("interactive", 0.010, {trace.WF_DEVICE_COMPUTE: 0.005})
    snap = agg.snapshot()
    assert len(snap["recent"]) == 3 and snap["recorded"] == 5
    assert snap["rtt_fraction"] == pytest.approx(0.5)
    assert snap["recent"][-1]["cls"] == "interactive"
    assert agg.snapshot(limit=1)["recent"][-1] == snap["recent"][-1]
    # the per-stage summary landed in the registry, labeled cls+stage
    ms = metrics.snapshot()
    assert any(
        k.startswith(metrics.LATENCY_STAGE_SECONDS)
        and "cls:interactive" in k
        and "stage:device.compute" in k
        for k in ms
    )
    assert agg.record("interactive", 0.01, None) is None  # no attribution ran
    agg.clear()
    assert agg.snapshot()["recorded"] == 0


def test_executor_attributes_device_and_transfer_legs(server):
    """A multi-shard device-path query lands device.compute (fenced
    kernel) and transfer.decode legs in an active attribution ctx —
    the waterfall reflects the live serving path, not a side probe."""
    from pilosa_tpu import SHARD_WIDTH

    _seed(server, index="dev")
    for sh in range(3):
        req(server, "POST", "/index/dev/query", b"Set(%d, f=1)" % (sh * SHARD_WIDTH + 5))
        req(server, "POST", "/index/dev/query", b"Set(%d, f=2)" % (sh * SHARD_WIDTH + 9))
    server.executor.execute("dev", "Count(Row(f=1))")  # warm jits
    wf: dict = {}
    with trace.attrib_activate(wf):
        res = server.executor.execute("dev", "Count(Union(Row(f=1), Row(f=2)))")
    assert res == [7]  # {1, 5, SW+5, 2SW+5} ∪ {9, SW+9, 2SW+9}
    assert wf.get(trace.WF_DEVICE_COMPUTE, 0.0) > 0.0
    assert wf.get(trace.WF_TRANSFER_DECODE, 0.0) > 0.0
    assert set(wf) - {"_wave"} <= set(trace.WATERFALL_STAGES)
    # the compile tracker saw the jit wrap for this plan signature
    comp = profiler.COMPILES.snapshot()
    assert comp["total_compiles"] >= 1
    assert any(r["kind"] == "tree_count" for r in comp["signatures"])


# -- compile tracking ---------------------------------------------------------


def test_compile_tracker_counts_forced_recompile():
    ct = CompileTracker()
    ct.note("tree_count", "sig-a", 0.5)
    # a dropped jit cache forces a recompile of the SAME signature: the
    # tracker must show 2 compiles for one plan shape
    ct.note("tree_count", "sig-a", 0.25)
    ct.note("topn", "sig-b", 0.1)
    snap = ct.snapshot()
    assert snap["total_compiles"] == 3
    assert snap["total_seconds"] == pytest.approx(0.85)
    row = next(r for r in snap["signatures"] if r["signature"] == "tree_count:'sig-a'")
    assert row["compiles"] == 2 and row["seconds"] == pytest.approx(0.75)
    assert any(
        k.startswith(metrics.PROFILER_COMPILES) for k in metrics.snapshot()
    )


def test_compile_tracker_bounded_by_overflow_row():
    ct = CompileTracker(max_sigs=4)
    for i in range(10):
        ct.note("k", f"sig-{i}", 0.01)
    snap = ct.snapshot(top=100)
    assert len(snap["signatures"]) <= 5  # max_sigs + the overflow row
    over = next(r for r in snap["signatures"] if r["signature"] == "(overflow)")
    assert over["compiles"] == 6


def test_compile_storm_edge_triggered():
    ct = CompileTracker(storm_threshold=4, storm_window_s=30.0)
    for i in range(6):
        ct.note("k", f"s{i}", 0.01)
    assert ct.storms == 1  # fires once per episode, not per compile
    evs = events.snapshot(kind=events.PROFILER_RECOMPILE_STORM)
    assert len(evs) == 1 and evs[0]["window_s"] == 30.0


# -- continuous stack sampler -------------------------------------------------


def _fake_frame(name, filename="x.py", lineno=1):
    code = SimpleNamespace(co_name=name, co_filename=filename)
    return SimpleNamespace(f_code=code, f_lineno=lineno, f_back=None)


def test_stack_sampler_aggregates_and_bounds_memory(monkeypatch):
    sam = StackSampler(hz=10.0, max_keys=4, frame_depth=2)
    calls = {"n": 0}

    def frames():
        calls["n"] += 1
        # more distinct stacks than max_keys: overflow must fold
        return {i: _fake_frame(f"fn{calls['n']}_{i}") for i in range(8)}

    monkeypatch.setattr(profiler, "_current_frames", frames)
    sam.sample_once()
    sam.sample_once()
    snap = sam.snapshot()
    assert snap["samples"] == 2
    assert snap["keys"] <= 5  # max_keys + "(other)"
    other = next(r for r in snap["top"] if r["frames"] == "(other)")
    assert other["count"] > 0
    sam.clear()
    assert sam.snapshot()["samples"] == 0


def test_stack_sampler_start_stop_lifecycle():
    sam = StackSampler(hz=200.0)
    assert not sam.running
    sam.start()
    assert sam.running
    deadline = time.monotonic() + 5
    while sam.samples == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    sam.stop()
    assert not sam.running
    assert sam.samples > 0
    # the sampler skips its own thread; real frames aggregate
    assert any(r["count"] > 0 for r in sam.top(5))
    n = sam.samples
    time.sleep(0.03)
    assert sam.samples == n  # stopped means stopped
    # hz<=0 never starts a thread (the config off-switch)
    off = StackSampler(hz=0.0)
    off.start()
    assert not off.running


# -- device telemetry ---------------------------------------------------------


def test_device_telemetry_cpu_backend_degrades_to_no_samples():
    tel = DeviceTelemetry()
    snap = tel.poll_once()  # CPU backend: no memory_stats — no error
    assert snap["devices"] == {}
    assert "stager" not in snap


def test_device_telemetry_gauges_and_watermark_event(monkeypatch):
    tel = DeviceTelemetry(watermark_pct=0.8)
    stats = {"bytes_in_use": 900, "bytes_limit": 1000, "peak_bytes_in_use": 950}
    monkeypatch.setattr(tel, "_device_stats", lambda: [("tpu:0", stats)])
    tel.stager_probe = lambda: (250, 1000)
    snap = tel.poll_once()
    dev = snap["devices"]["tpu:0"]
    assert dev["fraction"] == 0.9 and dev["peak_bytes"] == 950
    assert snap["stager"]["fraction"] == 0.25
    ms = metrics.snapshot()
    for name in (
        metrics.HBM_BYTES_IN_USE,
        metrics.HBM_PEAK_BYTES,
        metrics.HBM_BYTES_LIMIT,
    ):
        assert any(k.startswith(name) and "tpu:0" in k for k in ms)
    assert any(k.startswith(metrics.HBM_STAGER_FRACTION) for k in ms)
    # watermark is edge-triggered: above, above, below, above → 2 events
    tel.poll_once()
    stats["bytes_in_use"] = 100
    tel.poll_once()
    stats["bytes_in_use"] = 950
    tel.poll_once()
    evs = events.snapshot(kind=events.PROFILER_HBM_WATERMARK)
    assert len(evs) == 2
    assert evs[0]["device"] == "tpu:0" and evs[0]["fraction"] == 0.9


# -- SLO burn-rate monitoring -------------------------------------------------


def test_parse_objectives():
    assert parse_objectives("interactive=250@0.999") == {
        "interactive": (0.25, 0.999)
    }
    # malformed entries are skipped, not fatal; target defaults to 0.999
    out = parse_objectives("a=100, garbage, b=oops@0.9, c=50@2.0, d=200@0.99")
    assert out == {"a": (0.1, 0.999), "d": (0.2, 0.99)}
    # a spec that parses to nothing falls back to the defaults
    assert parse_objectives("total-garbage") == parse_objectives(
        slo.DEFAULT_OBJECTIVES
    )
    assert parse_objectives("") == {}


def test_slo_burn_fires_on_both_windows_with_cooldown():
    mon = SLOMonitor(
        objectives={"interactive": (0.1, 0.999)}, burn_threshold=14.4
    )
    t0 = 10_000.0
    # injected latency: every query blows the 100ms objective
    for i in range(20):
        mon.record("interactive", duration_s=1.0, ok=True, now=t0 + i)
    fired = mon.tick(now=t0 + 21)
    assert len(fired) == 1
    ev = fired[0]
    assert ev["kind"] == events.SLO_BURN and ev["cls"] == "interactive"
    assert ev["burn_5m"] >= 14.4 and ev["burn_1h"] >= 14.4
    assert ev["latency_ms"] == 100.0
    # edge-triggered: still burning → no second event
    assert mon.tick(now=t0 + 22) == []
    snap = mon.snapshot(now=t0 + 22)
    st = snap["classes"]["interactive"]
    assert st["firing"] is True and st["budget_remaining"] == 0.0
    assert st["samples"] == {"good": 0, "bad": 20}
    # recovery: enough good traffic drops both windows below threshold
    for i in range(20_000):
        mon.record("interactive", duration_s=0.01, ok=True, now=t0 + 23 + i % 280)
    assert mon.tick(now=t0 + 300) == []
    assert mon.snapshot(now=t0 + 300)["classes"]["interactive"]["firing"] is False
    assert any(k.startswith(metrics.SLO_BURNS) for k in metrics.snapshot())


def test_slo_short_window_alone_does_not_fire():
    """A brief blip trips the 5m window but not the 1h window — no
    alert (the long window proves it matters)."""
    mon = SLOMonitor(objectives={"interactive": (0.1, 0.99)}, burn_threshold=10.0)
    t0 = 50_000.0
    # an hour of good traffic, then a 30-second blip of failures
    for i in range(0, 3500, 10):
        mon.record("interactive", 0.01, ok=True, now=t0 + i)
    for i in range(30):
        mon.record("interactive", 1.0, ok=False, now=t0 + 3500 + i)
    rates = mon.burn_rates(now=t0 + 3531)["interactive"]
    assert rates["5m"] > 10.0 > rates["1h"]
    assert mon.tick(now=t0 + 3531) == []


def test_slo_4xx_is_not_budget_burn(server):
    """Client errors are the client's fault: a 400 parse error must not
    consume availability budget (ok=True accounting path)."""
    _seed(server, index="slo4")
    st, _ = req(server, "POST", "/index/slo4/query", b"NotAFunction(")
    assert st == 400
    snap = slo.MONITOR.snapshot()
    for cls in snap["classes"].values():
        assert cls["samples"]["bad"] == 0


# -- server surfaces ----------------------------------------------------------


def test_query_profile_waterfall_param(server):
    _seed(server, index="wfq")
    req(server, "POST", "/index/wfq/query", b"Count(Row(f=1))")  # warm
    st, body = req(
        server, "POST", "/index/wfq/query?profile=waterfall", b"Count(Row(f=1))"
    )
    assert st == 200 and body["results"] == [1]
    wf = body["profile"]["waterfall"]
    assert wf["total_ms"] > 0.0
    # stages partition the total (each stage rounded to 1µs in the
    # response, so allow one rounding step per stage)
    assert abs(sum(wf["stages"].values()) - wf["total_ms"]) < 0.001 * (
        len(wf["stages"]) + 1
    )
    assert set(wf["stages"]) <= set(trace.WATERFALL_STAGES)
    assert 0.0 <= wf["rtt_fraction"] <= 1.0
    # plain queries don't carry the split (but are still aggregated)
    st, body = req(server, "POST", "/index/wfq/query", b"Count(Row(f=1))")
    assert st == 200 and "profile" not in body and "_waterfall" not in body


def test_served_query_splits_into_stages_with_the_launch_and_the_wake_ups(server):
    """A device read over HTTP: ``device.launch`` (the jit call) apart
    from ``device.compute`` (the wait), ``handoff.wake`` (the three
    hand-backs, the handler's joined from outside ``api.query``'s
    total), and the stages with ``other`` still partition the total, in
    the response and in the ring."""
    from pilosa_tpu import SHARD_WIDTH

    _seed(server, index="hb")
    for sh in range(3):
        req(server, "POST", "/index/hb/query", b"Set(%d, f=1)" % (sh * SHARD_WIDTH + 5))
    q = b"Count(Row(f=1))"
    req(server, "POST", "/index/hb/query?cache=false", q)  # stage and compile
    st, body = req(server, "POST", "/index/hb/query?profile=waterfall", q)
    assert st == 200 and body["results"] == [4]
    wf = body["profile"]["waterfall"]
    for stage in (trace.WF_DEVICE_LAUNCH, trace.WF_DEVICE_COMPUTE, trace.WF_HANDOFF_WAKE):
        assert wf["stages"].get(stage, 0.0) > 0.0, (stage, wf)
    assert set(wf["stages"]) <= set(trace.WATERFALL_STAGES)
    assert abs(sum(wf["stages"].values()) - wf["total_ms"]) < 0.001 * (len(wf["stages"]) + 1)
    st, lat = req(server, "GET", "/debug/latency?limit=1")
    recent = lat["recent"][-1]
    assert recent["stages"][trace.WF_HANDOFF_WAKE] >= wf["stages"][trace.WF_HANDOFF_WAKE]
    assert trace.WF_RESPOND in recent["stages"]
    assert abs(sum(recent["stages"].values()) - recent["total_ms"]) < 0.001 * (
        len(recent["stages"]) + 1
    )
    # the kinds' launches, beside launch → ready
    ms = metrics.snapshot()
    launched = {k for k in ms if k.startswith(metrics.SPMD_LAUNCH_SECONDS + ".hist")}
    assert launched and all(
        ms[k]["sum"] <= ms[k.replace(metrics.SPMD_LAUNCH_SECONDS, metrics.SPMD_EXECUTE_SECONDS)]["sum"]
        for k in launched
    )


def test_debug_latency_endpoint(server):
    _seed(server, index="lat")
    for _ in range(3):
        req(server, "POST", "/index/lat/query", b"Count(Row(f=1))")
    st, body = req(server, "GET", "/debug/latency")
    assert st == 200
    assert body["recorded"] >= 3
    assert set(body["stages"]) == set(trace.WATERFALL_STAGES)
    assert body["recent"] and body["recent"][-1]["total_ms"] > 0
    assert body["rtt_fraction"] is not None
    # per-class/per-stage histograms ride the registry
    assert any(
        k.startswith(metrics.LATENCY_STAGE_SECONDS) and "stage:" in k
        for k in body["summary"]
    )
    st, body2 = req(server, "GET", "/debug/latency?limit=1")
    assert st == 200 and len(body2["recent"]) == 1
    st, _ = req(server, "GET", "/debug/latency?limit=bogus")
    assert st == 400


def test_debug_profile_endpoint(server):
    st, body = req(server, "GET", "/debug/profile")
    assert st == 200
    assert body["sampler"]["running"] is True  # always-on by default
    assert body["sampler"]["hz"] == server.config.profiler_hz
    assert "compiles" in body and "hbm" in body
    assert body["capture"]["running"] is False
    # capture control: stop with nothing running reports, never raises
    st, body = req(server, "GET", "/debug/profile?capture=stop")
    assert st == 200 and body["capture"]["ok"] is False
    st, _ = req(server, "GET", "/debug/profile?capture=bogus")
    assert st == 400
    st, _ = req(server, "GET", "/debug/profile?top=bogus")
    assert st == 400


def test_debug_slo_endpoint_and_burn_event(server):
    _seed(server, index="slos")
    # the first Count compiles, which alone can take longer than the
    # 250 ms interactive objective: count the sample of a warm repeat
    req(server, "POST", "/index/slos/query", b"Count(Row(f=1))")
    st, body = req(server, "GET", "/debug/slo")
    assert st == 200
    assert body["burn_threshold"] == server.config.slo_burn_threshold
    cold = body["classes"]["interactive"]["samples"]
    assert cold["good"] + cold["bad"] == 2  # the seed's Set and the Count
    req(server, "POST", "/index/slos/query", b"Count(Row(f=1))")
    st, body = req(server, "GET", "/debug/slo")
    warm = body["classes"]["interactive"]["samples"]
    assert (warm["good"], warm["bad"]) == (cold["good"] + 1, cold["bad"])
    # injected latency: force the interactive class over budget in both
    # windows, then let the scrape-path tick fire the burn event
    now = time.monotonic()
    for i in range(50):
        slo.MONITOR.record("interactive", duration_s=5.0, ok=True, now=now - i)
    st, body = req(server, "GET", "/debug/slo")
    assert st == 200 and body["classes"]["interactive"]["firing"] is True
    evs = events.snapshot(kind=events.SLO_BURN)
    assert evs and evs[-1]["cls"] == "interactive"
    st, body = req(server, "GET", "/debug/events?kind=slo.burn")
    assert st == 200 and body["events"]


def test_debug_events_limit_param(server):
    for i in range(5):
        events.record(events.GANG_DEGRADE, reason=f"r{i}")
    st, body = req(server, "GET", "/debug/events?limit=2")
    assert st == 200 and len(body["events"]) == 2
    # limit keeps the NEWEST entries
    assert [e["reason"] for e in body["events"]] == ["r3", "r4"]
    st, _ = req(server, "GET", "/debug/events?limit=bogus")
    assert st == 400


def test_uptime_and_start_time_gauges(server):
    st, raw = req(server, "GET", "/metrics", raw=True)
    assert st == 200
    text = raw.decode()
    lines = {
        l.split(" ")[0]: float(l.split(" ")[1])
        for l in text.splitlines()
        if l.startswith(("pilosa_uptime_seconds", "pilosa_process_start_time_seconds"))
    }
    assert lines["pilosa_uptime_seconds"] >= 0.0
    assert abs(lines["pilosa_process_start_time_seconds"] - time.time()) < 600


def _sample(server, name: str) -> float:
    st, raw = req(server, "GET", "/metrics", raw=True)
    assert st == 200
    values = [
        float(l.rsplit(" ", 1)[1])
        for l in raw.decode().splitlines()
        if l.startswith(name + " ") or l.startswith(name + "{")
    ]
    assert values, f"{name} missing from /metrics"
    return sum(values)


def test_process_cpu_seconds_gauge_grows_with_the_cpu_spent(server):
    """``process.cpu_seconds`` is ``time.process_time()`` at the scrape:
    what the process burned between two scrapes is their difference."""
    before = _sample(server, "pilosa_process_cpu_seconds")
    assert 0.0 < before <= time.process_time()
    t0 = time.process_time()
    while time.process_time() - t0 < 0.05:  # burn, on the CPU's own clock
        sum(range(1000))
    after = _sample(server, "pilosa_process_cpu_seconds")
    assert after - before >= 0.05
    assert after <= time.process_time()


def test_one_cache_flush_pass_is_one_observation(server):
    _seed(server, index="fl")
    key = metrics.CACHE_FLUSH_SECONDS + ".hist"
    before = metrics.snapshot().get(key, {"count": 0, "sum": 0.0})
    server.flush_caches()
    server.flush_caches()
    after = metrics.snapshot()[key]
    assert after["count"] == before["count"] + 2
    assert after["sum"] > before["sum"]
    assert _sample(server, "pilosa_holder_cache_flush_seconds_count") == after["count"]


def test_fleet_scrape_carries_profile_and_slo_samples(server):
    """The PR 9 fleet scrape federates the new attribution samples:
    every profile/slo family appears instance-labeled per rank."""
    _seed(server, index="fl")
    req(server, "POST", "/index/fl/query", b"Count(Row(f=1))")
    req(server, "GET", "/metrics", raw=True)  # tick refreshes the slo gauges
    st, raw = req(server, "GET", "/metrics?fleet=true", raw=True)
    assert st == 200
    text = raw.decode()
    for family in (
        "pilosa_latency_stage_seconds",
        "pilosa_slo_burn_rate",
        "pilosa_executor_rtt_fraction",
        "pilosa_uptime_seconds",
    ):
        sample = [
            l
            for l in text.splitlines()
            if l.startswith(family) and not l.startswith("#")
        ]
        assert sample, f"{family} missing from fleet scrape"
        assert all(f'instance="{server.uri}"' in l for l in sample)


def test_logger_correlation_includes_dispatch_wave():
    from pilosa_tpu.utils.logger import StandardLogger

    buf = io.StringIO()
    lg = StandardLogger(stream=buf)
    tok = trace.set_wave(41)
    try:
        tr = trace.Tracer()
        with tr.trace("query", force=True):
            lg.printf("inside wave")
    finally:
        trace.reset_wave(tok)
    out = buf.getvalue().splitlines()[-1]
    assert "wave=41" in out and "trace=" in out
    # wave 0 (no wave) adds nothing
    lg.printf("outside")
    assert "wave=" not in buf.getvalue().splitlines()[-1]


# -- docs drift guard ---------------------------------------------------------


def _doc_table_names(section: str) -> dict:
    import os
    import re

    path = os.path.join(
        os.path.dirname(__file__), "..", "docs", "administration.md"
    )
    with open(path) as f:
        text = f.read()
    assert section in text, f"docs/administration.md lost section {section!r}"
    chunk = re.split(r"\n#{2,3} ", text.split(section, 1)[1])[0]
    rows = re.findall(r"^\| `([^`]+)` \|", chunk, re.M)
    return {name: None for name in rows}


def test_docs_waterfall_stage_table_in_sync():
    doc = set(_doc_table_names("### Waterfall stages"))
    code = set(trace.WATERFALL_STAGES)
    assert doc == code, f"docs-only: {doc - code}; code-only: {code - doc}"


def test_docs_event_kind_catalog_in_sync():
    doc = set(_doc_table_names("### Event kinds"))
    code = set(events.EVENT_KINDS)
    assert doc == code, f"docs-only: {doc - code}; code-only: {code - doc}"


# -- overhead gate ------------------------------------------------------------


@pytest.mark.slow
def test_attribution_overhead_gate(tmp_path):
    """Executor micro with sampler + attribution enabled stays within
    5% of disabled (interleaved rounds, min-of-rounds; the CI profiling
    step runs this explicitly — it is excluded from tier-1 as
    timing-sensitive)."""
    cfg = Config(
        data_dir=str(tmp_path / "data"),
        bind="127.0.0.1:0",
        metric="expvar",
        device_policy="always",
        device_timeout=0,
    )
    s = Server(cfg)
    s.open()
    try:
        s.api.create_index("ov")
        s.api.create_field("ov", "f", {})
        s.api.query("ov", "Set(1, f=1)")
        for _ in range(20):
            s.api.query("ov", "Count(Row(f=1))")  # warm

        def round_(attrib: bool, iters=60) -> float:
            t0 = time.perf_counter()
            for _ in range(iters):
                if attrib:
                    with trace.attrib_activate({}):
                        s.executor.execute("ov", "Count(Row(f=1))")
                else:
                    s.executor.execute("ov", "Count(Row(f=1))")
            return time.perf_counter() - t0

        # interleave base/instrumented rounds so a transient load spike
        # hits both sides, and take the min of each — scheduling noise
        # is strictly additive, so min is the honest per-iteration cost.
        # CI runners are still noisy, so best of up to 3 attempts.
        profiler.SAMPLER.hz = 10.0
        overhead = float("inf")
        for _ in range(3):
            base = instrumented = float("inf")
            for _ in range(9):
                profiler.SAMPLER.stop()
                base = min(base, round_(attrib=False))
                profiler.SAMPLER.start()
                try:
                    instrumented = min(instrumented, round_(attrib=True))
                finally:
                    profiler.SAMPLER.stop()
            overhead = min(overhead, instrumented / base - 1.0)
            if overhead < 0.05:
                break
        assert overhead < 0.05, f"attribution overhead {overhead:.1%} >= 5%"
    finally:
        s.close()
