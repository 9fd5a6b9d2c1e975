"""Serving pipeline (ISSUE 2): bounded admission with 503 + Retry-After
sheds, deadline propagation/cancellation at stage boundaries,
singleflight coalescing, one execution a queued request, graceful
drain, and the /debug/pipeline + metrics surface.

Server-level tests run a real in-process server on :0 under
JAX_PLATFORMS=cpu (the tier-1 environment)."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.server import Config, Server
from pilosa_tpu.server import deadline as dl_mod
from pilosa_tpu.server.deadline import Deadline, DeadlineExceeded
from pilosa_tpu.server.pipeline import Overloaded, QueryPipeline
from pilosa_tpu.utils import metrics


def req(server, method, path, body=None, headers=None, raw=False):
    url = server.uri + path
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    r = urllib.request.Request(url, data=data, method=method, headers=headers or {})
    try:
        with urllib.request.urlopen(r) as resp:
            payload = resp.read()
            return (
                resp.status,
                payload if raw else json.loads(payload or b"{}"),
                dict(resp.headers),
            )
    except urllib.error.HTTPError as e:
        payload = e.read()
        return (
            e.code,
            payload if raw else json.loads(payload or b"{}"),
            dict(e.headers),
        )


def make_server(tmp_path, **cfg_kwargs):
    cfg = Config(
        data_dir=str(tmp_path / "data"),
        bind="127.0.0.1:0",
        metric="expvar",
        device_policy="never",
        device_timeout=0,
        **cfg_kwargs,
    )
    s = Server(cfg)
    s.open()
    return s


def seed(server, index="pl", n_rows=4):
    st, _, _ = req(server, "POST", f"/index/{index}", {})
    assert st == 200
    st, _, _ = req(server, "POST", f"/index/{index}/field/f", {})
    assert st == 200
    rows, cols = [], []
    for r in range(n_rows):
        # row r gets r+1 bits in shard 0 and r+1 in shard 1 — distinct
        # per-row counts, so an answer handed to the wrong request shows
        for c in range(r + 1):
            rows.append(r)
            cols.append(c * 13 + r)
            rows.append(r)
            cols.append(SHARD_WIDTH + c * 17 + r)
    st, _, _ = req(
        server, "POST", f"/index/{index}/field/f/import",
        {"rowIDs": rows, "columnIDs": cols},
    )
    assert st == 200


# -- deadline unit behavior -------------------------------------------------


def test_deadline_from_request_parsing():
    assert dl_mod.from_request({}, {}) is None
    d = dl_mod.from_request({}, {"timeout": ["2.5"]})
    assert 2.0 < d.remaining() <= 2.5
    # header: absolute unix epoch seconds
    d = dl_mod.from_request({"x-request-deadline": str(time.time() + 5)}, {})
    assert 4.0 < d.remaining() <= 5.1
    # past header deadline admits but is already expired
    d = dl_mod.from_request({"x-request-deadline": str(time.time() - 5)}, {})
    assert d.expired()
    # configured default applies only when the client sent nothing
    d = dl_mod.from_request({}, {}, default_timeout=1.0)
    assert d is not None and 0.5 < d.remaining() <= 1.0
    # timeout param wins over header and default
    d = dl_mod.from_request(
        {"x-request-deadline": str(time.time() + 99)},
        {"timeout": ["1.0"]},
        default_timeout=50.0,
    )
    assert d.remaining() <= 1.0
    for bad in ({"timeout": ["abc"]}, {"timeout": ["-1"]}, {"timeout": ["inf"]}):
        with pytest.raises(ValueError):
            dl_mod.from_request({}, bad)
    with pytest.raises(ValueError):
        dl_mod.from_request({"x-request-deadline": "tomorrow"}, {})


def test_deadline_check_and_context():
    d = Deadline.after(60)
    d.check("anywhere")  # not expired: no raise
    expired = Deadline.after(-1)
    with pytest.raises(DeadlineExceeded):
        expired.check("stage")
    assert dl_mod.current() is None
    with dl_mod.activate(d):
        assert dl_mod.current() is d
        with dl_mod.activate(None):  # None activation is a no-op
            assert dl_mod.current() is d
    assert dl_mod.current() is None


# -- executor-level cancellation -------------------------------------------


def test_deadline_cancels_before_per_shard_map(tmp_path):
    s = make_server(tmp_path)
    try:
        seed(s, "exq")
        ex = s.executor

        # expired BEFORE the executor: zero call dispatch happens
        before = metrics.snapshot().get("executor.calls;call:Count", 0)
        with dl_mod.activate(Deadline.after(-1)):
            with pytest.raises(DeadlineExceeded):
                ex.execute("exq", "Count(Row(f=1))")
        assert metrics.snapshot().get("executor.calls;call:Count", 0) == before

        # expires MID-map: the second shard's work is cancelled at the
        # shard boundary instead of computed and discarded
        mapped = []
        orig = ex._bitmap_call_shard_cpu

        def slow_shard(index, c, shard):
            mapped.append(shard)
            time.sleep(0.08)
            return orig(index, c, shard)

        ex._bitmap_call_shard_cpu = slow_shard
        try:
            with dl_mod.activate(Deadline.after(0.04)):
                with pytest.raises(DeadlineExceeded):
                    ex.execute("exq", "Count(Row(f=1))")
        finally:
            ex._bitmap_call_shard_cpu = orig
        assert len(mapped) == 1, f"expected cancellation after shard 1, mapped {mapped}"
    finally:
        s.close()


# -- HTTP deadline surface --------------------------------------------------


def test_http_deadline_504_and_bad_values(tmp_path):
    s = make_server(tmp_path)
    try:
        seed(s)
        st, body, _ = req(
            s, "POST", "/index/pl/query?timeout=0.000001", b"Count(Row(f=1))"
        )
        assert st == 504 and "deadline" in body["error"]
        st, body, _ = req(
            s,
            "POST",
            "/index/pl/query",
            b"Count(Row(f=1))",
            headers={"X-Request-Deadline": str(time.time() - 10)},
        )
        assert st == 504
        st, body, _ = req(
            s, "POST", "/index/pl/query?timeout=banana", b"Count(Row(f=1))"
        )
        assert st == 400
        # an ample deadline answers normally
        st, body, _ = req(
            s, "POST", "/index/pl/query?timeout=30", b"Count(Row(f=1))"
        )
        assert st == 200 and body["results"] == [4]
    finally:
        s.close()


# -- overload shedding ------------------------------------------------------


def test_overload_sheds_503_with_retry_after(tmp_path):
    # queue-full is WHOLE-SERVER overload → 503 + Retry-After (the
    # internal client retries 503 against replicas); the per-tenant
    # throttle is the only 429 (tests/test_tenancy.py)
    s = make_server(
        tmp_path,
        pipeline_interactive_workers=2,
        pipeline_interactive_queue=2,
        pipeline_shed_retry_after=3.0,
    )
    try:
        seed(s, "ov")
        gate = threading.Event()
        orig = s.executor.execute

        def gated(index, query, shards=None, opt=None):
            gate.wait(10)
            return orig(index, query, shards, opt)

        s.executor.execute = gated
        results = []
        lock = threading.Lock()

        def client(i):
            # writes: never coalesced, so each one
            # occupies a real worker/queue slot
            st, body, hd = req(s, "POST", "/index/ov/query", f"Set({i}, f=9)".encode())
            with lock:
                results.append((st, hd))

        threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        # wait until the pipeline is saturated: 2 executing + 2 queued,
        # everyone else shed
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with lock:
                if len(results) >= 12:
                    break
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join()
        s.executor.execute = orig
        codes = sorted(st for st, _ in results)
        assert codes.count(200) == 4, codes
        assert codes.count(503) == 12, codes
        shed_headers = [hd for st, hd in results if st == 503]
        assert all(hd.get("Retry-After") == "3" for hd in shed_headers)
        stats = s.pipeline.stats()
        assert stats["classes"]["interactive"]["sheds"] == 12
        # the registry carries the same counters for /metrics
        snap = metrics.snapshot()
        assert snap.get("pipeline.sheds;cls:interactive", 0) >= 12
    finally:
        s.close()


# -- singleflight coalescing ------------------------------------------------


def test_identical_concurrent_queries_coalesce(tmp_path):
    s = make_server(tmp_path)
    try:
        seed(s, "co")
        calls = []
        orig = s.executor.execute

        def slow(index, query, shards=None, opt=None):
            calls.append(1)
            time.sleep(0.25)
            return orig(index, query, shards, opt)

        s.executor.execute = slow
        results = []
        lock = threading.Lock()

        def client():
            st, body, _ = req(s, "POST", "/index/co/query", b"Count(Row(f=2))")
            with lock:
                results.append((st, body))

        threads = [threading.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s.executor.execute = orig
        assert all(st == 200 and body == {"results": [6]} for st, body in results)
        hits = s.pipeline.stats()["coalesce_hits"]
        assert hits >= 1
        # every coalesced duplicate saved one execution
        assert len(calls) <= 8 - hits
    finally:
        s.close()


def test_permuted_argument_order_queries_coalesce(tmp_path):
    """Regression (ISSUE 4 satellite 1): singleflight used to key on
    raw PQL text, so Intersect(Row(a),Row(b)) vs Intersect(Row(b),
    Row(a)) never coalesced. Keys are now the canonical plan hash —
    permuted spellings of one query attach to one in-flight leader."""
    s = make_server(tmp_path)
    try:
        seed(s, "perm")
        orig = s.executor.execute

        def slow(index, query, shards=None, opt=None):
            time.sleep(0.25)
            return orig(index, query, shards, opt)

        s.executor.execute = slow
        spellings = [
            b"Count(Intersect(Row(f=1), Row(f=2)))",
            b"Count(Intersect(Row(f=2), Row(f=1)))",
            b"Count(Intersect( Row(f=2) , Row(f=1) ))",
        ]
        results = []
        lock = threading.Lock()

        def client(ci):
            st, body, _ = req(
                s, "POST", "/index/perm/query", spellings[ci % len(spellings)]
            )
            with lock:
                results.append((st, body))

        threads = [threading.Thread(target=client, args=(ci,)) for ci in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s.executor.execute = orig
        # Intersect of rows 1 (2 bits/shard) and 2 (3 bits/shard):
        # identical correct result for every spelling
        first = results[0][1]
        assert all(st == 200 and body == first for st, body in results)
        assert s.pipeline.stats()["coalesce_hits"] >= 1
    finally:
        s.close()


# -- a backlog behind one worker --------------------------------------------


@pytest.mark.parametrize("dispatch_enabled", [True, False])
def test_queued_same_shape_queries_run_one_execution_each(tmp_path, dispatch_enabled):
    """A lone worker stalled on the first request, the rest piled up
    behind it: the worker pops one entry and runs it, so every request
    is answered right by its own execution, with or without a dispatch
    engine (wide waves are tests/test_dispatch.py's)."""
    s = make_server(
        tmp_path,
        pipeline_interactive_workers=1,
        dispatch_enabled=dispatch_enabled,
    )
    try:
        seed(s, "ba", n_rows=4)
        gate = threading.Event()
        exec_calls = []
        orig = s.executor.execute

        def gated(index, query, shards=None, opt=None):
            exec_calls.append(query)
            if len(exec_calls) == 1:
                gate.wait(10)  # stall the lone worker on the first query
            return orig(index, query, shards, opt)

        s.executor.execute = gated
        results = {}
        lock = threading.Lock()

        def client(row):
            st, body, _ = req(s, "POST", "/index/ba/query", f"Count(Row(f={row}))".encode())
            with lock:
                results[row] = (st, body)

        # first request occupies the worker; the rest pile into the queue
        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        deadline = time.monotonic() + 5
        while not exec_calls and time.monotonic() < deadline:
            time.sleep(0.005)
        rest = [threading.Thread(target=client, args=(r,)) for r in (1, 2, 3)]
        for t in rest:
            t.start()
        # wait until they are actually queued before releasing the gate
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if s.pipeline.stats()["classes"]["interactive"]["queue_depth"] >= 3:
                break
            time.sleep(0.005)
        gate.set()
        t0.join()
        for t in rest:
            t.join()
        s.executor.execute = orig
        # every request got ITS OWN correct per-row count
        for row in range(4):
            st, body = results[row]
            assert st == 200, body
            assert body == {"results": [2 * (row + 1)]}, (row, body)
        assert len(exec_calls) == 4  # one execution a request
        # `completed` is counted after the waiter is released
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            stats = s.pipeline.stats()
            if stats["classes"]["interactive"]["completed"] >= 4:
                break
            time.sleep(0.005)
        assert stats["classes"]["interactive"]["completed"] == 4
        assert stats["classes"]["interactive"]["sheds"] == 0
        assert stats["deadline_expired"] == 0
    finally:
        s.close()


# -- graceful drain ---------------------------------------------------------


def test_drain_completes_in_flight_work(tmp_path):
    s = make_server(tmp_path)
    seed(s, "dr")
    started = threading.Event()
    orig = s.executor.execute

    def slow(index, query, shards=None, opt=None):
        started.set()
        time.sleep(0.4)
        return orig(index, query, shards, opt)

    s.executor.execute = slow
    outcome = {}

    def client():
        outcome["resp"] = req(s, "POST", "/index/dr/query", b"Count(Row(f=1))")

    t = threading.Thread(target=client)
    t.start()
    assert started.wait(5)
    s.close()  # drains the pipeline before tearing anything down
    t.join(5)
    st, body, _ = outcome["resp"]
    assert st == 200 and body == {"results": [4]}
    # after the drain, new submissions are refused as shutting down
    with pytest.raises(Overloaded) as ei:
        s.pipeline.submit("interactive", lambda: None)
    assert ei.value.status == 503


def test_bare_pipeline_drain_fails_leftovers_503():
    pl = QueryPipeline(
        workers={"interactive": 1, "bulk": 1, "internal": 1},
        queue_limits={"interactive": 8, "bulk": 1, "internal": 1},
        drain_timeout=0.2,
    )
    gate = threading.Event()
    outcomes = []

    def submit_one(i):
        try:
            outcomes.append(("ok", pl.submit("interactive", lambda: gate.wait(10))))
        except BaseException as e:
            outcomes.append(("err", e))

    threads = [threading.Thread(target=submit_one, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)  # the first occupies the worker; two sit queued
    clean = pl.close(drain=0.2)  # worker is stuck: drain times out
    assert not clean
    gate.set()
    for t in threads:
        t.join(5)
    errs = [o for kind, o in outcomes if kind == "err"]
    assert any(isinstance(e, Overloaded) and e.status == 503 for e in errs)


# -- disabled pipeline ------------------------------------------------------


def test_pipeline_disabled_still_serves_with_deadlines(tmp_path):
    s = make_server(tmp_path, pipeline_enabled=False)
    try:
        assert s.pipeline is None
        seed(s, "nd")
        st, body, _ = req(s, "POST", "/index/nd/query", b"Count(Row(f=1))")
        assert st == 200 and body == {"results": [4]}
        # deadlines are honored even without the pipeline
        st, body, _ = req(
            s, "POST", "/index/nd/query?timeout=0.000001", b"Count(Row(f=1))"
        )
        assert st == 504
        st, body, _ = req(s, "GET", "/debug/pipeline")
        assert st == 200 and body == {"enabled": False}
    finally:
        s.close()


# -- closed-loop smoke: the serving surface lights up -----------------------


def test_closed_loop_smoke_populates_queue_wait_metrics(tmp_path):
    """A short closed-loop window
    through the full HTTP path populates the pipeline's queue-wait and
    admission metrics, /debug/pipeline, and the Prometheus families."""
    s = make_server(tmp_path, pipeline_interactive_workers=2)
    try:
        seed(s, "cl")
        queries = [f"Count(Row(f={r}))".encode() for r in range(4)]
        stop = time.perf_counter() + 0.8
        counts = [0] * 6
        errors = []

        def client(ci):
            i = ci
            try:
                while time.perf_counter() < stop:
                    st, body, _ = req(
                        s, "POST", "/index/cl/query", queries[i % len(queries)]
                    )
                    assert st == 200, body
                    counts[ci] += 1
                    i += 1
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(ci,)) for ci in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        total = sum(counts)
        assert total > 0
        st, stats, _ = req(s, "GET", "/debug/pipeline")
        assert st == 200
        icl = stats["classes"]["interactive"]
        assert icl["admitted"] > 0
        assert icl["completed"] > 0
        assert icl["queue_depth"] == 0  # drained after the window
        snap = metrics.snapshot()
        wait = snap.get("pipeline.wait_seconds.hist;cls:interactive")
        assert wait and wait["count"] > 0, sorted(snap)[:20]
        st, raw, _ = req(s, "GET", "/metrics", raw=True)
        text = raw.decode()
        assert "pilosa_pipeline_wait_seconds_count" in text
        assert 'pilosa_pipeline_admitted{cls="interactive"}' in text
        assert "pilosa_pipeline_queue_depth" in text
    finally:
        s.close()


# -- /debug/pipeline shape --------------------------------------------------


def test_debug_pipeline_snapshot_shape(tmp_path):
    s = make_server(tmp_path)
    try:
        seed(s, "sh")
        req(s, "POST", "/index/sh/query", b"Count(Row(f=1))")
        st, stats, _ = req(s, "GET", "/debug/pipeline")
        assert st == 200
        assert stats["enabled"] is True and stats["closing"] is False
        assert set(stats["classes"]) == {"interactive", "bulk", "internal"}
        for cls in stats["classes"].values():
            assert {
                "queue_depth",
                "queue_limit",
                "workers",
                "busy",
                "admitted",
                "sheds",
                "completed",
            } <= set(cls)
        for k in ("coalesce_hits", "deadline_expired"):
            assert k in stats
    finally:
        s.close()


def test_generated_config_loads_back_as_the_defaults():
    """What `generate-config` renders today is what the version before
    the pipeline's gang batching went rendered, less its one
    `pipeline-batch-max = 16` line: a file mended as
    docs/configuration.md "Retired options" says loads, unchanged."""
    from pilosa_tpu.server import config as config_mod

    toml = Config().to_toml()
    assert "pipeline-batch" not in toml
    assert Config.from_dict(config_mod.tomllib.loads(toml)) == Config()


@pytest.mark.parametrize("line", ["pipeline-batch-max = 16", "pipeline-batch-window = 0.002"])
def test_a_retired_pipeline_option_is_refused_by_name(line):
    """The loader's rule has no exception for keys an older version
    wrote: the server refuses to start, names the key and says where
    the way out is written down (and the docs do hold it)."""
    from pilosa_tpu.server import config as config_mod

    key = line.split(" = ")[0]
    with pytest.raises(ValueError) as ei:
        Config.from_dict(config_mod.tomllib.loads(line + "\n" + Config().to_toml()))
    assert key in str(ei.value) and "Retired options" in str(ei.value)
    docs = os.path.join(os.path.dirname(__file__), "..", "docs", "configuration.md")
    with open(docs) as f:
        retired = f.read().split("## Retired options")[1]
    assert f"`{key}`" in retired
