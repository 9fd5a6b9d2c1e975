"""Continuous-batching async dispatch engine (ISSUE 8): heterogeneous
waves bit-identical to the CPU oracle, wave-level singleflight dedup,
overlap correctness under concurrent writes, deadline cancellation of
queued-but-unlaunched items, the gang/serial bypass (PR 5/6
determinism contract), engine drain on close (bare and via server),
the read-pool close/submit race regression, and the /debug/dispatch +
metrics surface.

The engine is ON by default for bare executors, so
the whole tier-1 suite exercises the routed path implicitly; these
tests pin the engine-specific behaviors explicitly."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.core.field import FIELD_TYPE_INT
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.server import deadline as dl_mod
from pilosa_tpu.server.deadline import Deadline, DeadlineExceeded
from pilosa_tpu.utils import metrics


@pytest.fixture
def holder():
    h = Holder()  # in-memory
    h.open()
    return h


def seed_mixed(h, n_shards=3):
    """Multi-shard index with a set field and a BSI field — enough
    surface for TopN / Count / Sum / chain plans in one wave."""
    rng = np.random.default_rng(9)
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type=FIELD_TYPE_INT, min=-50, max=5000))
    rows = rng.integers(0, 12, size=3000)
    cols = rng.integers(0, n_shards * SHARD_WIDTH, size=3000)
    f.import_bits(rows.tolist(), cols.tolist())
    vcols = rng.choice(n_shards * SHARD_WIDTH, size=800, replace=False)
    vvals = rng.integers(-50, 5000, size=800)
    v.import_values(vcols.tolist(), vvals.tolist())


# heterogeneous plan mix: bitmap, count, TopN, BSI Sum, fused chains
MIXED_QUERIES = [
    "Row(f=1)",
    "Count(Row(f=2))",
    "TopN(f, n=5)",
    "TopN(f, Row(f=3), n=4)",
    'Sum(field="v")',
    'Sum(Row(f=1), field="v")',
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Count(Union(Row(f=3), Xor(Row(f=4), Row(f=5)), Difference(Row(f=6), Row(f=7))))",
    "Count(Range(v > 100))",
]


def _gated_executor(h, **kw):
    """Device executor whose FIRST _execute blocks on a gate: wave 1
    occupies the single in-flight slot while everything submitted
    meanwhile piles into the queue, so wave 2 is provably wide."""
    ex = Executor(
        h, device_policy="always", dispatch_enabled=True,
        dispatch_max_inflight=1, dispatch_max_wave=32, **kw
    )
    orig = ex._execute
    gate = threading.Event()
    first = threading.Event()

    def gated(index, query, shards=None, opt=None):
        if not first.is_set():
            first.set()
            assert gate.wait(10), "test gate never released"
        return orig(index, query, shards, opt)

    ex._execute = gated
    return ex, gate, first


def _run_clients(ex, queries, index="i"):
    results = {}
    errors = {}
    lock = threading.Lock()

    def client(i, q):
        try:
            r = ex.execute(index, q)
        except BaseException as e:
            with lock:
                errors[i] = e
            return
        with lock:
            results[i] = r

    ts = [
        threading.Thread(target=client, args=(i, q))
        for i, q in enumerate(queries)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results, errors


def _wait_queued(engine, n, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if engine.stats()["queued"] >= n:
            return
        time.sleep(0.005)
    raise AssertionError(
        f"queue never reached {n}: {engine.stats()}"
    )


class TestHeterogeneousWave:
    def test_mixed_wave_bit_identical_to_cpu_oracle(self, holder):
        """TopN/Count/BSI Sum/chain plans coexisting in ONE wave return
        exactly what the blocking CPU oracle returns per query."""
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        want = {i: oracle.execute("i", q) for i, q in enumerate(MIXED_QUERIES)}

        ex, gate, first = _gated_executor(holder)
        try:
            # wave 1: a lone query holds the only slot at the gate
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            # everything else queues behind it -> one heterogeneous wave
            t_res = {}
            ts = []

            def client(i, q):
                t_res[i] = ex.execute("i", q)

            for i, q in enumerate(MIXED_QUERIES):
                t = threading.Thread(target=client, args=(i, q))
                t.start()
                ts.append(t)
            _wait_queued(ex.dispatch_engine, len(MIXED_QUERIES))
            gate.set()
            for t in ts:
                t.join()
            blocker.join()
            for i, q in enumerate(MIXED_QUERIES):
                assert t_res[i] == want[i], q
            st = ex.dispatch_engine.stats()
            # the drained wave really was wide and really combined
            # heterogeneous members into one execution
            assert st["waves"] >= 2
            assert st["combined_items"] >= len(MIXED_QUERIES) - 1
        finally:
            gate.set()
            ex.close()

    def test_duplicate_queries_dedup_to_one_execution(self, holder):
        """Wave-level singleflight: identical plans queued in the same
        wave execute once; every waiter gets the shared result."""
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        (want,) = oracle.execute("i", "Count(Row(f=1))")

        ex, gate, first = _gated_executor(holder)
        try:
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            dup_queries = ["Count(Row(f=1))"] * 6
            ts = []
            res = {}

            def client(i):
                res[i] = ex.execute("i", dup_queries[i])

            for i in range(len(dup_queries)):
                t = threading.Thread(target=client, args=(i,))
                t.start()
                ts.append(t)
            _wait_queued(ex.dispatch_engine, len(dup_queries))
            gate.set()
            for t in ts:
                t.join()
            blocker.join()
            for i in range(len(dup_queries)):
                assert res[i] == [want]
            assert ex.dispatch_engine.stats()["dedup_hits"] >= 5
        finally:
            gate.set()
            ex.close()


class TestOverlapCorrectness:
    def test_read_after_write_never_stale_mid_wave(self, holder):
        """A read submitted AFTER a write completes must observe that
        write even when an earlier wave (started pre-write) is still
        executing — generation bumps mid-wave never serve stale
        blocks."""
        seed_mixed(holder)
        ex, gate, first = _gated_executor(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        try:
            (before,) = oracle.execute("i", "Count(Row(f=0))")
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            # wave 1 is mid-flight; write through the SAME executor
            # (writes bypass the engine and run inline)
            new_cols = [SHARD_WIDTH * 2 + 777 + k for k in range(5)]
            for c in new_cols:
                assert ex.execute("i", f"Set({c}, f=0)") == [True]
            (after,) = oracle.execute("i", "Count(Row(f=0))")
            assert after == before + len(new_cols)
            # read submitted after the write returned: queued behind
            # the stalled wave, must see the post-write generation
            res = {}
            t = threading.Thread(
                target=lambda: res.update(r=ex.execute("i", "Count(Row(f=0))"))
            )
            t.start()
            _wait_queued(ex.dispatch_engine, 1)
            gate.set()
            t.join()
            blocker.join()
            assert res["r"] == [after]
        finally:
            gate.set()
            ex.close()


class TestDeadlines:
    def test_queued_item_deadline_cancels_without_hurting_wave(self, holder):
        """An item whose deadline expires while queued is cancelled at
        wave build (clients see DeadlineExceeded -> 504); wave-mates
        are unaffected."""
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        (want,) = oracle.execute("i", "Count(Row(f=2))")
        ex, gate, first = _gated_executor(holder)
        try:
            base_expired = metrics.snapshot().get(
                "pipeline.deadline_expired;stage:dispatch", 0
            )
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            outcome = {}

            def doomed():
                with dl_mod.activate(Deadline.after(0.15)):
                    try:
                        ex.execute("i", "Count(Row(f=1))")
                    except DeadlineExceeded as e:
                        outcome["err"] = e

            def healthy():
                outcome["ok"] = ex.execute("i", "Count(Row(f=2))")

            td = threading.Thread(target=doomed)
            th = threading.Thread(target=healthy)
            td.start()
            th.start()
            _wait_queued(ex.dispatch_engine, 2)
            time.sleep(0.3)  # let the queued deadline lapse
            gate.set()
            td.join()
            th.join()
            blocker.join()
            assert isinstance(outcome.get("err"), DeadlineExceeded)
            assert outcome["ok"] == [want]  # wave unaffected
            st = ex.dispatch_engine.stats()
            assert st["deadline_expired"] >= 1
            assert (
                metrics.snapshot().get(
                    "pipeline.deadline_expired;stage:dispatch", 0
                )
                > base_expired
            )
        finally:
            gate.set()
            ex.close()

    def test_failed_wave_never_reexecutes_lapsed_deadline_item(self, holder):
        """Blast-radius fix (ISSUE 14): when a combined wave attempt
        fails, an item whose deadline lapsed DURING the failed attempt
        gets DeadlineExceeded (-> 504) instead of burning a full solo
        re-execution on a future its waiter already abandoned.
        Wave-mates still re-run solo and answer correctly."""
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        want3 = oracle.execute("i", "Count(Row(f=3))")
        want4 = oracle.execute("i", "Count(Row(f=4))")
        ex, gate, first = _gated_executor(holder)
        inner = ex._execute
        state = {"faulted": False, "solo_calls": []}

        def faulty(index, query, shards=None, opt=None):
            n = len(query.calls)
            if n == 4:  # the combined 3-item group (2 + 1 + 1 calls)
                state["faulted"] = True
                time.sleep(2.0)  # the doomed item's deadline lapses here
                raise RuntimeError("injected wave fault")
            if state["faulted"]:
                state["solo_calls"].append(n)
            return inner(index, query, shards, opt)

        ex._execute = faulty
        try:
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            outcome = {}

            def doomed():
                with dl_mod.activate(Deadline.after(1.2)):
                    try:
                        ex.execute("i", "Count(Row(f=1))Count(Row(f=2))")
                    except DeadlineExceeded as e:
                        outcome["err"] = e

            def healthy(name, q):
                outcome[name] = ex.execute("i", q)

            ts = [
                threading.Thread(target=doomed),
                threading.Thread(
                    target=healthy, args=("h3", "Count(Row(f=3))")
                ),
                threading.Thread(
                    target=healthy, args=("h4", "Count(Row(f=4))")
                ),
            ]
            for t in ts:
                t.start()
            _wait_queued(ex.dispatch_engine, 3)
            gate.set()
            for t in ts:
                t.join()
            blocker.join()
            assert state["faulted"], "combined wave attempt never ran"
            assert isinstance(outcome.get("err"), DeadlineExceeded)
            assert outcome["h3"] == want3 and outcome["h4"] == want4
            # the lapsed 2-call item was NEVER re-executed solo — only
            # its two healthy wave-mates were
            assert sorted(state["solo_calls"]) == [1, 1]
            st = ex.dispatch_engine.stats()
            assert st["fallbacks"] >= 1 and st["deadline_expired"] >= 1
        finally:
            gate.set()
            ex.close()
            oracle.close()


class TestBypass:
    """The PR 5/6 determinism contract: gang-dispatched execution keeps
    ExecOptions.serial and never reaches the async engine."""

    def test_serial_opt_bypasses_engine(self, holder):
        seed_mixed(holder)
        ex = Executor(holder, device_policy="always", dispatch_enabled=True)
        try:
            r = ex.execute("i", "Count(Row(f=1))", opt=ExecOptions(serial=True))
            oracle = Executor(
                holder, device_policy="never", dispatch_enabled=False
            )
            assert r == oracle.execute("i", "Count(Row(f=1))")
            # the engine never saw it (loop not even started)
            assert ex.dispatch_engine.stats()["items"] == 0
        finally:
            ex.close()

    def test_gang_and_cluster_modes_ineligible(self, holder):
        ex = Executor(holder, device_policy="always", dispatch_enabled=True)
        try:
            opt = ExecOptions()
            assert ex._engine_eligible(opt)
            ex.gang = object()  # multihost leader: gang dispatch owns it
            assert not ex._engine_eligible(opt)
            ex.gang = None
            ex.cluster = object()  # cluster fan-out owns routing
            assert not ex._engine_eligible(opt)
            ex.cluster = None
            assert not ex._engine_eligible(ExecOptions(remote=True))
            assert not ex._engine_eligible(ExecOptions(serial=True))
        finally:
            ex.gang = None
            ex.cluster = None
            ex.close()

    def test_writes_bypass_engine(self, holder):
        seed_mixed(holder)
        ex = Executor(holder, device_policy="always", dispatch_enabled=True)
        try:
            assert ex.execute("i", f"Set({SHARD_WIDTH + 123456}, f=9)") == [True]
            assert ex.dispatch_engine.stats()["items"] == 0
        finally:
            ex.close()


class TestDrain:
    def test_close_fails_queued_work_and_falls_back_inline(self, holder):
        """close() drains what it can within the budget, fails the
        rest; afterwards execute() runs inline (submit returns None) —
        shutdown can never strand or race a submit."""
        seed_mixed(holder)
        ex, gate, first = _gated_executor(holder)
        try:
            blocker_res = {}
            blocker = threading.Thread(
                target=lambda: blocker_res.update(
                    r=ex.execute("i", "Count(Row(f=0))")
                )
            )
            blocker.start()
            assert first.wait(10)
            errs = {}

            def stuck(i):
                try:
                    ex.execute("i", "Count(Row(f=1))")
                except BaseException as e:
                    errs[i] = e

            ts = [threading.Thread(target=stuck, args=(i,)) for i in range(3)]
            for t in ts:
                t.start()
            _wait_queued(ex.dispatch_engine, 3)
            assert ex.dispatch_engine.close(drain=0.2) is False
            for t in ts:
                t.join()
            assert len(errs) == 3
            for e in errs.values():
                assert "shut down" in str(e)
            gate.set()
            blocker.join()
            # the in-flight wave still completed for its waiter
            assert blocker_res["r"] is not None
            # post-close execution runs inline and stays correct
            oracle = Executor(
                holder, device_policy="never", dispatch_enabled=False
            )
            assert ex.execute("i", "Count(Row(f=2))") == oracle.execute(
                "i", "Count(Row(f=2))"
            )
        finally:
            gate.set()
            ex.close()

    def test_clean_close_after_traffic(self, holder):
        seed_mixed(holder)
        ex = Executor(holder, device_policy="always", dispatch_enabled=True)
        try:
            results, errors = _run_clients(ex, MIXED_QUERIES)
            assert not errors
            assert len(results) == len(MIXED_QUERIES)
            assert ex.dispatch_engine.close(drain=5.0) is True
        finally:
            ex.close()


class TestReadPoolRace:
    def test_close_during_concurrent_execution_is_clean(self, holder):
        """Regression for the _read_pool close/submit race: close()
        used to null the attr while a concurrent execute() held a local
        ref. Now shutdown drains pool users within the budget and late
        acquires run serially inline — every concurrent read completes
        correctly, before and after close."""
        seed_mixed(holder)
        # engine OFF so every execute drives the read pool from its own
        # caller thread — the racy pre-PR shape
        ex = Executor(holder, device_policy="always", dispatch_enabled=False)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        q = "Count(Union(Row(f=3), Xor(Row(f=4), Row(f=5)), Difference(Row(f=6), Row(f=7))))"
        want = oracle.execute("i", q)
        stop = time.monotonic() + 2.0
        errors = []
        done = []

        def reader():
            try:
                while time.monotonic() < stop:
                    assert ex.execute("i", q) == want
                done.append(True)
            except BaseException as e:  # pragma: no cover - the regression
                errors.append(e)

        ts = [threading.Thread(target=reader) for _ in range(6)]
        for t in ts:
            t.start()
        time.sleep(0.3)
        ex.close()  # mid-traffic: must drain or reject cleanly
        for t in ts:
            t.join()
        assert not errors, errors[0]
        assert len(done) == 6
        assert ex._read_pool is None


class TestServerSurface:
    def _mkserver(self, tmp_path, **cfg_kwargs):
        from pilosa_tpu.server import Config, Server

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

    def _post(self, s, path, body):
        r = urllib.request.Request(s.uri + path, data=body, method="POST")
        with urllib.request.urlopen(r) as resp:
            return json.loads(resp.read() or b"{}")

    def _get(self, s, path):
        with urllib.request.urlopen(s.uri + path) as resp:
            return resp.read()

    def test_debug_dispatch_metrics_and_server_close_drain(self, tmp_path):
        s = self._mkserver(tmp_path)
        try:
            assert s.executor.dispatch_engine is not None
            self._post(s, "/index/ds", b"{}")
            self._post(s, "/index/ds/field/f", b"{}")
            self._post(
                s, "/index/ds/field/f/import",
                json.dumps(
                    {"rowIDs": [0, 0, 1, 1, 1], "columnIDs": [1, 2, 3, 4, 5]}
                ).encode(),
            )
            for _ in range(3):
                got = self._post(s, "/index/ds/query", b"Count(Row(f=1))")
                assert got == {"results": [3]}
            snap = json.loads(self._get(s, "/debug/dispatch"))
            assert snap["enabled"] is True
            assert snap["items"] >= 3
            assert snap["waves"] >= 1
            assert 0.0 <= snap["device_idle_fraction"] <= 1.0
            for key in ("queued", "inflight_waves", "dedup_hits",
                        "combined_items", "deadline_expired"):
                assert key in snap
            prom = self._get(s, "/metrics").decode()
            assert "pilosa_dispatch_wave_size" in prom
            assert "pilosa_dispatch_queue_wait_seconds" in prom
            assert "pilosa_dispatch_inflight_depth" in prom
            assert "pilosa_dispatch_device_idle_fraction" in prom
            engine = s.executor.dispatch_engine
        finally:
            s.close()
        # server close closed the engine; snapshot says so
        assert engine.stats()["closing"] is True
        assert engine.stats()["queued"] == 0

    def test_cli_metrics_dispatch_flag(self, tmp_path, capsys):
        from pilosa_tpu.cli.main import main

        s = self._mkserver(tmp_path)
        try:
            self._post(s, "/index/dc", b"{}")
            self._post(s, "/index/dc/field/f", b"{}")
            self._post(s, "/index/dc/query", b"Set(1, f=1)")
            self._post(s, "/index/dc/query", b"Count(Row(f=1))")
            rc = main(["metrics", "--host", s.uri, "--dispatch"])
            assert rc == 0
            out = capsys.readouterr().out
            snap = json.loads(out)
            assert snap["enabled"] is True
            assert snap["items"] >= 1
        finally:
            s.close()

    def test_dispatch_disabled_config(self, tmp_path):
        s = self._mkserver(tmp_path, dispatch_enabled=False)
        try:
            assert s.executor.dispatch_engine is None
            snap = json.loads(self._get(s, "/debug/dispatch"))
            assert snap == {"enabled": False}
        finally:
            s.close()


class TestStageAhead:
    def test_stage_ahead_warms_queued_rows(self, holder):
        """The legacy thunk-based stage-ahead hook fires at wave launch
        for items still queued behind the wave; warming is advisory
        (errors swallowed, execution correct regardless). The
        plan-driven prefetcher (the default) is covered by
        test_plan_driven_prefetcher_stages_queued_operands."""
        seed_mixed(holder)
        # max_wave=1 so each launch leaves the rest of the backlog
        # queued — that leftover is what the peek prefetches
        ex = Executor(
            holder, device_policy="always", dispatch_enabled=True,
            dispatch_max_inflight=1, dispatch_max_wave=1,
            prefetch_enabled=False,
        )
        orig = ex._execute
        gate = threading.Event()
        first = threading.Event()

        def gated(index, query, shards=None, opt=None):
            if not first.is_set():
                first.set()
                assert gate.wait(10), "test gate never released"
            return orig(index, query, shards, opt)

        ex._execute = gated
        try:
            warmed = []
            orig_warm = ex._warm_query
            ex._warm_query = lambda *a: warmed.append(a) or orig_warm(*a)
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            res = {}

            def client(i):
                res[i] = ex.execute("i", f"Count(Row(f={i + 3}))")

            ts = [threading.Thread(target=client, args=(i,)) for i in range(3)]
            for t in ts:
                t.start()
            _wait_queued(ex.dispatch_engine, 3)
            gate.set()
            for t in ts:
                t.join()
            blocker.join()
            deadline = time.monotonic() + 2.0
            while not warmed and time.monotonic() < deadline:
                time.sleep(0.01)
            assert warmed  # the async stage-ahead hook really ran
            oracle = Executor(
                holder, device_policy="never", dispatch_enabled=False
            )
            for i in range(3):
                assert res[i] == oracle.execute("i", f"Count(Row(f={i + 3}))")
        finally:
            gate.set()
            ex.close()

    def test_plan_driven_prefetcher_stages_queued_operands(self, holder):
        """With the prefetcher enabled (the default), wave launch hands
        queued items' PLANS to the scheduler, which stages exactly the
        operand rows they name — observable as prefetch_issued on the
        stager and scheduled on the prefetcher; results stay
        bit-identical to the CPU oracle."""
        seed_mixed(holder)
        ex = Executor(
            holder, device_policy="always", dispatch_enabled=True,
            dispatch_max_inflight=1, dispatch_max_wave=1,
            prefetch_enabled=True,
        )
        assert ex.prefetcher is not None and ex.prefetcher.enabled
        orig = ex._execute
        gate = threading.Event()
        first = threading.Event()

        def gated(index, query, shards=None, opt=None):
            if not first.is_set():
                first.set()
                assert gate.wait(10), "test gate never released"
            return orig(index, query, shards, opt)

        ex._execute = gated
        try:
            blocker = threading.Thread(
                target=lambda: ex.execute("i", "Count(Row(f=0))")
            )
            blocker.start()
            assert first.wait(10)
            res = {}

            def client(i):
                res[i] = ex.execute("i", f"Count(Row(f={i + 3}))")

            ts = [threading.Thread(target=client, args=(i,)) for i in range(3)]
            for t in ts:
                t.start()
            _wait_queued(ex.dispatch_engine, 3)
            gate.set()
            for t in ts:
                t.join()
            blocker.join()
            deadline = time.monotonic() + 2.0
            while ex.prefetcher.scheduled == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert ex.prefetcher.scheduled > 0
            st = ex.dispatch_engine.stats()
            assert st["prefetch"]["enabled"] is True
            assert st["prefetch"]["scheduled"] == ex.prefetcher.scheduled
            oracle = Executor(
                holder, device_policy="never", dispatch_enabled=False
            )
            for i in range(3):
                assert res[i] == oracle.execute("i", f"Count(Row(f={i + 3}))")
        finally:
            gate.set()
            ex.close()


# -- who runs a wave (ISSUE 38) ------------------------------------------------


def _waves(how: str) -> float:
    return metrics.snapshot().get(f"dispatch.waves;how:{how}", 0)


class _held_slots:
    """Every runner slot taken, as by waves that compute: whatever is
    submitted meanwhile queues, and leaving hands the backlog over."""

    def __init__(self, engine):
        self.engine = engine

    def __enter__(self):
        for _ in range(self.engine.max_inflight):
            assert self.engine._slots.acquire(timeout=10)
        return self

    def __exit__(self, *exc):
        for _ in range(self.engine.max_inflight):
            self.engine._slots.release()
        return False


def _recording_executor(h, **kw):
    """Device executor that notes every ``_execute``: the thread, the
    number of calls, the engine's re-entry flag."""
    ex = Executor(h, device_policy="always", dispatch_enabled=True, **kw)
    orig = ex._execute
    seen = []

    def recording(index, query, shards=None, opt=None):
        seen.append(
            (threading.get_ident(), len(query.calls), ex.dispatch_engine.in_wave())
        )
        return orig(index, query, shards, opt)

    ex._execute = recording
    return ex, seen


class TestLedWave:
    def test_idle_engine_runs_the_wave_on_the_callers_thread(
        self, holder, monkeypatch
    ):
        from pilosa_tpu.executor import dispatch

        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        ex, seen = _recording_executor(holder)
        started = []

        class Recorded(threading.Thread):
            def start(self):
                started.append(self.name)
                super().start()

        monkeypatch.setattr(dispatch.threading, "Thread", Recorded)
        try:
            led, handed = _waves("led"), _waves("handed")
            for k, q in enumerate(MIXED_QUERIES[:4]):
                assert ex.execute("i", q) == oracle.execute("i", q)
                assert seen[k][0] == threading.get_ident()
                assert seen[k][2] is True  # the re-entry guard, on this thread
                assert not ex.dispatch_engine.in_wave()
            assert _waves("led") == led + 4 and _waves("handed") == handed
            st = ex.dispatch_engine.stats()
            assert (st["waves"], st["led"], st["handed"]) == (4, 4, 0)
            assert st["items"] == 4 and st["inflight_waves"] == 0
            assert 0.0 <= st["device_idle_fraction"] < 1.0
            # neither the loop nor a wave thread was ever needed
            assert started == [] and ex.dispatch_engine._loop_thread is None
            assert ex.dispatch_engine.close(drain=1.0) is True
        finally:
            monkeypatch.undo()
            ex.close()
            oracle.close()

    def test_held_slots_queue_the_submitters_into_one_handed_wave(self, holder):
        """Both slots taken: N submitters queue and come out as ONE
        handed wave of N, deduped and combined as ever."""
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        queries = ["Count(Row(f=1))"] * 3 + MIXED_QUERIES[1:5]
        want = [oracle.execute("i", q) for q in queries]
        ex, seen = _recording_executor(
            holder, dispatch_max_inflight=2, dispatch_max_wave=32
        )
        engine = ex.dispatch_engine
        try:
            led, handed = _waves("led"), _waves("handed")
            res = {}
            with _held_slots(engine):
                ts = [
                    threading.Thread(
                        target=lambda k=k, q=q: res.update({k: ex.execute("i", q)})
                    )
                    for k, q in enumerate(queries)
                ]
                for t in ts:
                    t.start()
                _wait_queued(engine, len(queries))
                assert seen == []  # nobody runs without a slot
            for t in ts:
                t.join(10)
            assert [res[k] for k in range(len(queries))] == want
            st = engine.stats()
            assert (st["waves"], st["led"], st["handed"]) == (1, 0, 1)
            assert _waves("led") == led and _waves("handed") == handed + 1
            assert st["dedup_hits"] == 2
            assert st["combined_items"] == len(queries) - 2
            # one combined execution, on a wave thread
            (ident, n_calls, in_wave), = seen
            assert ident != threading.get_ident() and in_wave
            assert n_calls == len(queries) - 2
            # the slots came back: the next request leads
            assert ex.execute("i", queries[0]) == want[0]
            assert engine.stats()["led"] == 1
        finally:
            ex.close()
            oracle.close()

    def test_a_submitter_does_not_overtake_one_that_is_queued(self, holder):
        """A free slot is not enough to lead: with somebody queued the
        newcomer queues behind it, and the loop serves both in order."""
        seed_mixed(holder)
        ex = Executor(
            holder, device_policy="always", dispatch_enabled=True,
            dispatch_max_inflight=1, dispatch_max_wave=32,
        )
        engine = ex.dispatch_engine
        orig = ex._execute
        order = []

        def recording(index, query, shards=None, opt=None):
            order.append([str(c) for c in query.calls])
            return orig(index, query, shards, opt)

        ex._execute = recording

        class LoopHeld:
            """The engine's semaphore; the loop's blocking acquire also
            waits for the test, a submitter's try does not."""

            def __init__(self, sem):
                self.sem, self.go = sem, threading.Event()

            def acquire(self, blocking=True, timeout=None):
                if blocking:
                    assert self.go.wait(10), "the loop was never let go"
                return self.sem.acquire(blocking, timeout)

            def release(self):
                self.sem.release()

        held = engine._slots = LoopHeld(engine._slots)
        try:
            res = {}

            def client(name, q):
                res[name] = ex.execute("i", q)

            assert held.sem.acquire(timeout=10)  # the one slot computes
            first = threading.Thread(target=client, args=("first", "Count(Row(f=1))"))
            first.start()
            _wait_queued(engine, 1)
            held.sem.release()  # a slot is free, the loop not yet there
            second = threading.Thread(target=client, args=("second", "Count(Row(f=2))"))
            second.start()
            _wait_queued(engine, 2)
            assert order == [] and engine.stats()["led"] == 0
            held.go.set()
            first.join(10)
            second.join(10)
            oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
            assert res["first"] == oracle.execute("i", "Count(Row(f=1))")
            assert res["second"] == oracle.execute("i", "Count(Row(f=2))")
            # one handed wave, the earlier submitter's call first
            (calls,) = order
            assert calls == ["Count(Row(f=1))", "Count(Row(f=2))"]
            st = engine.stats()
            assert (st["led"], st["handed"], st["combined_items"]) == (0, 1, 2)
        finally:
            held.go.set()
            ex.close()

    def test_led_item_with_a_lapsed_deadline_is_cancelled_before_execute(
        self, holder
    ):
        seed_mixed(holder)
        ex, seen = _recording_executor(holder)
        try:
            base = metrics.snapshot().get("pipeline.deadline_expired;stage:dispatch", 0)
            with dl_mod.activate(Deadline(time.monotonic() - 1.0)):
                with pytest.raises(DeadlineExceeded):
                    ex.execute("i", "Count(Row(f=1))")
            assert seen == []  # no parse, translate or kernel work
            st = ex.dispatch_engine.stats()
            assert st["deadline_expired"] == 1 and st["led"] == 1
            assert st["tenants"]["i"] == {"items": 1, "dedup_hits": 0, "expired": 1}
            assert (
                metrics.snapshot().get("pipeline.deadline_expired;stage:dispatch", 0)
                == base + 1
            )
            assert st["inflight_waves"] == 0 and not ex.dispatch_engine.in_wave()
            assert ex.execute("i", "Count(Row(f=1))")  # the slot came back
        finally:
            ex.close()

    def test_an_error_in_a_led_wave_reaches_its_caller_and_frees_the_slot(
        self, holder
    ):
        seed_mixed(holder)
        ex = Executor(
            holder, device_policy="always", dispatch_enabled=True,
            dispatch_max_inflight=2,
        )
        engine = ex.dispatch_engine
        orig = ex._execute
        where = []

        def broken(index, query, shards=None, opt=None):
            where.append((threading.get_ident(), engine.in_wave()))
            raise RuntimeError("injected: the wave's own error")

        ex._execute = broken
        try:
            for _ in range(3):  # more often than there are slots
                with pytest.raises(RuntimeError, match="the wave's own error"):
                    ex.execute("i", "Count(Row(f=1))")
                assert not engine.in_wave()
            assert where == [(threading.get_ident(), True)] * 3
            st = engine.stats()
            assert (st["led"], st["handed"], st["inflight_waves"]) == (3, 0, 0)
            # both slots are back
            with _held_slots(engine):
                pass
            ex._execute = orig
            assert ex.execute("i", "Count(Row(f=1))")
            assert engine.stats()["led"] == 4
        finally:
            ex.close()

    def test_close_waits_for_a_led_wave_and_later_submits_run_inline(self, holder):
        seed_mixed(holder)
        oracle = Executor(holder, device_policy="never", dispatch_enabled=False)
        want = oracle.execute("i", "Count(Row(f=0))")
        ex, gate, first = _gated_executor(holder)
        engine = ex.dispatch_engine
        try:
            res = {}
            leader = threading.Thread(
                target=lambda: res.update(r=ex.execute("i", "Count(Row(f=0))"))
            )
            leader.start()
            assert first.wait(10)
            assert engine.stats()["inflight_waves"] == 1 and engine.stats()["led"] == 1
            closed = {}
            closer = threading.Thread(
                target=lambda: closed.update(clean=engine.close(drain=10.0))
            )
            closer.start()
            closer.join(0.3)
            assert closer.is_alive()  # the led wave counts in flight
            gate.set()
            closer.join(10)
            leader.join(10)
            assert closed == {"clean": True} and res["r"] == want
            # closed: submit returns None, the caller runs inline
            assert ex.execute("i", "Count(Row(f=0))") == want
            st = engine.stats()
            assert st["closing"] and (st["items"], st["waves"]) == (1, 1)
        finally:
            gate.set()
            ex.close()
            oracle.close()
