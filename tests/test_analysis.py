"""Invariant checker tests — static rules (good/bad fixture per rule,
including the planted PR 6 ``import_values`` gang-bypass shape), the
dynamic lock-order detector (AB/BA cycle, Condition integration,
self-deadlock), suppression handling, the repo-clean CI gate, and the
OrderedLock overhead bound on the executor-style hot path."""

import os
import threading
import time

import pytest

from pilosa_tpu.analysis import lint
from pilosa_tpu.analysis.lint import check_source
from pilosa_tpu.analysis.locks import (
    LockGraph,
    LockOrderError,
    OrderedLock,
    held_locks,
)


def rules_of(findings):
    return sorted({f.rule for f in findings})


def run_rule(src, rule, relpath="pilosa_tpu/somemod.py", **kw):
    return [
        f
        for f in check_source(src, relpath, **kw)
        if f.rule == rule
    ]


# -- lock-discipline ---------------------------------------------------------


class TestLockDiscipline:
    def test_blocking_result_under_lock_flagged(self):
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            x = fut.result()\n"
            "        return x\n"
        )
        fs = run_rule(src, "lock-discipline")
        assert len(fs) == 1 and fs[0].line == 4
        assert ".result()" in fs[0].message

    def test_block_until_ready_and_sleep_flagged(self):
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            arr.block_until_ready()\n"
            "            time.sleep(1)\n"
        )
        fs = run_rule(src, "lock-discipline")
        assert len(fs) == 2

    def test_result_outside_lock_clean(self):
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            fut = self._q.popleft()\n"
            "        return fut.result()\n"
        )
        assert run_rule(src, "lock-discipline") == []

    def test_condition_wait_not_flagged(self):
        # Condition.wait releases the lock — the one legal block-in-lock
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            while not self._done:\n"
            "                self._cond.wait(timeout=0.05)\n"
        )
        assert run_rule(src, "lock-discipline") == []

    def test_event_wait_under_lock_flagged(self):
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            self._ready_event.wait()\n"
        )
        assert len(run_rule(src, "lock-discipline")) == 1

    def test_self_deadlock_shape_flagged(self):
        # the pipeline.close() bug: a method that re-acquires self._mu
        # called from inside `with self._mu:`
        src = (
            "class P:\n"
            "    def _finish(self, e):\n"
            "        with self._mu:\n"
            "            self._inflight.pop(e, None)\n"
            "    def close(self):\n"
            "        with self._mu:\n"
            "            for e in self._q:\n"
            "                self._finish(e)\n"
        )
        fs = run_rule(src, "lock-discipline")
        assert len(fs) == 1 and "self-deadlock" in fs[0].message
        assert fs[0].line == 8

    def test_self_call_on_reentrant_lock_clean(self):
        src = (
            "class P:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.RLock()\n"
            "    def _finish(self, e):\n"
            "        with self._mu:\n"
            "            pass\n"
            "    def close(self):\n"
            "        with self._mu:\n"
            "            self._finish(1)\n"
        )
        assert run_rule(src, "lock-discipline") == []

    def test_nested_function_body_not_scanned(self):
        # a closure defined under the lock runs later, off-lock
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            def thunk():\n"
            "                return fut.result()\n"
            "            self._q.append(thunk)\n"
        )
        assert run_rule(src, "lock-discipline") == []


# -- lock-wrapper ------------------------------------------------------------


class TestLockWrapper:
    def test_module_level_bare_lock_flagged(self):
        src = "import threading\n_mu = threading.Lock()\n"
        fs = run_rule(src, "lock-wrapper")
        assert len(fs) == 1 and "module-level" in fs[0].message

    def test_instance_lock_in_uninstrumented_module_clean(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
        )
        assert run_rule(src, "lock-wrapper", relpath="pilosa_tpu/core/x.py") == []

    def test_instance_lock_in_instrumented_module_flagged(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
        )
        fs = run_rule(
            src, "lock-wrapper", relpath="pilosa_tpu/server/pipeline.py"
        )
        assert len(fs) == 1

    def test_orderedlock_clean_everywhere(self):
        src = (
            "from pilosa_tpu.analysis.locks import OrderedLock\n"
            "_mu = OrderedLock('mod.mu')\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._mu = OrderedLock('c.mu')\n"
        )
        assert (
            run_rule(src, "lock-wrapper", relpath="pilosa_tpu/server/pipeline.py")
            == []
        )

    def test_bare_condition_in_instrumented_module_flagged(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
        )
        fs = run_rule(
            src, "lock-wrapper", relpath="pilosa_tpu/executor/dispatch.py"
        )
        assert len(fs) == 1 and "Condition" in fs[0].message


# -- gang-routing (the planted PR 6 bug shape) -------------------------------

# the exact shape PR 6 shipped with: the owner-local write leg inside
# the shard_nodes() routing loop calling the fragment mutator directly
# instead of the *_local gang-replicating entry point — followers
# missed the replay and the next gloo collective diverged
PR6_IMPORT_VALUES_BUG = """
class API:
    def import_values(self, index, field, shard, cols, vals):
        for node in self.cluster.shard_nodes(index, shard):
            if node.id == self.cluster.node_id:
                f = self._field(index, field)
                f.import_values(cols, vals)
            else:
                self.client.import_values(node, index, field, cols, vals)
"""

PR6_FIXED = """
class API:
    def import_values(self, index, field, shard, cols, vals):
        for node in self.cluster.shard_nodes(index, shard):
            if node.id == self.cluster.node_id:
                self.import_values_local(index, field, cols, vals)
            else:
                self.client.import_values(node, index, field, cols, vals)
"""


class TestGangRouting:
    def test_planted_pr6_bug_detected(self):
        fs = run_rule(PR6_IMPORT_VALUES_BUG, "gang-routing")
        assert len(fs) == 1
        assert "gang replay" in fs[0].message
        assert "import_values_local" in fs[0].message

    def test_fixed_routing_clean(self):
        assert run_rule(PR6_FIXED, "gang-routing") == []

    def test_client_leg_not_flagged(self):
        # the remote leg goes through the internal HTTP client — fine
        fs = run_rule(PR6_IMPORT_VALUES_BUG, "gang-routing")
        assert all("client" not in f.message.split("(")[0] for f in fs)
        assert len(fs) == 1  # only the owner leg

    def test_mutator_outside_routing_loop_clean(self):
        src = (
            "def replay(frag, cols, vals):\n"
            "    frag.import_values(cols, vals)\n"
        )
        assert run_rule(src, "gang-routing") == []

    def test_other_mutators_flagged_too(self):
        src = (
            "class API:\n"
            "    def set(self, index, shard, row, col):\n"
            "        for node in self.cluster.shard_nodes(index, shard):\n"
            "            frag = self._frag(index, shard)\n"
            "            frag.set_bit(row, col)\n"
        )
        fs = run_rule(src, "gang-routing")
        assert len(fs) == 1 and "set_bit" in fs[0].message


# -- dispatch-bypass ---------------------------------------------------------


class TestDispatchBypass:
    def test_external_direct_execute_flagged(self):
        src = (
            "def fast_path(executor, index, q):\n"
            "    return executor._execute(index, q, None, None)\n"
        )
        fs = run_rule(src, "dispatch-bypass", relpath="pilosa_tpu/server/x.py")
        assert len(fs) == 1 and "_engine_eligible" in fs[0].message or (
            "eligibility" in fs[0].message
        )

    def test_whitelisted_modules_clean(self):
        src = (
            "def _run(self, item):\n"
            "    return self.executor._execute(item.index, item.q, None, None)\n"
        )
        assert (
            run_rule(
                src, "dispatch-bypass", relpath="pilosa_tpu/executor/dispatch.py"
            )
            == []
        )

    def test_executor_entry_point_without_predicate_flagged(self):
        src = (
            "class Executor:\n"
            "    def execute_fast(self, index, q):\n"
            "        return self._execute(index, q, None, None)\n"
        )
        fs = [
            f
            for f in check_source(
                src, "fixture_exec.py", fixture_role="executor"
            )
            if f.rule == "dispatch-bypass"
        ]
        assert len(fs) == 1 and "execute_fast" in fs[0].message

    def test_executor_entry_point_with_predicate_clean(self):
        src = (
            "class Executor:\n"
            "    def execute_fast(self, index, q, opt):\n"
            "        engine = self.dispatch_engine\n"
            "        if engine is not None and self._engine_eligible(opt):\n"
            "            return engine.submit(index, q, opt).result()\n"
            "        return self._execute(index, q, opt, None)\n"
        )
        fs = [
            f
            for f in check_source(
                src, "fixture_exec.py", fixture_role="executor"
            )
            if f.rule == "dispatch-bypass"
        ]
        assert fs == []


# -- jit-purity --------------------------------------------------------------


class TestJitPurity:
    def test_wall_clock_in_jit_flagged(self):
        src = (
            "import jax, time\n"
            "@jax.jit\n"
            "def k(x):\n"
            "    t = time.time()\n"
            "    return x + t\n"
        )
        fs = run_rule(src, "jit-purity")
        assert len(fs) == 1 and "wall-clock" in fs[0].message

    def test_partial_jit_detected(self):
        src = (
            "import functools, jax\n"
            "@functools.partial(jax.jit, donate_argnums=0)\n"
            "def k(x):\n"
            "    print(x)\n"
            "    return x\n"
        )
        fs = run_rule(src, "jit-purity")
        assert len(fs) == 1

    def test_host_rng_flagged_jax_random_ok(self):
        bad = (
            "@jax.jit\n"
            "def k(x):\n"
            "    return x + np.random.rand()\n"
        )
        good = (
            "@jax.jit\n"
            "def k(x, key):\n"
            "    return x + jax.random.uniform(key)\n"
        )
        assert len(run_rule(bad, "jit-purity")) == 1
        assert run_rule(good, "jit-purity") == []

    def test_metrics_and_locks_flagged(self):
        src = (
            "@jax.jit\n"
            "def k(x):\n"
            "    metrics.count('executor.calls')\n"
            "    with _mu:\n"
            "        pass\n"
            "    return x\n"
        )
        fs = run_rule(src, "jit-purity")
        assert len(fs) == 2

    def test_unjitted_function_clean(self):
        src = "def k(x):\n    return time.time()\n"
        assert run_rule(src, "jit-purity") == []

    def test_expansion_kernels_lint_clean(self):
        """The compressed-upload expansion kernels (ops.packed
        expand_blocks jit scatter, ops.pallas_kernels expand_runs_pallas)
        stay jit-pure — no wall-clock, host RNG, metrics, or locks
        inside the traced bodies."""
        import os

        root = os.path.join(
            os.path.dirname(__file__), "..", "pilosa_tpu", "ops"
        )
        for rel in ("packed.py", "pallas_kernels.py"):
            with open(os.path.join(root, rel)) as fp:
                src = fp.read()
            fs = run_rule(src, "jit-purity", relpath=f"pilosa_tpu/ops/{rel}")
            assert fs == [], "\n".join(f.format() for f in fs)


# -- donation-safety ---------------------------------------------------------


class TestDonationSafety:
    def test_use_after_donation_flagged(self):
        src = (
            "def f(buf):\n"
            "    out = ops.zeros_like_donated(buf)\n"
            "    return buf.sum()\n"
        )
        fs = run_rule(src, "donation-safety")
        assert len(fs) == 1 and fs[0].line == 3

    def test_rebind_after_donation_clean(self):
        src = (
            "def f(buf):\n"
            "    out = ops.zeros_like_donated(buf)\n"
            "    buf = out + 1\n"
            "    return buf.sum()\n"
        )
        assert run_rule(src, "donation-safety") == []

    def test_no_use_after_clean(self):
        src = (
            "def f(buf):\n"
            "    return ops.zeros_like_donated(buf)\n"
        )
        assert run_rule(src, "donation-safety") == []


# -- metrics-sync ------------------------------------------------------------


class TestMetricsSync:
    def test_unregistered_literal_flagged(self):
        src = "metrics.count('no.such.metric', 1)\n"
        fs = run_rule(src, "metrics-sync")
        assert len(fs) == 1 and "no.such.metric" in fs[0].message

    def test_registered_literal_clean(self):
        src = "metrics.count('executor.calls', 1)\n"
        assert run_rule(src, "metrics-sync") == []

    def test_constant_reference_checked(self):
        good = "metrics.gauge(metrics.ANALYSIS_LOCK_CYCLES, 1)\n"
        bad = "metrics.gauge(metrics.NO_SUCH_CONSTANT, 1)\n"
        assert run_rule(good, "metrics-sync") == []
        assert len(run_rule(bad, "metrics-sync")) == 1

    def test_non_metrics_receiver_ignored(self):
        src = "collections.Counter().count('whatever')\nstats.gauge('x', 1)\n"
        assert run_rule(src, "metrics-sync") == []


# -- env-config --------------------------------------------------------------


class TestEnvConfig:
    READS = (
        "import os\nX = os.environ.get('PILOSA_TPU_DEVICE_POLICY', 'auto')\n",
        "import os\nX = os.getenv('PILOSA_LOCK_STRICT')\n",
        "from os import environ\n",
    )

    def test_planted_read_on_hot_path_flagged(self):
        for relpath in (
            "pilosa_tpu/executor/executor.py",
            "pilosa_tpu/plan/cache.py",
            "pilosa_tpu/server/pipeline.py",
        ):
            for src in self.READS:
                fs = run_rule(src, "env-config", relpath=relpath)
                assert len(fs) == 1, (relpath, src)

    def test_deployment_setting_read_elsewhere_clean(self):
        for relpath in ("pilosa_tpu/server/config.py", "pilosa_tpu/analysis/locks.py"):
            for src in self.READS:
                assert run_rule(src, "env-config", relpath=relpath) == []

    def test_hot_path_reads_no_environment(self):
        pkg = os.path.join(lint.repo_root(), "pilosa_tpu")
        roots = [
            os.path.join(pkg, "executor"),
            os.path.join(pkg, "plan"),
            os.path.join(pkg, "server", "pipeline.py"),
        ]
        assert len(lint.iter_py_files(roots)) > 10
        fs = [f for f in lint.check_paths(roots) if f.rule == "env-config"]
        assert fs == [], "\n".join(f.format() for f in fs)


# -- suppressions ------------------------------------------------------------


class TestSuppressions:
    SRC = (
        "class C:\n"
        "    def run(self):\n"
        "        with self._mu:\n"
        "            x = fut.result()  # check: disable=lock-discipline (bounded: future already done)\n"
    )

    def test_same_line_suppression(self):
        assert run_rule(self.SRC, "lock-discipline") == []

    def test_line_above_suppression(self):
        src = (
            "class C:\n"
            "    def run(self):\n"
            "        with self._mu:\n"
            "            # check: disable=lock-discipline (bounded: future already done)\n"
            "            x = fut.result()\n"
        )
        assert run_rule(src, "lock-discipline") == []

    def test_wrong_rule_does_not_suppress(self):
        src = self.SRC.replace("lock-discipline", "jit-purity")
        assert len(run_rule(src, "lock-discipline")) == 1

    def test_strict_requires_reason(self):
        src = self.SRC.replace(" (bounded: future already done)", "")
        fs = check_source(src, "x.py", strict=True)
        assert any(
            f.rule == "suppression" and "reason" in f.message for f in fs
        )

    def test_strict_flags_unknown_rule(self):
        src = self.SRC.replace("lock-discipline", "no-such-rule")
        fs = check_source(src, "x.py", strict=True)
        assert any(
            f.rule == "suppression" and "unknown rule" in f.message for f in fs
        )
        # and the original finding survives (unknown rule suppresses
        # nothing for lock-discipline)
        assert any(f.rule == "lock-discipline" for f in fs)


# -- the CI gate: checker runs clean on this repo ----------------------------


class TestRepoClean:
    def test_check_exits_zero_on_repo(self):
        findings = lint.check_paths(None, strict=True)
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_cli_check_strict_exits_zero(self, capsys):
        from pilosa_tpu.cli.main import main

        assert main(["check", "--strict"]) == 0
        out = capsys.readouterr().out
        assert "clean" in out

    def test_cli_check_flags_planted_bug(self, tmp_path, capsys):
        bad = tmp_path / "planted.py"
        bad.write_text(PR6_IMPORT_VALUES_BUG)
        from pilosa_tpu.cli.main import main

        assert main(["check", str(bad)]) == 1
        err = capsys.readouterr().err
        assert "gang-routing" in err


# -- dynamic lock-order detection --------------------------------------------


@pytest.fixture()
def fresh_graph():
    """Isolated graph so tests don't pollute the process-global one."""
    g = LockGraph()
    yield g


class TestOrderedLock:
    def test_ab_ba_cycle_raises_under_tests(self, fresh_graph):
        a = OrderedLock("test.A", graph=fresh_graph)
        b = OrderedLock("test.B", graph=fresh_graph)
        with a:
            with b:
                pass
        with b:
            with pytest.raises(LockOrderError) as ei:
                a.acquire()
        assert "test.A" in str(ei.value) and "test.B" in str(ei.value)
        # the cycle is recorded once, canonically
        assert list(fresh_graph.cycles()) == [("test.A", "test.B")]

    def test_consistent_order_never_raises(self, fresh_graph):
        a = OrderedLock("test.A", graph=fresh_graph)
        b = OrderedLock("test.B", graph=fresh_graph)
        for _ in range(100):
            with a:
                with b:
                    pass
        assert fresh_graph.cycles() == {}

    def test_three_lock_cycle_detected(self, fresh_graph):
        a = OrderedLock("t3.A", graph=fresh_graph)
        b = OrderedLock("t3.B", graph=fresh_graph)
        c = OrderedLock("t3.C", graph=fresh_graph)
        with a, b:
            pass
        with b, c:
            pass
        with c:
            with pytest.raises(LockOrderError):
                a.acquire()

    def test_self_deadlock_always_raises(self, fresh_graph):
        a = OrderedLock("test.self", graph=fresh_graph)
        with a:
            with pytest.raises(LockOrderError, match="self-deadlock"):
                a.acquire()
        # and the stack is clean afterwards
        assert held_locks() == ()

    def test_reentrant_lock_reacquire_ok(self, fresh_graph):
        a = OrderedLock("test.re", reentrant=True, graph=fresh_graph)
        with a:
            with a:
                assert a._is_owned()
        assert held_locks() == ()

    def test_nonstrict_counts_instead_of_raising(self, fresh_graph, monkeypatch):
        monkeypatch.setenv("PILOSA_LOCK_STRICT", "0")
        a = OrderedLock("prod.A", graph=fresh_graph)
        b = OrderedLock("prod.B", graph=fresh_graph)
        with a, b:
            pass
        with b:
            with a:  # inversion: recorded, not raised
                pass
        assert list(fresh_graph.cycles()) == [("prod.A", "prod.B")]

    def test_same_name_instances_never_edge(self, fresh_graph):
        # two stagers' locks share a name: nesting across instances is
        # an ownership question, not an ordering one
        a1 = OrderedLock("inst.mu", graph=fresh_graph)
        a2 = OrderedLock("inst.mu", graph=fresh_graph)
        with a1:
            with a2:
                pass
        with a2:
            with a1:
                pass
        assert fresh_graph.edges() == {}

    def test_condition_wait_integration(self, fresh_graph):
        mu = OrderedLock("cond.mu", graph=fresh_graph)
        cond = threading.Condition(mu)
        state = []

        def waiter():
            with cond:
                while not state:
                    cond.wait(timeout=2.0)
                state.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            state.append("go")
            cond.notify_all()
        t.join(timeout=5)
        assert not t.is_alive() and state == ["go", "woke"]
        assert held_locks() == ()

    def test_timeout_and_nonblocking_acquire(self, fresh_graph):
        a = OrderedLock("nb.mu", graph=fresh_graph)
        assert a.acquire(blocking=False)
        # same-thread non-blocking re-acquire: returns False, no raise
        assert a.acquire(blocking=False) is False
        a.release()
        assert held_locks() == ()
        assert not a.locked()

    def test_cross_thread_contention(self, fresh_graph):
        a = OrderedLock("ct.mu", graph=fresh_graph)
        order = []

        def worker(i):
            with a:
                order.append(i)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(8)
        ]
        with a:
            for t in threads:
                t.start()
            time.sleep(0.02)
            order.append("main")
        for t in threads:
            t.join(timeout=5)
        assert order[0] == "main" and len(order) == 9

    def test_gauges_exported_on_cycle(self, fresh_graph, monkeypatch):
        from pilosa_tpu.utils import metrics

        monkeypatch.setenv("PILOSA_LOCK_STRICT", "0")
        a = OrderedLock("g.A", graph=fresh_graph)
        b = OrderedLock("g.B", graph=fresh_graph)
        with a, b:
            pass
        with b, a:
            pass
        snap = metrics.REGISTRY.snapshot()
        assert snap.get(metrics.ANALYSIS_LOCK_CYCLES) == 1
        assert snap.get(metrics.ANALYSIS_LOCK_GRAPH_EDGES, 0) >= 2


class TestMigratedModulesUseOrderedLock:
    def test_five_modules_instrumented(self):
        from pilosa_tpu.executor.dispatch import DispatchEngine  # noqa: F401
        from pilosa_tpu.executor.stager import DeviceStager  # noqa: F401
        from pilosa_tpu.plan.cache import PlanCache
        from pilosa_tpu.server.pipeline import QueryPipeline  # noqa: F401

        pc = PlanCache()
        assert isinstance(pc._mu, OrderedLock)
        # names are lock classes: check each migrated module constructs
        # its locks with the expected class names
        import importlib
        import inspect

        for mod, names in [
            ("pilosa_tpu.executor.dispatch", ["dispatch.mu"]),
            ("pilosa_tpu.server.pipeline", ["pipeline.mu"]),
            ("pilosa_tpu.executor.stager", ["stager.mu", "stager.ahead_mu"]),
            ("pilosa_tpu.plan.cache", ["plancache.mu"]),
            (
                "pilosa_tpu.parallel.multihost",
                ["multihost.gang.mu", "multihost.loopback.mu"],
            ),
        ]:
            src = inspect.getsource(importlib.import_module(mod))
            for n in names:
                assert f'OrderedLock("{n}")' in src, (mod, n)

    def test_pipeline_close_finishes_queued_signatured_entries(self):
        # regression for the close() self-deadlock: a queued entry WITH
        # a coalescing signature must drain without hanging
        from pilosa_tpu.server.pipeline import QueryPipeline, _Entry

        pl = QueryPipeline.__new__(QueryPipeline)
        pl._mu = OrderedLock("pipeline.mu")
        pl._cond = threading.Condition(pl._mu)
        pl._threads = []
        pl._closing = False
        pl._inflight = {}
        pl.drain_timeout = 0.1

        class _Q:
            def __init__(self, entries):
                self.q = __import__("collections").deque(entries)

        e = _Entry.__new__(_Entry)
        e.signature = ("sig", 1)
        e.event = threading.Event()
        e.result = None
        e.error = None
        pl._inflight[e.signature] = e
        pl._classes = {"read": _Q([e])}

        done = []

        def closer():
            pl.close(drain=0.05)
            done.append(True)

        t = threading.Thread(target=closer)
        t.start()
        t.join(timeout=5)
        assert done, "close() hung on a queued signatured entry"
        assert e.event.is_set() and e.error is not None
        assert pl._inflight == {}


class TestOverhead:
    @staticmethod
    def _per_acquire_delta():
        """Best-of-N per-iteration cost of `with lock: pass` for the
        instrumented wrapper vs bare threading.Lock, in seconds."""
        N = 50_000
        bare = threading.Lock()
        inst = OrderedLock("bench.mu", graph=LockGraph())

        def run(lock):
            t0 = time.perf_counter()
            for _ in range(N):
                with lock:
                    pass
            return time.perf_counter() - t0

        run(bare), run(inst)  # warm both paths
        t_bare = min(run(bare) for _ in range(5))
        t_inst = min(run(inst) for _ in range(5))
        return max(0.0, (t_inst - t_bare) / N)

    def test_wrapper_absolute_cost_bounded(self):
        # the wrapper adds one python call frame + a frozenset probe +
        # a thread-local append/pop; keep its absolute per-acquire cost
        # pinned so a regression (e.g. taking the graph mutex on the
        # fast path) shows up here
        delta = self._per_acquire_delta()
        assert delta < 20e-6, f"per-acquire overhead {delta * 1e6:.1f}us"

    def test_executor_microbench_overhead_under_5_percent(self):
        """The acceptance criterion: OrderedLock instrumentation costs
        <5% of the executor micro-bench. Measured as (per-acquire
        wrapper delta x acquisitions per query) against the measured
        per-query wall time — robust against CI noise, unlike
        subtracting two whole-bench timings."""
        from pilosa_tpu.core import Holder
        from pilosa_tpu.executor import Executor

        h = Holder()
        h.open()
        try:
            idx = h.create_index("i")
            f = idx.create_field("general")
            for row in range(16):
                for col in range(0, 4096, 7):
                    f.set_bit(row, col + row)
            ex = Executor(h, device_policy="never")
            q = "Count(Intersect(Row(general=1), Row(general=2)))"
            ex.execute("i", q)  # warm caches/compile

            acquires = [0]
            orig = OrderedLock.acquire

            def counting(self, blocking=True, timeout=-1):
                acquires[0] += 1
                return orig(self, blocking, timeout)

            OrderedLock.acquire = counting
            try:
                reps = 30
                t0 = time.perf_counter()
                for _ in range(reps):
                    ex.execute("i", q)
                elapsed = time.perf_counter() - t0
            finally:
                OrderedLock.acquire = orig
            n_per_query = acquires[0] / reps
            t_per_query = elapsed / reps
        finally:
            h.close()

        delta = self._per_acquire_delta()
        overhead = (n_per_query * delta) / t_per_query
        assert overhead < 0.05, (
            f"instrumentation {overhead:.2%} of query time "
            f"({n_per_query:.0f} acquires x {delta * 1e6:.1f}us over "
            f"{t_per_query * 1e3:.2f}ms)"
        )
