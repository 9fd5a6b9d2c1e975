"""Device-policy autotune tests (executor/autotune.py): the crossover
comes from measured dispatch RTT vs per-container CPU cost, a high-RTT
rig routes small queries to CPU with NO env var, and a wedged device
never stalls startup."""


from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.autotune import (
    MAX_CROSSOVER,
    MIN_CROSSOVER,
    autotune_executor,
    measure_cpu_container_ms,
    tuned_min_containers,
)


class TestCrossoverMath:
    def test_slow_dispatch(self):
        # a deployment whose dispatch costs 66 ms against 0.018 ms/ctr
        got = tuned_min_containers(dispatch_ms=66.0, cpu_ms_per_container=0.018)
        assert 3000 <= got <= 4000, got

    def test_fast_dispatch(self):
        got = tuned_min_containers(dispatch_ms=1.5, cpu_ms_per_container=0.018)
        assert 50 <= got <= 120, got

    def test_clamps(self):
        assert tuned_min_containers(0.0001, 10.0) == MIN_CROSSOVER
        assert tuned_min_containers(1e9, 0.001) == MAX_CROSSOVER

    def test_unmeasurable_device_keeps_none(self, monkeypatch):
        from pilosa_tpu.executor import autotune

        monkeypatch.setattr(autotune, "measure_dispatch_ms", lambda **kw: None)
        assert tuned_min_containers(cpu_ms_per_container=0.02) is None

    def test_cpu_measurement_is_sane(self):
        ms = measure_cpu_container_ms(reps=3)
        assert 0.0001 < ms < 10.0, ms


class TestExecutorAdoption:
    def _executor(self):
        h = Holder()
        idx = h.create_index("i")
        idx.create_field("f")
        for r in range(4):
            for c in range(0, SHARD_WIDTH, SHARD_WIDTH // 64):
                h.field("i", "f").set_bit(r, c)
        return Executor(h, device_policy="auto")

    def test_slow_dispatch_routes_small_queries_to_cpu_without_env(self):
        ex = self._executor()
        # simulated deployment measurement: a 66 ms dispatch
        autotune_executor(
            ex, blocking=True,
            measure=lambda: tuned_min_containers(66.0, 0.018),
        )
        assert ex.auto_min_containers > 3000
        from pilosa_tpu.pql import parse

        call = parse("Count(Row(f=1))").calls[0]
        assert not ex._use_device("i", call.children[0], 0)

    def test_colocated_routes_same_query_to_device(self):
        ex = self._executor()
        autotune_executor(
            ex, blocking=True,
            measure=lambda: tuned_min_containers(1.0, 0.018),
        )
        assert ex.auto_min_containers <= 64

    def test_unmeasurable_keeps_default(self):
        ex = self._executor()
        before = ex.auto_min_containers
        autotune_executor(ex, blocking=True, measure=lambda: None)
        assert ex.auto_min_containers == before

    def test_async_thread_lands(self):
        ex = self._executor()
        t = autotune_executor(ex, measure=lambda: 1234)
        t.join(timeout=10)
        assert ex.auto_min_containers == 1234
