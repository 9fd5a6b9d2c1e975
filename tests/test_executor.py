"""Executor tests — every PQL call, CPU vs device paths bit-identical
(mirrors reference executor_test.go)."""

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.core.field import FIELD_TYPE_INT, FIELD_TYPE_TIME
from pilosa_tpu.executor import Executor, ValCount


@pytest.fixture()
def holder():
    h = Holder()  # in-memory
    h.open()
    return h


def execu(holder, policy="never"):
    return Executor(holder, device_policy=policy)


class TestBitmapCalls:
    def setup_holder(self, h):
        idx = h.create_index("i")
        f = idx.create_field("general")
        f.set_bit(10, 3)
        f.set_bit(10, SHARD_WIDTH + 1)
        f.set_bit(10, SHARD_WIDTH + 2)
        f.set_bit(11, 2)
        f.set_bit(11, SHARD_WIDTH + 2)
        f.set_bit(12, SHARD_WIDTH + 2)
        return idx

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_row(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Row(general=10)")
        assert res.columns().tolist() == [3, SHARD_WIDTH + 1, SHARD_WIDTH + 2]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_intersect(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Intersect(Row(general=10), Row(general=11))")
        assert res.columns().tolist() == [SHARD_WIDTH + 2]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_union(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Union(Row(general=10), Row(general=11))")
        assert res.columns().tolist() == [2, 3, SHARD_WIDTH + 1, SHARD_WIDTH + 2]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_difference(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Difference(Row(general=10), Row(general=11))")
        assert res.columns().tolist() == [3, SHARD_WIDTH + 1]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_xor(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Xor(Row(general=10), Row(general=11))")
        assert res.columns().tolist() == [2, 3, SHARD_WIDTH + 1]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_count(self, holder, policy):
        self.setup_holder(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "Count(Row(general=10))")
        assert res == 3
        (res,) = e.execute(
            "i", "Count(Intersect(Row(general=10), Row(general=12)))"
        )
        assert res == 1

    def test_empty_union(self, holder):
        self.setup_holder(holder)
        e = execu(holder)
        (res,) = e.execute("i", "Union()")
        assert res.columns().tolist() == []

    def test_empty_intersect_raises(self, holder):
        self.setup_holder(holder)
        e = execu(holder)
        with pytest.raises(ValueError):
            e.execute("i", "Intersect()")

    def test_set_and_clear(self, holder):
        idx = holder.create_index("i")
        idx.create_field("f")
        e = execu(holder)
        assert e.execute("i", "Set(3, f=10)") == [True]
        assert e.execute("i", "Set(3, f=10)") == [False]
        (row,) = e.execute("i", "Row(f=10)")
        assert row.columns().tolist() == [3]
        assert e.execute("i", "Clear(3, f=10)") == [True]
        assert e.execute("i", "Clear(3, f=10)") == [False]


class TestBSICalls:
    def setup_bsi(self, h):
        idx = h.create_index("i")
        idx.create_field("f")  # for filters
        idx.create_field(
            "foo", FieldOptions(type=FIELD_TYPE_INT, min=-100, max=3000)
        )
        e = execu(h)
        vals = {0: 20, 1: -5, 2: -5, 3: 10, SHARD_WIDTH: 30, SHARD_WIDTH + 2: 40}
        for col, v in vals.items():
            e.execute("i", f"SetValue(col={col}, foo={v})")
        # filter rows
        for col in [0, 1, 2, 3, SHARD_WIDTH, SHARD_WIDTH + 2]:
            e.execute("i", f"Set({col}, f=1)")
        for col in [0, 3, SHARD_WIDTH + 2]:
            e.execute("i", f"Set({col}, f=2)")
        return vals

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_sum(self, holder, policy):
        vals = self.setup_bsi(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", 'Sum(field="foo")')
        assert res == ValCount(sum(vals.values()), len(vals))
        (res,) = e.execute("i", 'Sum(Row(f=2), field="foo")')
        assert res == ValCount(20 + 10 + 40, 3)

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_min_max(self, holder, policy):
        self.setup_bsi(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", 'Min(field="foo")')
        assert res == ValCount(-5, 2)
        (res,) = e.execute("i", 'Max(field="foo")')
        assert res == ValCount(40, 1)
        (res,) = e.execute("i", 'Min(Row(f=2), field="foo")')
        assert res == ValCount(10, 1)
        (res,) = e.execute("i", 'Max(Row(f=2), field="foo")')
        assert res == ValCount(40, 1)

    @pytest.mark.parametrize("policy", ["never", "always"])
    @pytest.mark.parametrize(
        "q,want",
        [
            ("Range(foo > 20)", {SHARD_WIDTH, SHARD_WIDTH + 2}),
            ("Range(foo >= 20)", {0, SHARD_WIDTH, SHARD_WIDTH + 2}),
            ("Range(foo < 10)", {1, 2}),
            ("Range(foo <= 10)", {1, 2, 3}),
            ("Range(foo == -5)", {1, 2}),
            ("Range(foo != -5)", {0, 3, SHARD_WIDTH, SHARD_WIDTH + 2}),
            ("Range(foo != null)", {0, 1, 2, 3, SHARD_WIDTH, SHARD_WIDTH + 2}),
            ("Range(foo >< [10, 30])", {0, 3, SHARD_WIDTH}),
            # out-of-range guards
            ("Range(foo > 5000)", set()),
            ("Range(foo < -200)", set()),
            # fully-encompassing → not-null
            ("Range(foo < 99999)", {0, 1, 2, 3, SHARD_WIDTH, SHARD_WIDTH + 2}),
        ],
    )
    def test_range(self, holder, policy, q, want):
        self.setup_bsi(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", q)
        assert set(res.columns().tolist()) == want

    def test_range_as_filter(self, holder):
        self.setup_bsi(holder)
        for policy in ("never", "always"):
            e = execu(holder, policy)
            (res,) = e.execute("i", 'Count(Range(foo > 0))')
            assert res == 4
            (res,) = e.execute("i", 'Sum(Range(foo > 0), field="foo")')
            assert res == ValCount(20 + 10 + 30 + 40, 4)


class TestTopN:
    def setup_topn(self, h):
        idx = h.create_index("i")
        f = idx.create_field("f")
        other = idx.create_field("other")
        e = execu(h)
        # row 0: 5 bits, row 10: 3 bits, row 20: 2 bits, row 30: 1 bit
        bits = []
        for col in range(5):
            bits.append((0, col))
        for col in range(3):
            bits.append((10, col))
        for col in [0, SHARD_WIDTH]:
            bits.append((20, col))
        bits.append((30, SHARD_WIDTH + 5))
        f.import_bits([b[0] for b in bits], [b[1] for b in bits])
        other.import_bits([0] * 3, [0, 1, 2])
        return e

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_topn_plain(self, holder, policy):
        self.setup_topn(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "TopN(f, n=2)")
        assert res == [{"id": 0, "count": 5}, {"id": 10, "count": 3}]
        (res,) = e.execute("i", "TopN(f)")
        assert res == [
            {"id": 0, "count": 5},
            {"id": 10, "count": 3},
            {"id": 20, "count": 2},
            {"id": 30, "count": 1},
        ]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_topn_with_src(self, holder, policy):
        self.setup_topn(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "TopN(f, Row(other=0), n=2)")
        # intersection with cols {0,1,2}: row0 → 3, row10 → 3, row20 → 1
        assert res == [{"id": 0, "count": 3}, {"id": 10, "count": 3}]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_topn_ids(self, holder, policy):
        self.setup_topn(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "TopN(f, ids=[10, 30])")
        assert res == [{"id": 10, "count": 3}, {"id": 30, "count": 1}]

    @pytest.mark.parametrize("policy", ["never", "always"])
    def test_topn_threshold(self, holder, policy):
        self.setup_topn(holder)
        e = execu(holder, policy)
        (res,) = e.execute("i", "TopN(f, threshold=2)")
        # row 20 has 2 bits total but 1 per shard: the threshold applies
        # per shard in the reference (fragment.top MinThreshold check), so
        # it is excluded here exactly as the reference excludes it.
        assert res == [
            {"id": 0, "count": 5},
            {"id": 10, "count": 3},
        ]


class TestTimeRange:
    def test_range_quantum_views(self, holder):
        idx = holder.create_index("i")
        idx.create_field(
            "f", FieldOptions(type=FIELD_TYPE_TIME, time_quantum="YMDH")
        )
        e = execu(holder)
        e.execute("i", "Set(2, f=1, 2010-01-01T00:00)")
        e.execute("i", "Set(3, f=1, 2010-01-02T00:00)")
        e.execute("i", "Set(4, f=1, 2010-01-05T00:00)")
        e.execute("i", "Set(5, f=1, 2010-02-01T00:00)")
        e.execute("i", "Set(6, f=1, 2011-01-01T00:00)")
        for policy in ("never", "always"):
            e2 = execu(holder, policy)
            (res,) = e2.execute(
                "i", "Range(f=1, 2010-01-01T00:00, 2010-01-03T00:00)"
            )
            assert res.columns().tolist() == [2, 3], policy
            (res,) = e2.execute(
                "i", "Range(f=1, 2010-01-01T00:00, 2012-01-01T00:00)"
            )
            assert res.columns().tolist() == [2, 3, 4, 5, 6], policy

    def test_auto_policy_estimates_time_range_views(self, holder):
        """The touched-container estimate must COUNT quantum views for
        a time-range Range (it was 0 before, so the auto policy never
        routed time ranges to the existing device lowering — VERDICT
        §6), and must still estimate 0 for an empty span."""
        from pilosa_tpu.pql import parse

        idx = holder.create_index("tr")
        idx.create_field(
            "f", FieldOptions(type=FIELD_TYPE_TIME, time_quantum="YMD")
        )
        e = execu(holder)
        for day in (1, 2, 5):
            e.execute("tr", f"Set(2, f=1, 2010-01-0{day}T00:00)")
        call = parse("Range(f=1, 2010-01-01T00:00, 2010-01-06T00:00)").calls[0]
        est = e._touched_containers("tr", call, 0)
        # row 1 occupies one container in each of: 3 day views, 1 month
        # view, 1 year view, plus the standard view union targets — the
        # exact count depends on quantum fan-out; what matters is that
        # the populated span is VISIBLE to the policy
        assert est > 0
        empty = parse("Range(f=1, 2015-01-01T00:00, 2015-01-06T00:00)").calls[0]
        assert e._touched_containers("tr", empty, 0) == 0
        # a batched Count over the populated span routes like the
        # policy's own estimate says (crossover default 64)
        e_auto = execu(holder, "auto")
        cnt_call = parse(
            "Count(Range(f=1, 2010-01-01T00:00, 2010-01-06T00:00))"
        ).calls[0]
        expect = (
            sum(
                e_auto._touched_containers("tr", cnt_call.children[0], s)
                for s in [0]
            )
            >= e_auto.auto_min_containers
        )
        assert e_auto._use_device_batched_decide("tr", cnt_call.children[0], [0]) is (
            False
        )  # single shard: batched path needs >= 2 shards
        assert isinstance(expect, bool)


class TestAutoPolicyEquivalence:
    def test_large_random_workload(self, holder):
        """Property test: CPU vs device identical on a random workload."""
        rng = np.random.default_rng(42)
        idx = holder.create_index("i")
        f = idx.create_field("f")
        rows = rng.integers(0, 50, size=3000)
        cols = rng.integers(0, 2 * SHARD_WIDTH, size=3000)
        f.import_bits(rows.tolist(), cols.tolist())
        queries = [
            "Count(Row(f=1))",
            "Count(Intersect(Row(f=1), Row(f=2), Row(f=3)))",
            "Count(Union(Row(f=1), Row(f=2), Xor(Row(f=4), Row(f=5))))",
            "Count(Difference(Row(f=1), Row(f=2)))",
            "TopN(f, n=10)",
            "TopN(f, Row(f=7), n=5)",
            "Row(f=3)",
            "Union(Row(f=1), Row(f=9))",
        ]
        e_cpu = execu(holder, "never")
        e_dev = execu(holder, "always")
        for q in queries:
            r_cpu = e_cpu.execute("i", q)
            r_dev = e_dev.execute("i", q)
            for a, b in zip(r_cpu, r_dev):
                if hasattr(a, "columns"):
                    assert a.columns().tolist() == b.columns().tolist(), q
                else:
                    assert a == b, q


class TestBatchedShardPath:
    def test_batched_count_and_sum_match_cpu(self, holder):
        """Shard-batched device path (one dispatch over u32[S, W] stacks)
        vs the CPU per-shard path on a many-shard workload."""
        rng = np.random.default_rng(77)
        idx = holder.create_index("i")
        f = idx.create_field("f")
        from pilosa_tpu.core.field import FieldOptions

        v = idx.create_field("v", FieldOptions(type="int", min=-50, max=5000))
        n_shards = 6
        rows = rng.integers(0, 20, size=4000)
        cols = rng.integers(0, n_shards * SHARD_WIDTH, size=4000)
        f.import_bits(rows.tolist(), cols.tolist())
        vcols = rng.choice(n_shards * SHARD_WIDTH, size=1500, replace=False)
        vvals = rng.integers(-50, 5000, size=1500)
        v.import_values(vcols.tolist(), vvals.tolist())

        queries = [
            "Count(Row(f=1))",
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Union(Row(f=3), Xor(Row(f=4), Row(f=5)), Difference(Row(f=6), Row(f=7))))",
            "Count(Range(v > 100))",
            "Count(Range(v >< [0, 2500]))",
            'Sum(field="v")',
            'Sum(Row(f=1), field="v")',
            'Sum(Range(v != null), field="v")',
        ]
        e_cpu = execu(holder, "never")
        e_dev = execu(holder, "always")
        for q in queries:
            assert e_cpu.execute("i", q) == e_dev.execute("i", q), q


def _configured(ex):
    """What the retired environment switches used to decide."""
    return {
        "dispatch": ex.dispatch_engine is not None,
        "prefetch": ex.prefetcher is not None,
        "fusion": ex.fuser is not None,
        "stacked_max_batch": ex.stacked_scorer.max_batch,
        "auto_min_containers": ex.auto_min_containers,
        "device_cache_bytes": ex.device_cache.max_bytes,
        "oom_cpu_cooldown_s": ex.oom_cpu_cooldown_s,
    }


@pytest.mark.parametrize(
    "suffix,value",
    [
        ("DISPATCH", "0"),
        ("FUSION", "0"),
        ("PREFETCH", "0"),
        ("CHAIN_BATCH", "1"),
        ("CHAIN_MAX_BATCH", "7"),
        ("STACKED_MAX_BATCH", "7"),
        ("AUTO_DEVICE_MIN_CONTAINERS", "7"),
        ("PLAN_CACHE_DEVICE_BYTES", "0"),
        ("OOM_CPU_COOLDOWN_S", "0.5"),
    ],
)
def test_executor_reads_no_environment_switch(holder, monkeypatch, suffix, value):
    """The nine variables the executor once read straight from the
    environment decide nothing: the constructor's arguments (the
    server's config) are the one configuration surface."""
    from pilosa_tpu.plan.cache import PlanCache

    monkeypatch.delenv("PILOSA_" + suffix, raising=False)
    base = Executor(holder, plan_cache=PlanCache())
    monkeypatch.setenv("PILOSA_" + suffix, value)
    ex = Executor(holder, plan_cache=PlanCache())
    try:
        assert _configured(ex) == _configured(base)
        assert _configured(ex) == {
            "dispatch": True,
            "prefetch": True,
            "fusion": True,
            "stacked_max_batch": 32,
            "auto_min_containers": 64,
            "device_cache_bytes": 256 << 20,
            "oom_cpu_cooldown_s": 30.0,
        }
    finally:
        ex.close()
        base.close()
