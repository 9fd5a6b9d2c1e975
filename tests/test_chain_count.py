"""Count(chain) on the shard-batched device path: one fused tree-count
program a query, keyed by the tree's structure, bit-identical to the CPU
roaring path (reference executor.go:704-1000 semantics) sequentially
and under concurrency."""

import threading

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor


@pytest.fixture()
def executors(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    fld = h.create_index("i").create_field("f")
    rng = np.random.default_rng(17)
    rows, cols = [], []
    for shard in range(3):
        base = shard * SHARD_WIDTH
        # draw each row's columns from a small shared pool so chains of
        # Intersect/Union/Difference produce nonzero counts (a bug that
        # zeroes everything must not pass vacuously)
        pool = rng.integers(0, SHARD_WIDTH, size=500)
        for r in range(12):
            k = int(rng.integers(120, 260))
            rows += [r] * k
            cols += (base + rng.choice(pool, size=k)).tolist()
    fld.import_bits(rows, cols)
    cpu = Executor(h, device_policy="never")
    # dispatch engine off: each caller thread runs its own tree-count
    # launch (with the engine on, concurrent requests combine at the
    # wave layer, covered by tests/test_dispatch.py)
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    yield cpu, dev
    h.close()


def _chain(a, b, c, d):
    return (
        f"Count(Intersect(Union(Row(f={a}), Row(f={b})),"
        f" Union(Row(f={c}), Row(f={d}))))"
    )


def test_sequential_chains_bit_identical(executors):
    cpu, dev = executors
    for r in range(4):
        q = _chain(r, r + 1, r + 2, r + 3)
        assert cpu.execute("i", q) == dev.execute("i", q), q
    # different tree shapes take different jits and stay correct
    q2 = "Count(Difference(Union(Row(f=0), Row(f=1), Row(f=2)), Row(f=3)))"
    assert cpu.execute("i", q2) == dev.execute("i", q2)


def test_concurrent_same_shape_chains_identical(executors):
    """Concurrent same-shape chains dispatch per query and stay
    bit-identical to the CPU oracle."""
    cpu, dev = executors
    queries = [_chain(r, r + 1, r + 4, r + 6) for r in range(6)]
    want = [cpu.execute("i", q) for q in queries]
    results = [None] * len(queries)

    def run(i):
        results[i] = dev.execute("i", queries[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(queries))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == want
    assert any(w[0] > 0 for w in want)  # not vacuously zero


def test_distinct_shapes_do_not_mix(executors):
    """Two different tree structures queried concurrently resolve under
    different keys — each gets its own launch and the right answer."""
    cpu, dev = executors
    qa = _chain(0, 1, 2, 3)
    qb = "Count(Union(Intersect(Row(f=0), Row(f=1)), Row(f=4)))"
    want = {qa: cpu.execute("i", qa), qb: cpu.execute("i", qb)}
    results = {}

    def run(q):
        results[q] = dev.execute("i", q)

    threads = [threading.Thread(target=run, args=(q,)) for q in (qa, qb) * 3]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == want
