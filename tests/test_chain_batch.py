"""Coalesced Count(chain) dispatches (opt-in via PILOSA_CHAIN_BATCH):
concurrent same-shape chains batch into one tree-count kernel launch,
bit-identical to the CPU roaring path (reference executor.go:704-1000
semantics; the batching itself has no reference analog). The default
serving path dispatches per query (rationale in
executor._execute_count) and must stay bit-identical under concurrency
too."""

import threading
import time

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
    # dispatch engine off: these tests pin the legacy thread-coalescing
    # path, where each caller thread enqueues behind the chain scorer's
    # dispatcher flag. With the engine on, cross-request combining
    # happens at the wave layer instead (covered by tests/test_dispatch.py).
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    dev._chain_batch = True  # coalescing is opt-in (see _make_chain_scorer)
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


def test_concurrent_same_shape_chains_coalesce(executors):
    """Deterministic coalescing (same technique as the TopN scorer
    test): hold the dispatcher flag so every caller enqueues, then run
    one drain round — all queries must land in ONE batched launch and
    every result must equal the CPU oracle."""
    cpu, dev = executors
    queries = [_chain(r, (r + 3) % 12, (r + 5) % 12, (r + 7) % 12) for r in range(6)]
    want = [cpu.execute("i", q) for q in queries]

    s = dev.chain_scorer
    with s._lock:
        s._dispatching = True  # this thread plays the leader
    results = [None] * len(queries)

    def run(i):
        results[i] = dev.execute("i", queries[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(queries))]
    for t in threads:
        t.start()
    deadline = time.time() + 10
    enqueued = False
    while time.time() < deadline:
        with s._lock:
            if sum(len(e[1]) for e in s._pending.values()) == len(queries):
                enqueued = True
                break
        time.sleep(0.001)
    s._dispatch_loop()
    for t in threads:
        t.join()
    assert enqueued, "callers never enqueued behind the held dispatcher"
    assert results == want
    # same tree shape + same leaf shapes = one key = one coalesced launch
    assert s.dispatches == 1
    assert s.batched_queries == len(queries)


def test_chain_batch_pads_with_repeat(executors):
    """3 coalesced chains pad to pow2 4 by repeating a real source
    (leaves tuples have no zeros_like); pad lane results are never
    assigned, so counts stay exact."""
    from pilosa_tpu.executor.batcher import _Slot
    from pilosa_tpu.pql import parse

    cpu, dev = executors
    queries = [_chain(r, r + 2, r + 4, r + 6) for r in range(3)]
    want = [cpu.execute("i", q) for q in queries]

    slots, tree_ref = [], None
    for q in queries:
        call = parse(q).calls[0].children[0]
        leaves, tree = dev._tree_leaves("i", call, [0, 1, 2])
        tree_ref = tree
        slots.append(_Slot(tuple(leaves)))
    dev.chain_scorer._fill(slots, tree_ref)
    got = [[int(np.asarray(s.result).reshape(-1)[0])] for s in slots]
    assert got == want
    assert any(w[0] > 0 for w in want)  # not vacuously zero


def test_default_direct_path_concurrent_identical(executors):
    """With the gate OFF (shipped default), concurrent chains dispatch
    per-query and stay bit-identical to the CPU oracle."""
    cpu, dev = executors
    dev._chain_batch = False
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
    assert dev.chain_scorer.dispatches == 0  # scorer never engaged


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
