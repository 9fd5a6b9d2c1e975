"""Block-sparse TopN staging: kernel equivalence with the dense matrix
pass and executor-level bit-identity on tall sparse fragments (the
1B-row regime where dense candidate staging is not a memory plan)."""

import numpy as np
import pytest

from pilosa_tpu import SHARD_WIDTH, ops
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor


def _sparse_fragment(tmp_path, n_rows=300, seed=31):
    h = Holder(str(tmp_path / "data"))
    h.open()
    idx = h.create_index("i")
    fld = idx.create_field("f")
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for r in range(n_rows):
        k = int(rng.integers(1, 4))
        rows += [r] * k
        cols += rng.integers(0, SHARD_WIDTH, size=k).tolist()
    # a couple of hot rows so TopN has structure + an interesting src
    rows += [7] * 2000 + [11] * 1500
    cols += (np.arange(2000) * 17 % SHARD_WIDTH).tolist()
    cols += (np.arange(1500) * 29 % SHARD_WIDTH).tolist()
    fld.import_bits(rows, cols)
    return h


class TestSparseKernel:
    def test_matches_dense_scores(self, tmp_path):
        h = _sparse_fragment(tmp_path)
        frag = h.fragment("i", "f", "standard", 0)
        ids = frag.row_ids()
        blocks, brow, bslot = frag.sparse_row_blocks(ids)
        assert blocks.shape[0] == frag.sparse_block_count(ids)
        # src = row 7's words
        src64 = frag.row_words(7)
        src = np.ascontiguousarray(src64).view("<u4")
        dense = np.ascontiguousarray(frag.packed_rows(ids)).view("<u4").reshape(
            len(ids), -1
        )
        want = np.asarray(ops.intersection_counts_matrix(src, dense))
        got = np.asarray(
            ops.sparse_intersection_counts(
                src,
                np.ascontiguousarray(blocks).view("<u4"),
                brow,
                bslot,
                len(ids),
            )
        )
        assert np.array_equal(got, want)
        h.close()

    def test_empty_rows_score_zero(self, tmp_path):
        h = _sparse_fragment(tmp_path, n_rows=5)
        frag = h.fragment("i", "f", "standard", 0)
        ids = [0, 1, 9999]  # 9999 has no bits
        blocks, brow, bslot = frag.sparse_row_blocks(ids)
        src = np.ascontiguousarray(frag.row_words(7)).view("<u4")
        got = np.asarray(
            ops.sparse_intersection_counts(
                src, np.ascontiguousarray(blocks).view("<u4"), brow, bslot, 3
            )
        )
        assert got[2] == 0
        h.close()


class TestSparseTopN:
    def test_executor_bit_identity_and_path(self, tmp_path):
        h = _sparse_fragment(tmp_path)
        cpu = Executor(h, device_policy="never")
        dev = Executor(h, device_policy="always")
        q = "TopN(f, Row(f=7), n=10)"
        want = cpu.execute("i", q)
        got = dev.execute("i", q)
        assert want == got
        # the tall sparse candidate set must have taken the sparse path
        kinds = {k[1] for k in dev.stager._cache if len(k) > 1}
        assert "sparse_rows" in kinds
        h.close()

    def test_multishard_stacked_batched(self, tmp_path):
        h = Holder(str(tmp_path / "ms"))
        h.open()
        idx = h.create_index("i")
        fld = idx.create_field("f")
        rng = np.random.default_rng(41)
        rows, cols = [], []
        for shard in range(3):
            base = shard * SHARD_WIDTH
            for r in range(200):
                k = int(rng.integers(1, 4))
                rows += [r + 100] * k
                cols += (base + rng.integers(0, SHARD_WIDTH, size=k)).tolist()
            rows += [7] * 900
            cols += (base + rng.integers(0, SHARD_WIDTH, size=900)).tolist()
        fld.import_bits(rows, cols)
        cpu = Executor(h, device_policy="never")
        dev = Executor(h, device_policy="always")
        for q in ["TopN(f, Row(f=7), n=5)", "TopN(f, n=5)"]:
            assert cpu.execute("i", q) == dev.execute("i", q), q
        kinds = {k[1] for k in dev.stager._cache if len(k) > 1}
        assert "sparse_stack" in kinds
        # fused count tree: one jit per structure
        q = "Count(Intersect(Union(Row(f=101), Row(f=102)), Row(f=7)))"
        assert cpu.execute("i", q) == dev.execute("i", q)
        assert len(dev._tree_jits) == 1
        h.close()

    def test_pass2_reuses_pass1_scores(self, tmp_path, monkeypatch):
        """TopN's exact-count pass must not re-dispatch scoring for ids
        pass 1 already scored: the second round trip would be pure
        waste."""
        import pilosa_tpu.ops as ops_mod

        # skewed fixture: a dozen hot rows with distinct high overlap
        # vs a count-1 tail, so the ranked walk's threshold break
        # prunes inside the head chunk (the 1B-bench shape)
        h = Holder(str(tmp_path / "data"))
        h.open()
        fld = h.create_index("i").create_field("f")
        rows, cols = [], []
        for r in range(12):
            k = 200 + r * 50
            rows += [r] * k
            cols += ((np.arange(k) * (r + 3)) % SHARD_WIDTH).tolist()
        for r in range(300):  # singleton tail
            rows.append(100 + r)
            cols.append((r * 7919) % SHARD_WIDTH)
        fld.import_bits(rows, cols)
        cpu = Executor(h, device_policy="never")
        dev = Executor(h, device_policy="always")
        q = "TopN(f, Row(f=0), n=5)"
        want = cpu.execute("i", q)
        dev.execute("i", q)  # warm staging + compile

        calls = []
        for name in (
            "sparse_intersection_counts_stacked",
            "sparse_intersection_counts",
        ):
            orig = getattr(ops_mod, name)

            def spy(*a, _orig=orig, _name=name, **kw):
                calls.append(_name)
                return _orig(*a, **kw)

            monkeypatch.setattr(ops_mod, name, spy)
        got = dev.execute("i", q)
        assert got == want
        # one scoring dispatch for pass 1; pass 2 served from the carry
        assert len(calls) == 1
        h.close()

    def test_concurrent_topn_coalesce_stacked(self, tmp_path):
        """Concurrent TopN queries sharing the staged candidate chunk
        must coalesce into batched stacked-kernel launches (one device
        round-trip serves the batch) and stay bit-identical."""
        from concurrent.futures import ThreadPoolExecutor

        h = Holder(str(tmp_path / "cc"))
        h.open()
        fld = h.create_index("i").create_field("f")
        rng = np.random.default_rng(13)
        rows, cols = [], []
        for shard in range(3):
            base = shard * SHARD_WIDTH
            for r in range(16):
                k = 300 + 20 * r
                rows += [r] * k
                cols += (base + rng.integers(0, SHARD_WIDTH, size=k)).tolist()
            for r in range(150):
                rows.append(100 + r)
                cols.append(base + (r * 7919) % SHARD_WIDTH)
        fld.import_bits(rows, cols)
        cpu = Executor(h, device_policy="never")
        dev = Executor(h, device_policy="always")
        queries = [f"TopN(f, Row(f={r}), n=5)" for r in range(8)]
        want = {q: cpu.execute("i", q) for q in queries}
        dev.execute("i", queries[0])  # warm staging + compile

        with ThreadPoolExecutor(max_workers=8) as pool:
            for _ in range(3):
                futs = {q: pool.submit(dev.execute, "i", q) for q in queries}
                for q, f in futs.items():
                    assert f.result() == want[q], q
        h.close()

    def test_stacked_scorer_batches_deterministically(self, tmp_path):
        """Coalescing itself, without thread-timing luck: hold the
        dispatch lock while peers enqueue, then release — one batched
        launch must serve them all with per-query-correct scores."""
        import threading
        from concurrent.futures import ThreadPoolExecutor

        from pilosa_tpu import ops
        from pilosa_tpu.executor.batcher import BatchedScorer

        h = _sparse_fragment(tmp_path)
        frag = h.fragment("i", "f", "standard", 0)
        ids = tuple(frag.row_ids()[:32])
        blocks, brow, bslot = frag.sparse_row_blocks(list(ids))
        blocks32 = np.ascontiguousarray(blocks).view("<u4")
        bshard = np.zeros(len(brow), dtype=brow.dtype)  # single shard
        staged = (blocks32, brow, bslot, bshard, len(ids))

        scorer = BatchedScorer(
            max_batch=8,
            single_fn=lambda src, st: ops.sparse_intersection_counts_stacked(
                src, *st
            ),
            batch_fn=lambda srcs, st: ops.sparse_intersection_counts_stacked_batch_list(
                srcs, *st
            ),
        )
        key = (id(blocks32), id(brow))
        srcs = [
            np.ascontiguousarray(frag.row_words(r)).view("<u4")[None, :]
            for r in (7, 11, 0, 1)
        ]
        want = [
            np.asarray(ops.sparse_intersection_counts_stacked(s, *staged))
            for s in srcs
        ]

        # mark the dispatcher active so every score() call enqueues as
        # a waiter; run one dispatch round once all four are pending
        with scorer._lock:
            scorer._dispatching = True
        with ThreadPoolExecutor(max_workers=4) as pool:
            futs = [pool.submit(scorer.score, key, staged, s) for s in srcs]
            while sum(len(v[1]) for v in scorer._pending.values()) < 4:
                pass
            scorer._dispatch_loop()
            got = [f.result() for f in futs]
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        assert scorer.batched_queries == 4
        assert scorer.dispatches == 1
        h.close()

    def test_dense_fragment_keeps_dense_path(self, tmp_path):
        h = Holder(str(tmp_path / "dense"))
        h.open()
        idx = h.create_index("i")
        fld = idx.create_field("f")
        rng = np.random.default_rng(5)
        rows, cols = [], []
        for r in range(8):  # few rows, each spread over many containers
            rows += [r] * 4000
            cols += rng.integers(0, SHARD_WIDTH, size=4000).tolist()
        fld.import_bits(rows, cols)
        cpu = Executor(h, device_policy="never")
        dev = Executor(h, device_policy="always")
        q = "TopN(f, Row(f=1), n=4)"
        assert cpu.execute("i", q) == dev.execute("i", q)
        kinds = {k[1] for k in dev.stager._cache if len(k) > 1}
        assert "sparse_rows" not in kinds
        h.close()


class TestAdvisoryPrefetchNeverEvicts:
    """A hot set wider than the head chunk sends the TopN walk into the
    second chunk, which stages the third ahead on a side thread where
    the walk may go on to it. That prefetch is advisory: where it does
    not fit it must be skipped, or it pushes out the chunks the walk is
    scoring and every later query stages all of them again; and where
    the walk is sure to end in the second chunk it is not asked for."""

    # n=5: every shard has its threshold after the head chunk and meets
    # a cached count below it in the second, so the walk ends there.
    # n=130: the head chunk's 128 rows fix no threshold, so the walk may
    # go on past the second chunk and the third is asked for; the twelve
    # hot rows left fix it there and the one-bit tail breaks the walk,
    # so whatever holds the third chunk was staged ahead, not by the walk.
    ENDS = "TopN(f, Row(f=0), n=5)"
    MAY_GO_ON = "TopN(f, Row(f=0), n=130)"

    @staticmethod
    def _deep_walk_holder(tmp_path):
        h = Holder(str(tmp_path / "deep"))
        h.open()
        fld = h.create_index("i").create_field("f")
        rows, cols = [], []
        for shard in range(2):
            base = shard * SHARD_WIDTH
            # 140 hot rows over the same 50 columns: more than the 128
            # of the head chunk clear any threshold a TopN can reach
            for r in range(140):
                rows += [r] * 50
                cols += (base + np.arange(50)).tolist()
            # a one-bit tail long enough for a third chunk
            for r in range(4500):
                rows.append(1000 + r)
                cols.append(base + 100 + r)
        fld.import_bits(rows, cols)
        return h

    @staticmethod
    def _staged_chunks(ex):
        import threading
        import time

        for _ in range(200):  # let the side thread finish
            if not any(t.name == "stage-prefetch" for t in threading.enumerate()):
                break
            time.sleep(0.05)
        return sorted(k[2] for k in ex.stager._cache if k[1] == "sparse_stack")

    def test_prefetch_runs_where_it_fits_and_the_walk_may_go_on(self, tmp_path):
        from pilosa_tpu.utils import metrics

        h = self._deep_walk_holder(tmp_path)
        ex = Executor(h, device_policy="always")
        cpu = Executor(h, device_policy="never")
        starts = _counter(metrics.TOPN_PREFETCH_STARTS)
        assert ex.execute("i", self.MAY_GO_ON) == cpu.execute("i", self.MAY_GO_ON)
        assert self._staged_chunks(ex) == [128, 4096, 8192]
        assert _counter(metrics.TOPN_PREFETCH_STARTS) == starts + 1
        h.close()

    def test_prefetch_is_not_asked_for_where_the_walk_ends_in_the_chunk(self, tmp_path):
        """Without the walk's word the third chunk was built and held
        for nothing: 8 GiB at 128 shards, assembled on a side thread
        through the first half of the benchmark's window (PR 29)."""
        from pilosa_tpu.utils import metrics

        h = self._deep_walk_holder(tmp_path)
        ex = Executor(h, device_policy="always")
        cpu = Executor(h, device_policy="never")
        before = _decisions(), _counter(metrics.TOPN_PREFETCH_STARTS)
        for _ in range(2):
            assert ex.execute("i", self.ENDS) == cpu.execute("i", self.ENDS)
        assert self._staged_chunks(ex) == [128, 4096]
        assert (_decisions(), _counter(metrics.TOPN_PREFETCH_STARTS)) == before
        h.close()

    def test_prefetch_is_skipped_where_it_would_evict(self, tmp_path):
        from pilosa_tpu.executor.stager import DeviceStager
        from pilosa_tpu.utils import metrics

        h = self._deep_walk_holder(tmp_path)
        # room for the head chunk (2 MiB), the second (64 MiB) and the
        # source row, not for the third chunk's 8 MiB as well
        ex = Executor(
            h, device_policy="always", stager=DeviceStager(budget_bytes=70 << 20)
        )
        cpu = Executor(h, device_policy="never")
        q = self.MAY_GO_ON
        before = _decisions(), _counter(metrics.TOPN_PREFETCH_STARTS)
        assert ex.execute("i", q) == cpu.execute("i", q)
        assert self._staged_chunks(ex) == [128, 4096]
        misses = ex.stager.misses
        assert ex.execute("i", q) == cpu.execute("i", q)
        assert ex.stager.misses == misses  # nothing was pushed out
        assert self._staged_chunks(ex) == [128, 4096]
        # the third chunk was asked for by both walks and refused on the
        # bound alone: no thread, no count of its blocks
        after = _decisions(), _counter(metrics.TOPN_PREFETCH_STARTS)
        assert after[0] == {**before[0], "bound": before[0]["bound"] + 2}
        assert after[1] == before[1]
        h.close()

    def test_mesh_bundle_pads_every_shard_to_the_widest(self):
        from pilosa_tpu.executor.executor import _SpmdLazyScores, _StackedLazyScores

        counts = [3, 0, 9, 5]
        assert _StackedLazyScores._bundle_blocks(None, counts) == 32
        assert _SpmdLazyScores._bundle_blocks(None, counts) == 4 * 16


def _counter(name, **labels):
    from pilosa_tpu.utils import metrics

    key = metrics._flat_key(name, metrics._labels_key(labels))
    return metrics.snapshot().get(key, 0)


def _decisions():
    from pilosa_tpu.utils import metrics

    return {
        how: _counter(metrics.TOPN_PREFETCH_DECISIONS, how=how)
        for how in ("bound", "memo", "counted")
    }


def _ranked(counts):
    return [(i, c) for i, c in enumerate(counts)]


@pytest.mark.parametrize(
    "lists, has_n, T, mth, ends",
    [
        # each shard has its threshold and the chunk's last cached count is under it
        ([[9] * 130 + [1] * 5000] * 2, [True, True], [4, 7], 1, True),
        # one shard's threshold is not fixed and its list goes on
        ([[9] * 130 + [1] * 5000] * 2, [True, False], [4, 1 << 62], 1, False),
        # ... but a list that ends inside the chunk ends the walk whatever it scores
        ([[9] * 130 + [1] * 5000, [9] * 300], [True, False], [4, 1 << 62], 1, True),
        # the last cached count still clears the threshold: the walk goes on
        ([[9] * 5000], [True], [4], 1, False),
        # a count under the minimum is no break, the walk reads on to the list's end
        ([[9] * 130 + [1] * 5000], [True], [4], 2, False),
        ([[]], [False], [1 << 62], 1, True),
    ],
    ids=["break_in_chunk", "no_threshold", "list_ends", "no_break", "below_minimum", "empty"],
)
def test_chunk_ends_walk(lists, has_n, T, mth, ends):
    from pilosa_tpu.executor.executor import FIRST_CHUNK, _chunk_ends_walk

    pairs = [_ranked(c) for c in lists]
    got = _chunk_ends_walk(pairs, FIRST_CHUNK, np.array(has_n), np.array(T, dtype=np.int64), mth)
    assert got is ends


class TestPrefetchDecisionCost:
    """The advisory prefetch's question is asked by every request of a
    deep walk, so its cost follows what the walk reads: the block counts
    of the next chunk are kept with the rankings snapshot and the
    fragment generation, and a chunk the stager holds starts no thread."""

    Q = TestAdvisoryPrefetchNeverEvicts.MAY_GO_ON

    @staticmethod
    def _spy_threads(monkeypatch):
        import threading

        started = []

        class Spy(threading.Thread):
            def start(self):
                started.append(self.name)
                super().start()

        monkeypatch.setattr(threading, "Thread", Spy)
        return started

    @pytest.mark.parametrize(
        "budget, first, staged",
        [
            (None, "counted", [128, 4096, 8192]),
            (70 << 20, "bound", [128, 4096]),
        ],
        ids=["with_room", "without_room"],
    )
    def test_second_query_counts_nothing_and_starts_no_thread(
        self, tmp_path, monkeypatch, budget, first, staged
    ):
        from pilosa_tpu.executor.stager import DeviceStager
        from pilosa_tpu.utils import metrics

        deep = TestAdvisoryPrefetchNeverEvicts
        h = deep._deep_walk_holder(tmp_path)
        stager = DeviceStager(budget_bytes=budget) if budget else None
        ex = Executor(h, device_policy="always", stager=stager)
        cpu = Executor(h, device_policy="never")
        want = cpu.execute("i", self.Q)
        started = self._spy_threads(monkeypatch)
        starts = _counter(metrics.TOPN_PREFETCH_STARTS)

        # the first query decides by `first` (and stages ahead where
        # there is room); the second reads what the first left
        again = "memo" if first == "counted" else "bound"
        threads = 1 if budget is None else 0
        for how in (first, again):
            before = _decisions()
            assert ex.execute("i", self.Q) == want
            assert deep._staged_chunks(ex) == staged
            after = _decisions()
            grown = {k: after[k] - before[k] for k in after}
            assert grown == {"bound": 0, "memo": 0, "counted": 0, how: 1}
            assert started.count("stage-prefetch") == threads
        assert _counter(metrics.TOPN_PREFETCH_STARTS) - starts == threads
        h.close()

    def test_a_set_into_the_next_chunk_is_counted_and_seen(
        self, tmp_path, monkeypatch
    ):
        from pilosa_tpu.core import cache as cache_mod

        # no recalculate between the queries: the Set must show through
        # the generation, under the same rankings snapshot
        monkeypatch.setattr(cache_mod, "INVALIDATE_DEBOUNCE_SECONDS", 1e9)
        h = TestAdvisoryPrefetchNeverEvicts._deep_walk_holder(tmp_path)
        fld = h.index("i").field("f")
        # 96 more one-bit rows a shard: the third chunk then holds 512
        # candidates a shard, 1024 blocks, and one more container
        # crosses the bundle's power of two
        rows, cols = [], []
        for shard in range(2):
            for r in range(96):
                rows.append(5500 + r)
                cols.append(shard * SHARD_WIDTH + 100 + 4500 + r)
        fld.import_bits(rows, cols)
        for shard in range(2):
            h.fragment("i", "f", "standard", shard).cache.recalculate()
        ex = Executor(h, device_policy="always")
        cpu = Executor(h, device_policy="never")
        asked = []
        real = ex.stager.has_room

        def has_room(nbytes):
            asked.append(nbytes)
            return real(nbytes)

        monkeypatch.setattr(ex.stager, "has_room", has_room)
        block = 8192
        assert ex.execute("i", self.Q) == cpu.execute("i", self.Q)
        assert asked == [1024 * block, 1024 * block]
        TestAdvisoryPrefetchNeverEvicts._staged_chunks(ex)

        frag = h.fragment("i", "f", "standard", 0)
        snap = frag.cache.top()
        third = snap[4224 + 10][0]
        assert snap.chunk_blocks(4224, 12416, frag) == (512, False)
        assert ex.execute("i", f"Set({3 * 65536 + 7}, f={third})") == [True]
        assert frag.cache.top() is snap
        before = _decisions()
        del asked[:]
        assert ex.execute("i", self.Q) == cpu.execute("i", self.Q)
        after = _decisions()
        assert after["counted"] - before["counted"] == 1
        assert after["memo"] == before["memo"]
        # the bound still says 1024, the count says 1025 -> 2048
        assert asked == [1024 * block, 2048 * block]
        assert snap.chunk_blocks(4224, 12416, frag) == (513, False)
        TestAdvisoryPrefetchNeverEvicts._staged_chunks(ex)
        h.close()

    def test_per_shard_scorer_reads_the_same_memo(self, tmp_path):
        from pilosa_tpu.executor.executor import _chunk_blocks

        h = _sparse_fragment(tmp_path)
        frag = h.fragment("i", "f", "standard", 0)
        snap = frag.cache.top()
        ids = [p[0] for p in snap[0:128]]
        want = frag.sparse_block_count(ids)
        assert _chunk_blocks(snap, 0, 128, frag) == (want, True)
        assert _chunk_blocks(snap, 0, 128, frag) == (want, False)
        # a plain list (an ids= walk) has no memo and counts as before
        assert _chunk_blocks(list(snap), 0, 128, frag) == (want, True)
        assert _chunk_blocks(list(snap), 0, 128, frag) == (want, True)
        h.close()


def _old_seed(chunks, rids):
    """_ScoreCarry.seed as it was: a dict of every scored id, built to
    look up the few ids asked for."""
    lut = {}
    for ids, scores in chunks:
        lut.update(zip(ids, scores[: len(ids)].tolist()))
    return {rid: int(lut[rid]) for rid in rids if rid in lut}


class _Scored:
    """What a lazy-score provider shows the cross-pass carry."""

    def __init__(self, carry, shards, pairs_by_shard):
        self.shards, self.pairs, self.mats = shards, pairs_by_shard, []
        carry.attach(self)

    def scored(self):
        scores = np.concatenate(self.mats, axis=1) if self.mats else None
        return self.shards, [None] * len(self.shards), self.pairs, scores


@pytest.mark.parametrize("snapshot", [True, False], ids=["rankings", "list"])
@pytest.mark.parametrize("stacked", [True, False], ids=["stacked", "per_shard"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_carry_seed_equals_the_old_form(seed, stacked, snapshot):
    """seed() reads the providers' matrices as they stand (one provider
    over all shards, or one a shard as the per-shard scorer attaches)
    and answers like the per-id dict fan-out it replaced."""
    from pilosa_tpu.core.cache import Rankings
    from pilosa_tpu.executor.executor import _ScoreCarry, _chunk_size

    rng = np.random.default_rng(seed)
    shards = [3, 5, 8, 13]
    pairs_by_shard = []
    for _ in shards:
        n = int(rng.integers(0, 6000))
        ids = rng.choice(20000, size=n, replace=False).tolist()
        pairs = [(i, int(c)) for i, c in zip(ids, rng.integers(1, 99, size=n))]
        pairs_by_shard.append(Rankings(pairs) if snapshot else pairs)
    carry = _ScoreCarry()
    assert not carry
    if stacked:
        providers = [_Scored(carry, shards, pairs_by_shard)] * len(shards)
    else:
        providers = [
            _Scored(carry, [s], [ps]) for s, ps in zip(shards, pairs_by_shard)
        ]
    assert carry
    assert carry.seed(shards[0], [1, 2]) == {}  # attached, nothing scored yet
    old = {s: [] for s in shards}
    lo = 0
    for _ in range(int(rng.integers(1, 4))):
        size = _chunk_size(lo)
        # score rows padded past the chunk's ids, as the kernels return
        mat = rng.integers(0, 1 << 20, size=(len(shards), size), dtype=np.int32)
        if stacked:
            providers[0].mats.append(mat)
        for i, s in enumerate(shards):
            ids = tuple(p[0] for p in pairs_by_shard[i][lo : lo + size])
            if not stacked:
                providers[i].mats.append(mat[i : i + 1])
            if ids:
                old[s].append((ids, mat[i]))
        lo += size
    for i, s in enumerate(shards):
        scored = [p[0] for p in pairs_by_shard[i][:lo]]
        absent = [p[0] for p in pairs_by_shard[i][lo:]][:50] + [20001, 20002]
        rids = rng.permutation(scored[:: max(len(scored) // 40, 1)] + absent).tolist()
        got = carry.seed(s, rids)
        assert got == _old_seed(old[s], rids)
        assert list(got) == list(_old_seed(old[s], rids))
        assert all(type(v) is int for v in got.values())
        assert carry.seed(s, []) == {}
    assert carry.seed(99, [1, 2]) == {}


@pytest.mark.parametrize("cache_type", ["ranked", "lru"])
def test_top_bitmap_pairs_counts_reads_once(tmp_path, cache_type):
    from pilosa_tpu.core import cache as cache_mod
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.utils import metrics

    h = Holder(str(tmp_path / "data"))
    h.open()
    fld = h.create_index("i").create_field(
        "f", FieldOptions(cache_type=cache_type, cache_size=50)
    )
    rng = np.random.default_rng(7)
    rows, cols = [], []
    for r in range(120):
        k = int(rng.integers(1, 30))
        rows += [r] * k
        cols += rng.choice(SHARD_WIDTH, size=k, replace=False).tolist()
    fld.import_bits(rows, cols)
    frag = h.fragment("i", "f", "standard", 0)
    # cached ids, ids that fell out of the cache, ids with no bits
    ids = rng.permutation(140).tolist() + [100000]

    def per_id():
        pairs, missing = [], []
        for row_id in ids:
            n = frag.cache.get(row_id)
            if n > 0:
                pairs.append((row_id, n))
            else:
                missing.append(row_id)
        counts = frag.row_counts_for(np.asarray(missing, dtype=np.uint64))
        pairs += [(r, int(c)) for r, c in zip(missing, counts) if c > 0]
        return cache_mod.sort_pairs(pairs)

    def reads():
        return _counter(metrics.CACHE_HITS), _counter(metrics.CACHE_MISSES)

    h0, m0 = reads()
    want = per_id()
    h1, m1 = reads()
    got = frag._top_bitmap_pairs(ids)
    h2, m2 = reads()
    assert got == want
    assert (h1 - h0) + (m1 - m0) == len(ids)
    assert (h2 - h1, m2 - m1) == (h1 - h0, m1 - m0)
    assert 0 < h2 - h1 < len(ids)
    h.close()


# -- pass 2 from pass 1's matrices (_ScoreCarry.answer) ---------------------

PASS2_SHARDS = 3
PASS2_Q = "TopN(f, Row(f=0), n=5)"


def _pass2_holder(cache_type="ranked", beyond_prefix=False, absent=False):
    """Three shards whose top five under Row(f=0) differ, so the union
    pass 2 re-reads is wider than any shard's own list; 300 singleton
    rows behind the 20 hot ones, so a walk ends in its head chunk and
    the scored prefix is shorter than the ranked list."""
    from pilosa_tpu.core.field import FieldOptions

    rng = np.random.default_rng(11)
    h = Holder()
    h.open()
    fld = h.create_index("i").create_field(
        "f", FieldOptions(cache_type=cache_type, cache_size=50000)
    )
    rows, cols = [], []

    def put(row, shard, columns):
        rows.extend([row] * len(columns))
        cols.extend((shard * SHARD_WIDTH + np.asarray(columns)).tolist())

    for shard in range(PASS2_SHARDS):
        src = rng.choice(SHARD_WIDTH, size=3000, replace=False)
        put(0, shard, src)
        for r in range(1, 20):
            if absent and r == 18 and shard == 1:
                continue  # a winner elsewhere, no bit here
            if beyond_prefix and r == 19 and shard == 2:
                put(r, shard, src[:2])  # a winner elsewhere, ranked ~170th here
                continue
            # the shard's favourites overlap the source most
            inside = 100 + 60 * ((r + 7 * shard) % 19)
            if r == 19 and shard == 0 or r == 18 and shard == 2:
                inside = 1500
            put(r, shard, src[:inside])
            put(r, shard, SHARD_WIDTH - 1 - rng.choice(2000, size=300, replace=False))
        if beyond_prefix and shard == 2:
            for r in range(2000, 2150):  # 150 rows of 50 bits outrank row 19
                put(r, shard, 10000 + rng.choice(50000, size=50, replace=False))
        for r in range(300):
            put(1000 + r, shard, [(r * 7919) % SHARD_WIDTH])
    fld.import_bits(rows, cols)
    return h


def _pass2_ids():
    from pilosa_tpu.utils import metrics

    return {
        how: _counter(metrics.TOPN_PASS2_IDS, how=how) for how in ("vector", "scalar")
    }


def _scalar_pass2(monkeypatch):
    """Send every shard through the per-id pass 2, as before answer()."""
    from pilosa_tpu.executor.executor import _ScoreCarry

    monkeypatch.setattr(_ScoreCarry, "answer", lambda self, winners, mth: [])


def _source_column(h, shard, row, held):
    """A column of the source row (f=0) in ``shard`` that ``row`` holds,
    or does not: writing it moves the row's score."""
    frag = h.fragment("i", "f", "standard", shard)
    has = set(frag.row(row).columns())
    return next(c for c in frag.row(0).columns() if (c in has) == held)


# case -> (holder arguments, query, (write, row) for shards 0.. after the
#          warm-up query, shards answered from the matrices)
PASS2_CASES = {
    "ranked": ({}, PASS2_Q, [], 3),
    "lru": ({"cache_type": "lru"}, PASS2_Q, [], 0),
    "none": ({"cache_type": "none"}, PASS2_Q, [], 0),
    "threshold": ({}, "TopN(f, Row(f=0), n=5, threshold=400)", [], 3),
    "beyond_prefix": ({"beyond_prefix": True}, PASS2_Q, [], 2),
    "absent": ({"absent": True}, PASS2_Q, [], 2),
    # a write after the ranked snapshot, inside the 10 s debounce: pass 1
    # walks the snapshot, pass 2 has to read the cache as it now is
    "set_after": ({}, PASS2_Q, [("Set", 19)], 2),
    "clear_after": ({}, PASS2_Q, [("Clear", 19)], 2),
    "set_and_clear_after": ({}, PASS2_Q, [("Set", 18), ("Clear", 19)], 1),
}


@pytest.mark.parametrize("route", ["one_chip", "mesh4", "fused"])
@pytest.mark.parametrize("case", list(PASS2_CASES))
def test_vector_pass2_equals_scalar_pass2(case, route, monkeypatch):
    """Pass 2 read off pass 1's matrices answers like the per-id pass 2
    and like the CPU, whatever mix of shards each of them serves."""
    import jax

    from pilosa_tpu.executor.executor import _ScoreCarry
    from pilosa_tpu.parallel.spmd import make_mesh

    kwargs, q, writes, vector_shards = PASS2_CASES[case]
    h = _pass2_holder(**kwargs)
    cpu = Executor(h, device_policy="never", dispatch_enabled=False)
    mesh = make_mesh(jax.devices()[:4]) if route == "mesh4" else None
    dev = Executor(h, device_policy="always", dispatch_enabled=False, mesh=mesh)
    if route == "fused":
        q = "Count(Row(f=0))" + q
    asked = []
    answer = _ScoreCarry.answer

    def spy(self, winners, mth):
        asked.append(len(winners))
        return answer(self, winners, mth)

    try:
        dev.execute("i", q)  # ranks every cache, stages, compiles
        for shard, (write, row) in enumerate(writes):
            col = _source_column(h, shard, row, held=write == "Clear")
            assert dev.execute("i", f"{write}({col}, f={row})") == [True]
        want = cpu.execute("i", q)
        monkeypatch.setattr(_ScoreCarry, "answer", spy)
        before = _pass2_ids()
        got = dev.execute("i", q)
        after = _pass2_ids()
        assert got == want
        if case == "none":  # no candidates: nothing to fuse, no pass 2
            assert want[-1] == [] and asked == [] and after == before
        else:
            if route == "fused":  # the head chunk came from the fused launch
                assert dev.fuser.stats()["fused_launches"] >= 2
            (winners,) = asked
            assert winners > len(want[-1]) == 5  # the union is wider than n
            assert after["vector"] - before["vector"] == vector_shards * winners
            assert after["scalar"] - before["scalar"] == (
                PASS2_SHARDS - vector_shards
            ) * winners
        _scalar_pass2(monkeypatch)
        assert dev.execute("i", q) == want
        assert _pass2_ids()["vector"] == after["vector"]
    finally:
        dev.close()
        cpu.close()
        h.close()


@pytest.mark.parametrize("dirty", [False, True], ids=["clean", "dirty"])
def test_pass2_ids_say_which_way_and_a_clean_pass2_builds_no_provider(
    dirty, monkeypatch
):
    """On clean ranked caches one request reads shards x winners ids off
    the matrices and builds pass 1's provider alone; after a write into
    every shard it is the other way round."""
    from pilosa_tpu.executor import executor as ex_mod

    h = _pass2_holder()
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    built, asked = [], []
    init = ex_mod._StackedLazyScores.__init__
    answer = ex_mod._ScoreCarry.answer

    def spy_init(self, ex, frags, pairs_by_shard, *a, **kw):
        built.append(len(frags))
        init(self, ex, frags, pairs_by_shard, *a, **kw)

    def spy_answer(self, winners, mth):
        asked.append(len(winners))
        return answer(self, winners, mth)

    try:
        want = dev.execute("i", PASS2_Q)
        if dirty:
            for shard in range(PASS2_SHARDS):
                col = _source_column(h, shard, 5, held=False)
                assert dev.execute("i", f"Set({col}, f=5)") == [True]
            want = Executor(h, device_policy="never").execute("i", PASS2_Q)
        monkeypatch.setattr(ex_mod._StackedLazyScores, "__init__", spy_init)
        monkeypatch.setattr(ex_mod._ScoreCarry, "answer", spy_answer)
        before = _pass2_ids()
        assert dev.execute("i", PASS2_Q) == want
        after = _pass2_ids()
        (winners,) = asked
        reads = PASS2_SHARDS * winners
        grown = {how: after[how] - before[how] for how in after}
        if dirty:
            assert grown == {"vector": 0, "scalar": reads}
            assert built == [PASS2_SHARDS, PASS2_SHARDS]  # pass 1, pass 2
        else:
            assert grown == {"vector": reads, "scalar": 0}
            assert built == [PASS2_SHARDS]
    finally:
        dev.close()
        h.close()


def test_the_benchmarks_metric_reads_the_counter_and_0_where_it_is_not_published():
    """``executor.topn_pass2_vector_ids_per_query`` by its files alone:
    the growth of ``topn.pass2_ids{how=vector}`` a request, and 0 (not
    an error) from a program that never publishes the sample."""
    import json
    import os

    from benchmark import layer_metrics, run
    from benchmark.server import parse_metrics
    from pilosa_tpu.utils import metrics

    name = "executor.topn_pass2_vector_ids_per_query"
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry["name"] == name and entry["moves"] == "query_p50_ms"
    assert "workloads" not in entry  # every cell that reports query_p50_ms
    spec = layer_metrics.load(name)

    h = _pass2_holder()
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    try:
        dev.execute("i", PASS2_Q)
        before = parse_metrics(metrics.render_prometheus())
        vector = _pass2_ids()["vector"]
        for _ in range(4):
            dev.execute("i", PASS2_Q)
        after = parse_metrics(metrics.render_prometheus())
        grown = _pass2_ids()["vector"] - vector
    finally:
        dev.close()
        h.close()
    assert grown > 0 and grown % (4 * PASS2_SHARDS) == 0
    assert layer_metrics.evaluate(spec, before, after, 4, None) == grown / 4
    parent = [m for m in after if m[0] != "topn_pass2_ids"]
    assert layer_metrics.evaluate(spec, parent, parent, 4, None) == 0
