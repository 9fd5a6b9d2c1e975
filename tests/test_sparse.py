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
        # laid out as the stager stages a bundle: a block is [16, 128] words
        blocks32 = np.ascontiguousarray(blocks).view("<u4").reshape(-1, 16, 128)
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
    the walk is sure to end in the second chunk it is not asked for,
    and the second chunk ends where the walk does."""

    # n=5: every shard has its threshold after the head chunk and meets
    # a cached count below it at position 140, so the second chunk is
    # the 12 hot rows left (padded to 128) and the walk ends there.
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
        assert self._staged_chunks(ex) == [128, 512, 4096]
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
        assert self._staged_chunks(ex) == [128, 128]
        assert (_decisions(), _counter(metrics.TOPN_PREFETCH_STARTS)) == before
        h.close()

    @pytest.mark.parametrize(
        "query, grown",
        [
            (ENDS, {"head": 1, "bounded": 1, "ladder": 0}),
            # no threshold after the head: the ladder's 4096, which fixes
            # every threshold and holds every break
            (MAY_GO_ON, {"head": 1, "bounded": 0, "ladder": 1}),
            # every walk ends inside the head
            ("TopN(f, Row(f=0), n=5, threshold=51)", {"head": 1, "bounded": 0, "ladder": 0}),
        ],
        ids=["ends", "may_go_on", "head_only"],
    )
    def test_a_chunk_is_counted_by_what_set_its_size(self, tmp_path, query, grown):
        h = self._deep_walk_holder(tmp_path)
        ex = Executor(h, device_policy="always")
        cpu = Executor(h, device_policy="never")
        before = _chunks()
        assert ex.execute("i", query) == cpu.execute("i", query)
        after = _chunks()
        assert {how: after[how] - before[how] for how in after} == grown
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


def _chunks():
    from pilosa_tpu.utils import metrics

    return {
        how: _counter(metrics.TOPN_CHUNKS, how=how)
        for how in ("head", "bounded", "ladder")
    }


def _ranked(counts):
    return [(i, c) for i, c in enumerate(counts)]


def _table_scores(pairs_by_shard, table):
    """The cross-shard chunk provider (executor._ChunkedLazyScores) over
    a table of scores a shard: stages a chunk's ids, scores them from
    the table, records the chunks it was asked to stage ahead."""
    from pilosa_tpu.executor.executor import _bounded_chunk_size, _ChunkedLazyScores

    class Provider(_ChunkedLazyScores):
        def _stage(self, ids_by_shard, size, peek=False):
            return ids_by_shard if table is not None else None  # None: all score 0

        def _score(self, staged, size):
            mat = np.zeros((len(staged), size), dtype=np.int32)
            for i, ids in enumerate(staged):
                mat[i, : len(ids)] = [table[i][rid] for rid in ids]
            return mat

        def _prefetch(self, lo, need=None):
            self.prefetched.append((lo, _bounded_chunk_size(lo, need)))

    p = Provider(None, [object()] * len(pairs_by_shard), pairs_by_shard, None)
    p.prefetched = []
    return p


def _chunk_sizes(provider):
    return [size for _, size, _ in provider._chunk_meta]


@pytest.mark.parametrize(
    "lists, has_n, T, mth, need, size, ends",
    [
        # each shard has its threshold and meets a cached count under it at 130
        ([[9] * 130 + [1] * 5000] * 2, [True, True], [4, 7], 1, 130, 128, True),
        # one shard's threshold is not fixed and its list goes on: the ladder's chunk
        ([[9] * 130 + [1] * 5000] * 2, [True, False], [4, 1 << 62], 1, 5130, 4096, False),
        # ... but a list that ends inside the chunk ends the walk whatever it scores
        ([[9] * 130 + [1] * 5000, [9] * 300], [True, False], [4, 1 << 62], 1, 300, 256, True),
        # the last cached count still clears the threshold: the walk goes on
        ([[9] * 5000], [True], [4], 1, 5000, 4096, False),
        # a count under the minimum is no break, but nothing under it is ever
        # scored: the walk skips to the list's end and reads no further
        ([[9] * 130 + [1] * 5000], [True], [4], 2, 130, 128, True),
        ([[9] * 130 + [1] * 5000], [False], [1 << 62], 2, 130, 128, True),
        ([[]], [False], [1 << 62], 1, 128, 128, True),
        # a count equal to the threshold is read; the first under it is not
        ([[9] * 128 + [4] * 300 + [3] * 5000], [True], [4], 1, 428, 512, True),
        # the bound is a power of two from the prefix: the chunk ends on the
        # break candidate, which no chunk scores
        ([[9] * 256 + [1] * 5000], [True], [4], 1, 256, 128, True),
        ([[9] * 640 + [1] * 5000], [True], [4], 1, 640, 512, True),
        # the break is the ladder's chunk's last candidate, or the first behind it
        ([[9] * 4223 + [1] * 5000], [True], [4], 1, 4223, 4096, True),
        ([[9] * 4224 + [1] * 5000], [True], [4], 1, 4224, 4096, True),
        # ... or further: the ladder's chunk, and the walk asks again behind it
        ([[9] * 4225 + [1] * 5000], [True], [4], 1, 4225, 4096, False),
        # the furthest shard decides
        ([[9] * 130 + [1] * 5000, [9] * 700 + [1] * 10], [True, True], [4, 4], 1, 700, 1024, True),
    ],
    ids=[
        "break_in_chunk", "no_threshold", "list_ends", "no_break", "below_minimum",
        "below_minimum_no_threshold", "empty", "tie_at_threshold", "pow2_bound",
        "pow2_bound_512", "break_ends_ladder_chunk", "break_behind_ladder_chunk",
        "break_past_ladder_chunk", "furthest_shard",
    ],
)
def test_walk_ends_bound_the_next_chunk(lists, has_n, T, mth, need, size, ends):
    """What was _chunk_ends_walk's question (PR 29: will every walk end
    inside the next chunk?) is now where: the chunk ends there, is the
    walk's last, and asks for no chunk to be staged ahead."""
    from pilosa_tpu.executor.executor import FIRST_CHUNK, _walk_ends

    pairs = [_ranked(c) for c in lists]
    done = np.zeros(len(pairs), dtype=bool)
    got = _walk_ends(pairs, FIRST_CHUNK, done, np.array(has_n), np.array(T, dtype=np.int64), mth)
    assert got.dtype == np.int64 and (got >= FIRST_CHUNK).all()
    assert int(got.max()) == need
    assert (_walk_ends(pairs, FIRST_CHUNK, ~done, np.array(has_n), np.array(T), mth) == FIRST_CHUNK).all()
    # lists that go on behind every chunk here, so that staging ahead is asked for
    provider = _table_scores([p + [(10**6, 1)] * 20000 for p in pairs], None)
    provider._pos = FIRST_CHUNK
    provider._score_next(need)
    assert _chunk_sizes(provider) == [size]
    # what is staged ahead ends where the walk does, too
    ahead = min(8192, max(128, 1 << (need - 4224 - 1).bit_length()))
    assert provider.prefetched == ([] if ends else [(4224, ahead)])


def _walk_case(rng, kind):
    """(cached counts a shard, scores a shard by position, n, threshold,
    the chunk sizes the walk must score) of one named shape."""
    S = 3
    hot = lambda k: np.sort(rng.integers(40000, 60000, size=k))[::-1].tolist()
    warm = lambda k: rng.integers(2000, 3000, size=k).tolist()
    if kind == "cliff":  # tall64's shape: hot rows, then a one-bit tail
        k = int(rng.integers(129, 257))
        return [hot(k) + [1] * 5000] * S, [warm(k) + [0] * 5000] * S, 10, 0, [128, 128]
    if kind == "pow2_bound":  # the break candidate is in no scored chunk
        return [hot(640) + [1] * 5000] * S, [warm(640) + [1] * 5000] * S, 10, 0, [128, 512]
    if kind == "ties_at_threshold":
        # every shard's threshold is 40; cached 40 is read, 39 is the break
        counts = [100] * 128 + [40] * 172 + [39] * 5000
        scores = [40] * 10 + rng.integers(0, 80, size=len(counts) - 10).tolist()
        return [counts] * S, [scores] * S, 10, 0, [128, 256]
    if kind == "min_threshold":
        # the tail is under the minimum: never scored, and no break
        counts = hot(150) + [3] * 5000
        scores = rng.integers(0, 9, size=150).tolist() + [3] * 5000
        return [counts] * S, [scores] * S, 10, 5, [128, 128]
    if kind == "short_shard":
        k = int(rng.integers(129, 257))
        return (
            [hot(k) + [1] * 5000, hot(50), hot(k) + [1] * 300],
            [warm(k) + [0] * 5000, warm(50), warm(k) + [1] * 300],
            10, 0, [128, 128],
        )
    if kind == "few_qualify":
        # one shard never pushes n: nothing bounds it but its list's end
        return (
            [hot(200) + [1] * 5000, hot(200) + [1] * 4800],
            [warm(200) + [0] * 5000, [7] * 3 + [0] * 4997],
            10, 0, [128, 4096, 1024],
        )
    if kind == "past_ladder_chunk":
        return [hot(5128) + [1] * 5000] * S, [warm(5128) + [0] * 5000] * S, 10, 0, [128, 4096, 1024]
    if kind == "head_only":
        return [hot(100) + [1] * 5000] * S, [warm(100) + [0] * 5000] * S, 10, 0, [128]
    raise ValueError(kind)


def _scalar_walks(pairs_by_shard, table, opt_):
    from pilosa_tpu.executor.executor import _ranked_walk, pairs_add

    out = []
    for pairs, scores in zip(pairs_by_shard, table):
        out = pairs_add(out, _ranked_walk(None, opt_, pairs, scores))
    return sorted(out)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize(
    "kind",
    ["cliff", "pow2_bound", "ties_at_threshold", "min_threshold", "short_shard",
     "few_qualify", "past_ladder_chunk", "head_only"],
)
def test_bounded_walk_equals_the_scalar_walk(kind, seed):
    from pilosa_tpu.core.cache import Rankings
    from pilosa_tpu.core.fragment import TopOptions
    from pilosa_tpu.executor.executor import _vectorized_topn_walk

    rng = np.random.default_rng([seed, sum(map(ord, kind))])
    counts, scores, n, threshold, sizes = _walk_case(rng, kind)
    pairs_by_shard, table = [], []
    for c, sc in zip(counts, scores):
        ids = rng.permutation(len(c) + 50)[: len(c)].tolist()  # shards share ids
        pairs_by_shard.append(Rankings(zip(ids, c)) if seed % 2 else list(zip(ids, c)))
        table.append(dict(zip(ids, sc)))
    opt_ = TopOptions(n=n, min_threshold=max(threshold, 1))
    provider = _table_scores(pairs_by_shard, table)
    got = _vectorized_topn_walk(pairs_by_shard, provider, opt_)
    assert sorted(got) == _scalar_walks(pairs_by_shard, table, opt_)
    assert _chunk_sizes(provider) == sizes
    # staged ahead only behind a ladder chunk that was not the walk's
    # last, and at the size the walk then asks for
    assert provider.prefetched == ([(4224, 1024)] if len(sizes) == 3 else [])


@pytest.mark.parametrize("seed", range(12))
def test_bounded_walk_equals_the_scalar_walk_on_random_lists(seed):
    """No shape in mind: lengths, counts with runs of ties, scores at or
    over the cached count, n and the minimum drawn at random."""
    from pilosa_tpu.core.fragment import TopOptions
    from pilosa_tpu.executor.executor import _chunk_size, _vectorized_topn_walk

    rng = np.random.default_rng(3200 + seed)
    pairs_by_shard, table = [], []
    for _ in range(int(rng.integers(1, 5))):
        k = int(rng.choice([0, 40, 128, 129, 256, 700, 4224, 6000]))
        c = np.sort(rng.integers(1, int(rng.choice([4, 60, 5000])), size=k))[::-1]
        if k and rng.random() < 0.5:  # a cliff somewhere
            c[int(rng.integers(0, k)) :] = 1
        ids = rng.permutation(k + 50)[:k].tolist()
        pairs_by_shard.append(list(zip(ids, c.tolist())))
        table.append(dict(zip(ids, rng.integers(0, c + 1).tolist())))
    opt_ = TopOptions(
        n=int(rng.choice([1, 3, 10, 130, 300])),
        min_threshold=int(rng.choice([1, 1, 2, 30])),
    )
    provider = _table_scores(pairs_by_shard, table)
    got = _vectorized_topn_walk(pairs_by_shard, provider, opt_)
    assert sorted(got) == _scalar_walks(pairs_by_shard, table, opt_)
    lo = 0
    for size in _chunk_sizes(provider):
        assert size & (size - 1) == 0 and 128 <= size <= _chunk_size(lo)
        lo += size


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
            (None, "counted", [128, 512, 4096]),
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
        assert snap.chunk_blocks(4224, 4736, frag) == (512, False)
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
        assert snap.chunk_blocks(4224, 4736, frag) == (513, False)
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


@pytest.mark.parametrize(
    "name, sample, how",
    [
        ("executor.topn_pass2_vector_ids_per_query", "topn.pass2_ids", "vector"),
        ("executor.topn_chunks_bounded_per_query", "topn.chunks", "bounded"),
    ],
)
def test_the_benchmarks_metric_reads_the_counter_and_0_where_it_is_not_published(
    tmp_path, name, sample, how
):
    """A counter's metric by its files alone: the growth of
    ``sample{how=...}`` a request, and 0 (not an error) from a program
    that never publishes the sample."""
    import json
    import os

    from benchmark import layer_metrics, run
    from benchmark.server import parse_metrics
    from pilosa_tpu.utils import metrics

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["moves"] == "query_p50_ms" and entry["layer"] == "executor host side"
    assert "workloads" not in entry  # every cell that reports query_p50_ms
    spec = layer_metrics.load(name)

    if sample == "topn.pass2_ids":
        h, q = _pass2_holder(), PASS2_Q
    else:  # a walk that goes past its head and knows where it ends
        h, q = TestAdvisoryPrefetchNeverEvicts._deep_walk_holder(tmp_path), TestAdvisoryPrefetchNeverEvicts.ENDS
    dev = Executor(h, device_policy="always", dispatch_enabled=False)
    try:
        dev.execute("i", q)
        before = parse_metrics(metrics.render_prometheus())
        was = _counter(sample, how=how)
        for _ in range(4):
            dev.execute("i", q)
        after = parse_metrics(metrics.render_prometheus())
        grown = _counter(sample, how=how) - was
    finally:
        dev.close()
        h.close()
    if sample == "topn.pass2_ids":
        assert grown > 0 and grown % (4 * PASS2_SHARDS) == 0
    else:
        assert grown == 4  # one bounded chunk a request
    assert layer_metrics.evaluate(spec, before, after, 4, None) == grown / 4
    parent = [m for m in after if m[0] != sample.replace(".", "_")]
    assert len(parent) < len(after)
    assert layer_metrics.evaluate(spec, parent, parent, 4, None) == 0
