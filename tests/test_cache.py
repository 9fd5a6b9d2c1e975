"""RankCache invalidation semantics (reference cache.go:136-286).

The reference's rankCache.Invalidate() re-sorts whenever its 10 s
debounce window has passed — including on the read-only TopN path
(topBitmapPairs, fragment.go:1004-1044). On an unmodified cache that
re-sort is a semantic no-op; at the 1B/64-shard scale it was measured
as the dominant GIL serialization under concurrent TopN (34 ms per 50k
entry fragment). The dirty flag skips it without changing any output.
"""

import numpy as np
import pytest

from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core.cache import RankCache


def _filled(n=1000):
    c = RankCache(50)
    for i in range(n):
        c.bulk_add(i, n - i)
    c.recalculate()
    return c


class TestInvalidateDirtyFlag:
    def test_clean_invalidate_is_free(self, monkeypatch):
        c = _filled()
        # expired debounce window: the old code would re-sort here
        c._update_time = -1e9
        before = c.rankings
        calls = []
        monkeypatch.setattr(
            cache_mod, "sort_pairs", lambda p: calls.append(1) or sorted(
                p, key=lambda x: (-x[1], x[0])
            )
        )
        c.invalidate()
        assert calls == []  # no re-sort
        assert c.rankings is before  # rankings snapshot untouched

    def test_write_then_invalidate_recalculates(self):
        c = _filled()
        c._update_time = -1e9
        c.add(5000, 99999)
        assert c.rankings[0] == (5000, 99999)

    def test_debounce_still_applies_to_dirty(self):
        c = _filled()
        # recent recalc: a write within the window must NOT re-sort
        # (reference debounce, cache.go:233-241)
        before = c.rankings
        c.bulk_add(6000, 88888)
        c.invalidate()
        assert c.rankings is before
        # ...but the dirtiness persists: after the window the next
        # invalidate picks it up
        c._update_time = -1e9
        c.invalidate()
        assert c.rankings[0] == (6000, 88888)

    def test_remove_marks_dirty(self):
        c = _filled()
        top_id = c.rankings[0][0]
        c.remove(top_id)
        assert all(p[0] != top_id for p in c.rankings)
        c._update_time = -1e9
        c.invalidate()  # rebuild from entries must also exclude it
        assert all(p[0] != top_id for p in c.rankings)

    def test_trim_and_threshold_unchanged(self):
        # reference trim behavior: maxEntries cut + thresholdValue from
        # the first trimmed entry (cache.go:250-270)
        c = RankCache(10)
        for i in range(30):
            c.bulk_add(i, 100 - i)
        c.recalculate()
        assert len(c.rankings) == 10
        assert c.threshold_value == 100 - 10


def _random_cache(seed, n=700):
    rng = np.random.default_rng(seed)
    c = RankCache(500)
    for i, cnt in zip(
        rng.choice(1 << 40, size=n, replace=False).tolist(),
        rng.integers(1, 60, size=n).tolist(),
    ):
        c.bulk_add(i, cnt)
    c.recalculate()
    return c


class TestSortedChunkMemo:
    """Rankings.chunk_sorted: what TopN pass 2 searches the winners in."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    @pytest.mark.parametrize("lo, hi", [(0, 128), (128, 4224), (0, 4224), (37, 91)])
    def test_equals_a_fresh_argsort(self, seed, lo, hi):
        snap = _random_cache(seed).rankings
        ids = np.array([p[0] for p in snap[lo:hi]], dtype=np.int64)
        counts = np.array([p[1] for p in snap[lo:hi]], dtype=np.int64)
        order = np.argsort(ids, kind="stable")
        sids, position, scounts = snap.chunk_sorted(lo, hi)
        assert np.array_equal(sids, ids[order]) and np.all(np.diff(sids) > 0)
        assert np.array_equal(position, order)
        assert np.array_equal(scounts, counts[order])
        # every id of the chunk is found at its position, with its count
        at = sids.searchsorted(ids)
        assert np.array_equal(position[at], np.arange(ids.size))
        assert np.array_equal(scounts[at], counts)

    def test_built_once_a_snapshot(self, monkeypatch):
        snap = _random_cache(4).rankings
        first = snap.chunk_sorted(0, 128)
        calls = []
        argsort = np.argsort
        monkeypatch.setattr(
            np, "argsort", lambda *a, **kw: calls.append(1) or argsort(*a, **kw)
        )
        again = snap.chunk_sorted(0, 128)
        assert calls == [] and all(a is b for a, b in zip(first, again))
        snap.chunk_sorted(128, 256)  # another chunk is another entry
        assert calls == [1]

    def test_not_shared_with_the_snapshot_a_recalculate_swaps_in(self):
        c = _random_cache(5)
        old = c.rankings
        kept = old.chunk_sorted(0, 128)
        top = old[0][0]
        c.bulk_add(top + 1, 10**6)  # a new first place
        c.recalculate()
        new = c.rankings
        assert new is not old and "_sorted_memo" not in new.__dict__
        fresh = new.chunk_sorted(0, 128)
        assert top + 1 in fresh[0] and top + 1 not in kept[0]
        # the old snapshot still answers for the list it is
        assert all(a is b for a, b in zip(old.chunk_sorted(0, 128), kept))


class TestIsCurrent:
    """RankCache.is_current: may a reader trust a snapshot it kept?"""

    def test_true_until_an_entry_changes(self):
        c = _filled()
        snap = c.rankings
        assert c.is_current(snap)
        c.get_many([1, 2, 3])
        c.invalidate()
        assert c.is_current(snap)  # reads and a clean invalidate change nothing
        c.add(5, 10**6)  # inside the debounce: same snapshot, stale
        assert c.rankings is snap and not c.is_current(snap)
        c.recalculate()
        assert not c.is_current(snap) and c.is_current(c.rankings)

    def test_false_after_remove_and_clear(self):
        c = _filled()
        snap = c.rankings
        c.remove(snap[0][0])
        assert not c.is_current(snap) and not c.is_current(c.rankings)
        c.recalculate()
        snap = c.rankings
        c.clear()
        assert not c.is_current(snap)


def test_is_current_never_vouches_for_a_stale_snapshot_under_writers():
    """Writers set and clear bits and re-rank while readers keep a
    snapshot: whenever the fragment vouches for it under its lock, every
    count it ranks is the count the cache holds."""
    import sys
    import threading
    import time

    from pilosa_tpu import SHARD_WIDTH
    from pilosa_tpu.core import Holder

    h = Holder()
    h.open()
    fld = h.create_index("i").create_field("f")
    fld.import_bits([r for r in range(40) for _ in range(5)], list(range(200)))
    frag = h.fragment("i", "f", "standard", 0)
    stop = time.monotonic() + 1.5
    vouched, broken = [0], []

    def writer(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            row, col = int(rng.integers(0, 40)), int(rng.integers(0, SHARD_WIDTH))
            (frag.set_bit if rng.integers(0, 2) else frag.clear_bit)(row, col)
            if rng.integers(0, 8) == 0:
                frag.recalculate_cache()

    def reader():
        while time.monotonic() < stop and not broken:
            snap = frag._top_bitmap_pairs([])
            with frag.mu:
                if frag.ranked_cache_is(snap):
                    vouched[0] += 1
                    if any(frag.cache.entries.get(i) != n for i, n in snap):
                        broken.append(list(snap))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=writer, args=(s,)) for s in range(4)]
    threads += [threading.Thread(target=reader) for _ in range(12)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not broken and vouched[0] > 0
    h.close()
