"""TopN row-count caches (reference cache.go).

The rank cache bounds which rows are *eligible* TopN candidates — its
threshold/trim behavior is part of the reference's observable TopN
semantics, so it is reproduced here exactly (thresholdFactor 1.1,
maxEntries trim, count-descending ranking, 10s invalidation debounce).
"""

from __future__ import annotations

import json
import time
from collections import OrderedDict
from typing import Optional

from pilosa_tpu.utils import metrics

# reference cache.go:29-31
THRESHOLD_FACTOR = 1.1
# reference field.go:38-44
CACHE_TYPE_LRU = "lru"
CACHE_TYPE_RANKED = "ranked"
CACHE_TYPE_NONE = "none"
DEFAULT_CACHE_SIZE = 50000

# reference rankCache.invalidate's hard-coded debounce (cache.go:233-241)
INVALIDATE_DEBOUNCE_SECONDS = 10.0


def sort_pairs(pairs: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Count-descending, id-ascending tiebreak.

    The reference uses Go's unstable sort with count-only comparison
    (cache.go:342); ties are therefore unspecified there — we pin them
    to ascending id for determinism.

    Vectorized for big inputs: recalculate() sorts 50k entries per
    fragment on the open path (64 fragments at the 1B scale), and a
    per-element key lambda was the single largest line in the warm-open
    profile. lexsort(ids asc, then counts desc stable) = the same
    (-count, id) order.
    """
    if len(pairs) < 1024:
        return sorted(pairs, key=lambda p: (-p[1], p[0]))
    import numpy as np

    ids = np.fromiter((p[0] for p in pairs), dtype=np.int64, count=len(pairs))
    counts = np.fromiter((p[1] for p in pairs), dtype=np.int64, count=len(pairs))
    order = np.lexsort((ids, -counts))
    return list(zip(ids[order].tolist(), counts[order].tolist()))


def pairs_arrays(pairs):
    """(ids int64[L], counts int64[L]) from a list of (id, count)."""
    import numpy as np

    ids = np.fromiter((p[0] for p in pairs), dtype=np.int64, count=len(pairs))
    cnts = np.fromiter((p[1] for p in pairs), dtype=np.int64, count=len(pairs))
    return ids, cnts


def _count_reads(found: list[Optional[int]]) -> list[int]:
    """One get_many's reads as get() returns them (0 for an id the cache
    does not hold), counted into cache.hits / cache.misses once."""
    misses = found.count(None)
    if len(found) - misses:
        metrics.count(metrics.CACHE_HITS, value=len(found) - misses)
    if misses:
        metrics.count(metrics.CACHE_MISSES, value=misses)
        return [n or 0 for n in found]
    return found


class Rankings(list):
    """Rankings snapshot (a list of (id, count) pairs) carrying its own
    memo of per-slice id tuples. The memo lives ON the snapshot — not
    on the cache — so a concurrent recalculate() swapping the cache's
    rankings can never hand a caller ids inconsistent with the pairs
    list it is iterating."""

    def _memo_of(self, name: str) -> dict:
        memo = self.__dict__.get(name)
        if memo is None:
            memo = self.__dict__[name] = {}
        return memo

    def chunk_ids(self, lo: int, hi: int) -> tuple[int, ...]:
        memo = self._memo_of("_memo")
        t = memo.get((lo, hi))
        if t is None:
            # a racing duplicate build produces an identical tuple — benign
            t = tuple(p[0] for p in self[lo:hi])
            memo[(lo, hi)] = t
        return t

    def chunk_arrays(self, lo: int, hi: int):
        """(ids int64[L], counts int64[L]) for self[lo:hi], memoized on
        the snapshot (same rationale as chunk_ids): the vectorized
        cross-shard TopN walk consumes candidate ids/counts as numpy
        arrays per shard per chunk on every query."""
        memo = self._memo_of("_np_memo")
        t = memo.get((lo, hi))
        if t is None:
            t = memo[(lo, hi)] = pairs_arrays(self[lo:hi])
        return t

    def chunk_index(self, lo: int, hi: int) -> dict[int, int]:
        """{id: position in self[lo:hi]}, memoized on the snapshot: TopN
        pass 2 looks the winners' scores up in the chunks pass 1 scored.
        Only a chunk a walk has scored is ever asked for."""
        memo = self._memo_of("_ix_memo")
        t = memo.get((lo, hi))
        if t is None:
            ids = self.chunk_ids(lo, hi)
            t = memo[(lo, hi)] = dict(zip(ids, range(len(ids))))
        return t

    def chunk_sorted(self, lo: int, hi: int):
        """(ids of self[lo:hi] ascending, the position in the chunk of
        each, its cached count), memoized on the snapshot: TopN pass 2
        finds all the winners of a shard with one ``searchsorted`` in
        the prefix pass 1 scored (executor._ScoreCarry.answer)."""
        memo = self._memo_of("_sorted_memo")
        t = memo.get((lo, hi))
        if t is None:
            import numpy as np

            ids, counts = self.chunk_arrays(lo, hi)
            order = np.argsort(ids, kind="stable")
            t = memo[(lo, hi)] = (ids[order], order, counts[order])
        return t

    def chunk_blocks(self, lo: int, hi: int, frag) -> tuple[int, bool]:
        """(nonempty container blocks of self[lo:hi]'s rows in ``frag``,
        whether they had to be counted now). The count is kept with the
        ``frag.generation`` it was read at, the tag the fragment's own
        occupancy snapshot is validated by: a Set into a ranked row adds
        a container without a recalculate, so the snapshot alone does
        not fix the count. The generation is read BEFORE the count: a
        write in between leaves the memo under the old tag, and the
        next call counts again."""
        memo = self._memo_of("_blocks_memo")
        gen = frag.generation
        t = memo.get((lo, hi))
        if t is not None and t[0] == gen:
            return t[1], False
        n = frag.sparse_block_count(self.chunk_ids(lo, hi))
        memo[(lo, hi)] = (gen, n)
        return n, True


class RankCache:
    """Sorted top-K cache (reference rankCache, cache.go:136-286)."""

    def __init__(self, max_entries: int) -> None:
        self.max_entries = max_entries
        self.threshold_buffer = int(THRESHOLD_FACTOR * max_entries)
        self.entries: dict[int, int] = {}
        self.rankings: list[tuple[int, int]] = Rankings()
        self.threshold_value = 0
        self._update_time = 0.0
        self._dirty = False

    def add(self, id_: int, n: int) -> None:
        if n < self.threshold_value:
            return
        self.entries[id_] = n
        self._dirty = True
        self.invalidate()

    def bulk_add(self, id_: int, n: int) -> None:
        if n < self.threshold_value:
            return
        self.entries[id_] = n
        self._dirty = True

    def get(self, id_: int) -> int:
        n = self.entries.get(id_)
        if n is None:
            metrics.count(metrics.CACHE_MISSES)
            return 0
        metrics.count(metrics.CACHE_HITS)
        return n

    def get_many(self, ids) -> list[int]:
        """get() for each id, the hits and misses counted once a call:
        a TopN pass 2 re-reads the winners in every shard."""
        return _count_reads(list(map(self.entries.get, ids)))

    def remove(self, id_: int) -> None:
        if self.entries.pop(id_, None) is not None:
            self.rankings = Rankings(p for p in self.rankings if p[0] != id_)
            self._dirty = True

    def is_current(self, rankings) -> bool:
        """Are ``entries`` what ``rankings`` was sorted from? True while
        it is this cache's snapshot and nothing was added or removed
        since: get() of any id in it then returns the count it ranks.
        Ask under the lock the writers hold (Fragment.ranked_cache_is):
        recalculate() clears the flag before it swaps the snapshot."""
        return self.rankings is rankings and not self._dirty

    def __len__(self) -> int:
        return len(self.entries)

    def ids(self) -> list[int]:
        return sorted(self.entries)

    def restore(self, ids, counts) -> None:
        """Bulk-load (id, count) pairs at open — C-speed dict build +
        one recalculate instead of 50k bulk_add calls (the open path
        at 64 fragments × 50k cached rows)."""
        ids = ids.tolist() if hasattr(ids, "tolist") else ids
        counts = counts.tolist() if hasattr(counts, "tolist") else counts
        self.entries.update(zip(map(int, ids), map(int, counts)))
        self.recalculate()

    def invalidate(self) -> None:
        # the reference recalculates whenever the debounce window has
        # passed (cache.go:233-241) even if nothing changed; on an
        # unmodified cache the re-sort is a semantic no-op, and on the
        # read path (topBitmapPairs) it cost ~34 ms of GIL per 50k-entry
        # fragment — measured as the dominant serialization at c8 on the
        # 1B/64-shard config. Skipping it when clean is bit-identical.
        if not self._dirty:
            return
        if time.monotonic() - self._update_time < INVALIDATE_DEBOUNCE_SECONDS:
            return
        self.recalculate()

    def recalculate(self) -> None:
        self._dirty = False
        rankings = sort_pairs(list(self.entries.items()))
        remove_items: list[tuple[int, int]] = []
        if len(rankings) > self.max_entries:
            self.threshold_value = rankings[self.max_entries][1]
            remove_items = rankings[self.max_entries :]
            rankings = rankings[: self.max_entries]
        else:
            self.threshold_value = 1
        self.rankings = Rankings(rankings)
        self._update_time = time.monotonic()
        if len(self.entries) > self.threshold_buffer:
            for id_, _ in remove_items:
                self.entries.pop(id_, None)

    def top(self) -> list[tuple[int, int]]:
        return self.rankings

    def clear(self) -> None:
        self.entries.clear()
        self.rankings = Rankings()
        self.threshold_value = 0
        self._update_time = 0.0
        self._dirty = False


class LRUCache:
    """LRU row-count cache (reference lruCache over lru/lru.go)."""

    def __init__(self, max_entries: int) -> None:
        self.max_entries = max_entries
        self._lru: OrderedDict[int, int] = OrderedDict()

    def add(self, id_: int, n: int) -> None:
        if id_ in self._lru:
            self._lru.move_to_end(id_)
        self._lru[id_] = n
        if self.max_entries and len(self._lru) > self.max_entries:
            self._lru.popitem(last=False)

    bulk_add = add

    def restore(self, ids, counts) -> None:
        for i, c in zip(ids, counts):
            self.add(int(i), int(c))

    def get(self, id_: int) -> int:
        n = self._lru.get(id_)
        if n is None:
            metrics.count(metrics.CACHE_MISSES)
            return 0
        self._lru.move_to_end(id_)
        metrics.count(metrics.CACHE_HITS)
        return n

    def get_many(self, ids) -> list[int]:
        found = list(map(self._lru.get, ids))
        for i, n in zip(ids, found):
            if n is not None:
                self._lru.move_to_end(i)
        return _count_reads(found)

    def remove(self, id_: int) -> None:
        self._lru.pop(id_, None)

    def __len__(self) -> int:
        return len(self._lru)

    def ids(self) -> list[int]:
        return sorted(self._lru)

    def invalidate(self) -> None:
        pass

    def recalculate(self) -> None:
        pass

    def top(self) -> list[tuple[int, int]]:
        return sort_pairs(list(self._lru.items()))

    def clear(self) -> None:
        self._lru.clear()


class NopCache:
    """No-op cache (cache type \"none\")."""

    def add(self, id_: int, n: int) -> None:
        pass

    bulk_add = add

    def restore(self, ids, counts) -> None:
        pass

    def get(self, id_: int) -> int:
        return 0

    def remove(self, id_: int) -> None:
        pass

    def __len__(self) -> int:
        return 0

    def ids(self) -> list[int]:
        return []

    def invalidate(self) -> None:
        pass

    def recalculate(self) -> None:
        pass

    def top(self) -> list[tuple[int, int]]:
        return []

    def clear(self) -> None:
        pass


def new_cache(cache_type: str, cache_size: int):
    if cache_type == CACHE_TYPE_RANKED:
        return RankCache(cache_size)
    if cache_type == CACHE_TYPE_LRU:
        return LRUCache(cache_size)
    if cache_type == CACHE_TYPE_NONE:
        return NopCache()
    raise ValueError(f"unknown cache type: {cache_type}")


def encode_cache(ids: list[int]) -> bytes:
    """The reference's .cache protobuf bytes
    (internal/private.proto Cache{repeated uint64 IDs = 1}, packed)."""
    from pilosa_tpu.utils.protometa import _write_tag, _write_varint

    out = bytearray()
    if ids:
        buf = bytearray()
        for v in ids:
            _write_varint(buf, int(v))
        _write_tag(out, 1, 2)
        _write_varint(out, len(buf))
        out += buf
    return bytes(out)


def write_cache(path: str, ids: list[int]) -> None:
    # write-then-rename: a crash mid-flush must never leave a truncated
    # .cache that chokes the next startup (the periodic flush loop
    # exists precisely to survive crashes)
    import os

    tmp = path + ".flushing"
    with open(tmp, "wb") as f:
        f.write(encode_cache(ids))
    os.replace(tmp, path)


def read_cache(path: str) -> Optional[list[int]]:
    try:
        with open(path, "rb") as f:
            return decode_cache(f.read())
    except FileNotFoundError:
        return None


def _decode_packed_varints(payload: bytes) -> list[int]:
    """Vectorized decode of concatenated uvarints: one masked
    shift-or round per varint BYTE POSITION (≤10) instead of a Python
    loop per byte — the .cache open path decodes 50k ids in ~1 ms."""
    import numpy as np

    b = np.frombuffer(payload, dtype=np.uint8)
    if b.size == 0:
        return []
    ends = np.nonzero((b & 0x80) == 0)[0]
    if ends.size == 0 or ends[-1] != b.size - 1:
        raise ValueError("cache file: packed ids overrun field")
    starts = np.empty_like(ends)
    starts[0] = 0
    starts[1:] = ends[:-1] + 1
    lens = ends - starts + 1
    if int(lens.max()) > 10:
        # a u64 uvarint is at most 10 bytes; longer means corruption —
        # numpy's >=64-bit shifts would silently decode it to garbage
        # where the scalar reader raised (callers rebuild the cache)
        raise ValueError("cache file: varint too long")
    vals = np.zeros(ends.size, dtype=np.uint64)
    for j in range(int(lens.max())):
        take = lens > j
        byte = b[starts[take] + j].astype(np.uint64) & np.uint64(0x7F)
        vals[take] |= byte << np.uint64(7 * j)
    return vals.tolist()


def decode_cache(data: bytes) -> list[int]:
    """Decode .cache bytes: reference protobuf, or the JSON this
    framework wrote before adopting the reference format."""
    from pilosa_tpu.utils.protometa import _read_varint

    if not data:
        return []
    if data[:1] == b"[":  # legacy JSON
        return json.loads(data.decode())
    ids: list[int] = []
    i = 0
    while i < len(data):
        key, i = _read_varint(data, i)
        field_no, wire = key >> 3, key & 7
        if wire == 2:
            ln, i = _read_varint(data, i)
            end = i + ln
            if field_no == 1:
                ids.extend(_decode_packed_varints(data[i:end]))
            i = end  # skip unknown length-delimited fields
        elif wire == 0:
            v, i = _read_varint(data, i)
            if field_no == 1:
                ids.append(v)
        else:
            raise ValueError(f"unsupported wire type in cache file: {wire}")
    return ids
