"""Tiered block staging (ISSUE 17) — oversubscribed HBM.

The stager's LRU (executor/stager.py) is tier 0: packed u32 blocks
resident in device memory under the HBM governor's tenancy. When the
hot set outgrows the chip, every re-entry of an evicted block costs a
full fragment walk (roaring → dense pack) plus a 131 KB/row PCIe/ICI
upload. This module adds the two layers that make oversubscription
cheap:

* **Tier 1** (``Tier1Cache``) — a host-RAM cache of *serialized roaring
  containers* per (fragment, row set): the exact array/RLE/bitmap
  payloads a dense block is built from, at a fraction of the dense
  bytes. A T0 miss that hits T1 skips the fragment walk entirely and
  rebuilds (or compressed-uploads, below) straight from the payloads.
  Admission is cost-modeled, not unconditional: a candidate's value is
  ``(1 + heat) × rebuild_cost / bytes`` — decayed EWMA heat from the
  workload ledger (utils/heat.py), the measured fragment-walk seconds,
  and the payload footprint — and it only displaces LRU entries that
  score no better. Byte accounting is exact and, when a governor is
  attached, mirrored into a ``tier1`` *host-domain* tenant so
  ``/debug/hbm`` shows the tier without its bytes counting against the
  device budget (executor/hbm.py domains).

* **Tier 2** — the mmapped fragment itself (core/fragment.py), reached
  through ``Fragment.container_blocks``; always the backing store.

* **Plan-driven prefetch** (``PrefetchScheduler``) — the dispatch
  engine's wave builder hands the QUEUED waves' plans here instead of
  enqueueing opaque warm thunks: Row operands are extracted from the
  call trees (plan/planner.py), resolved to fragments, and staged with
  ``prefetch=True`` so the stager can account accuracy — a prefetched
  block later hit by a real query counts ``prefetch_used``; one evicted
  untouched counts ``prefetch_evicted``.

The compressed-upload path (stager._dense_from_blocks) rides T1: when
the dense/compressed ratio clears ``compressed-upload-min-ratio``, the
container payloads themselves cross the wire and a jit scatter kernel
(ops.packed.expand_blocks) expands them to packed words on device.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from pilosa_tpu import SHARD_WIDTH
from pilosa_tpu.analysis.locks import OrderedLock
from pilosa_tpu.utils import heat, metrics


class _T1Entry:
    __slots__ = ("entries", "nbytes", "gen", "cost", "cell")

    def __init__(self, entries, nbytes: int, gen, cost: float, cell) -> None:
        self.entries = entries  # [(row_pos, slot, typ, payload), ...]
        self.nbytes = nbytes  # payload bytes (host RAM footprint)
        self.gen = gen  # fragment generation the payloads reflect
        self.cost = cost  # measured fragment-walk seconds
        self.cell = cell  # (index, field, shard) for heat lookups


def _value(nbytes: int, cost: float, cell) -> float:
    """Admission/retention score: seconds of fragment-walk work saved
    per byte of host RAM, scaled by how hot the cell currently runs.
    The +1 keeps the cost model meaningful on an idle ledger — cold
    entries still rank by rebuild efficiency."""
    score = heat.LEDGER.score(*cell) if cell is not None else 0.0
    return (1.0 + score) * cost / max(nbytes, 1)


class Tier1Cache:
    """Host-RAM compressed tier between the stager's device LRU and the
    mmapped fragment. Thread-safe; keys mirror the stager's
    ``(id(frag), row_ids)`` identity (no strong fragment refs held —
    validation gets the fragment from the caller)."""

    def __init__(self, max_bytes: int) -> None:
        self.max_bytes = int(max_bytes)
        self._mu = OrderedLock("tiering.t1_mu")
        self._cache: OrderedDict[tuple, _T1Entry] = OrderedDict()
        self._bytes = 0
        self.governor = None
        self.hits = 0
        self.misses = 0
        self.admitted = 0
        self.rejected = 0
        self.evicted = 0

    # -- internal ------------------------------------------------------------

    @staticmethod
    def _key(frag, row_ids) -> tuple:
        return (id(frag), tuple(int(r) for r in row_ids))

    def _evict_locked(self, ent: _T1Entry) -> int:
        self._bytes -= ent.nbytes
        self.evicted += 1
        metrics.count(metrics.TIER1_EVICTED)
        return ent.nbytes

    def _gauge_locked(self) -> None:
        metrics.gauge(metrics.TIER1_BYTES, self._bytes)

    # -- API -----------------------------------------------------------------

    def get(self, frag, row_ids):
        """Container payloads for ``(frag, row_ids)`` or None. A stale
        entry is revalidated through the fragment's delta log: deltas
        since the entry's generation that miss every cached row leave
        the payloads exact (generation refreshed); anything else — a
        truncated log or a delta landing in a cached row — evicts."""
        key = self._key(frag, row_ids)
        with self._mu:
            ent = self._cache.get(key)
        if ent is None:
            self.misses += 1
            metrics.count(metrics.TIER1_MISSES)
            return None
        fresh_gen = None
        if frag.generation != ent.gen:
            d = frag.deltas_since(ent.gen)
            stale = d is None
            if not stale:
                pos, _is_set, fresh_gen = d
                if pos.size:
                    rows = np.unique(
                        (pos // np.uint64(SHARD_WIDTH)).astype(np.int64)
                    )
                    stale = bool(np.isin(rows, np.asarray(key[1], np.int64)).any())
            if stale:
                freed = 0
                with self._mu:
                    if self._cache.get(key) is ent:
                        del self._cache[key]
                        freed = self._evict_locked(ent)
                        self._gauge_locked()
                if freed and self.governor is not None:
                    self.governor.release("tier1", freed, index=ent.cell[0])
                self.misses += 1
                metrics.count(metrics.TIER1_MISSES)
                return None
        with self._mu:
            if self._cache.get(key) is ent:
                self._cache.move_to_end(key)
                if fresh_gen is not None:
                    ent.gen = fresh_gen
        self.hits += 1
        metrics.count(metrics.TIER1_HITS)
        return ent.entries

    def put(self, frag, row_ids, entries, nbytes: int, gen, cost: float) -> bool:
        """Offer a freshly-walked payload set. Admitted when it fits —
        evicting only LRU entries whose retention score is no better
        than the candidate's; a candidate that would displace hotter
        work is rejected outright (TIER1_REJECTED)."""
        nbytes = int(nbytes)
        if nbytes <= 0 or nbytes > self.max_bytes:
            self.rejected += 1
            metrics.count(metrics.TIER1_REJECTED)
            return False
        cell = (frag.index, frag.field, frag.shard)
        cand = _value(nbytes, cost, cell)
        key = self._key(frag, row_ids)
        # per-tenant freed ledger: evicted payloads credit back to the
        # index that owned them (governor by_index attribution)
        freed_by: dict = {}
        freed = 0
        with self._mu:
            old = self._cache.pop(key, None)
            if old is not None:
                n = self._evict_locked(old)
                freed += n
                t = old.cell[0] if old.cell else ""
                freed_by[t] = freed_by.get(t, 0) + n
            while self._bytes + nbytes > self.max_bytes:
                k, ent = next(iter(self._cache.items()))
                if _value(ent.nbytes, ent.cost, ent.cell) > cand:
                    self._gauge_locked()
                    admitted = False
                    break
                del self._cache[k]
                n = self._evict_locked(ent)
                freed += n
                t = ent.cell[0] if ent.cell else ""
                freed_by[t] = freed_by.get(t, 0) + n
            else:
                self._cache[key] = _T1Entry(entries, nbytes, gen, cost, cell)
                self._bytes += nbytes
                self._gauge_locked()
                admitted = True
        if admitted:
            self.admitted += 1
            metrics.count(metrics.TIER1_ADMITTED)
        else:
            self.rejected += 1
            metrics.count(metrics.TIER1_REJECTED)
        gov = self.governor
        if gov is not None:
            if admitted:
                gov.reserve("tier1", nbytes, index=cell[0])
            for t, n in freed_by.items():
                gov.release("tier1", n, index=t)
        return admitted

    def set_governor(self, governor) -> None:
        """Mirror the tier's byte ledger into a host-domain governor
        tenant — visible in /debug/hbm stats, excluded from the device
        budget (executor/hbm.py domains)."""
        self.governor = governor
        if governor is None:
            return
        governor.register(
            "tier1", share_bytes=self.max_bytes, tier=9, domain="host"
        )
        with self._mu:
            current = self._bytes
        if current:
            governor.reserve("tier1", current)

    def clear(self) -> None:
        with self._mu:
            freed_by: dict = {}
            for ent in self._cache.values():
                t = ent.cell[0] if ent.cell else ""
                freed_by[t] = freed_by.get(t, 0) + ent.nbytes
            self._cache.clear()
            self._bytes = 0
            self._gauge_locked()
        if self.governor is not None:
            for t, n in freed_by.items():
                self.governor.release("tier1", n, index=t)

    def stats(self) -> dict:
        with self._mu:
            n, b = len(self._cache), self._bytes
        return {
            "entries": n,
            "bytes": b,
            "max_bytes": self.max_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "admitted": self.admitted,
            "rejected": self.rejected,
            "evicted": self.evicted,
        }


class PrefetchScheduler:
    """Plan-driven speculative prefetch. The dispatch engine's wave
    builder (dispatch.py _stage_ahead_peek) hands the next waves'
    queued items here; Row operands are extracted from the parsed call
    trees and their fragment blocks promoted T1/T2 → T0 ahead of
    compute, marked ``prefetch=True`` so the stager's accuracy
    counters attribute the outcome."""

    def __init__(self, executor, depth: int = 2, enabled: bool = True) -> None:
        self.executor = executor
        self.depth = max(0, int(depth))
        self.enabled = bool(enabled) and self.depth > 0
        self._mu = threading.Lock()
        self.scheduled = 0  # thunks enqueued (pre-dedup accounting)

    def schedule(self, items) -> int:
        """Enqueue stage-ahead work for queued dispatch items; returns
        the number of (fragment, row) promotions enqueued. Best-effort
        and advisory: errors are swallowed, the real execution path
        re-stages anything missed."""
        ex = self.executor
        if not self.enabled or ex.device_policy == "never" or ex._cpu_forced():
            return 0
        from pilosa_tpu.core import VIEW_STANDARD
        from pilosa_tpu.plan.planner import extract_row_operands

        stager = ex.stager
        n = 0
        seen: set = set()
        for it in items:
            try:
                operands = extract_row_operands(it.query.calls)
                if not operands:
                    continue
                shards = it.shards
                if shards is None:
                    idx = ex.holder.index(it.index)
                    if idx is None:
                        continue
                    shards = range(idx.max_shard() + 1)
                shards = tuple(shards)
                for field, row_id in operands:
                    frags = []
                    for shard in shards:
                        key = (it.index, field, row_id, shard)
                        frag = ex.holder.fragment(
                            it.index, field, VIEW_STANDARD, shard
                        )
                        frags.append(frag)
                        if key in seen or frag is None:
                            continue
                        seen.add(key)
                        stager.stage_ahead(
                            lambda f=frag, r=row_id: stager.row(
                                f, r, prefetch=True
                            )
                        )
                        n += 1
                    # batched and fused execution (GroupBy dims, fused
                    # Count trees) read rows as one [S, W] stack keyed
                    # by the whole fragment tuple — warm that key too,
                    # or the speculative copies never attribute as used
                    skey = (it.index, field, row_id, "stack", shards)
                    if skey not in seen and any(
                        f is not None for f in frags
                    ):
                        seen.add(skey)
                        ft = tuple(frags)
                        stager.stage_ahead(
                            lambda fs=ft, r=row_id: stager.row_stack(
                                fs, r, prefetch=True
                            )
                        )
                        n += 1
            except BaseException:
                continue
        if n:
            with self._mu:
                self.scheduled += n
        return n

    def stats(self) -> dict:
        st = self.executor.stager
        used = getattr(st, "prefetch_used", 0)
        evicted = getattr(st, "prefetch_evicted", 0)
        return {
            "enabled": self.enabled,
            "depth": self.depth,
            "scheduled": self.scheduled,
            "issued": getattr(st, "prefetch_issued", 0),
            "used": used,
            "evicted": evicted,
            "accuracy": round(used / max(used + evicted, 1), 4),
        }
