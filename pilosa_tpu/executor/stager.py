"""HBM staging manager — the device-side cache of fragment state.

Fragments are CPU source of truth (roaring + op log); queries run on
packed-word copies staged in device memory. Device state follows a
SNAPSHOT + DELTA model: entries are keyed by (fragment identity, form)
and remember the fragment generation their array was built at. A
mutation no longer cold-invalidates the block — on the next use the
stager replays the fragment's delta log (core/fragment.py) onto the
already-resident array with a jit scatter kernel (ops/delta.py),
falling back to a full rebuild + re-upload only when the log can't
prove continuity (bulk imports, log truncation) or the delta batch is
large enough that re-staging is cheaper (``delta_max_ratio``). This is
the device-side analog of the reference's op-log-over-mmap write
absorption (reference fragment.go:66-110): one ``set_bit`` costs a
K-word scatter instead of a 537 MB re-upload of the dense matrix.

Staged forms and their delta paths:
  * row         — u32[W]           scatter into the one row
  * rows(_p2)   — u32[K, W]        scatter into staged rows; deltas on
                                   unstaged rows don't touch the block
  * matrix      — u32[R, W]        scatter while the non-empty row set
                                   is unchanged; a new/emptied row is a
                                   shape change → full rebuild
  * planes      — u32[D+1, W]      scatter into planes 0..D
  * row_stack / planes_stack       per-shard scatter (re-pinned to the
                                   entry's sharding afterwards)
  * sparse_rows / sparse_*_stack   documented fallback: the block-
                                   sparse layout has no stable scatter
                                   targets (a delta can land in an
                                   unstaged container), so a
                                   generation mismatch full-rebuilds

Every delta apply produces a NEW array (functional update), so batched
scorers that coalesce on staged-array identity (executor/batcher.py)
keep working: same object ⇔ same snapshot, and post-update queries key
on the fresh object.

Eviction is LRU by byte budget — the stager is the scheduler of HBM
residency (SURVEY.md §7 hard part 2).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Optional

import jax
import numpy as np

from pilosa_tpu import SHARD_WIDTH, ops
from pilosa_tpu.analysis.locks import OrderedLock
from pilosa_tpu.utils import events, heat, metrics, trace

_W32 = SHARD_WIDTH // 32  # u32 words per staged row
# compressed-upload ceiling: global bit coordinates are u32, so a block
# can span at most 2^32 / SHARD_WIDTH staged rows before they wrap
# (2048 rows × 2^20 bits = 2^31 — also keeps the expansion kernel's
# i32 word indexes exact, with 0xFFFFFFFF position padding still
# landing past every real word)
_MAX_COMPRESSED_ROWS = (1 << 32) // SHARD_WIDTH // 2


class _InFlight:
    __slots__ = ("event", "value", "error", "gen")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None
        self.gen = None  # generation token the published value reflects


class _Entry:
    __slots__ = ("value", "nbytes", "gen", "tenant")

    def __init__(self, value, nbytes: int, gen, tenant: str = "") -> None:
        self.value = value
        self.nbytes = nbytes
        self.gen = gen  # int, or tuple of per-fragment ints for stacks
        # owning index (ISSUE 19): governor sub-tenant attribution and
        # quota-preferring eviction; "" for untracked internal entries
        self.tenant = tenant


def _gen_fresh(have, want) -> bool:
    """Is a staged snapshot at generation ``have`` acceptable for a
    reader that observed ``want``? Generations only grow, and a builder
    records the generation it read BEFORE packing (content is at least
    that fresh), so >= is the right comparison."""
    if isinstance(want, tuple):
        if not isinstance(have, tuple) or len(have) != len(want):
            return False
        for h, w in zip(have, want):
            if w is None or h is None:
                if h is not w:
                    return False
            elif h < w:
                return False
        return True
    return have >= want


class DeviceStager:
    """Thread-safe: concurrent executor threads (parallel multi-call
    requests, ThreadingHTTPServer handlers) share one stager. A cold
    key is staged ONCE — concurrent misses for the same key wait on the
    first builder's in-flight entry and receive the same device array,
    which also keeps BatchedScorer coalescing intact (its key is the
    staged array's identity)."""

    def __init__(
        self,
        budget_bytes: int = 8 << 30,
        device=None,
        mesh=None,
        delta_enabled: bool = True,
        delta_max_ratio: float = 0.25,
        tier1_max_bytes: int = 0,
        compressed_min_ratio: float = 0.0,
    ) -> None:
        self.budget_bytes = budget_bytes
        self.device = device
        # When a mesh is configured, shard-major stacks ([S, ...] arrays
        # from *_stack) are placed split over the mesh's shard axis so
        # the executor's SPMD kernels consume them in place — the HBM
        # form of the reference's shards-spread-over-nodes layout.
        self.mesh = mesh
        # delta staging: patch resident arrays on generation mismatch
        # instead of rebuilding; a batch touching more than
        # delta_max_ratio of the block's words full-rebuilds instead
        # (the scatter stops winning once it rewrites much of the block)
        self.delta_enabled = delta_enabled
        self.delta_max_ratio = delta_max_ratio
        # process-wide HBM governor (executor/hbm.py): when attached via
        # set_governor, budget_bytes becomes this stager's tenant SHARE
        # of the global ledger and cold LRU blocks its relief tier —
        # the stager can no longer overcommit the chip jointly with the
        # device plan cache
        self.governor = None
        self._cache: OrderedDict[tuple, _Entry] = OrderedDict()
        self._bytes = 0
        self._mu = OrderedLock("stager.mu")
        self._inflight: dict[tuple, _InFlight] = {}
        # bumped by reset_after_wedge: a builder that started before a
        # wedge publishes to its own waiters but must never re-insert a
        # dead-runtime handle into the post-reset cache
        self._epoch = 0
        self.hits = 0
        self.misses = 0
        self.delta_applies = 0
        # async stage-ahead (dispatch engine): a single advisory
        # prefetch side-thread drains a bounded thunk queue — same
        # idiom as the chunked TopN walk's _prefetch thread
        self._ahead_q: deque = deque(maxlen=32)
        self._ahead_mu = OrderedLock("stager.ahead_mu")
        self._ahead_cv = threading.Condition(self._ahead_mu)
        self._ahead_thread: Optional[threading.Thread] = None
        # stage-ahead thunks that raised: counted (not swallowed blind),
        # first occurrence per exception type journaled (ISSUE 17 s1)
        self.ahead_errors = 0
        self._ahead_err_seen: set = set()
        # tiered staging (executor/tiering.py): T1 host container cache
        # (0 = off, the bare-executor default) and the compressed-upload
        # crossover — dense/payload ratios at or above it ship container
        # payloads and expand on device (ops.expand_blocks) instead of
        # uploading the dense block (0 = always upload dense)
        self.compressed_min_ratio = float(compressed_min_ratio)
        if tier1_max_bytes > 0:
            from pilosa_tpu.executor.tiering import Tier1Cache

            self.tier1 = Tier1Cache(tier1_max_bytes)
        else:
            self.tier1 = None
        # prefetch accuracy (plan-driven prefetcher, tiering.py): keys
        # staged speculatively, resolved to used on the first real hit
        # or to evicted when LRU/governor pressure drops them untouched
        self._prefetched: set = set()
        self.prefetch_issued = 0
        self.prefetch_used = 0
        self.prefetch_evicted = 0
        # keys dropped under capacity pressure: a later cold miss on one
        # of these is a RE-ENTRY — bytes an earlier stage already paid
        # to upload (stager.restaged_bytes). Bounded below; explicit
        # clears/wedges forget it (those aren't capacity pressure).
        self._evicted_keys: set = set()

    # -- internal --

    def _key(self, frag, kind: str, extra=()) -> tuple:
        # NOTE: no generation — entries persist across mutations and
        # track their snapshot generation in _Entry.gen instead
        return (id(frag), kind) + tuple(extra)

    @staticmethod
    def _tenant_of(frag) -> str:
        """Owning index name for a fragment (or stack of fragments —
        one field, one index); "" when untracked."""
        if frag is None:
            return ""
        if isinstance(frag, (list, tuple)):
            for f in frag:
                if f is not None:
                    return getattr(f, "index", "") or ""
            return ""
        return getattr(frag, "index", "") or ""

    @staticmethod
    def _heat_stage(frag, nbytes: int, hit: bool) -> None:
        """Attribute a stager hit/miss to the heat ledger. ``frag`` is a
        fragment, a list of fragments (stacked forms — the uploaded
        bytes are split evenly across live members), or None (untracked
        internal entries)."""
        if frag is None or not heat.LEDGER.enabled:
            return
        frags = frag if isinstance(frag, (list, tuple)) else (frag,)
        live = [f for f in frags if f is not None]
        if not live:
            return
        per = 0 if hit else int(nbytes) // len(live)
        for f in live:
            heat.LEDGER.record_stage(f.index, f.field, f.shard, per, hit)

    def _note_evicted_locked(self, key: tuple) -> None:
        """A cache entry left under pressure: if it was staged
        speculatively and never hit, the prefetch was wasted — the
        accuracy counters' denominator. The key is also remembered so a
        later re-stage can be attributed to oversubscription
        (stager.restaged_bytes). Caller holds _mu."""
        if key in self._prefetched:
            self._prefetched.discard(key)
            self.prefetch_evicted += 1
            metrics.count(metrics.PREFETCH_EVICTED)
        if len(self._evicted_keys) >= 65536:
            # pathological key churn: reset rather than grow without
            # bound (loses re-entry attribution for the dropped keys)
            self._evicted_keys.clear()
        self._evicted_keys.add(key)

    def _get_or_build(
        self,
        key,
        gen,
        builder: Callable,
        delta_fn: Optional[Callable] = None,
        frag=None,
        prefetch: bool = False,
    ):
        """Return the staged value for ``key``, fresh w.r.t. the
        caller-observed generation token ``gen``.

        builder() -> (value, nbytes, built_gen); runs when no usable
        entry exists. delta_fn(old_value, old_gen) -> (value, built_gen,
        n_updates) or None; runs when an entry exists at an older
        generation — None falls back to builder() (full re-stage).
        Both capture built_gen BEFORE reading fragment state, so the
        recorded generation never overstates the content.
        """
        while True:
            fl = None
            stale: Optional[_Entry] = None
            # stager.lookup: the probe of every call, hit or miss — a
            # content-derived key (a TopN chunk's: 64 tuples of up to
            # 4,096 ids) is hashed again by each dict it is looked up in
            with trace.leg(trace.WF_STAGER_LOOKUP), self._mu:
                ent = self._cache.get(key)
                if ent is not None and _gen_fresh(ent.gen, gen):
                    self._cache.move_to_end(key)
                    self.hits += 1
                    metrics.count(metrics.STAGER_HITS)
                    if not prefetch and key in self._prefetched:
                        # a real query reached a speculatively staged
                        # block — the prefetch paid off
                        self._prefetched.discard(key)
                        self.prefetch_used += 1
                        metrics.count(metrics.PREFETCH_USED)
                    self._heat_stage(frag, 0, True)
                    return ent.value
                epoch = self._epoch
                fl = self._inflight.get(key)
                if fl is None:
                    fl = _InFlight()
                    self._inflight[key] = fl
                    building = True
                    stale = ent
                else:
                    building = False
            if not building:
                with trace.leg(trace.WF_STAGER):  # another thread's build
                    fl.event.wait()
                if fl.error is not None:
                    raise fl.error
                if fl.gen is None or _gen_fresh(fl.gen, gen):
                    return fl.value
                # the build we joined predates our observed generation:
                # retry — the fresh cache entry makes the next lap a
                # cheap hit or delta apply
                continue
            try:
                value = nbytes = built_gen = None
                if (
                    stale is not None
                    and delta_fn is not None
                    and self.delta_enabled
                ):
                    sp = trace.current()
                    with trace.leg(trace.WF_STAGER) as lg:
                        if sp is None:
                            res = delta_fn(stale.value, stale.gen)
                        else:
                            with sp.child(metrics.STAGE_DELTA) as ssp:
                                res = delta_fn(stale.value, stale.gen)
                                if res is not None:
                                    ssp.annotate(nupdates=res[2])
                    if res is not None:
                        value, built_gen, _n = res
                        nbytes = stale.nbytes  # delta never changes shape
                        self.delta_applies += 1
                        metrics.count(metrics.STAGER_DELTA_APPLIED)
                        metrics.observe(
                            metrics.STAGER_DELTA_APPLY_SECONDS, lg.seconds
                        )
                if value is None:
                    sp = trace.current()
                    with trace.leg(trace.WF_STAGER) as lg:
                        if sp is None:
                            value, nbytes, built_gen = builder()
                        else:
                            with sp.child(metrics.STAGE_STAGE) as ssp:
                                value, nbytes, built_gen = builder()
                                ssp.annotate(nbytes=nbytes)
                    metrics.observe(metrics.STAGER_STAGE_SECONDS, lg.seconds)
                    metrics.count(metrics.STAGER_MISSES)
                    self._heat_stage(frag, nbytes, False)
                    if stale is None:
                        metrics.count(metrics.STAGER_MISSES_COLD)
                    else:
                        # generation-bump invalidation that could not be
                        # absorbed as a delta — the bytes we re-uploaded
                        # are the cost delta staging exists to avoid
                        metrics.count(metrics.STAGER_MISSES_INVALIDATION)
                        metrics.count(metrics.STAGER_RESTAGED_BYTES, nbytes)
                    with self._mu:
                        self.misses += 1
                        reentry = stale is None and key in self._evicted_keys
                        if reentry:
                            self._evicted_keys.discard(key)
                    if reentry:
                        # capacity-eviction re-entry: an upload already
                        # paid for once — the bytes tiering (T1 +
                        # compressed upload) exists to cheapen
                        metrics.count(metrics.STAGER_RESTAGED_BYTES, nbytes)
            except BaseException as e:
                with self._mu:
                    # identity check mirrors the success path: an
                    # epoch-stale zombie that raises must not evict a
                    # post-reset rebuild's in-flight entry
                    if self._inflight.get(key) is fl:
                        self._inflight.pop(key, None)
                fl.error = e
                fl.event.set()
                raise
            # ledger first, insert second: reserve runs the governor's
            # relief sweep over OTHER tenants (device plan cache) and
            # MUST NOT hold _mu — its eviction callbacks take their
            # owners' locks (lock order: tenant lock → governor lock,
            # never the reverse). The charge names the owning index so
            # the governor's per-tenant quota accounting (ISSUE 19)
            # sees who the bytes belong to; an over-quota index's
            # reserve triggers a targeted sweep of its OWN blocks.
            tenant = self._tenant_of(frag)
            gov = self.governor
            if gov is not None:
                gov.reserve("stager", nbytes, index=tenant)
            # bytes handed back to the ledger after insert, by index
            gov_return: dict[str, int] = {}
            with self._mu:
                if self._epoch == epoch:
                    old = self._cache.pop(key, None)
                    if old is not None:
                        self._bytes -= old.nbytes
                        gov_return[old.tenant] = (
                            gov_return.get(old.tenant, 0) + old.nbytes
                        )
                    self._cache[key] = _Entry(value, nbytes, built_gen, tenant)
                    self._bytes += nbytes
                    if prefetch:
                        self._prefetched.add(key)
                        self.prefetch_issued += 1
                        metrics.count(metrics.PREFETCH_ISSUED)
                    else:
                        # a real rebuild at a previously-prefetched key
                        # (delta/invalidation): the speculative copy is
                        # gone, stop attributing this key
                        self._prefetched.discard(key)
                    # evict LRU past the tenant share — and past the
                    # GLOBAL budget (over_budget already nets out the
                    # gov_return bytes released below)
                    returned = sum(gov_return.values())
                    while (
                        self._bytes > self.budget_bytes
                        or (gov is not None and gov.over_budget() > returned)
                    ) and len(self._cache) > 1:
                        old_key, old_ent = self._cache.popitem(last=False)
                        self._bytes -= old_ent.nbytes
                        returned += old_ent.nbytes
                        gov_return[old_ent.tenant] = (
                            gov_return.get(old_ent.tenant, 0) + old_ent.nbytes
                        )
                        self._note_evicted_locked(old_key)
                    self._inflight.pop(key, None)
                    metrics.gauge(metrics.STAGER_BYTES, self._bytes)
                else:
                    # epoch-stale: the value never enters the cache, so
                    # its reservation goes straight back
                    gov_return[tenant] = gov_return.get(tenant, 0) + nbytes
                    if self._inflight.get(key) is fl:
                        # same epoch-stale builder still registered (no
                        # rebuild raced in): unregister without caching
                        # the stale value
                        self._inflight.pop(key, None)
            if gov is not None:
                for t, n in gov_return.items():
                    gov.release("stager", n, index=t)
            fl.gen = built_gen
            fl.value = value
            fl.event.set()
            return value

    def _to_device(self, words64: np.ndarray):
        w32 = np.ascontiguousarray(words64).view("<u4")
        return jax.device_put(w32, self.device)

    def upload(self, w32: np.ndarray):
        """Place an already-u32 host array on the stager's device.

        Used by the executor's device-resident plan cache to pin
        ``__cached`` bitmap stacks in HBM with the same placement the
        kernels expect; bypasses the staging cache (the plan cache does
        its own byte accounting and invalidation)."""
        return jax.device_put(np.ascontiguousarray(w32), self.device)

    def _to_device_sharded(self, words64: np.ndarray):
        """Place a shard-major [S, ...] stack split over the mesh's
        shard axis; falls back to single-device placement when no mesh
        is configured (or S doesn't divide — callers pad via the
        executor's shard plan, so that only happens off the SPMD path)."""
        w32 = np.ascontiguousarray(words64).view("<u4")
        if self.mesh is not None and w32.shape[0] % self.mesh.devices.size == 0:
            from pilosa_tpu.parallel.spmd import put_sharded

            return put_sharded(self.mesh, w32)
        return jax.device_put(w32, self.device)

    # -- tiered dense builds (executor/tiering.py) ---------------------------

    def _tiering_on(self) -> bool:
        return self.tier1 is not None or self.compressed_min_ratio > 0

    def _container_entries(self, frag, row_ids):
        """Container payloads for ``row_ids``, T1-first: a hit skips
        the fragment walk entirely; a miss walks T2 (the mmapped
        fragment) and offers the result to T1 with the walk's measured
        cost — the admission model's "what a hit saves"."""
        t1 = self.tier1
        if t1 is not None:
            entries = t1.get(frag, row_ids)
            if entries is not None:
                return entries
        gen = frag.generation  # before the walk: content at least this fresh
        t0 = time.monotonic()
        entries, nbytes = frag.container_blocks(list(row_ids))
        cost = time.monotonic() - t0
        if t1 is not None:
            t1.put(frag, row_ids, entries, nbytes, gen, cost)
        return entries

    def _dense_from_blocks(self, frag, row_ids, rows_total: int):
        """Dense staged block for ``row_ids`` (zero-padded to
        ``rows_total`` rows) built from container payloads instead of a
        fragment word walk. Returns (flat device u32[rows_total * W],
        dense_nbytes — the device-resident size the governor is
        charged). When the dense/payload ratio clears
        ``compressed_min_ratio`` the wire carries the payloads and
        ops.expand_blocks rebuilds packed words on device; otherwise
        the dense block is assembled on host and uploaded as before."""
        entries = self._container_entries(frag, row_ids)
        num_words = rows_total * _W32
        dense_nbytes = num_words * 4
        cbytes = sum(p.nbytes for _, _, _, p in entries)
        if (
            self.compressed_min_ratio > 0
            and cbytes
            # global bit coordinates must stay inside u32 (and word
            # indexes inside the scatter kernel's i32 cast)
            and rows_total <= _MAX_COMPRESSED_ROWS
            and dense_nbytes >= self.compressed_min_ratio * cbytes
        ):
            return self._compressed_upload(entries, num_words), dense_nbytes
        from pilosa_tpu.roaring.bitmap import (
            CONTAINER_ARRAY,
            CONTAINER_RUN,
            Container,
        )

        words32 = np.zeros((rows_total, _W32), dtype="<u4")
        for i, slot, typ, payload in entries:
            if typ == CONTAINER_ARRAY:
                w64 = Container.from_array(payload).words()
            elif typ == CONTAINER_RUN:
                w64 = Container.from_runs(payload).words()
            else:
                w64 = payload
            lo = slot << 11  # 2048 u32 words per 2^16-bit container
            words32[i, lo : lo + 2048] = np.ascontiguousarray(w64).view("<u4")
        return jax.device_put(words32.reshape(-1), self.device), dense_nbytes

    def _compressed_upload(self, entries, num_words: int):
        """Ship container payloads and expand on device: every entry's
        bits become coordinates in the block's flat bit space
        (row_index * SHARD_WIDTH + slot * 2^16 + local) and the jit
        scatter kernel (ops.packed.expand_blocks) ORs them into packed
        words. Input shapes are pow2-bucketed to bound recompiles;
        padding uses coordinates the kernel provably drops (positions
        0xFFFFFFFF → out-of-range word; runs with start > end; dense
        rows aimed at num_words)."""
        from pilosa_tpu.executor.batcher import _next_pow2
        from pilosa_tpu.roaring.bitmap import CONTAINER_ARRAY, CONTAINER_RUN

        pos_l, rs_l, re_l, dense_l, dw_l = [], [], [], [], []
        uploaded = 0
        for i, slot, typ, payload in entries:
            base = np.uint32(i * SHARD_WIDTH + (slot << 16))
            if typ == CONTAINER_ARRAY:
                pos_l.append(base + payload.astype(np.uint32))
            elif typ == CONTAINER_RUN:
                rs_l.append(base + payload[:, 0].astype(np.uint32))
                re_l.append(base + payload[:, 1].astype(np.uint32))
            else:
                dense_l.append(np.ascontiguousarray(payload).view("<u4"))
                dw_l.append(i * _W32 + (slot << 11))

        def bucketed(parts, fill, dtype):
            a = (
                np.concatenate(parts).astype(dtype, copy=False)
                if parts
                else np.empty(0, dtype)
            )
            out = np.full(_next_pow2(max(a.size, 1)), fill, dtype)
            out[: a.size] = a
            return out

        positions = bucketed(pos_l, 0xFFFFFFFF, np.uint32)
        starts = bucketed(rs_l, 1, np.uint32)
        ends = bucketed(re_l, 0, np.uint32)
        d = len(dense_l)
        dense = np.zeros((_next_pow2(max(d, 1)), 2048), dtype=np.uint32)
        dword = np.full(dense.shape[0], num_words, dtype=np.int32)
        for k, row in enumerate(dense_l):
            dense[k] = row
        if d:
            dword[:d] = np.asarray(dw_l, dtype=np.int32)
        dev = self.device
        out = ops.expand_blocks(
            jax.device_put(positions, dev),
            jax.device_put(starts, dev),
            jax.device_put(ends, dev),
            jax.device_put(dense, dev),
            jax.device_put(dword, dev),
            num_words=num_words,
        )
        uploaded = (
            positions.nbytes
            + starts.nbytes
            + ends.nbytes
            + dense.nbytes
            + dword.nbytes
        )
        metrics.count(metrics.TIERING_COMPRESSED_UPLOADS)
        metrics.count(
            metrics.TIERING_UPLOAD_BYTES_SAVED,
            max(0, num_words * 4 - uploaded),
        )
        return out

    # -- delta helpers -------------------------------------------------------

    def _fallback(self, reason: str, form: Optional[str] = None) -> None:
        if form is None:
            metrics.count(metrics.STAGER_DELTA_FALLBACK, reason=reason)
            return
        # sparse_form alone says "a block-sparse layout re-staged" but
        # not WHICH — the form rides as a second label and on the
        # current trace stage so a tail of full re-stages is
        # attributable to the layout that caused it (ISSUE 17 s2)
        metrics.count(metrics.STAGER_DELTA_FALLBACK, reason=reason, form=form)
        sp = trace.current()
        if sp is not None:
            sp.annotate(fallback_form=form)

    def _deltas(self, frag, since_gen):
        """Fragment delta stream since ``since_gen`` split into row /
        word-in-row / bit coordinates, or None (+ fallback metric)."""
        d = frag.deltas_since(since_gen)
        if d is None:
            self._fallback("log")
            return None
        pos, is_set, gen = d
        rows = (pos // np.uint64(SHARD_WIDTH)).astype(np.int64)
        local = (pos % np.uint64(SHARD_WIDTH)).astype(np.int64)
        return rows, local >> 5, (local & 31), is_set, gen

    def _scatter(self, dev, word_idx, bit_idx, is_set, gen, n_slots_words):
        """Coalesce + pad + run the delta kernel over a flat word space
        of ``n_slots_words`` words; returns (new_value, gen, K) or None
        when the batch is too large to beat a re-stage."""
        if word_idx.size == 0:
            return dev, gen, 0
        sh = getattr(dev, "sharding", None)
        if sh is not None and any(
            d.process_index != jax.process_index() for d in sh.device_set
        ):
            # multi-process (jax.distributed) sharded stacks full-rebuild
            # on a generation mismatch: the post-scatter re-pin would be
            # a cross-host reshard, and the rebuild path already places
            # globally via make_array_from_callback
            self._fallback("multihost")
            return None
        idx, om, am = ops.coalesce_bit_updates(word_idx, bit_idx, is_set)
        if idx.size > int(self.delta_max_ratio * n_slots_words):
            self._fallback("ratio")
            return None
        idx, om, am = ops.pad_updates(idx, om, am, n_slots_words)
        new = ops.apply_word_updates(dev, idx, om, am)
        if getattr(dev, "sharding", None) is not None:
            # stacks staged over a mesh axis must come back with the
            # entry's placement — scatter output sharding is whatever
            # GSPMD propagated through the flatten
            new = jax.device_put(new, dev.sharding)
        return new, gen, int(idx.size)

    # -- staging entry points --

    def row(self, frag, row_id: int, prefetch: bool = False):
        """u32[W] for one row. ``prefetch=True`` marks a speculative
        build (plan-driven prefetcher, executor/tiering.py) for the
        accuracy counters."""

        def build():
            gen = frag.generation
            if self._tiering_on():
                dev, nbytes = self._dense_from_blocks(frag, (row_id,), 1)
                return dev, nbytes, gen
            words = frag.row_words(row_id)
            return self._to_device(words), words.nbytes, gen

        def delta(old, old_gen):
            d = self._deltas(frag, old_gen)
            if d is None:
                return None
            rows, widx, bidx, is_set, gen = d
            m = rows == row_id
            return self._scatter(
                old, widx[m], bidx[m], is_set[m], gen, _W32
            )

        return self._get_or_build(
            self._key(frag, "row", (row_id,)),
            frag.generation,
            build,
            delta,
            frag=frag,
            prefetch=prefetch,
        )

    def _delta_for_slots(self, frag, slot_of: dict, n_rows_staged: int):
        """delta_fn for forms staging a fixed set of rows as [K, W]:
        slot_of maps row id → block row. Deltas on unmapped rows don't
        touch the block (they're not staged) and are dropped."""

        def delta(old, old_gen):
            d = self._deltas(frag, old_gen)
            if d is None:
                return None
            rows, widx, bidx, is_set, gen = d
            if rows.size:
                slots = np.fromiter(
                    (slot_of.get(int(r), -1) for r in rows),
                    dtype=np.int64,
                    count=rows.size,
                )
                keep = slots >= 0
                widx = slots[keep] * _W32 + widx[keep]
                bidx = bidx[keep]
                is_set = is_set[keep]
            return self._scatter(
                old, widx, bidx, is_set, gen, n_rows_staged * _W32
            )

        return delta

    def rows(self, frag, row_ids: tuple[int, ...], pad_pow2: bool = False):
        """u32[K, W] stack of specific rows.

        pad_pow2=True pads the row count up to the next power of two
        with zero rows (SURVEY.md §7 hard part 5: bucketed shapes keep
        the XLA compile cache at log2 distinct row counts instead of
        one entry per candidate-set size). Zero rows score 0 and
        callers index results by the true row_ids, so padding is
        invisible. Only valid for scoring-style consumers — boolean
        folds over the stack would see the zero rows.
        """
        from pilosa_tpu.executor.batcher import _next_pow2

        kind = "rows_p2" if pad_pow2 else "rows"
        nrows = len(row_ids)
        if pad_pow2 and nrows:
            nrows = _next_pow2(nrows)

        def build():
            gen = frag.generation
            if self._tiering_on() and row_ids:
                dev, nbytes = self._dense_from_blocks(frag, row_ids, nrows)
                return dev.reshape(nrows, _W32), nbytes, gen
            words = frag.packed_rows(list(row_ids))
            if pad_pow2 and len(row_ids):
                target = _next_pow2(words.shape[0])
                if target > words.shape[0]:
                    words = np.pad(words, ((0, target - words.shape[0]), (0, 0)))
            return self._to_device(words), words.nbytes, gen

        slot_of = {int(r): k for k, r in enumerate(row_ids)}
        return self._get_or_build(
            self._key(frag, kind, (row_ids,)),
            frag.generation,
            build,
            self._delta_for_slots(frag, slot_of, nrows),
            frag=frag,
        )

    def sparse_rows(self, frag, row_ids: tuple[int, ...]):
        """Block-sparse candidate staging for TopN scoring:
        (blocks u32[B, 2048], block_row i32[B], block_slot i32[B],
        num_rows) with B and the row count padded to powers of two
        (zero blocks aimed at row 0 score 0; callers slice results to
        len(row_ids)). The memory-scalable alternative to rows() —
        bytes staged scale with set containers, not candidates × 128 KB
        (SURVEY.md §7 hard part 2).

        No delta path: a mutation can occupy a container the sparse
        form didn't stage (no scatter target exists), so a generation
        mismatch always full-rebuilds (counted as delta_fallback)."""
        from pilosa_tpu.executor.batcher import _next_pow2

        def build():
            gen = frag.generation
            blocks, brow, bslot = frag.sparse_row_blocks(list(row_ids))
            num_rows = _next_pow2(max(len(row_ids), 1))
            b = blocks.shape[0]
            b_pad = _next_pow2(max(b, 1))
            if b_pad > b:
                blocks = np.pad(blocks, ((0, b_pad - b), (0, 0)))
                brow = np.pad(brow, (0, b_pad - b))
                bslot = np.pad(bslot, (0, b_pad - b))
            w32 = np.ascontiguousarray(blocks).view("<u4")
            dev = (
                jax.device_put(w32, self.device),
                jax.device_put(brow, self.device),
                jax.device_put(bslot, self.device),
                num_rows,
            )
            return dev, w32.nbytes + brow.nbytes + bslot.nbytes, gen

        return self._get_or_build(
            self._key(frag, "sparse_rows", (row_ids,)),
            frag.generation,
            build,
            self._sparse_fallback_for("sparse_rows"),
            frag=frag,
        )

    def _sparse_fallback_for(self, form: str):
        """Documented non-path: block-sparse forms always re-stage on a
        generation mismatch (see sparse_rows). ``form`` names the
        concrete layout so the fallback metric/trace say which one."""

        def fallback(old, old_gen):
            self._fallback("sparse_form", form=form)
            return None

        return fallback

    def matrix(self, frag):
        """(row_ids, u32[R, W]) for all non-empty rows."""

        def build():
            gen = frag.generation
            ids, words = frag.row_matrix()
            dev = self._to_device(words) if len(ids) else None
            return (ids, dev), words.nbytes, gen

        def delta(old, old_gen):
            ids, dev = old
            d = self._deltas(frag, old_gen)
            if d is None:
                return None
            rows, widx, bidx, is_set, gen = d
            if rows.size == 0:
                return old, gen, 0
            if dev is None:
                # empty matrix gaining rows is a shape change
                self._fallback("shape")
                return None
            slot_of = {int(r): k for k, r in enumerate(ids)}
            slots = np.fromiter(
                (slot_of.get(int(r), -1) for r in rows),
                dtype=np.int64,
                count=rows.size,
            )
            if (slots < 0).any():
                # a row outside the staged non-empty set changed — the
                # matrix's row list (and shape) would change on rebuild
                self._fallback("shape")
                return None
            cleared = np.unique(rows[~is_set])
            if cleared.size and (
                frag.row_counts_for(cleared.astype(np.uint64)) == 0
            ).any():
                # a clear emptied a row: a rebuild would drop it from
                # the matrix — shape change, patching can't express it
                self._fallback("shape")
                return None
            res = self._scatter(
                dev,
                slots * _W32 + widx,
                bidx,
                is_set,
                gen,
                len(ids) * _W32,
            )
            if res is None:
                return None
            new_dev, gen, n = res
            return (ids, new_dev), gen, n

        return self._get_or_build(
            self._key(frag, "matrix"), frag.generation, build, delta, frag=frag
        )

    def planes(self, frag, bit_depth: int):
        """u32[bit_depth+1, W] BSI plane stack."""

        def build():
            gen = frag.generation
            if self._tiering_on():
                dev, nbytes = self._dense_from_blocks(
                    frag, tuple(range(bit_depth + 1)), bit_depth + 1
                )
                return dev.reshape(bit_depth + 1, _W32), nbytes, gen
            words = frag.bsi_planes(bit_depth)
            return self._to_device(words), words.nbytes, gen

        # plane p is row p; rows above the staged depth aren't in this
        # block (a deeper write keys a different planes(depth) entry)
        slot_of = {r: r for r in range(bit_depth + 1)}
        return self._get_or_build(
            self._key(frag, "planes", (bit_depth,)),
            frag.generation,
            build,
            self._delta_for_slots(frag, slot_of, bit_depth + 1),
            frag=frag,
        )

    # -- shard-batched staging (one array covering many fragments) ----------

    def _stack_key(self, frags, kind: str, extra=()) -> tuple:
        return (
            tuple(id(f) if f is not None else None for f in frags),
            kind,
        ) + tuple(extra)

    def _stack_gen(self, frags) -> tuple:
        return tuple(f.generation if f is not None else None for f in frags)

    def _delta_for_stack(self, frags, slot_of_fn, words_per_frag: int):
        """delta_fn for [S, ...] stacks: per changed fragment, map its
        deltas through slot_of_fn(row) → word offset within the
        fragment's words_per_frag slice (or None to drop), then one
        combined scatter over the flat [S * words_per_frag] space."""

        def delta(old, old_gens):
            all_w, all_b, all_s = [], [], []
            new_gens = list(old_gens)
            for i, f in enumerate(frags):
                if f is None:
                    continue
                if old_gens[i] is None:
                    # can't happen with stable keys (the key pins which
                    # positions are None) — full rebuild, defensively
                    self._fallback("log")
                    return None
                if f.generation == old_gens[i]:
                    continue
                d = self._deltas(f, old_gens[i])
                if d is None:
                    return None
                rows, widx, bidx, is_set, gen = d
                new_gens[i] = gen
                if rows.size == 0:
                    continue
                slots = np.fromiter(
                    (slot_of_fn(int(r)) for r in rows),
                    dtype=np.int64,
                    count=rows.size,
                )
                keep = slots >= 0
                if not keep.any():
                    continue
                all_w.append(
                    i * words_per_frag + slots[keep] * _W32 + widx[keep]
                )
                all_b.append(bidx[keep])
                all_s.append(is_set[keep])
            gen_t = tuple(new_gens)
            if not all_w:
                return old, gen_t, 0
            res = self._scatter(
                old,
                np.concatenate(all_w),
                np.concatenate(all_b),
                np.concatenate(all_s),
                gen_t,
                len(frags) * words_per_frag,
            )
            return res

        return delta

    def row_stack(self, frags, row_id: int, prefetch: bool = False):
        """u32[S, W]: one row across S fragments (None → zeros).
        ``prefetch=True`` marks a speculative build (plan-driven
        prefetcher, executor/tiering.py) for the accuracy counters —
        batched and fused execution read rows through this stacked
        form, so the prefetcher warms the same key."""

        def build():
            gens = self._stack_gen(frags)
            words = np.zeros((len(frags), SHARD_WIDTH // 64), dtype=np.uint64)
            for i, f in enumerate(frags):
                if f is not None:
                    words[i] = f.row_words(row_id)
            return self._to_device_sharded(words), words.nbytes, gens

        delta = self._delta_for_stack(
            frags, lambda r: 0 if r == row_id else -1, _W32
        )
        return self._get_or_build(
            self._stack_key(frags, "row_stack", (row_id,)),
            self._stack_gen(frags),
            build,
            delta,
            frag=frags,
            prefetch=prefetch,
        )

    def sparse_rows_stacked(
        self,
        frags,
        ids_by_shard: tuple[tuple[int, ...], ...],
        chunk: int,
        peek: bool = False,
    ):
        """Merged block-sparse candidate staging for ALL shards: one
        (blocks u32[B, 16, 128], global_row i32[B], slot i32[B],
        shard i32[B], num_rows) bundle (a 2048-word container block as
        two (8, 128) tiles, so the one-pass scorer reads a block by one
        leading index), where global_row = shard_index
        * chunk + local candidate index. One kernel dispatch then
        scores the whole index's chunk (ops.sparse_intersection_counts_
        stacked). Returns None when no shard has candidates. No delta
        path (see sparse_rows). ``peek``: stage nothing, only say
        whether the bundle is staged or being built (_holds)."""
        from pilosa_tpu.executor.batcher import _next_pow2

        key = self._stack_key(frags, "sparse_stack", (chunk, ids_by_shard))
        if peek:
            return self._holds(key, self._stack_gen(frags))

        def build():
            gens = self._stack_gen(frags)
            all_blocks, rows, slots, shardix = [], [], [], []
            for i, (f, ids) in enumerate(zip(frags, ids_by_shard)):
                if f is None or not ids:
                    continue
                b, br, bs = f.sparse_row_blocks(list(ids))
                if not b.shape[0]:
                    continue
                all_blocks.append(b)
                rows.append(br.astype(np.int32) + np.int32(i * chunk))
                slots.append(bs)
                shardix.append(np.full(bs.size, i, dtype=np.int32))
            num_rows = len(frags) * chunk
            if not all_blocks:
                return None, 0, gens
            blocks = np.concatenate(all_blocks)
            brow = np.concatenate(rows)
            bslot = np.concatenate(slots)
            bshard = np.concatenate(shardix)
            b = blocks.shape[0]
            b_pad = _next_pow2(b)
            if b_pad > b:
                # zero blocks aimed at (shard 0, row 0) contribute 0
                blocks = np.pad(blocks, ((0, b_pad - b), (0, 0)))
                brow = np.pad(brow, (0, b_pad - b))
                bslot = np.pad(bslot, (0, b_pad - b))
                bshard = np.pad(bshard, (0, b_pad - b))
            w32 = np.ascontiguousarray(blocks).view("<u4").reshape(
                b_pad, *ops.STACKED_BLOCK_SHAPE
            )
            dev = (
                jax.device_put(w32, self.device),
                jax.device_put(brow, self.device),
                jax.device_put(bslot, self.device),
                jax.device_put(bshard, self.device),
                num_rows,
            )
            nbytes = w32.nbytes + brow.nbytes + bslot.nbytes + bshard.nbytes
            return dev, nbytes, gens

        return self._get_or_build(
            key,
            self._stack_gen(frags),
            build,
            self._sparse_fallback_for("sparse_stack"),
            frag=frags,
        )

    def sparse_rows_stack(
        self,
        frags,
        ids_by_shard: tuple[tuple[int, ...], ...],
        k: int,
        peek: bool = False,
    ):
        """Shard-major block-sparse candidate staging for the MESH TopN
        path: (blocks u32[S, B, 2048], brow i32[S, B], bslot i32[S, B])
        with every array's leading dim split over the mesh's shard axis
        and B padded to a common power of two across shards. Bytes
        staged scale with set containers, not candidates × 128 KB — the
        sparse analog of rows_stack (SURVEY.md §7 hard part 2). Padding
        blocks are zeros aimed at (row 0, slot 0): they contribute 0 to
        every intersection. Returns None when no shard has blocks. No
        delta path (see sparse_rows). ``peek`` as in sparse_rows_stacked."""
        from pilosa_tpu.executor.batcher import _next_pow2

        key = self._stack_key(frags, "sparse_rows_stack", (k, ids_by_shard))
        if peek:
            return self._holds(key, self._stack_gen(frags))

        def build():
            gens = self._stack_gen(frags)
            per_shard = []
            for f, ids in zip(frags, ids_by_shard):
                if f is None or not ids:
                    per_shard.append(None)
                    continue
                b, br, bs = f.sparse_row_blocks(list(ids))
                per_shard.append((b, br.astype(np.int32), bs))
            bmax = max(
                (p[0].shape[0] for p in per_shard if p is not None), default=0
            )
            if bmax == 0:
                return None, 0, gens
            bmax = _next_pow2(bmax)
            S = len(frags)
            blocks = np.zeros((S, bmax, 1024), dtype=np.uint64)
            brow = np.zeros((S, bmax), dtype=np.int32)
            bslot = np.zeros((S, bmax), dtype=np.int32)
            for i, p in enumerate(per_shard):
                if p is None:
                    continue
                b, br, bs = p
                blocks[i, : b.shape[0]] = b
                brow[i, : br.size] = br
                bslot[i, : bs.size] = bs
            w32 = np.ascontiguousarray(blocks).view("<u4").reshape(S, bmax, 2048)
            if self.mesh is not None and S % self.mesh.devices.size == 0:
                from pilosa_tpu.parallel.spmd import put_sharded

                dev = (
                    put_sharded(self.mesh, w32),
                    put_sharded(self.mesh, brow),
                    put_sharded(self.mesh, bslot),
                )
            else:
                dev = (
                    jax.device_put(w32, self.device),
                    jax.device_put(brow, self.device),
                    jax.device_put(bslot, self.device),
                )
            return dev, w32.nbytes + brow.nbytes + bslot.nbytes, gens

        return self._get_or_build(
            key,
            self._stack_gen(frags),
            build,
            self._sparse_fallback_for("sparse_rows_stack"),
            frag=frags,
        )

    def planes_stack(self, frags, bit_depth: int):
        """u32[S, bit_depth+1, W] across S fragments (None → zeros)."""

        def build():
            gens = self._stack_gen(frags)
            words = np.zeros(
                (len(frags), bit_depth + 1, SHARD_WIDTH // 64), dtype=np.uint64
            )
            for i, f in enumerate(frags):
                if f is not None:
                    words[i] = f.bsi_planes(bit_depth)
            return self._to_device_sharded(words), words.nbytes, gens

        delta = self._delta_for_stack(
            frags,
            lambda r: r if r <= bit_depth else -1,
            (bit_depth + 1) * _W32,
        )
        return self._get_or_build(
            self._stack_key(frags, "planes_stack", (bit_depth,)),
            self._stack_gen(frags),
            build,
            delta,
            frag=frags,
        )

    def _holds(self, key, gen) -> bool:
        """A peek for advisory staging: is ``key`` staged fresh for
        ``gen``, or being built? Counts no hit and leaves the LRU order
        alone: a chunk staged ahead that no walk reads should be the
        first to go."""
        with self._mu:
            ent = self._cache.get(key)
            return key in self._inflight or (
                ent is not None and _gen_fresh(ent.gen, gen)
            )

    def has_room(self, nbytes: int) -> bool:
        """Would an entry of ``nbytes`` fit without evicting anything?
        Advisory staging asks first: what it would push out is, as a
        rule, what the running query is reading."""
        with self._mu:
            fits = self._bytes + nbytes <= self.budget_bytes
        gov = self.governor
        return fits and (gov is None or nbytes <= gov.headroom())

    def stage_ahead(self, thunk) -> None:
        """Queue an advisory warm thunk on the background prefetch
        thread: the dispatch engine calls this with the NEXT wave's
        operand staging while the current wave computes, so uploads
        overlap kernel execution. Purely advisory — the deque is
        bounded (oldest dropped under pressure), errors are swallowed,
        and the real execution path re-stages anything missed. The
        thread retires after a few idle seconds and restarts on the
        next call."""
        start: Optional[threading.Thread] = None
        with self._ahead_mu:
            self._ahead_q.append(thunk)
            t = self._ahead_thread
            # ident None = created by a racing caller but not yet
            # started (start() happens below, outside the lock)
            if t is None or (t.ident is not None and not t.is_alive()):
                start = self._ahead_thread = threading.Thread(
                    target=self._stage_ahead_loop,
                    name="stage-ahead",
                    daemon=True,
                )
            self._ahead_cv.notify()
        if start is not None:
            start.start()

    def _stage_ahead_loop(self) -> None:
        while True:
            with self._ahead_mu:
                while not self._ahead_q:
                    if not self._ahead_cv.wait(timeout=5.0):
                        self._ahead_thread = None
                        return  # idle: let the thread retire
                thunk = self._ahead_q.popleft()
            try:
                thunk()
            except BaseException as e:
                # advisory — the query path stages for real — but NOT
                # invisible: a prefetcher that always raises would
                # otherwise look like one that never fires. Count every
                # failure; journal the first per exception type so the
                # event log has a sample without flooding.
                self.ahead_errors += 1
                metrics.count(metrics.STAGER_AHEAD_ERRORS)
                reason = type(e).__name__
                if reason not in self._ahead_err_seen:
                    self._ahead_err_seen.add(reason)
                    events.record(
                        events.STAGER_AHEAD_ERROR,
                        reason=reason,
                        error=str(e)[:200],
                    )

    def set_governor(self, governor) -> None:
        """Attach the process-wide HBM governor (executor/hbm.py): the
        budget knob becomes this stager's tenant share, cold LRU blocks
        its relief tier (tier 1 — evicted after the device plan cache),
        and any already-resident bytes join the ledger."""
        self.governor = governor
        if governor is None:
            return
        governor.register(
            "stager",
            share_bytes=self.budget_bytes,
            evict_fn=self._evict_cold,
            tier=1,
        )
        with self._mu:
            current = self._bytes
        if current:
            governor.reserve("stager", current)
        if self.tier1 is not None:
            # host-domain tenant: visible in /debug/hbm, outside the
            # device budget (executor/hbm.py domains)
            self.tier1.set_governor(governor)

    def _evict_cold(self, need: int, prefer=None) -> int:
        """Governor relief tier: drop cold (LRU) staged blocks until
        ``need`` bytes are freed, always keeping the hottest entry —
        the block a query is most likely touching right now. With
        ``prefer`` (a list of over-quota indexes, ISSUE 19) the sweep
        frees ONLY those tenants' blocks, coldest first — an
        under-quota tenant never loses a block to someone else's quota
        sweep. Called by the governor WITHOUT its lock held; the
        releases below keep the ledger exact."""
        freed = 0
        freed_by: dict[str, int] = {}
        with self._mu:
            if prefer is not None:
                wanted = set(prefer)
                # coldest-first among the preferred tenants' blocks
                victims = [
                    k
                    for k, ent in self._cache.items()
                    if ent.tenant in wanted
                ]
                for k in victims:
                    if freed >= need or len(self._cache) <= 1:
                        break
                    ent = self._cache.pop(k)
                    self._bytes -= ent.nbytes
                    freed += ent.nbytes
                    freed_by[ent.tenant] = (
                        freed_by.get(ent.tenant, 0) + ent.nbytes
                    )
                    self._note_evicted_locked(k)
            else:
                while freed < need and len(self._cache) > 1:
                    k, ent = self._cache.popitem(last=False)
                    self._bytes -= ent.nbytes
                    freed += ent.nbytes
                    freed_by[ent.tenant] = (
                        freed_by.get(ent.tenant, 0) + ent.nbytes
                    )
                    self._note_evicted_locked(k)
            if freed:
                metrics.gauge(metrics.STAGER_BYTES, self._bytes)
        if freed and self.governor is not None:
            for t, n in freed_by.items():
                self.governor.release("stager", n, index=t)
        return freed

    def clear(self) -> None:
        with self._mu:
            self._cache.clear()
            self._bytes = 0
            # Drop in-flight trackers too: builders still publish their
            # value to current waiters through the _InFlight object, but
            # nothing stale survives here if one errors after clear().
            self._inflight.clear()
            # explicit clears aren't cache pressure — forget prefetch
            # attribution without charging the accuracy counters, and
            # re-entry attribution with it
            self._prefetched.clear()
            self._evicted_keys.clear()
        if self.governor is not None:
            self.governor.reset("stager")
        if self.tier1 is not None:
            # fragment identities may be recycled after a clear (holder
            # restore paths) — host payloads keyed by id() must go too
            self.tier1.clear()

    def reset_after_wedge(self) -> None:
        """Recover from a device wedge (called by the health gate on
        restore): drop every staged array (handles created by the dead
        runtime may be invalid) and fail out in-flight entries whose
        builders are hung inside dead device calls — new queries
        rebuild instead of waiting on a zombie forever. Dropping the
        entries also drops their snapshot generations, so no delta can
        ever replay onto a dead-runtime array. Safe because ``_mu`` is
        never held across a device call."""
        with self._mu:
            self._cache.clear()
            self._bytes = 0
            self._epoch += 1  # zombie builders must not repopulate
            self._prefetched.clear()  # a wedge isn't cache pressure
            self._evicted_keys.clear()
            stale, self._inflight = self._inflight, {}
        # the ledger must forget the dead runtime's arrays with us —
        # the epoch fence extends to the governor (ISSUE 14)
        if self.governor is not None:
            self.governor.reset("stager")
        for fl in stale.values():
            if not fl.event.is_set():
                fl.error = RuntimeError("staging abandoned: device wedged")
                fl.event.set()

    def reset_for_reform(self) -> None:
        """Gang re-formation (parallel/federation.py): arrays staged
        under the previous gang epoch may reference the torn global
        mesh, and pending delta snapshots predate the re-synced host
        fragments — drop everything so post-reform queries re-stage
        from the current holder state. Same mechanics as a device
        wedge: epoch bump fences zombie builders."""
        self.reset_after_wedge()
        if self.tier1 is not None:
            # re-synced host fragments invalidate T1 payloads too (a
            # device wedge alone does not — those stay warm for the
            # recovery restage)
            self.tier1.clear()
