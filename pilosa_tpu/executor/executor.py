"""Query executor (L4) — lowers PQL call trees onto shard kernels.

Mirrors the reference's executor (reference executor.go): top-level
dispatch by call name, per-shard leaf functions, cross-shard map/reduce.
Two execution paths per shard:

  * CPU   — roaring Row algebra (the correctness oracle, always available)
  * device — packed-word XLA kernels over HBM-staged fragment state:
             bitmap subtrees fold elementwise, Count/Sum/Min/Max reduce
             via popcount kernels, TopN batches every candidate's
             intersection count into one matrix pass
             (replacing the reference's per-candidate heap loop).

Both paths are bit-identical; `device_policy` picks ("never" | "auto" |
"always"). Cross-node distribution plugs in through the `cluster`
seam (reference mapReduce, executor.go:1464) — single-node runs use a
local loop over shards.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import Counter
from dataclasses import dataclass
from datetime import datetime
from typing import Any, Optional

import numpy as np

from pilosa_tpu.utils import chaos, heat, metrics, profiler, trace

from pilosa_tpu import SHARD_WIDTH, ops
from pilosa_tpu.core import Row, TopOptions, VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD
from pilosa_tpu.core.cache import sort_pairs
from pilosa_tpu.core.cache import pairs_arrays as cache_pairs_arrays
from pilosa_tpu.core.fragment import DEFAULT_MIN_THRESHOLD, FragmentQuarantinedError
from pilosa_tpu.executor import analytics
from pilosa_tpu.core.timequantum import TIME_FORMAT, views_by_time_range
from pilosa_tpu.executor.batcher import BatchedScorer, _next_pow2
from pilosa_tpu.executor.devicehealth import DeviceDown
from pilosa_tpu.executor.hbm import (
    DeviceOom,
    HbmGovernor,
    OomRecovery,
    classify_device_error,
)
from pilosa_tpu.executor.stager import DeviceStager
from pilosa_tpu.pql import BETWEEN, Call, Condition, NEQ, Query, parse
from pilosa_tpu.roaring import Bitmap

_W32 = SHARD_WIDTH // 32

# Minimum packed words across a query's fragments before "auto" picks the
# device path (tiny fragments are faster in roaring on host).
AUTO_DEVICE_MIN_CONTAINERS = 64


# re-export: one canonical not-found type framework-wide (the HTTP
# layer maps it to 404 by type; any plain KeyError stays a 500)
from pilosa_tpu.utils.errors import NotFoundError  # noqa: E402

# Request-deadline seam (server/deadline.py). Imported LAZILY: a
# top-level import would pull the server package (L6) into this module
# (L4) at import time and trip the server→executor circular import;
# resolving once at first use costs one global check per call after.
_deadline_mod = None


def _deadline():
    global _deadline_mod
    if _deadline_mod is None:
        from pilosa_tpu.server import deadline as _m

        _deadline_mod = _m
    return _deadline_mod


@dataclass
class ValCount:
    """reference executor.go:1762."""

    val: int = 0
    count: int = 0

    def add(self, other: "ValCount") -> "ValCount":
        return ValCount(self.val + other.val, self.count + other.count)

    def smaller(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val < self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)

    def larger(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val > self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)


def pairs_add(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge id/count pair lists, summing counts (reference Pairs.Add)."""
    m = dict(a)
    for id_, cnt in b:
        m[id_] = m.get(id_, 0) + cnt
    return list(m.items())


@dataclass
class ExecOptions:
    """reference execOptions (executor.go:1714)."""

    remote: bool = False
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    # plan result cache participation (plan/cache.py): False bypasses
    # both lookup and insert — the `cache=false` query option, and the
    # profile=true path (a profiled query must show real execution)
    cache: bool = True
    # run a multi-call query's calls serially instead of through the
    # read pool. Gang-dispatched multihost execution requires it: every
    # rank must issue collectives in the identical order, and a thread
    # pool's interleaving is not deterministic across processes
    serial: bool = False


class _NotDeviceable(Exception):
    """Raised when a call subtree can't run on the device path. Every
    handler serves the call from the CPU instead, so raising one is
    counted: the route counters were bumped for the device by then."""

    def __init__(self, what: str) -> None:
        super().__init__(what)
        metrics.count(metrics.EXECUTOR_NOT_DEVICEABLE, what=what)


class _ScoreCarry:
    """Cross-pass TopN score carry: the providers pass 1 scored with, as
    they stand (score matrix, fragments, candidate lists). Nothing is
    copied out of them and no per-id dict is built.

    Pass 2 needs every winner's exact count in every shard. answer()
    reads whole shards at a time from the matrices: one searchsorted a
    shard, the gathers and compares stacked over the shards. seed()
    serves the per-id pass of the shards answer() leaves, through the
    scored prefix's id -> position index, which a Rankings snapshot
    keeps (core.cache.Rankings.chunk_index) and a plain list builds."""

    __slots__ = ("_scored", "_by_shard", "answered")

    def __init__(self) -> None:
        self._scored: list = []  # providers, in the order they attached
        # seed() is called once PER SHARD at a pass-2 provider's init
        self._by_shard: dict[int, list] = {}  # shard -> [(provider, row)]
        # shards answer() has summed: the per-id pass 2 reads nothing
        # of them, whichever route it takes
        self.answered: set[int] = set()

    def __len__(self) -> int:  # `if carry:` seeds only when non-empty
        return len(self._scored)

    def attach(self, provider) -> None:
        """``provider.scored()`` is (shards, fragments, candidate lists,
        scores i32[S, P] or None): ``scores[i, j]`` is the score of the
        id at position ``j`` of list ``i``; a row may be padded past its
        list, which no position of an index reaches."""
        self._scored.append(provider)
        for row, shard in enumerate(provider.scored()[0]):
            self._by_shard.setdefault(shard, []).append((provider, row))

    def seed(self, shard: int, rids) -> dict[int, int]:
        """{rid: score} for the requested ids present in this carry."""
        if not rids:
            return {}
        found = []
        for provider, row in self._by_shard.get(shard, ()):
            _, _, pairs_by_shard, scores = provider.scored()
            if scores is not None and pairs_by_shard[row]:
                index = _chunk_index(pairs_by_shard[row], 0, scores.shape[1])
                found.append((index.get, scores[row]))
        out: dict[int, int] = {}
        for rid in rids:
            for position, scores in found:
                j = position(rid)
                if j is not None:
                    out[rid] = int(scores[j])
                    break
        return out

    def answer(self, winners: list[int], mth: int) -> list[tuple[int, int]]:
        """Pass 2 of a plain threshold walk for the shards it can be
        read off for, which ``answered`` then names: the winners' pairs
        summed over them.

        A shard is answered here where its ranked cache still is the
        snapshot pass 1 walked (Fragment.ranked_cache_is: the counts
        _top_bitmap_pairs(winners) would read are then the snapshot's)
        and every winner sits in the prefix pass 1 scored. There the
        per-id pass picks a winner whose cached count and score both
        reach ``mth``, and so does this; its ``sort_pairs`` order never
        reaches the result, which the caller sorts. Any other shard is
        left whole to the per-id pass: one a write reached, one that
        lacks a winner or ranks it beyond the prefix (recounted from
        storage, scored on the device), an LRU cache. A shard with no
        fragment has nothing to read either way."""
        w = np.asarray(winners, dtype=np.int64)
        sums = np.zeros(w.size, dtype=np.int64)
        done = self.answered
        read = 0
        for provider in self._scored:
            shards, frags, pairs_by_shard, scores = provider.scored()
            if scores is None:
                continue
            rows = []
            for i, (shard, frag) in enumerate(zip(shards, frags)):
                if shard in done:
                    continue
                if frag is None:
                    done.add(shard)
                elif pairs_by_shard[i] and frag.ranked_cache_is(pairs_by_shard[i]):
                    rows.append(i)
            if not rows:
                continue
            ids = np.empty((len(rows), w.size), dtype=np.int64)
            at = np.empty_like(ids)
            cached = np.empty_like(ids)
            prefix = scores.shape[1]
            for i, ids_i, at_i, cached_i in zip(rows, ids, at, cached):
                sids, position, counts = pairs_by_shard[i].chunk_sorted(0, prefix)
                k = sids.searchsorted(w)
                sids.take(k, out=ids_i, mode="clip")
                position.take(k, out=at_i, mode="clip")
                counts.take(k, out=cached_i, mode="clip")
            # a cached count of 0 is one _top_bitmap_pairs would recount
            held = (ids == w) & (cached > 0)
            whole = held.all(axis=1)
            sc = scores[np.asarray(rows)[:, None], at]
            picked = whole[:, None] & (cached >= mth) & (sc >= mth)
            sums += np.where(picked, sc, 0).sum(axis=0, dtype=np.int64)
            covered = [shards[i] for i, ok in zip(rows, whole.tolist()) if ok]
            done.update(covered)
            read += len(covered)
        if read:
            metrics.count(metrics.CACHE_HITS, value=read * w.size)
            metrics.count(metrics.TOPN_PASS2_IDS, value=read * w.size, how="vector")
        some = np.flatnonzero(sums)
        return list(zip(w[some].tolist(), sums[some].tolist()))


def _eval_tree(t, leaves, shards=None):
    """Evaluate a lowered filter tree (``Executor._tree_leaves``) over
    its inputs. Traced inside the jit of the program that consumes the
    filter: boolean nodes, BSI compares and that program are one launch
    (reference executor.go:704-1000). ``leaves`` are shard stacks
    u32[S, W] and, under a ``range`` or ``exists`` node, a field's plane
    stack u32[S, D+1, W]; a tree with ``range`` nodes takes their
    predicates, in base-value form, as one u32 vector after its leaves
    (``leaves[-1]``), traced, so one program serves every constant.
    ``shards``: how many shards the program's arrays hold, as the
    caller observes it, where that may not be the batch a ``zeros`` node
    was lowered for (a mesh kernel sees a device's share)."""
    tag = t[0]
    if tag == "leaf":
        return leaves[t[1]]
    if tag == "range":
        import jax

        _, op, depth, at, slots = t
        compare = jax.vmap(_range_kernel(op, depth), in_axes=(0,) + (None,) * len(slots))
        return compare(leaves[at], *(leaves[-1][k] for k in slots))
    if tag == "exists":
        return leaves[t[1]][:, -1, :]
    if tag == "zeros":
        import jax.numpy as jnp

        return jnp.zeros((t[1] if shards is None else shards, _W32), dtype=jnp.uint32)
    fold = _STACK_FOLDS[tag][1]
    acc = _eval_tree(t[1][0], leaves, shards)
    for sub in t[1][1:]:
        acc = fold(acc, _eval_tree(sub, leaves, shards))
    return acc


def _eval_filter(tree, inputs, planes):
    """The optional filter of a BSI aggregate over ``planes``, inside
    its program: (filter words, has_filter) as the ``ops.bsi_*`` take
    them. Without a filter the words are the existence plane, which the
    kernels do not read."""
    if tree is None:
        return planes[:, -1, :], False
    return _eval_tree(tree, inputs, planes.shape[0]), True


def _trace_bsi_sum(depth: int, tree, planes, inputs):
    """Plane counts of a Sum under its filter's structure: the body of
    the lone program (``Executor._bsi_sum_jit``), of a fused unit and,
    before its psum, of a mesh kernel (``spmd.bsi_sum_spmd``)."""
    filt, has_filter = _eval_filter(tree, inputs, planes)
    return ops.bsi_plane_counts_batched(
        planes, filt, bit_depth=depth, has_filter=has_filter
    )


def _score_stacked(src, staged):
    """One launch of the stacked scorer, counted by how it reads the
    bundle (``topn.scorer_launches``; the fused head counts its own)."""
    metrics.count(
        metrics.TOPN_SCORER_LAUNCHES, how=ops.stacked_scorer_how(src.shape[0])
    )
    return ops.sparse_intersection_counts_stacked(src, *staged)


def _score_stacked_batch(srcs, staged):
    """One launch over coalesced sources: the batch form gathers."""
    metrics.count(metrics.TOPN_SCORER_LAUNCHES, how="gather")
    return ops.sparse_intersection_counts_stacked_batch_list(srcs, *staged)


def _make_stacked_scorer() -> BatchedScorer:
    """Coalescing scorer for the cross-shard stacked-sparse TopN path.
    max_batch bounds the lax.map sweep (32, a value not measured on
    the current machine); num_rows rides in the staged tuple. A factory
    because the device health gate rebuilds it on restore (its queue
    may be held by abandoned workers)."""
    return BatchedScorer(
        max_batch=32,
        single_fn=_score_stacked,
        batch_fn=_score_stacked_batch,
        kind="topn_score_stacked",
    )


# boolean PQL call -> (the op label of filter.launches and filter.inlined,
# the fold of two stacks)
_STACK_FOLDS = {
    "Intersect": ("and", ops.and_),
    "Union": ("or", ops.or_),
    "Xor": ("xor", ops.xor_),
    "Difference": ("andnot", ops.andnot),
}


def _range_kernel(op: str, depth: int):
    """The one-shard BSI compare for a Range operator: planes
    u32[D+1, W] and the predicate(s) in base-value form -> u32[W]."""
    if op == BETWEEN:
        return lambda p, lo, hi: ops.bsi_range_between(p, lo, hi, bit_depth=depth)
    if op == "==":
        return lambda p, pred: ops.bsi_range_eq(p, pred, bit_depth=depth)
    if op == NEQ:
        return lambda p, pred: ops.bsi_range_neq(p, pred, bit_depth=depth)
    if op in ("<", "<="):
        return lambda p, pred: ops.bsi_range_lt(
            p, pred, bit_depth=depth, allow_equality=op == "<="
        )
    if op in (">", ">="):
        return lambda p, pred: ops.bsi_range_gt(
            p, pred, bit_depth=depth, allow_equality=op == ">="
        )
    raise ValueError(f"invalid range operation: {op}")


def _timed_kernel(kind: str, fn, signature=None, recovery=None):
    """Wrap a cached jitted kernel with the compile-vs-execute timing
    split: the FIRST invocation traces + compiles inside XLA (observed
    as spmd.compile_seconds), warm invocations are dispatch only
    (spmd.execute_seconds). When the caller is traced, each invocation
    also lands as a spmd.kernel span.

    This is also the device-leg fence (ISSUE 12): ``block_until_ready``
    on the outputs pins the measurement to real device completion
    instead of async-dispatch return, so the timing feeds the waterfall
    (the jit call itself as device.launch and, warm,
    spmd.launch_seconds; the wait after it as device.compute) and the
    first call feeds the compile tracker under ``signature`` (the
    canonical plan key of the cached jit).

    And it is the OOM-recovery boundary (ISSUE 14): with ``recovery``
    (an executor's OomRecovery) an allocation failure at dispatch or at
    the fence evicts through the HBM governor and retries ONCE before
    degrading the call to the CPU leg. The chaos hook fires INSIDE the
    attempt, so a retry re-consults the injection counter and passes."""

    state = {"first": True}

    def attempt(*args, **kw):
        cf = chaos.FAULTS
        if cf is not None:
            cf.on_kernel(kind)
        # the jit call up to its return of the not-yet-ready result, the
        # host's dispatch (to every device of a mesh), apart from the
        # wait for it below
        with trace.leg(trace.WF_DEVICE_LAUNCH) as launch:
            profiler.count_operands(kind, args)
            out = fn(*args, **kw)
        if not state["first"]:
            metrics.observe(metrics.SPMD_LAUNCH_SECONDS, launch.seconds, kind=kind)
        try:
            import jax  # lazy, matching this module's other jax uses

            jax.block_until_ready(out)
        except Exception as e:
            # a device fault surfacing at the fence IS the kernel
            # failing — the recovery policy must see it; anything else
            # is a non-jax output with nothing to fence
            if classify_device_error(e) is not None:
                raise
        return out

    def run(*args, **kw):
        with trace.leg(trace.WF_DEVICE_COMPUTE) as lg:
            if recovery is not None:
                out = recovery.run(lambda: attempt(*args, **kw), kind=kind)
            else:
                out = attempt(*args, **kw)
        dt = lg.seconds
        first = state["first"]
        if first:
            state["first"] = False
            metrics.observe(metrics.SPMD_COMPILE_SECONDS, dt, kind=kind)
            profiler.COMPILES.note(kind, signature, dt)
        else:
            metrics.observe(metrics.SPMD_EXECUTE_SECONDS, dt, kind=kind)
        sp = trace.current()
        if sp is not None:
            sp.record(metrics.STAGE_SPMD_KERNEL, lg.t0, dt, kind=kind, first=first)
        return out

    return run


# post-OOM-degrade cooldown: after a device call degrades to CPU, the
# device predicates stay CPU-forced this long so the immediate re-run
# (and the next waves) don't launch straight back into the same OOM
OOM_CPU_COOLDOWN_S = 30.0


def _launch(kind: str, fn, *args, **kw):
    """Launch a module-level jitted kernel whose small result the caller
    reads at once, under its name, and return the result on the host:
    launch → fetched is ``spmd.execute_seconds{kind}`` and the request's
    device legs: the jit call ``device.launch``
    (``spmd.launch_seconds{kind}``; the operands count to
    ``kernel.operand_bytes{kind}`` in it), the rest ``device.compute``.
    Wait and copy stay one step, as
    ``np.asarray`` makes them (a count vector is a few KB): waiting
    apart would hand the interpreter lock over once more per launch. A
    first call's compile is in the time (``profiler.compiles{kind=xla}``
    counts it)."""
    with trace.leg(trace.WF_DEVICE_COMPUTE) as lg:
        with trace.leg(trace.WF_DEVICE_LAUNCH) as launch:
            profiler.count_operands(kind, args)
            out = fn(*args, **kw)
        if isinstance(out, tuple):
            out = tuple(np.asarray(o) for o in out)
        else:
            out = np.asarray(out)
    metrics.observe(metrics.SPMD_LAUNCH_SECONDS, launch.seconds, kind=kind)
    metrics.observe(metrics.SPMD_EXECUTE_SECONDS, lg.seconds, kind=kind)
    return out


def _fetch(arr) -> np.ndarray:
    """Materialize a device result on host. With attribution active the
    wait for a launch that nobody fenced is the device's leg and the
    copy alone is transfer.decode; a result that is ready (every
    ``_timed_kernel``'s) is only copied."""
    if trace.attrib_current() is None:
        return np.asarray(arr)
    is_ready = getattr(arr, "is_ready", None)
    if is_ready is not None and not is_ready():
        with trace.leg(trace.WF_DEVICE_COMPUTE):
            arr.block_until_ready()
    with trace.leg(trace.WF_TRANSFER_DECODE):
        return np.asarray(arr)


def _mesh_fetch(arr) -> np.ndarray:
    """The copy of a mesh kernel's replicated result (a chunk's gathered
    scores, a Sum's or a Count's reduced counts) from one replica, once
    ``_timed_kernel`` has fenced the program. What the mesh adds on the
    host has a leg of its own; it takes ``transfer.decode``'s place on
    a mesh and is 0 without one."""
    with trace.leg(trace.WF_MESH_FETCH):
        return np.asarray(arr)


class Executor:
    def __init__(
        self,
        holder,
        cluster=None,
        node=None,
        stager: Optional[DeviceStager] = None,
        device_policy: str = "auto",
        translate_store=None,
        max_writes_per_request: int = 5000,
        mesh=None,
        health=None,
        auto_min_containers: Optional[int] = None,
        plan_cache=None,
        dispatch_enabled: bool = True,
        dispatch_max_wave: int = 16,
        dispatch_max_inflight: int = 2,
        dispatch_stage_ahead: int = 1,
        prefetch_enabled: bool = True,
        prefetch_depth: int = 2,
        fusion_enabled: bool = True,
        fusion_max_calls: int = 64,
        plan_cache_device_bytes: int = 256 << 20,
        governor: Optional[HbmGovernor] = None,
        analytics_max_groups: Optional[int] = None,
    ) -> None:
        self.holder = holder
        self.cluster = cluster  # None = single-node
        self.node = node
        # A mesh turns the shard-batched device path SPMD: stacks stage
        # split over the mesh's shard axis and Count/Sum/TopN terminals
        # lower to shard_map kernels whose cross-shard reduces are ICI
        # collectives (parallel/spmd.py) — the reference's per-node
        # HTTP scatter-gather (executor.go:1444-1593) inside one program.
        self.mesh = mesh
        self.stager = stager or DeviceStager(mesh=mesh)
        if mesh is not None and self.stager.mesh is not mesh:
            # a shared stager staging on a different (or no) mesh would
            # hand the SPMD kernels wrongly-placed arrays — fail loud
            raise ValueError("executor mesh differs from the stager's mesh")
        self.device_policy = device_policy
        self.translate_store = translate_store
        self.max_writes_per_request = max_writes_per_request
        # GroupBy cross-product bound: a panel larger than this fails
        # loudly before K row stacks are staged into HBM
        self.analytics_max_groups = (
            int(analytics_max_groups)
            if analytics_max_groups is not None
            else analytics.DEFAULT_MAX_GROUPS
        )
        # coalesces concurrent TopN scoring against the same staged
        # matrix into one batched kernel launch (see batcher.py)
        self.scorer = BatchedScorer()
        # concurrent cross-shard TopN queries sharing a staged candidate
        # chunk (the common case: every TopN's pass-1 head is the same
        # cache-rankings prefix) coalesce into one stacked kernel launch
        # — one device round-trip serves the whole batch.
        self.stacked_scorer = _make_stacked_scorer()
        # optional device health gate (executor/devicehealth.py):
        # serving deployments pass one so a wedged accelerator degrades
        # reads to the CPU roaring path instead of hanging them; bare
        # executors (tests) skip the per-call guard hop
        self.health = health
        if health is not None:
            health.on_restore = self._on_device_restore
        # multihost gang runtime (parallel/multihost.py). When set (the
        # server wires it on the leader rank of a jax.distributed
        # deployment), non-remote queries entering execute() are routed
        # through the gang: the descriptor broadcasts to every rank and
        # all processes enter the identical execution in lockstep —
        # required because this executor's mesh spans processes, so any
        # SPMD kernel IS a multi-process collective program.
        self.gang = None
        # generation-stamped query result cache (plan/cache.py). None =
        # disabled (the default for bare executors, so tests opt in
        # explicitly); the server wires one per process. Only
        # consulted for locally-executed reads — on a cluster each
        # shard owner caches its own remote legs, because only IT can
        # see its fragments' generations.
        self.plan_cache = plan_cache
        # fused count-of-tree programs keyed by query structure
        self._tree_jits: dict[tuple, Any] = {}
        # shard-batched BSI compares keyed by (operator, bit depth)
        self._range_jits: dict[tuple, Any] = {}
        # auto-policy crossover, in estimated touched containers (see
        # _touched_containers). The default is not measured on the
        # current machine; executor/autotune.py measures the crossover
        # at server open; the server plumbs its config knob here.
        self.auto_min_containers = (
            int(auto_min_containers)
            if auto_min_containers is not None
            else AUTO_DEVICE_MIN_CONTAINERS
        )
        self._read_pool = None  # lazy; see execute()
        self._read_pool_mu = threading.Lock()
        # checkout refcount + closing flag: close() drains active
        # pool.map users instead of nulling the attr under them, and a
        # checkout during shutdown gets None (the caller runs the calls
        # serially inline) — see _read_pool_acquire
        self._read_pool_cv = threading.Condition(self._read_pool_mu)
        self._read_pool_users = 0
        self._read_pool_closing = False
        # continuous-batching async dispatch engine (dispatch.py):
        # eligible local reads entering execute() submit a future and
        # wait instead of blocking through the call tree, so concurrent
        # heterogeneous plans coalesce into device waves. The loop
        # thread starts lazily on first submit.
        if dispatch_enabled:
            from pilosa_tpu.executor.dispatch import DispatchEngine

            self.dispatch_engine = DispatchEngine(
                self,
                max_wave=dispatch_max_wave,
                max_inflight=dispatch_max_inflight,
                stage_ahead=dispatch_stage_ahead,
            )
        else:
            self.dispatch_engine = None
        # plan-driven prefetch scheduler (executor/tiering.py): the
        # dispatch engine's wave builder hands it queued plans so the
        # NEXT waves' Row blocks promote T1/T2 → T0 ahead of compute,
        # with accuracy attribution. Replaces the thunk-based advisory
        # warm when enabled.
        if prefetch_enabled and self.dispatch_engine is not None:
            from pilosa_tpu.executor.tiering import PrefetchScheduler

            self.prefetcher = PrefetchScheduler(self, depth=prefetch_depth)
        else:
            self.prefetcher = None
        # whole-query device fusion (fusion.py): multi-call read queries
        # — and the multi-call Queries the dispatch engine combines a
        # wave into — lower to ONE jitted program, intermediates stay in
        # HBM, only final scalars/score heads transfer.
        if fusion_enabled:
            from pilosa_tpu.executor.fusion import QueryFuser

            self.fuser = QueryFuser(self, max_calls=fusion_max_calls)
        else:
            self.fuser = None
        # device-resident plan cache (plan/cache.py DevicePlanCache):
        # __cached subtree stacks stay in HBM instead of round-tripping
        # through host Row decode + re-pack + re-upload. 0 disables;
        # single-device only (mesh placement differs — gated at the
        # probe site in _device_bitmap_stack).
        if plan_cache_device_bytes > 0 and self.plan_cache is not None:
            from pilosa_tpu.plan.cache import DevicePlanCache

            self.device_cache = DevicePlanCache(plan_cache_device_bytes)
        else:
            self.device_cache = None
        # compiled shard_map kernels keyed by (kind, static args) — the
        # closures in spmd.py are rebuilt per call, so cache here to keep
        # XLA's jit cache effective across queries
        self._spmd_kernels: dict[tuple, Any] = {}
        self._spmd_mu = threading.Lock()
        # one HBM byte ledger for every device-resident tenant
        # (executor/hbm.py): the stager, the device plan cache, and the
        # batcher pad scratch stop overcommitting the chip through
        # disjoint budgets — their old knobs become per-tenant shares
        self.governor = governor if governor is not None else HbmGovernor()
        self.stager.set_governor(self.governor)
        if self.device_cache is not None:
            self.device_cache.set_governor(self.governor)
        for sc in (self.scorer, self.stacked_scorer):
            sc.set_governor(self.governor)
        # OOM recovery policy shared by every device-call boundary:
        # evict → retry once → degrade this call to the CPU leg; the
        # health gate trips only on repeat unrecovered failures
        self._oom_cpu_until = 0.0
        self.oom_cpu_cooldown_s = OOM_CPU_COOLDOWN_S
        self._oom = OomRecovery(
            governor=self.governor,
            health=self.health,
            on_degrade=self._on_oom_degrade,
        )

    def _spmd_kernel(self, kind: str, *statics):
        key = (kind,) + statics
        with self._spmd_mu:
            fn = self._spmd_kernels.get(key)
            if fn is None:
                from pilosa_tpu.parallel import spmd

                if kind == "count":
                    fn = spmd.count_stack_spmd(self.mesh, *statics)
                elif kind == "plane_counts":
                    fn = spmd.bsi_sum_spmd(self.mesh, *statics)
                elif kind == "topn_scores_sparse":
                    fn = spmd.topn_scores_sparse_spmd(self.mesh, *statics)
                else:
                    raise ValueError(kind)
                fn = _timed_kernel(kind, fn, signature=key, recovery=self._oom)
                self._spmd_kernels[key] = fn
            return fn

    def _shard_plan(self, shards: list[int]) -> list[int]:
        """Pad the shard list to a mesh-size multiple (padding shards
        have no fragments and stage as zero words — identity for every
        reduce). No-op without a mesh."""
        if self.mesh is None:
            return shards
        from pilosa_tpu.parallel.spmd import ShardBatchPlan

        return ShardBatchPlan(self.mesh, shards).padded

    # -- entry point (reference Execute, executor.go:83) ---------------------

    def execute(
        self,
        index_name: str,
        query,
        shards: Optional[list[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> list[Any]:
        gang = self.gang
        if gang is not None and gang.should_dispatch_query(
            bool(opt is not None and opt.remote),
            query if isinstance(query, str) else str(query),
        ):
            # multihost leader: broadcast the descriptor so every rank
            # enters this execution in lockstep (the mesh spans
            # processes — executing here alone would deadlock the first
            # collective). The gang thread re-enters execute() with the
            # in-gang flag set and falls through to the normal path.
            from pilosa_tpu.parallel import multihost

            desc = multihost.query_descriptor(
                index_name,
                query if isinstance(query, str) else str(query),
                shards,
                opt or ExecOptions(),
                trace_ctx=trace.current_ctx(),
            )
            dl = _deadline().current()
            sp = trace.current()
            if sp is None:
                return gang.dispatch(desc, deadline=dl)
            with sp.child(metrics.STAGE_GANG, plan=desc.payload.get("plan")):
                return gang.dispatch(desc, deadline=dl)
        engine = self.dispatch_engine
        if engine is not None and self._engine_eligible(opt):
            parsed = parse(query) if isinstance(query, str) else query
            if parsed.write_call_n() == 0:
                fut = engine.submit(
                    index_name,
                    parsed,
                    shards,
                    opt or ExecOptions(),
                    deadline=_deadline().current(),
                    text=query if isinstance(query, str) else None,
                    trace_ctx=trace.current_ctx(),
                )
                # resolved already where this thread led its own wave
                # (nobody queued, a slot free); else wait for the loop's
                if fut is not None:  # None: engine closing -> inline
                    return fut.result()
            query = parsed  # already parsed; don't redo it below
        sp = trace.current()
        if sp is None:  # untraced: no span objects anywhere below
            return self._execute(index_name, query, shards, opt)
        with sp.child(metrics.STAGE_EXECUTOR, index=index_name):
            return self._execute(index_name, query, shards, opt)

    def _engine_eligible(self, opt) -> bool:
        """Route this execute() through the async dispatch engine?
        Only plain local reads: the PR 5/6 gang determinism contract
        keeps multihost/federation execution ``serial`` and
        engine-free; cluster fan-out and remote legs have their own
        scheduling; traced queries must show real execution in their
        span tree; and a thread already inside a wave re-enters inline
        rather than deadlocking against its own runner slot."""
        if self.gang is not None or self.cluster is not None:
            return False
        if opt is not None and (opt.remote or opt.serial):
            return False
        if trace.current() is not None:
            return False
        return not self.dispatch_engine.in_wave()

    def _execute(
        self,
        index_name: str,
        query,
        shards: Optional[list[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> list[Any]:
        if isinstance(query, str):
            query = parse(query)
        opt = opt or ExecOptions()
        # deadline boundary: a request whose deadline passed while it
        # crossed the API layer is cancelled before any shard work
        dl = _deadline().current()
        if dl is not None:
            dl.check(metrics.STAGE_EXECUTOR)
        idx = self.holder.index(index_name)
        if idx is None:
            raise NotFoundError(f"index not found: {index_name}")
        if (
            self.max_writes_per_request
            and query.write_call_n() > self.max_writes_per_request
        ):
            raise ValueError(
                f"too many writes: {query.write_call_n()} > {self.max_writes_per_request}"
            )
        if shards is None and self._needs_shards(query.calls):
            shards = list(range(idx.max_shard() + 1))
        if self.translate_store is not None and not opt.remote:
            # keys→ids BEFORE canonicalization (plan/planner.py): the
            # CSE hashes, plan-cache keys, and dispatch signatures all
            # see resolved integer ids only
            from pilosa_tpu.plan import planner as _planner

            _planner.resolve_keys(self, index_name, idx, query.calls)
        calls = query.calls
        if (
            self.plan_cache is not None
            and opt.cache
            and self._local_batchable(opt)
            and shards
            and query.write_call_n() == 0
        ):
            # CSE against the result cache (plan/planner.py): repeated
            # bitmap subtrees across this query's calls — which, via the
            # pipeline's cross-request combiner, may span a whole gang
            # of coalesced HTTP requests — execute once, and subtrees
            # already cached feed back in as materialized rows. Local
            # execution only: __cached placeholders never serialize.
            from pilosa_tpu.plan import planner

            with trace.leg(trace.WF_PLAN_CANON), trace.child(metrics.STAGE_PLAN_CANON):
                calls = planner.rewrite_for_cse(
                    self, index_name, query.calls, shards, opt
                )
        # whole-query fusion (fusion.py): lower the fusable calls of a
        # multi-call read into ONE jitted launch; residual calls fall
        # through to the per-call paths below and results merge
        # positionally. Gang/serial/remote/cluster legs bypass inside
        # try_execute, mirroring the dispatch-engine contract.
        fused: dict[int, Any] = {}
        if (
            self.fuser is not None
            # a single analytic call is itself a K-way panel — worth a
            # fused launch even without a second call to share it with
            and (
                len(calls) > 1
                or any(c.name in analytics.ANALYTIC_CALLS for c in calls)
            )
            and query.write_call_n() == 0
            and not opt.serial
            and shards
        ):
            fused = self.fuser.try_execute(index_name, calls, shards, opt) or {}
        run_calls = (
            [c for i, c in enumerate(calls) if i not in fused] if fused else calls
        )
        if len(run_calls) > 1 and query.write_call_n() == 0 and not opt.serial:
            # An all-read request has no cross-call ordering constraints
            # (the reference runs calls serially, executor.go:126-145,
            # but read results are order-independent); running them
            # concurrently lets the BatchedScorer coalesce their TopN
            # scoring into batched kernel launches — the intra-request
            # form of continuous micro-batching.
            pool = self._read_pool_acquire()

            # contextvars don't follow pool workers: the span, waterfall
            # accumulator and wave id are carried, the deadline re-entered
            @trace.carried
            def run_call(call):
                with _deadline().activate(dl):
                    return self._execute_call(index_name, call, shards, opt)

            if pool is None:
                # close() in progress: run serially inline instead of
                # racing a shutting-down pool
                results = [run_call(c) for c in run_calls]
            else:
                try:
                    results = list(pool.map(run_call, run_calls))
                finally:
                    self._read_pool_release()
        else:
            results = []
            for call in run_calls:
                results.append(self._execute_call(index_name, call, shards, opt))
        if fused:
            it = iter(results)
            results = [
                fused[i] if i in fused else next(it) for i in range(len(calls))
            ]
        if self.translate_store is not None and not opt.remote:
            results = [
                self._translate_result(index_name, idx, call, r)
                for call, r in zip(calls, results)
            ]
        return results

    # -- key translation (reference translateCall/translateResult,
    #    executor.go:1595-1696) --------------------------------------------

    def _translate_call(self, index, idx, c: Call) -> None:
        # delegated to the translate subsystem (translate/resolve.py);
        # kept as a method so direct callers and tests keep working
        from pilosa_tpu.translate import resolve

        resolve.resolve_call(self.translate_store, index, idx, c)

    def _translate_result(self, index, idx, call: Call, result):
        from pilosa_tpu.translate import resolve

        return resolve.translate_result(
            self.translate_store, index, idx, call, result
        )

    @staticmethod
    def _needs_shards(calls: list[Call]) -> bool:
        for c in calls:
            if c.name not in ("Clear", "Set", "SetRowAttrs", "SetColumnAttrs", "SetValue"):
                return True
        return False

    # -- dispatch (reference executeCall, executor.go:165) -------------------

    def _cpu_forced(self) -> bool:
        """True while the device gate is tripped OR the post-OOM-degrade
        cooldown is running. Checked by the device predicates, so it
        applies on EVERY thread — including cluster map-reduce pool
        workers — without per-thread state."""
        if self.health is not None and not self.health.healthy:
            return True
        return time.monotonic() < self._oom_cpu_until

    def _on_oom_degrade(self) -> None:
        """A device call degraded to CPU after failed OOM recovery:
        force the CPU predicates for a cooldown so the immediate re-run
        (and the next waves) don't launch straight back into the OOM."""
        self._oom_cpu_until = time.monotonic() + self.oom_cpu_cooldown_s

    def _on_device_restore(self) -> None:
        """Replace machinery whose locks abandoned guard workers may
        hold forever (a dispatcher hung inside a dead kernel launch
        keeps its per-fragment dispatch lock; a hung staging upload
        keeps the stager's). Fresh instances start clean; zombies keep
        mutating their orphaned predecessors harmlessly."""
        self.scorer = BatchedScorer()
        self.stacked_scorer = _make_stacked_scorer()
        # the ledger must forget the dead runtime's pad scratch with
        # the scorers; fresh instances re-register at zero
        self.governor.reset("batcher")
        for sc in (self.scorer, self.stacked_scorer):
            sc.set_governor(self.governor)
        self._oom_cpu_until = 0.0
        self.stager.reset_after_wedge()
        if self.plan_cache is not None:
            # results computed by the wedged device must not outlive it
            self.plan_cache.epoch_reset()
        if self.device_cache is not None:
            # ditto for HBM-resident arrays: handles created by the dead
            # runtime may be invalid
            self.device_cache.epoch_reset()

    def _execute_call(self, index, c: Call, shards, opt) -> Any:
        metrics.count(metrics.EXECUTOR_CALLS, call=c.name)
        sp = trace.current()
        if sp is None:
            return self._execute_call_cached(index, c, shards, opt)
        with sp.child(metrics.STAGE_CALL, call=c.name):
            return self._execute_call_cached(index, c, shards, opt)

    def _execute_call_cached(self, index, c: Call, shards, opt) -> Any:
        """Whole-call result cache around dispatch (plan/cache.py): a
        generation-valid entry answers without touching the executor;
        a miss executes under singleflight and stamps the entry with
        the pre-build generation vector. Uncacheable calls (writes,
        attr-dependent reads, malformed args) and non-local execution
        dispatch straight through."""
        from pilosa_tpu.pql.ast import WRITE_CALLS

        pc = self.plan_cache
        if (
            pc is None
            or not opt.cache
            or not self._local_batchable(opt)
            or shards is None
            or c.name in WRITE_CALLS
        ):
            return self._execute_call_guarded(index, c, shards, opt)
        from pilosa_tpu.plan import planner

        keyinfo = planner.call_cache_key(self, index, c, shards, opt)
        if keyinfo is None:
            return self._execute_call_guarded(index, c, shards, opt)
        key, genvec_fn = keyinfo
        return pc.get_or_build(
            key,
            genvec_fn,
            lambda: self._execute_call_guarded(index, c, shards, opt),
        )

    def _execute_call_guarded(self, index, c: Call, shards, opt) -> Any:
        """Read calls run under the device health gate when one is
        configured: a wedged accelerator trips the gate and the same
        call re-runs on the CPU roaring path (reads are pure — safe to
        re-run; the gate state itself forces the CPU predicates, so the
        re-run is device-free on every thread). Writes never touch the
        device and skip the guard."""
        from pilosa_tpu.pql.ast import WRITE_CALLS

        if c.name in WRITE_CALLS:
            # writes never touch the device: no guard, no OOM fallback
            return self._execute_call_inner(index, c, shards, opt)
        guarded = (
            self.health is not None
            and self.device_policy != "never"
            and not self._cpu_forced()
        )
        try:
            if guarded:
                return self.health.guard(
                    lambda: self._execute_call_inner(index, c, shards, opt)
                )
            return self._execute_call_inner(index, c, shards, opt)
        except DeviceDown:
            # gate closed, or an unrecovered OOM degraded this call
            # (DeviceOom): the CPU predicates are already forced (gate
            # state / OOM cooldown), so the re-run is device-free
            metrics.count(metrics.EXECUTOR_DEVICE_DOWN_FALLBACK)
        except Exception as e:
            # a raw device fault that escaped the kernel boundaries
            # (e.g. surfaced at a batcher fetch): apply the same
            # recovery policy here — classify, journal, evict, set the
            # CPU cooldown — then serve from the CPU leg
            if classify_device_error(e) is None:
                raise

            def _reraise():
                raise e

            try:
                self._oom.run(_reraise, kind="call")
            except DeviceOom:
                pass
            metrics.count(metrics.EXECUTOR_DEVICE_DOWN_FALLBACK)
        return self._execute_call_inner(index, c, shards, opt)

    def _execute_call_inner(self, index, c: Call, shards, opt) -> Any:
        name = c.name
        if name == "Sum":
            return self._execute_sum(index, c, shards, opt)
        if name == "Min":
            return self._execute_min(index, c, shards, opt)
        if name == "Max":
            return self._execute_max(index, c, shards, opt)
        if name == "Clear":
            return self._execute_clear_bit(index, c, opt)
        if name == "Count":
            return self._execute_count(index, c, shards, opt)
        if name == "Set":
            return self._execute_set_bit(index, c, opt)
        if name == "SetValue":
            self._execute_set_value(index, c, opt)
            return None
        if name == "SetRowAttrs":
            self._execute_set_row_attrs(index, c, opt)
            return None
        if name == "SetColumnAttrs":
            self._execute_set_column_attrs(index, c, opt)
            return None
        if name == "TopN":
            return self._execute_topn(index, c, shards, opt)
        if name == "GroupBy":
            return self._execute_groupby(index, c, shards, opt)
        if name == "Distinct":
            return self._execute_distinct(index, c, shards, opt)
        if name == "Percentile":
            return self._execute_percentile(index, c, shards, opt)
        if name == "Rows":
            raise ValueError("Rows() can only be used inside GroupBy()")
        return self._execute_bitmap_call(index, c, shards, opt)

    # -- map/reduce seam -----------------------------------------------------

    def _map_reduce(self, index, shards, c, opt, map_fn, reduce_fn, zero_factory=None):
        """Single-node: loop shards in order (deterministic reduce order —
        the reference's goroutine fan-in is arrival-ordered). The cluster
        layer overrides this via self.cluster.map_reduce.

        zero_factory builds a FRESH accumulator: reduce_fn may mutate its
        first argument (Row.merge), and mapped values can be cached
        fragment rows that must never be mutated."""
        if self.cluster is not None and not opt.remote:
            return self.cluster.map_reduce(
                index, shards, c, opt, map_fn, reduce_fn, zero_factory
            )
        result = zero_factory() if zero_factory else None
        # captured ONCE: the untraced loop body pays a single branch per
        # shard, no span objects (ISSUE 1 overhead bound); same for the
        # deadline — one contextvar read, then a monotonic compare per
        # shard, so expired work stops at the next shard boundary
        # instead of finishing a result nobody will read
        parent = trace.current()
        dl = _deadline().current()
        attrib = trace.attrib_current()  # same single-capture discipline
        # heat ledger read hook, captured once per query like the tracer:
        # the per-shard body pays one is-not-None branch when disabled
        if heat.LEDGER.enabled:
            _heat_read = heat.LEDGER.record_read
            try:
                _heat_field = c.field_arg()
            except (ValueError, AttributeError):
                _heat_field = ""
        else:
            _heat_read = None
            _heat_field = ""
        for shard in shards:
            if dl is not None:
                dl.check(metrics.STAGE_MAP_SHARD)
            if _heat_read is not None:
                _heat_read(index, _heat_field, shard)
            if parent is not None:
                with parent.child(metrics.STAGE_MAP_SHARD, shard=shard):
                    v = map_fn(shard)
            else:
                v = map_fn(shard)
            if result is None:
                result = v
            elif attrib is None:
                result = reduce_fn(result, v)
            else:
                with trace.leg(trace.WF_REDUCE):
                    result = reduce_fn(result, v)
        return result

    def _heat_read_legs(self, index, c, shards) -> None:
        """Shard-batched device launches (Count/Sum/TopN stacks, fused
        whole-query reads) bypass ``_map_reduce``'s per-shard loop, so
        their read legs land here — one per shard in the stack, same
        accounting as the serial path."""
        if not heat.LEDGER.enabled or not shards:
            return
        try:
            field = c.field_arg()
        except (ValueError, AttributeError):
            field = ""
        rec = heat.LEDGER.record_read
        for s in shards:
            rec(index, field, s)

    def _analytics_heat_legs(self, index, fields, shards) -> None:
        """Analytic segmented-reduction launches bypass ``_map_reduce``'s
        per-shard loop AND touch several fields per launch (dimension
        rows + aggregate planes), so their legs record here: one read
        per (field, shard), same accounting as the serial path."""
        if not heat.LEDGER.enabled or not shards:
            return
        rec = heat.LEDGER.record_read
        for f in fields:
            for s in shards:
                rec(index, f, s)

    # -- bitmap calls ---------------------------------------------------------

    def _execute_bitmap_call(self, index, c: Call, shards, opt) -> Row:
        def map_fn(shard):
            return self._bitmap_call_shard(index, c, shard)

        def reduce_fn(prev: Row, v: Row) -> Row:
            prev.merge(v)
            return prev

        other = self._map_reduce(index, shards, c, opt, map_fn, reduce_fn, zero_factory=Row)

        # Attach attributes for top-level Row() calls
        # (reference executeBitmapCall, executor.go:338-385).
        if c.name == "Row" and not opt.exclude_row_attrs:
            field_name = c.field_arg()
            fld = self.holder.field(index, field_name)
            if fld is not None and fld.row_attr_store is not None:
                row_id, ok = c.uint_arg(field_name)
                if ok:
                    attrs = fld.row_attr_store.attrs(row_id)
                    other.attrs = attrs or {}
        return other

    def _bitmap_call_shard(self, index, c: Call, shard: int) -> Row:
        """reference executeBitmapCallShard (executor.go:388-405)."""
        if self._use_device(index, c, shard):
            try:
                words = self._device_bitmap(index, c, shard)
                return _row_from_device(words, shard)
            except _NotDeviceable:
                pass
        return self._bitmap_call_shard_cpu(index, c, shard)

    def _bitmap_call_shard_cpu(self, index, c: Call, shard: int) -> Row:
        name = c.name
        if name == "__cached":
            # planner-substituted subtree (plan/planner.py): the
            # materialized per-shard rows ARE the result
            seg = c.args["_row"].shard_segment(shard)
            if seg is None:
                return Row()
            return Row.from_segment(shard, seg)
        if name == "Row":
            return self._row_shard(index, c, shard)
        if name == "Difference":
            return self._nary_shard(index, c, shard, "difference", require=True)
        if name == "Intersect":
            return self._nary_shard(index, c, shard, "intersect", require=True)
        if name == "Range":
            return self._range_shard(index, c, shard)
        if name == "Union":
            return self._nary_shard(index, c, shard, "union", require=False)
        if name == "Xor":
            return self._nary_shard(index, c, shard, "xor", require=False)
        raise ValueError(f"unknown call: {name}")

    def _row_shard(self, index, c: Call, shard: int) -> Row:
        field_name = c.field_arg()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise ValueError(f"Row() must specify {field_name}")
        frag = self.holder.fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return Row()
        return frag.row(row_id)

    def _nary_shard(self, index, c: Call, shard: int, op: str, require: bool) -> Row:
        if require and not c.children:
            raise ValueError(f"empty {c.name} query is currently not supported")
        other = Row()
        for i, child in enumerate(c.children):
            row = self._bitmap_call_shard(index, child, shard)
            other = row if i == 0 else getattr(other, op)(row)
        other.invalidate_count()
        return other

    def _range_shard(self, index, c: Call, shard: int) -> Row:
        """reference executeRangeShard / executeBSIGroupRangeShard."""
        if c.has_condition_arg():
            return self._bsi_range_shard(index, c, shard)
        # time range over quantum views
        field_name = c.field_arg()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise ValueError("Range() must specify row")
        start_str, ok = c.string_arg("_start")
        if not ok:
            raise ValueError("Range() start time required")
        end_str, ok = c.string_arg("_end")
        if not ok:
            raise ValueError("Range() end time required")
        start = datetime.strptime(start_str, TIME_FORMAT)
        end = datetime.strptime(end_str, TIME_FORMAT)
        q = f.time_quantum()
        if not q:
            return Row()
        row = Row()
        for view in views_by_time_range(VIEW_STANDARD, start, end, q):
            frag = self.holder.fragment(index, field_name, view, shard)
            if frag is None:
                continue
            row = row.union(frag.row(row_id))
        return row

    def _bsi_range_shard(self, index, c: Call, shard: int) -> Row:
        if len(c.args) == 0:
            raise ValueError("Range(): condition required")
        if len(c.args) > 1:
            raise ValueError("Range(): too many arguments")
        ((field_name, cond),) = c.args.items()
        if not isinstance(cond, Condition):
            raise ValueError(f"Range(): expected condition argument, got {cond!r}")
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        bsig = f.bsi_group(field_name)
        if bsig is None:
            raise NotFoundError(f"bsiGroup not found: {field_name}")
        frag = self.holder.fragment(
            index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, shard
        )

        # != null
        if cond.op == NEQ and cond.value is None:
            if frag is None:
                return Row()
            return frag.not_null(bsig.bit_depth())

        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise ValueError(
                    "Range(): BETWEEN condition requires exactly two integer values"
                )
            base_min, base_max, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range:
                return Row()
            if frag is None:
                return Row()
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return frag.not_null(bsig.bit_depth())
            return frag.range_between(bsig.bit_depth(), base_min, base_max)

        value = cond.value
        if not isinstance(value, int) or isinstance(value, bool):
            raise ValueError("Range(): conditions only support integer values")
        base_value, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return Row()
        if frag is None:
            return Row()
        # fully-encompassing ranges return all not-null
        if (
            (cond.op == "<" and value > bsig.max)
            or (cond.op == "<=" and value >= bsig.max)
            or (cond.op == ">" and value < bsig.min)
            or (cond.op == ">=" and value <= bsig.min)
        ):
            return frag.not_null(bsig.bit_depth())
        if out_of_range and cond.op == NEQ:
            return frag.not_null(bsig.bit_depth())
        return frag.range_op(cond.op, bsig.bit_depth(), base_value)

    # -- device path ---------------------------------------------------------

    def _use_device(self, index, c: Call, shard: int) -> bool:
        use = self._use_device_decide(index, c, shard)
        metrics.count(
            metrics.EXECUTOR_ROUTE_DEVICE if use else metrics.EXECUTOR_ROUTE_CPU,
            call=c.name,
        )
        sp = trace.current()
        if sp is not None:
            sp.event(
                metrics.STAGE_ROUTE,
                call=c.name,
                shard=shard,
                path="device" if use else "cpu",
            )
        return use

    def _use_device_decide(self, index, c: Call, shard: int) -> bool:
        if self.device_policy == "never" or self._cpu_forced():
            return False
        if self.device_policy == "always":
            return True
        return self._touched_containers(index, c, shard) >= self.auto_min_containers

    def _touched_containers(self, index, c: Call, shard: int) -> int:
        """Estimated container blocks this call subtree READS in this
        shard — the CPU path's cost driver. Counting the fragment's
        total containers (the old heuristic) mischooses the device for
        a 2-row query on a tall fragment. The CPU cost grows with the
        touched containers while a device dispatch is flat, so the
        crossover is a touched-container threshold
        (executor/autotune.py measures both sides)."""
        total = 0
        if c.name == "Row":
            try:
                fname = c.field_arg()
            except ValueError:
                fname = None
            if fname:
                frag = self.holder.fragment(index, fname, VIEW_STANDARD, shard)
                if frag is not None:
                    row_id, _ = c.uint_arg(fname)
                    total += frag.sparse_block_count([row_id])
        elif c.name == "Range" and c.has_condition_arg():
            for fname in c.args:
                f = self.holder.field(index, fname)
                bsig = f.bsi_group(fname) if f is not None else None
                frag = self.holder.fragment(
                    index, fname, VIEW_BSI_GROUP_PREFIX + fname, shard
                )
                if frag is not None and bsig is not None:
                    total += frag.sparse_block_count(
                        list(range(bsig.bit_depth() + 1))
                    )
        elif c.name == "Range":
            # time-range form: the row is read once per quantum view in
            # the span, so the cost estimate sums containers across
            # views. Without this branch the estimate was 0 and the
            # auto policy NEVER routed time ranges to the (existing)
            # shard-stacked device lowering — the CPU roaring union was
            # the only path that ever ran (VERDICT §6).
            total += self._time_range_containers(index, c, shard)
        elif c.name in ("GroupBy", "Distinct", "Percentile", "Rows"):
            total += self._analytics_containers(index, c, shard)
        for child in c.children:
            total += self._touched_containers(index, child, shard)
        return total

    def _time_range_containers(self, index, c: Call, shard: int) -> int:
        """Touched-container estimate for a time-range Range() — the
        queried row's container count summed over every quantum view in
        [start, end]. Malformed args estimate 0 (the execution path
        raises the real error)."""
        try:
            field_name = c.field_arg()
            row_id, ok = c.uint_arg(field_name)
            start_str, ok1 = c.string_arg("_start")
            end_str, ok2 = c.string_arg("_end")
            if not (ok and ok1 and ok2):
                return 0
            f = self.holder.field(index, field_name)
            if f is None:
                return 0
            q = f.time_quantum()
            if not q:
                return 0
            start = datetime.strptime(start_str, TIME_FORMAT)
            end = datetime.strptime(end_str, TIME_FORMAT)
        except ValueError:
            return 0
        total = 0
        for view in views_by_time_range(VIEW_STANDARD, start, end, q):
            frag = self.holder.fragment(index, field_name, view, shard)
            if frag is not None:
                total += frag.sparse_block_count([row_id])
        return total

    def _analytics_containers(self, index, c: Call, shard: int) -> int:
        """Touched-container estimate for the analytic calls. A Rows()
        dimension reads every listed (or discovered) row; Distinct /
        Percentile / a GroupBy Sum aggregate read the field's full BSI
        plane set. Filter subtrees and nested Rows() dimensions are
        counted by the caller's child recursion."""
        total = 0
        if c.name == "Rows":
            fname, ok = c.string_arg("_field")
            if ok and fname:
                frag = self.holder.fragment(index, fname, VIEW_STANDARD, shard)
                if frag is not None:
                    ids, has_ids = c.uint_slice_arg("ids")
                    total += frag.sparse_block_count(
                        list(ids) if has_ids else frag.row_ids()
                    )
            return total
        fname = ""
        if c.name in ("Distinct", "Percentile"):
            fname, _ = c.string_arg("field")
        elif c.name == "GroupBy":
            for child in c.children:
                if child.name == "Sum" and not child.children:
                    fname, _ = child.string_arg("field")
                    break
        if fname:
            f = self.holder.field(index, fname)
            bsig = f.bsi_group(fname) if f is not None else None
            frag = self.holder.fragment(
                index, fname, VIEW_BSI_GROUP_PREFIX + fname, shard
            )
            if frag is not None and bsig is not None:
                total += frag.sparse_block_count(
                    list(range(bsig.bit_depth() + 1))
                )
        return total

    def _cached_words(self, c: Call, shard: int):
        """u32[W] packed words for one shard of a ``__cached`` node's
        row, memoized on the node (a node is query-local, so the memo
        dies with the query; repeated shards within one query — device
        single-shard walks — pack once)."""
        memo = c.args.setdefault("_words", {})
        w = memo.get(shard)
        if w is None:
            w64 = np.zeros(SHARD_WIDTH // 64, dtype=np.uint64)
            seg = c.args["_row"].shard_segment(shard)
            if seg is not None:
                cols = np.asarray(seg.slice_all(), dtype=np.uint64) - np.uint64(
                    shard * SHARD_WIDTH
                )
                np.bitwise_or.at(
                    w64,
                    (cols >> np.uint64(6)).astype(np.int64),
                    np.uint64(1) << (cols & np.uint64(63)),
                )
            w = np.ascontiguousarray(w64).view("<u4")
            memo[shard] = w
        return w

    def _device_bitmap(self, index, c: Call, shard: int):
        """Lower a bitmap call subtree to a device u32[W] word vector."""
        name = c.name
        if name == "__cached":
            return self._cached_words(c, shard)
        if name == "Row":
            field_name = c.field_arg()
            f = self.holder.field(index, field_name)
            if f is None:
                raise NotFoundError(f"field not found: {field_name}")
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                raise ValueError(f"Row() must specify {field_name}")
            frag = self.holder.fragment(index, field_name, VIEW_STANDARD, shard)
            if frag is None:
                return np.zeros(_W32, dtype=np.uint32)
            return self.stager.row(frag, row_id)
        if name in ("Intersect", "Union", "Xor", "Difference"):
            if not c.children:
                if name in ("Intersect", "Difference"):
                    raise ValueError(f"empty {name} query is currently not supported")
                return np.zeros(_W32, dtype=np.uint32)
            acc = self._device_bitmap(index, c.children[0], shard)
            for child in c.children[1:]:
                w = self._device_bitmap(index, child, shard)
                if name == "Intersect":
                    acc = ops.and_(acc, w)
                elif name == "Union":
                    acc = ops.or_(acc, w)
                elif name == "Xor":
                    acc = ops.xor_(acc, w)
                else:
                    acc = ops.andnot(acc, w)
            return acc
        if name == "Range":
            return self._device_range(index, c, shard)
        raise _NotDeviceable(name)

    def _device_range(self, index, c: Call, shard: int):
        if not c.has_condition_arg():
            # time range: union staged rows across quantum views
            field_name = c.field_arg()
            f = self.holder.field(index, field_name)
            if f is None:
                raise NotFoundError(f"field not found: {field_name}")
            row_id, ok = c.uint_arg(field_name)
            start_str, ok1 = c.string_arg("_start")
            end_str, ok2 = c.string_arg("_end")
            if not (ok and ok1 and ok2):
                raise _NotDeviceable("Range")
            q = f.time_quantum()
            if not q:
                return np.zeros(_W32, dtype=np.uint32)
            start = datetime.strptime(start_str, TIME_FORMAT)
            end = datetime.strptime(end_str, TIME_FORMAT)
            acc = None
            for view in views_by_time_range(VIEW_STANDARD, start, end, q):
                frag = self.holder.fragment(index, field_name, view, shard)
                if frag is None:
                    continue
                w = self.stager.row(frag, row_id)
                acc = w if acc is None else ops.or_(acc, w)
            return acc if acc is not None else np.zeros(_W32, dtype=np.uint32)

        ((field_name, cond),) = c.args.items()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        bsig = f.bsi_group(field_name)
        if bsig is None:
            raise NotFoundError(f"bsiGroup not found: {field_name}")
        frag = self.holder.fragment(
            index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, shard
        )
        depth = bsig.bit_depth()
        zeros = np.zeros(_W32, dtype=np.uint32)

        if cond.op == NEQ and cond.value is None:
            if frag is None:
                return zeros
            return self.stager.row(frag, depth)
        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            base_min, base_max, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range or frag is None:
                return zeros
            planes = self.stager.planes(frag, depth)
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return planes[-1]
            return ops.bsi_range_between(
                planes, np.uint32(base_min), np.uint32(base_max), bit_depth=depth
            )
        value = cond.value
        if not isinstance(value, int) or isinstance(value, bool):
            raise ValueError("Range(): conditions only support integer values")
        base_value, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return zeros
        if frag is None:
            return zeros
        planes = self.stager.planes(frag, depth)
        if (
            (cond.op == "<" and value > bsig.max)
            or (cond.op == "<=" and value >= bsig.max)
            or (cond.op == ">" and value < bsig.min)
            or (cond.op == ">=" and value <= bsig.min)
        ):
            return planes[-1]
        if out_of_range and cond.op == NEQ:
            return planes[-1]
        pred = np.uint32(base_value)
        if cond.op == "==":
            return ops.bsi_range_eq(planes, pred, bit_depth=depth)
        if cond.op == "!=":
            return ops.bsi_range_neq(planes, pred, bit_depth=depth)
        if cond.op in ("<", "<="):
            return ops.bsi_range_lt(
                planes, pred, bit_depth=depth, allow_equality=cond.op == "<="
            )
        if cond.op in (">", ">="):
            return ops.bsi_range_gt(
                planes, pred, bit_depth=depth, allow_equality=cond.op == ">="
            )
        raise ValueError(f"invalid range operation: {cond.op}")

    # -- shard-batched device path -------------------------------------------
    # When this node executes many shards locally (single-node, or the
    # remote leg of a distributed query), the whole shard set runs as ONE
    # kernel dispatch over u32[S, W] stacks instead of S dispatches —
    # the reference's per-shard goroutine fan-out vectorised away
    # (SURVEY.md §2.2 'intra-node shard parallelism').

    def _local_batchable(self, opt) -> bool:
        return self.cluster is None or opt.remote

    def _use_device_batched(self, index, c: Call, shards) -> bool:
        use = self._use_device_batched_decide(index, c, shards)
        metrics.count(
            metrics.EXECUTOR_ROUTE_DEVICE if use else metrics.EXECUTOR_ROUTE_CPU,
            call=c.name,
        )
        sp = trace.current()
        if sp is not None:
            sp.event(
                metrics.STAGE_ROUTE,
                call=c.name,
                shards=len(shards),
                path="device" if use else "cpu",
            )
        return use

    def _use_device_batched_decide(self, index, c: Call, shards) -> bool:
        if self.device_policy == "never" or len(shards) < 2 or self._cpu_forced():
            return False
        if self.device_policy == "always":
            return True
        total = sum(self._touched_containers(index, c, s) for s in shards)
        return total >= self.auto_min_containers

    def _tree_leaves(self, index, c: Call, batch):
        """Lower a filter to (inputs, structure) for a consumer that is
        itself one jitted program, on one device or, a Sum or a Count,
        a ``shard_map`` kernel over the mesh (``_eval_tree`` traces the
        structure inside it). Boolean calls and BSI Ranges become
        structure: a Range is its field's staged plane stack, a leaf,
        and its predicates in base-value form, slots of one u32 vector
        that follows the leaves; where the field's bounds decide it, the
        existence plane of that stack or all-zero. A Row, a time-quantum
        Range and a ``__cached`` node stage or evaluate to a leaf array.
        Every node folded into the consumer's program counts to
        ``filter.inlined{op}`` as its eager launches would have counted
        to ``filter.launches{op}``."""
        inputs: list = []
        preds: list[int] = []
        inlined: Counter = Counter()

        def leaf(arr) -> int:
            for k, held in enumerate(inputs):
                if held is arr:  # two Ranges of one field read one stack
                    return k
            inputs.append(arr)
            return len(inputs) - 1

        def build(call: Call):
            if call.name in _STACK_FOLDS and call.children:
                inlined[_STACK_FOLDS[call.name][0]] += len(call.children) - 1
                return (call.name, tuple(build(ch) for ch in call.children))
            if call.name == "Range" and call.has_condition_arg():
                plan = self._range_plan(index, call, batch)
                if plan[0] == "zeros":
                    return ("zeros", len(batch))
                inlined["range"] += 1
                if plan[0] == "exists":
                    return ("exists", leaf(plan[1]))
                _, op, depth, planes, values = plan
                slots = tuple(range(len(preds), len(preds) + len(values)))
                preds.extend(values)
                return ("range", op, depth, leaf(planes), slots)
            return ("leaf", leaf(self._device_bitmap_stack(index, call, batch)))

        with trace.leg(trace.WF_FILTER_EVAL):
            tree = build(c)
            if preds:
                inputs.append(np.asarray(preds, dtype=np.uint32))
        for op, n in inlined.items():
            if n:
                metrics.count(metrics.FILTER_INLINED, value=n, op=op)
        return inputs, tree

    def _filter_tree(self, index, c: Call, batch):
        """``_tree_leaves`` of a BSI aggregate's optional filter child:
        ([], None) without one."""
        if len(c.children) == 1:
            return self._tree_leaves(index, c.children[0], batch)
        return [], None

    def _tree_jit(self, kind: str, key: tuple, body):
        """A jitted program of a filter's structure with one small
        result, kept under ``key`` (bounded by distinct query shapes,
        like the reference's parsed-query cache would be)."""
        import jax

        fn = self._tree_jits.get(key)
        if fn is None:
            program = jax.jit(jax.named_scope(kind)(body))

            def launch(*args):
                # the caller reads the few words of the result at once:
                # with the copy started behind the launch, the fence's
                # wait covers it, one round trip to the device, not two
                out = program(*args)
                out.copy_to_host_async()
                return out

            fn = self._tree_jits[key] = _timed_kernel(
                kind, launch, signature=key, recovery=self._oom
            )
        return fn

    def _tree_count_jit(self, tree):
        """Jitted popcount-of-tree: ``fn(*inputs)`` -> i32[1]."""
        return self._tree_jit(
            "tree_count",
            ("tree_count", tree),
            lambda *ls: ops.count_bits(_eval_tree(tree, ls))[None],
        )

    def _bsi_sum_jit(self, depth: int, tree):
        """Jitted plane counts of a lone Sum with its filter traced
        inside: ``fn(planes, *inputs)`` -> i32[depth + 1]."""
        return self._tree_jit(
            "bsi_sum",
            ("bsi_sum", depth, tree),
            lambda planes, *ls: _trace_bsi_sum(depth, tree, planes, ls),
        )

    def _device_bitmap_stack(self, index, c: Call, shards):
        """Lower a bitmap call subtree to one materialised u32[S, W]
        across shards, for a consumer that reads it as an array (a
        TopN's source, a per-call GroupBy, Distinct or Percentile, a
        leaf of ``_tree_leaves``):
        boolean calls fold eagerly and a Range launches its compare,
        each counted to ``filter.launches``. The host's time in it is
        the request's ``filter.eval``; a staged row's probe inside
        stays ``stager.lookup``, a miss ``stager``."""
        with trace.leg(trace.WF_FILTER_EVAL):
            return self._bitmap_stack(index, c, shards)

    def _bitmap_stack(self, index, c: Call, shards):
        name = c.name
        if name == "__cached":
            # device-resident plan cache: serve the packed stack from
            # HBM instead of re-packing + re-uploading the Row the
            # device just produced. Keyed by the subtree's canonical
            # hash; validated against the CURRENT generation vector
            # (the planner froze the insert stamp BEFORE resolving the
            # row, so a racing write can only over-invalidate). Mesh
            # runs skip it — stacks there are mesh-sharded and a plain
            # device_put array would be wrongly placed.
            dc = self.device_cache
            g0 = c.args.get("_genvec")
            gvfn = c.args.get("_gv")
            if dc is not None and g0 is not None and gvfn is not None and self.mesh is None:
                dkey = (index, c.args["_h"], tuple(shards))
                hit = dc.get(dkey, gvfn)
                if hit is not None:
                    return hit
                stack = np.stack([self._cached_words(c, s) for s in shards])
                epoch0 = dc.epoch
                try:
                    dev = self.stager.upload(stack)
                except Exception:
                    # upload failed: the host stack still works
                    metrics.count(metrics.PLANCACHE_DEVICE_UPLOAD_ERRORS)
                    return stack
                dc.put(dkey, g0, dev, int(stack.nbytes), epoch0=epoch0)
                return dev
            return np.stack([self._cached_words(c, s) for s in shards])
        if name == "Row":
            field_name = c.field_arg()
            f = self.holder.field(index, field_name)
            if f is None:
                raise NotFoundError(f"field not found: {field_name}")
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                raise ValueError(f"Row() must specify {field_name}")
            frags = tuple(
                self.holder.fragment(index, field_name, VIEW_STANDARD, s)
                for s in shards
            )
            return self.stager.row_stack(frags, row_id)
        if name in ("Intersect", "Union", "Xor", "Difference"):
            if not c.children:
                if name in ("Intersect", "Difference"):
                    raise ValueError(f"empty {name} query is currently not supported")
                return np.zeros((len(shards), _W32), dtype=np.uint32)
            op, fold = _STACK_FOLDS[name]
            acc = self._bitmap_stack(index, c.children[0], shards)
            for child in c.children[1:]:
                # eager: one launch a child, the host between them
                acc = fold(acc, self._bitmap_stack(index, child, shards))
                metrics.count(metrics.FILTER_LAUNCHES, op=op)
            return acc
        if name == "Range":
            return self._device_range_stack(index, c, shards)
        raise _NotDeviceable(name)

    def _range_launch(self, op: str, depth: int, planes, *preds):
        """One shard-batched BSI compare over ``planes`` u32[S, D+1, W].
        The vmapped kernel is jitted once per (operator, depth) and
        kept, as ``_tree_jits`` keeps tree programs; predicates are
        traced, so one program serves every constant. Not fenced: the
        leaf's consumer waits, and leaves dispatch one behind another."""
        key = (op, depth)
        fn = self._range_jits.get(key)
        first = fn is None
        if first:
            import jax

            fn = self._range_jits[key] = jax.jit(
                jax.vmap(_range_kernel(op, depth), in_axes=(0,) + (None,) * len(preds))
            )
        profiler.count_operands("bsi_range", (planes,))
        metrics.count(metrics.FILTER_LAUNCHES, op="range")
        t0 = time.monotonic()
        out = self._oom.run(lambda: fn(planes, *preds), kind="bsi_range")
        if first:
            profiler.COMPILES.note("bsi_range", key, time.monotonic() - t0)
        return out

    def _exists_stack(self, planes):
        """The existence plane of every shard, where the field's bounds
        decide a Range: a copy, launched like a compare."""
        metrics.count(metrics.FILTER_LAUNCHES, op="range")
        return planes[:, -1, :]

    def _device_range_stack(self, index, c: Call, shards):
        zeros = np.zeros((len(shards), _W32), dtype=np.uint32)
        if not c.has_condition_arg():
            field_name = c.field_arg()
            f = self.holder.field(index, field_name)
            if f is None:
                raise NotFoundError(f"field not found: {field_name}")
            row_id, ok = c.uint_arg(field_name)
            start_str, ok1 = c.string_arg("_start")
            end_str, ok2 = c.string_arg("_end")
            if not (ok and ok1 and ok2):
                raise _NotDeviceable("Range")
            q = f.time_quantum()
            if not q:
                return zeros
            start = datetime.strptime(start_str, TIME_FORMAT)
            end = datetime.strptime(end_str, TIME_FORMAT)
            acc = None
            for view in views_by_time_range(VIEW_STANDARD, start, end, q):
                frags = tuple(
                    self.holder.fragment(index, field_name, view, s) for s in shards
                )
                if not any(frags):
                    continue
                w = self.stager.row_stack(frags, row_id)
                if acc is None:
                    acc = w
                else:
                    acc = ops.or_(acc, w)
                    metrics.count(metrics.FILTER_LAUNCHES, op="or")
            return acc if acc is not None else zeros

        plan = self._range_plan(index, c, shards)
        if plan[0] == "zeros":
            return zeros
        if plan[0] == "exists":
            return self._exists_stack(plan[1])
        _, op, depth, planes, values = plan
        return self._range_launch(op, depth, planes, *map(np.uint32, values))

    def _range_plan(self, index, c: Call, shards) -> tuple:
        """A BSI Range's condition decided on the host as far as the
        field's bounds decide it: ``("zeros",)`` (out of range, or no
        fragment), ``("exists", planes)`` (every column that has a
        value), or ``("range", op, depth, planes, predicates)`` with
        the predicates in base-value form; ``planes`` is the field's
        staged stack u32[S, D+1, W]."""
        ((field_name, cond),) = c.args.items()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        bsig = f.bsi_group(field_name)
        if bsig is None:
            raise NotFoundError(f"bsiGroup not found: {field_name}")
        depth = bsig.bit_depth()
        frags = tuple(
            self.holder.fragment(
                index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, s
            )
            for s in shards
        )
        if not any(frags):
            return ("zeros",)
        planes = self.stager.planes_stack(frags, depth)

        if cond.op == NEQ and cond.value is None:
            return ("exists", planes)
        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            base_min, base_max, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range:
                return ("zeros",)
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return ("exists", planes)
            return ("range", BETWEEN, depth, planes, (base_min, base_max))
        value = cond.value
        if not isinstance(value, int) or isinstance(value, bool):
            raise ValueError("Range(): conditions only support integer values")
        base_value, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return ("zeros",)
        if (
            (cond.op == "<" and value > bsig.max)
            or (cond.op == "<=" and value >= bsig.max)
            or (cond.op == ">" and value < bsig.min)
            or (cond.op == ">=" and value <= bsig.min)
            or (out_of_range and cond.op == NEQ)
        ):
            return ("exists", planes)
        return ("range", cond.op, depth, planes, (base_value,))

    # -- Count ---------------------------------------------------------------

    def _execute_count(self, index, c: Call, shards, opt) -> int:
        if len(c.children) == 0:
            raise ValueError("Count() requires an input bitmap")
        if len(c.children) > 1:
            raise ValueError("Count() only accepts a single bitmap input")
        child = c.children[0]

        if (
            self._local_batchable(opt)
            and shards
            and self._use_device_batched(index, child, shards)
        ):
            try:
                with trace.child(metrics.STAGE_DEVICE_BATCH, call="Count"):
                    n = self._count_device_batched(index, child, shards)
                self._heat_read_legs(index, child, shards)
                return n
            except _NotDeviceable:
                pass

        def map_fn(shard):
            if self._use_device(index, child, shard):
                try:
                    words = self._device_bitmap(index, child, shard)
                    return int(ops.count_bits(words))
                except _NotDeviceable:
                    pass
            return self._bitmap_call_shard_cpu(index, child, shard).count()

        result = self._map_reduce(
            index, shards, c, opt, map_fn, lambda a, b: a + b, zero_factory=lambda: 0
        )
        return int(result or 0)

    def _count_device_batched(self, index, child, shards) -> int:
        batch = self._shard_plan(shards)
        # One fused program per query-tree structure: boolean
        # internal nodes trace into a single jit so the whole
        # chain is one XLA fusion + one dispatch, instead of an
        # eager op (one dispatch each) per tree node (SURVEY.md
        # §7 step 4). On a mesh it is a shard_map kernel.
        leaves, tree = self._tree_leaves(index, child, batch)
        if self.mesh is not None:
            return int(_mesh_fetch(self._spmd_kernel("count", tree)(*leaves)))
        res = self._tree_count_jit(tree)(*leaves)
        return int(_fetch(res).reshape(-1)[0])

    # -- Sum / Min / Max -----------------------------------------------------

    def _bsi_shard_parts(self, index, c: Call, shard: int):
        """(fragment, bsig, filter) for a Sum/Min/Max shard; None if missing."""
        field_name, _ = c.string_arg("field")
        f = self.holder.field(index, field_name)
        if f is None:
            return None
        bsig = f.bsi_group(field_name)
        if bsig is None:
            return None
        frag = self.holder.fragment(
            index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, shard
        )
        if frag is None:
            return None
        return frag, bsig

    def _bsi_filter(self, index, c: Call, shard: int) -> Optional[Row]:
        if len(c.children) == 1:
            return self._bitmap_call_shard(index, c.children[0], shard)
        return None

    def _device_filter(self, index, c: Call, shard: int):
        """(filter_words, has_filter) on the device path."""
        if len(c.children) == 1:
            return self._device_bitmap(index, c.children[0], shard), True
        return np.zeros(_W32, dtype=np.uint32), False

    def _execute_sum(self, index, c: Call, shards, opt) -> ValCount:
        if not c.args.get("field"):
            raise ValueError("Sum(): field required")
        if len(c.children) > 1:
            raise ValueError("Sum() only accepts a single bitmap input")

        # shard-batched fast path: one dispatch for all local shards
        if self._local_batchable(opt) and shards and self._use_device_batched(index, c, shards):
            field_name, _ = c.string_arg("field")
            f = self.holder.field(index, field_name)
            bsig = f.bsi_group(field_name) if f else None
            if bsig is not None:
                batch = self._shard_plan(shards)
                frags = tuple(
                    self.holder.fragment(
                        index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, s
                    )
                    for s in batch
                )
                if any(frags):
                    try:
                        with trace.child(metrics.STAGE_DEVICE_BATCH, call="Sum"):
                            vc = self._sum_device_batched(
                                index, c, batch, bsig, frags
                            )
                        self._heat_read_legs(index, c, shards)
                        return vc
                    except _NotDeviceable:
                        pass

        def map_fn(shard):
            parts = self._bsi_shard_parts(index, c, shard)
            if parts is None:
                return ValCount()
            frag, bsig = parts
            depth = bsig.bit_depth()
            if self._use_device(index, c, shard) or (
                self.device_policy != "never"
                and frag.sparse_block_count(list(range(depth + 1)))
                >= self.auto_min_containers
            ):
                try:
                    filt, has_filter = self._device_filter(index, c, shard)
                    planes = self.stager.planes(frag, depth)
                    counts = _launch(
                        "bsi_sum",
                        ops.bsi_plane_counts,
                        planes,
                        filt,
                        bit_depth=depth,
                        has_filter=has_filter,
                    )
                    vsum = sum(int(counts[i]) << i for i in range(depth))
                    vcount = int(counts[depth])
                    return ValCount(vsum + vcount * bsig.min, vcount)
                except _NotDeviceable:
                    pass
            filt = self._bsi_filter(index, c, shard)
            vsum, vcount = frag.sum(filt, depth)
            return ValCount(vsum + vcount * bsig.min, vcount)

        result = self._map_reduce(
            index, shards, c, opt, map_fn, lambda a, b: a.add(b), zero_factory=ValCount
        )
        if result is None or result.count == 0:
            return ValCount()
        return result

    def _sum_device_batched(self, index, c: Call, batch, bsig, frags) -> ValCount:
        depth = bsig.bit_depth()
        # one program a filter structure: the filter's compares and
        # folds are traced into the sum's launch, as Count's are
        inputs, tree = self._filter_tree(index, c, batch)
        planes = self.stager.planes_stack(frags, depth)
        if self.mesh is not None:
            kernel = self._spmd_kernel("plane_counts", depth, tree)
            counts = _mesh_fetch(kernel(planes, *inputs))
        else:
            counts = _fetch(self._bsi_sum_jit(depth, tree)(planes, *inputs))
        vsum = sum(int(counts[i]) << i for i in range(depth))
        vcount = int(counts[depth])
        if vcount == 0:
            return ValCount()
        return ValCount(vsum + vcount * bsig.min, vcount)

    def _execute_min(self, index, c: Call, shards, opt) -> ValCount:
        return self._execute_minmax(index, c, shards, opt, is_min=True)

    def _execute_max(self, index, c: Call, shards, opt) -> ValCount:
        return self._execute_minmax(index, c, shards, opt, is_min=False)

    def _execute_minmax(self, index, c: Call, shards, opt, is_min: bool) -> ValCount:
        if not c.args.get("field"):
            raise ValueError(f"{'Min' if is_min else 'Max'}(): field required")
        if len(c.children) > 1:
            raise ValueError(
                f"{'Min' if is_min else 'Max'}() only accepts a single bitmap input"
            )

        def map_fn(shard):
            parts = self._bsi_shard_parts(index, c, shard)
            if parts is None:
                return ValCount()
            frag, bsig = parts
            depth = bsig.bit_depth()
            if self._use_device(index, c, shard) or (
                self.device_policy != "never"
                and frag.sparse_block_count(list(range(depth + 1)))
                >= self.auto_min_containers
            ):
                try:
                    filt, has_filter = self._device_filter(index, c, shard)
                    planes = self.stager.planes(frag, depth)
                    bits, count = _launch(
                        "bsi_min" if is_min else "bsi_max",
                        ops.bsi_min if is_min else ops.bsi_max,
                        planes,
                        filt,
                        bit_depth=depth,
                        has_filter=has_filter,
                    )
                    count = int(count)
                    if count == 0:
                        return ValCount()
                    val = sum(1 << i for i, b in enumerate(bits) if b)
                    return ValCount(val + bsig.min, count)
                except _NotDeviceable:
                    pass
            filt = self._bsi_filter(index, c, shard)
            val, count = (frag.min if is_min else frag.max)(filt, depth)
            return ValCount(val + bsig.min, count)

        reduce_fn = (
            (lambda a, b: a.smaller(b)) if is_min else (lambda a, b: a.larger(b))
        )
        result = self._map_reduce(
            index, shards, c, opt, map_fn, reduce_fn, zero_factory=ValCount
        )
        if result is None or result.count == 0:
            return ValCount()
        return result

    # -- device-resident analytics (ISSUE 18) --------------------------------
    #
    # GroupBy / Distinct / Percentile execute shard-batched as segmented
    # device reductions (one jitted launch per panel, intermediates
    # never leaving HBM) with the same degrade ladder as Count/Sum/TopN:
    # batched device -> per-shard CPU oracle via _map_reduce (which the
    # cluster layer federates). A FragmentQuarantinedError raised while
    # STAGING a batch degrades that launch to the classic path, where
    # the quarantined shard's leg surfaces the clean 503 instead of
    # poisoning the whole fused launch.

    def _execute_groupby(self, index, c: Call, shards, opt) -> list[dict]:
        plan = analytics.parse_groupby(c)
        metrics.count(metrics.ANALYTICS_QUERIES, call="GroupBy")
        dims = analytics.resolve_dims(
            self.holder, index, plan, shards, self.analytics_max_groups
        )
        if (
            self._local_batchable(opt)
            and shards
            and self.mesh is None  # group stacks flatten the shard axis
            and all(ids for _, ids in dims)
            and self._use_device_batched(index, c, shards)
        ):
            try:
                with trace.child(metrics.STAGE_DEVICE_BATCH, call="GroupBy"):
                    groups = self._groupby_device_batched(
                        index, plan, dims, shards, finalize=not opt.remote
                    )
                fields = [f for f, _ in dims] + (
                    [plan.agg_field] if plan.agg_field else []
                )
                self._analytics_heat_legs(index, fields, shards)
                return groups
            except _NotDeviceable:
                pass
            except FragmentQuarantinedError:
                metrics.count(metrics.ANALYTICS_DEGRADED_LEGS, call="GroupBy")

        def map_fn(shard):
            return analytics.groupby_shard(self, index, plan, dims, shard)

        merged = self._map_reduce(
            index,
            shards,
            c,
            opt,
            map_fn,
            analytics.merge_group_lists,
            zero_factory=list,
        )
        if opt.remote:
            # un-finalized wire list: the coordinator merges remote legs
            # first, then orders + applies limit exactly once
            return merged or []
        return analytics.finalize_groups(plan, merged or [])

    def _groupby_device_batched(
        self, index, plan, dims, shards, finalize: bool
    ) -> list[dict]:
        """One segmented-reduction launch for the whole panel: stack each
        dimension's rows, cross-product AND on device, popcount the K
        group bitmaps (and their BSI plane intersections for Sum). The
        wire list, ordered and limited where ``finalize`` (a remote leg's
        is left to the coordinator). The host's lowering is the request's
        ``groupby.lower``, the answer's assembly its
        ``groupby.finalize``."""
        import jax.numpy as jnp

        with trace.leg(trace.WF_GROUPBY_LOWER):
            wf = len(shards) * _W32
            dim_stacks = []
            for field, ids in dims:
                frags = tuple(
                    self.holder.fragment(index, field, VIEW_STANDARD, s)
                    for s in shards
                )
                rows = [self.stager.row_stack(frags, rid) for rid in ids]
                dim_stacks.append(jnp.stack(rows).reshape(len(ids), wf))
            if plan.filter is not None:
                filt = jnp.asarray(
                    self._device_bitmap_stack(index, plan.filter, shards)
                ).reshape(wf)
            else:
                filt = None
            planes = depth = bsig = None
            if plan.agg_field is not None:
                f = self.holder.field(index, plan.agg_field)
                bsig = f.bsi_group(plan.agg_field) if f is not None else None
                if bsig is None:
                    raise NotFoundError(f"bsiGroup not found: {plan.agg_field}")
                depth = bsig.bit_depth()
                afrags = tuple(
                    self.holder.fragment(
                        index,
                        plan.agg_field,
                        VIEW_BSI_GROUP_PREFIX + plan.agg_field,
                        s,
                    )
                    for s in shards
                )
                if any(afrags):
                    planes = jnp.transpose(
                        self.stager.planes_stack(afrags, depth), (1, 0, 2)
                    ).reshape(depth + 1, wf)
        k = 1
        for _, ids in dims:
            k *= len(ids)
        metrics.count(metrics.FUSION_GROUPBY_LAUNCHES)
        metrics.observe(metrics.FUSION_GROUPBY_GROUPS, k)
        if planes is None:
            counts = _launch("groupby_counts", ops.groupby_counts, tuple(dim_stacks), filt)
            plane_counts = None
        else:
            counts, plane_counts = _launch(
                "groupby_sum", ops.groupby_sum_reduce, tuple(dim_stacks), filt, planes
            )
        with trace.leg(trace.WF_GROUPBY_FINALIZE):
            if plane_counts is not None:
                sums = analytics.assemble_sums(plane_counts, depth, bsig.min)
            elif bsig is not None:
                sums = [0] * int(counts.shape[0])  # no value fragments: every sum 0
            else:
                sums = None
            groups = analytics.emit_device_groups(dims, counts, sums=sums)
            return analytics.finalize_groups(plan, groups) if finalize else groups

    def _execute_distinct(self, index, c: Call, shards, opt) -> list[int]:
        field, ok = c.string_arg("field")
        if not ok or not field:
            raise ValueError("Distinct(): field required")
        if len(c.children) > 1:
            raise ValueError("Distinct() only accepts a single bitmap input")
        metrics.count(metrics.ANALYTICS_QUERIES, call="Distinct")
        f = self.holder.field(index, field)
        bsig = f.bsi_group(field) if f is not None else None
        if bsig is None:
            raise NotFoundError(f"bsiGroup not found: {field}")
        if (
            self._local_batchable(opt)
            and shards
            and self.mesh is None
            and bsig.bit_depth() <= analytics.DISTINCT_DEVICE_MAX_DEPTH
            and self._use_device_batched(index, c, shards)
        ):
            try:
                with trace.child(metrics.STAGE_DEVICE_BATCH, call="Distinct"):
                    vals = self._distinct_device_batched(index, c, shards, bsig)
                self._analytics_heat_legs(index, [field], shards)
                return vals
            except _NotDeviceable:
                pass
            except FragmentQuarantinedError:
                metrics.count(metrics.ANALYTICS_DEGRADED_LEGS, call="Distinct")

        def map_fn(shard):
            return analytics.distinct_shard(self, index, c, field, shard)

        result = self._map_reduce(
            index,
            shards,
            c,
            opt,
            map_fn,
            analytics.merge_distinct_lists,
            zero_factory=list,
        )
        return result or []

    def _distinct_device_batched(self, index, c: Call, shards, bsig) -> list[int]:
        """OR-reduce the per-shard value presence into one 2^depth
        bitmap on device; the host decodes set positions to values."""
        field, _ = c.string_arg("field")
        depth = bsig.bit_depth()
        frags = tuple(
            self.holder.fragment(index, field, VIEW_BSI_GROUP_PREFIX + field, s)
            for s in shards
        )
        if not any(frags):
            return []
        if len(c.children) == 1:
            filt = self._device_bitmap_stack(index, c.children[0], shards)
            has_filter = True
        else:
            filt = np.zeros((len(shards), _W32), dtype=np.uint32)
            has_filter = False
        planes = self.stager.planes_stack(frags, depth)
        words = _launch(
            "bsi_distinct",
            ops.bsi_distinct_presence,
            planes,
            filt,
            bit_depth=depth,
            has_filter=has_filter,
        )
        return analytics.decode_presence_words(words, bsig.min)

    def _execute_percentile(self, index, c: Call, shards, opt) -> ValCount:
        field, nth_bp = analytics.parse_percentile(c)
        metrics.count(metrics.ANALYTICS_QUERIES, call="Percentile")
        f = self.holder.field(index, field)
        bsig = f.bsi_group(field) if f is not None else None
        if bsig is None:
            raise NotFoundError(f"bsiGroup not found: {field}")
        if (
            self._local_batchable(opt)
            and shards
            and self.mesh is None
            and self._use_device_batched(index, c, shards)
        ):
            try:
                with trace.child(metrics.STAGE_DEVICE_BATCH, call="Percentile"):
                    vc = self._percentile_device_batched(
                        index, c, shards, bsig, nth_bp
                    )
                self._analytics_heat_legs(index, [field], shards)
                return vc
            except _NotDeviceable:
                pass
            except FragmentQuarantinedError:
                metrics.count(
                    metrics.ANALYTICS_DEGRADED_LEGS, call="Percentile"
                )
        return self._percentile_by_counting(
            index, c, shards, opt, field, bsig, nth_bp
        )

    def _percentile_device_batched(
        self, index, c: Call, shards, bsig, nth_bp: int
    ) -> ValCount:
        """Bit-sliced binary search over the BSI planes, entirely on
        device: one launch, one fetch of (depth bits, count)."""
        field, _ = c.string_arg("field")
        depth = bsig.bit_depth()
        frags = tuple(
            self.holder.fragment(index, field, VIEW_BSI_GROUP_PREFIX + field, s)
            for s in shards
        )
        if not any(frags):
            return ValCount()
        if len(c.children) == 1:
            filt = self._device_bitmap_stack(index, c.children[0], shards)
            has_filter = True
        else:
            filt = np.zeros((len(shards), _W32), dtype=np.uint32)
            has_filter = False
        planes = self.stager.planes_stack(frags, depth)
        bits, count = _launch(
            "bsi_percentile",
            ops.bsi_percentile_batched,
            planes,
            filt,
            np.int32(nth_bp),
            bit_depth=depth,
            has_filter=has_filter,
        )
        count = int(count)
        if count == 0:
            return ValCount()
        val = sum(1 << i for i, b in enumerate(bits) if b)
        return ValCount(val + bsig.min, count)

    def _percentile_by_counting(
        self, index, c: Call, shards, opt, field, bsig, nth_bp: int
    ) -> ValCount:
        """Classic leg: O(depth) counting binary search over the value
        domain built from synthesized Count(Range(...)) calls — each
        Count federates (and device-routes) through its own path, so
        this leg is cluster-correct without a new merge type, and it is
        the CPU oracle the device descent must match bit-for-bit."""

        def count_where(cond: Condition) -> int:
            child: Call = Call("Range", {field: cond})
            if len(c.children) == 1:
                child = Call(
                    "Intersect", children=[c.children[0].clone(), child]
                )
            return self._execute_count(
                index, Call("Count", children=[child]), shards, opt
            )

        n = count_where(Condition(NEQ, None))
        if n == 0:
            return ValCount()
        k = analytics.nearest_rank(nth_bp, n)
        lo, hi = bsig.min, bsig.max
        while lo < hi:
            mid = (lo + hi) // 2
            if count_where(Condition("<=", mid)) >= k:
                hi = mid
            else:
                lo = mid + 1
        return ValCount(lo, n)

    # -- TopN (reference executeTopN two-pass, executor.go:521-585) ----------

    def _execute_topn(
        self, index, c: Call, shards, opt, prescored=None
    ) -> list[dict]:
        # topn.walk is what the call spends outside its inner legs
        # (candidates, staging, the device's wait, the fetch): the ranked
        # walk, the cross-shard merge, the sorts, the pass-2 trim
        with trace.leg(trace.WF_TOPN_WALK):
            ids_arg, _ = c.uint_slice_arg("ids")
            n, _ = c.uint_arg("n")
            # (shard, row_id) -> exact intersection count, filled by pass
            # 1's scoring dispatches and consulted by pass 2: on skewed
            # data the winning ids sit in every shard's cache head, so
            # pass 2 usually needs no device round-trip at all
            carry = _ScoreCarry()
            pairs = self._execute_topn_shards(
                index, c, shards, opt, carry, prescored=prescored
            )
            if not pairs or ids_arg or opt.remote:
                return _pairs_result(pairs)
            # Pass 2: the union of candidate ids, counted exactly in
            # every shard. Pass 1's matrices answer the shards they can
            # (_ScoreCarry.answer); the rest are re-queried per id.
            winners = sorted(p[0] for p in pairs)
            trimmed = []
            if _plain_threshold_walk(c):
                threshold, _ = c.uint_arg("threshold")
                trimmed = carry.answer(
                    winners, max(int(threshold), DEFAULT_MIN_THRESHOLD)
                )
            rest = sum(s not in carry.answered for s in shards)
            if rest:
                # over all the shards, the answered ones empty-handed:
                # routes, shapes and staging keys stay what a per-id
                # pass 2 over every shard has
                metrics.count(
                    metrics.TOPN_PASS2_IDS, value=rest * len(winners), how="scalar"
                )
                other = c.clone()
                other.args["ids"] = winners
                trimmed = pairs_add(
                    trimmed,
                    self._execute_topn_shards(index, other, shards, opt, carry),
                )
            trimmed = sort_pairs(trimmed)
            if n and n < len(trimmed):
                trimmed = trimmed[:n]
            return _pairs_result(trimmed)

    def _execute_topn_shards(
        self, index, c: Call, shards, opt, carry=None, prescored=None
    ) -> list[tuple[int, int]]:
        if (
            self._local_batchable(opt)
            and shards
            and len(c.children) == 1
            # a fused launch already scored the head chunk on device —
            # honor it regardless of the (re-evaluated) auto crossover,
            # so the prescore is never discarded by a borderline flip
            and (
                prescored is not None
                or self._use_device_batched(index, c, shards)
            )
        ):
            try:
                with trace.child(metrics.STAGE_DEVICE_BATCH, call="TopN"):
                    if self.mesh is not None:
                        pairs = self._topn_shards_spmd(index, c, shards, carry)
                    else:
                        pairs = self._topn_shards_batched(
                            index, c, shards, carry, prescored=prescored
                        )
                self._heat_read_legs(index, c, shards)
                return sort_pairs(pairs)
            except _NotDeviceable:
                pass

        def map_fn(shard):
            return self._execute_topn_shard(index, c, shard, carry)

        result = self._map_reduce(index, shards, c, opt, map_fn, pairs_add, zero_factory=list)
        return sort_pairs(result or [])

    def _topn_shards_batched(
        self, index, c: Call, shards, carry=None, prescored=None
    ) -> list[tuple[int, int]]:
        """Single-device cross-shard TopN: every shard's candidate
        scoring lands in ONE chunked kernel dispatch over the merged
        block-sparse staging (sparse_intersection_counts_stacked) —
        per-shard sequential launches cost a host round-trip each.
        The per-shard ranked walk replays on the host for bit-identical
        pruning."""
        field, _ = c.string_arg("_field")
        n, _ = c.uint_arg("n")
        attr_name, _ = c.string_arg("attrName")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise ValueError("Tanimoto Threshold is from 1 to 100 only")
        if tanimoto > 0:
            # tanimoto pruning needs each shard's CPU source count
            raise _NotDeviceable("TopN+tanimoto")
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD

        if prescored is not None:
            # fused whole-query launch already staged + scored the head
            # chunk: reuse ITS fragment/pairs snapshot (the injected
            # matrix and the walk must agree on candidate order) and
            # its resolved source stack
            frags, pairs_by_shard, ids0, mat0, srcs0 = prescored
        else:
            frags = tuple(
                self.holder.fragment(index, field, VIEW_STANDARD, s)
                for s in shards
            )
            with trace.leg(trace.WF_TOPN_CANDIDATES):
                pairs_by_shard = _candidate_pairs(frags, shards, row_ids, carry)
        if not any(pairs_by_shard):
            return []
        # lazy: a pass 2 fully covered by the carry never resolves the
        # source stack (no device re-fold of compound sources)
        with trace.leg(trace.WF_TOPN_CANDIDATES):  # seeds from the carry
            provider = _StackedLazyScores(
                self,
                frags,
                pairs_by_shard,
                (
                    srcs0
                    if prescored is not None
                    else lambda: self._device_bitmap_stack(
                        index, c.children[0], shards
                    )
                ),
                shards=shards,
                carry=carry,
            )
        if prescored is not None:
            # inject the fused head as chunk 0; the walk continues from
            # _chunk_size(FIRST_CHUNK) exactly as the unfused schedule
            # would, so chunk boundaries (and staging keys) match
            provider._mats.append(mat0)
            provider._chunk_meta.append((0, mat0.shape[1], ids0))
            provider._pos = mat0.shape[1]
        opt_ = TopOptions(
            n=int(n),
            src=None,
            row_ids=row_ids,
            min_threshold=min_threshold,
            filter_name=attr_name,
            filter_values=attr_values,
            tanimoto_threshold=0,
        )
        fast = _vectorized_topn_walk(pairs_by_shard, provider, opt_)
        if fast is not None:
            return fast
        out: list[tuple[int, int]] = []
        for i, (frag, pairs) in enumerate(zip(frags, pairs_by_shard)):
            if frag is None or not pairs:
                continue
            out = pairs_add(out, _ranked_walk(frag, opt_, pairs, provider.view(i)))
        return out

    def _topn_shards_spmd(
        self, index, c: Call, shards, carry=None
    ) -> list[tuple[int, int]]:
        """Cross-shard TopN on the mesh with LAZY chunked staging: the
        ranked walk (replayed per shard for bit-identical pruning)
        pulls pow2 chunks of block-sparse candidates on demand; each
        chunk is one shard_map program whose all_gather replaces the
        reference's HTTP Pairs exchange (executor.go:563-585). Eagerly
        staging every ranked-cache candidate densely cost k × S ×
        128 KB — tens of GB at the reference's 50k-candidate cache
        (cache.go:136-233) — where the lazy walk usually prunes within
        the head chunk (fragment.go:870-1002 threshold break)."""
        field, _ = c.string_arg("_field")
        n, _ = c.uint_arg("n")
        attr_name, _ = c.string_arg("attrName")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise ValueError("Tanimoto Threshold is from 1 to 100 only")
        if tanimoto > 0:
            # tanimoto pruning needs each shard's CPU source count;
            # the per-shard path already has those rows in hand
            raise _NotDeviceable("TopN+tanimoto")
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD

        batch = self._shard_plan(shards)
        frags = tuple(
            self.holder.fragment(index, field, VIEW_STANDARD, s) for s in batch
        )
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            pairs_by_shard = _candidate_pairs(frags, batch, row_ids, carry)
        if not any(pairs_by_shard):
            return []
        # carry-seeded provider: pass 2's id subset was scored by pass 1
        # (same source, same fragment snapshot), so a fully-covered
        # second pass dispatches nothing — not even the source stack
        # (srcs is a thunk resolved on first chunk dispatch)
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            provider = _SpmdLazyScores(
                self,
                frags,
                pairs_by_shard,
                lambda: self._device_bitmap_stack(index, c.children[0], batch),
                shards=batch,
                carry=carry,
            )
        opt_ = TopOptions(
            n=int(n),
            src=None,
            row_ids=row_ids,
            min_threshold=min_threshold,
            filter_name=attr_name,
            filter_values=attr_values,
            tanimoto_threshold=0,
        )
        fast = _vectorized_topn_walk(pairs_by_shard, provider, opt_)
        if fast is not None:
            return fast
        out: list[tuple[int, int]] = []
        for i, (frag, pairs) in enumerate(zip(frags, pairs_by_shard)):
            if frag is None or not pairs:
                continue
            out = pairs_add(out, _ranked_walk(frag, opt_, pairs, provider.view(i)))
        return out

    def _execute_topn_shard(
        self, index, c: Call, shard: int, carry=None
    ) -> list[tuple[int, int]]:
        field, _ = c.string_arg("_field")
        n, _ = c.uint_arg("n")
        attr_name, _ = c.string_arg("attrName")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, has_threshold = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if carry is not None and shard in carry.answered:
            return []

        src = None
        if len(c.children) == 1:
            src = self._bitmap_call_shard(index, c.children[0], shard)
        elif len(c.children) > 1:
            raise ValueError("TopN() can only have one input bitmap")

        frag = self.holder.fragment(index, field, VIEW_STANDARD, shard)
        if frag is None:
            return []
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD
        if tanimoto > 100:
            raise ValueError("Tanimoto Threshold is from 1 to 100 only")
        opt_ = TopOptions(
            n=int(n),
            src=src,
            row_ids=row_ids,
            min_threshold=min_threshold,
            filter_name=attr_name,
            filter_values=attr_values,
            tanimoto_threshold=tanimoto,
        )
        if src is not None and self._use_device(index, c, shard):
            return self._top_device(frag, opt_, index, c, shard, carry)
        return frag.top(opt_)

    def _top_device(self, frag, opt_: TopOptions, index, c: Call, shard: int, carry=None):
        """Device-accelerated TopN: batch all candidate intersection counts
        into one matrix kernel pass, then replay the reference's ranked
        walk on the precomputed scores (bit-identical outputs)."""
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            pairs = frag._top_bitmap_pairs(opt_.row_ids)
        if not pairs:
            return []
        try:
            src_words = self._device_bitmap(index, c.children[0], shard)
        except _NotDeviceable:
            return frag.top(opt_)
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            scores = _LazyScores(self, frag, pairs, src_words, shard=shard, carry=carry)
        return _ranked_walk(frag, opt_, pairs, scores)

    # -- writes (reference executor.go:998-1258) -----------------------------

    def _shard_nodes_local(self, index, shard) -> bool:
        """True when this node owns the shard (single-node: always)."""
        return True

    def _execute_set_bit(self, index, c: Call, opt) -> bool:
        field_name = c.field_arg()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise ValueError("Set() row argument required")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise ValueError("Set() col argument required")
        timestamp = None
        ts_str, ok = c.string_arg("_timestamp")
        if ok:
            timestamp = datetime.strptime(ts_str, TIME_FORMAT)
        if self.cluster is not None and not opt.remote:
            return self.cluster.set_bit(index, c, f, row_id, col_id, timestamp, opt)
        # local apply leg: every rank that lands the bit (direct,
        # remote-leg, or gang replay) records the write exactly once
        heat.record_write(index, field_name, col_id // SHARD_WIDTH, 1)
        return f.set_bit(row_id, col_id, timestamp)

    def _execute_clear_bit(self, index, c: Call, opt) -> bool:
        field_name = c.field_arg()
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise ValueError("Clear() row argument required")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise ValueError("Clear() col argument required")
        if self.cluster is not None and not opt.remote:
            return self.cluster.clear_bit(index, c, f, row_id, col_id, opt)
        heat.record_write(index, field_name, col_id // SHARD_WIDTH, 1)
        return f.clear_bit(row_id, col_id)

    def _gang_forward_write(self, index, c: Call, opt) -> bool:
        """Federated leader receiving a forward-style write (SetValue /
        attrs) at top level: the LOCAL apply must replay through the
        gang (so follower holders stay identical), then fan out to
        peers as usual. True when handled."""
        lex = self.cluster.local_executor if self.cluster is not None else None
        if lex is None or opt.remote:
            return False
        lex(index, c, None, opt)
        self.cluster.forward_to_all(index, c, opt)
        return True

    def _execute_set_value(self, index, c: Call, opt) -> None:
        col_id, ok = c.uint_arg("col")
        if not ok:
            raise ValueError("SetValue() col argument required")
        args = {k: v for k, v in c.args.items() if k != "col"}
        if self._gang_forward_write(index, c, opt):
            return
        for name, value in args.items():
            f = self.holder.field(index, name)
            if f is None:
                raise NotFoundError(f"field not found: {name}")
            if isinstance(value, bool) or not isinstance(value, int):
                raise ValueError("invalid BSI group value type")
            f.set_value(col_id, value)
        if self.cluster is not None and not opt.remote:
            self.cluster.forward_to_all(index, c, opt)

    def _execute_set_row_attrs(self, index, c: Call, opt) -> None:
        field_name, ok = c.string_arg("_field")
        if not ok:
            raise ValueError("SetRowAttrs() field required")
        f = self.holder.field(index, field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        row_id, ok = c.uint_arg("_row")
        if not ok:
            raise ValueError("SetRowAttrs() row required")
        attrs = {
            k: v for k, v in c.args.items() if k not in ("_field", "_row")
        }
        if f.row_attr_store is None:
            raise ValueError("row attr store not configured")
        if self._gang_forward_write(index, c, opt):
            return
        f.row_attr_store.set_attrs(row_id, attrs)
        if self.cluster is not None and not opt.remote:
            self.cluster.forward_to_all(index, c, opt)

    def _execute_set_column_attrs(self, index, c: Call, opt) -> None:
        idx = self.holder.index(index)
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise ValueError("SetColumnAttrs() col required")
        attrs = {k: v for k, v in c.args.items() if k != "_col"}
        if idx.column_attrs is None:
            raise ValueError("column attr store not configured")
        if self._gang_forward_write(index, c, opt):
            return
        idx.column_attrs.set_attrs(col_id, attrs)
        if self.cluster is not None and not opt.remote:
            self.cluster.forward_to_all(index, c, opt)

    def _read_pool_acquire(self):
        """Check out the shared read pool (lazily built), or None while
        close() is in progress. The checkout refcount lets close()
        drain active ``pool.map`` users before shutting the pool down —
        previously close() nulled the attribute while a concurrent
        execute() held a local ref and raced ``shutdown``."""
        with self._read_pool_cv:
            if self._read_pool_closing:
                return None
            if self._read_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._read_pool = ThreadPoolExecutor(
                    max_workers=16, thread_name_prefix="pql-read"
                )
            self._read_pool_users += 1
            return self._read_pool

    def _read_pool_release(self) -> None:
        with self._read_pool_cv:
            self._read_pool_users -= 1
            if self._read_pool_users == 0:
                self._read_pool_cv.notify_all()

    def _warm_query(self, index: str, query, shards) -> None:
        """Advisory stage-ahead warm (dispatch engine): upload the Row
        operands a QUEUED query will touch while the current wave
        computes, so staging overlaps kernel execution. Best-effort —
        every error is swallowed, staging is idempotent, and the real
        execution re-stages whatever this missed."""
        if self.device_policy == "never" or self._cpu_forced():
            return
        try:
            idx = self.holder.index(index)
            if idx is None:
                return
            if shards is None:
                shards = list(range(idx.max_shard() + 1))
            for call in query.calls:
                self._warm_call(index, call, shards)
        except BaseException:
            pass

    def _warm_call(self, index: str, c: Call, shards) -> None:
        if c.name == "Row":
            field_name = c.field_arg()
            row_id, ok = c.uint_arg(field_name)
            if not ok:
                return
            for shard in shards:
                frag = self.holder.fragment(
                    index, field_name, VIEW_STANDARD, shard
                )
                if frag is not None:
                    self.stager.row(frag, row_id)
            return
        for child in c.children:
            self._warm_call(index, child, shards)

    def close(self, drain: float = 5.0) -> None:
        """Drain the dispatch engine, then the read pool (called from
        Server.close). New read-pool checkouts are refused from here on
        (those executions run their calls serially inline); in-flight
        ``pool.map`` users get up to ``drain`` seconds to finish before
        the pool shuts down under them."""
        if self.dispatch_engine is not None:
            self.dispatch_engine.close(drain=drain)
        t0 = time.monotonic()
        with self._read_pool_cv:
            self._read_pool_closing = True
            while (
                self._read_pool_users > 0
                and time.monotonic() - t0 < drain
            ):
                self._read_pool_cv.wait(timeout=0.05)
            pool, self._read_pool = self._read_pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        if self.health is not None:
            self.health.close()


# Lazy-scoring chunk schedule, shared by both providers: a small head
# (the walk usually prunes inside it), then the ladder's large chunks
# for a walk that knows no end yet. The sizes are not measured on the
# current machine. They are a cap, not the schedule: where every
# shard's threshold is fixed the cached counts say where the walk
# ends, and the next chunk stops there (_walk_ends, _score_next). With
# 256 hot rows a shard over a one-bit tail the second chunk holds the
# 128 hot rows the head left, not 4096 candidates (PERF.md, PR 32).
FIRST_CHUNK = 128
SCORE_CHUNK = 4096
MAX_CHUNK = 16384


def _chunk_size(pos: int) -> int:
    """The ladder: the most candidates a shard the chunk at scored-prefix
    position ``pos`` may hold. A small head (most walks prune inside it
    on skewed data), then geometric growth SCORE_CHUNK → MAX_CHUNK so a
    deep/full walk over the reference's 50k-entry ranked cache pays ~6
    dispatches instead of ~13. A walk whose thresholds are fixed ends
    its chunk sooner (_score_next's ``need``), at a power of two from
    FIRST_CHUNK up: sizes stay pow2 (bounded XLA compile cache), and a
    chunk is a function of its position and where the cached counts
    end the walk, so the stager's content-derived staging keys repeat
    with the request and the HBM cache keeps hitting."""
    if pos == 0:
        return FIRST_CHUNK
    boundary, size = FIRST_CHUNK, SCORE_CHUNK
    while boundary + size <= pos:
        boundary += size
        if size < MAX_CHUNK:
            size *= 2
    return size


def _bounded_chunk_size(pos: int, need: Optional[int]) -> int:
    """Size of the chunk at ``pos`` of a walk that reads no candidate
    at or past ``need`` (None: no end known): the least power of two
    that reaches it, not under FIRST_CHUNK, not over the ladder's."""
    ladder = _chunk_size(pos)
    if need is None:
        return ladder
    return min(ladder, max(FIRST_CHUNK, _next_pow2(need - pos)))


def _chunk_ids(pairs, lo: int, hi: int) -> tuple[int, ...]:
    """Candidate ids for pairs[lo:hi]. Rankings snapshots memoize their
    slice tuples on themselves (core.cache.Rankings), so repeated
    queries don't rebuild multi-thousand-element tuples per shard per
    query — and the memo can never disagree with the pairs list the
    walk iterates, even across a concurrent cache recalculate."""
    chunk = getattr(pairs, "chunk_ids", None)
    if chunk is not None:
        return chunk(lo, hi)
    return tuple(p[0] for p in pairs[lo:hi])


def _chunk_arrays(pairs, lo: int, hi: int):
    """(ids int64[L], counts int64[L]) for pairs[lo:hi]; memoized on
    Rankings snapshots, built fresh for plain lists (small row_ids
    walks)."""
    chunk = getattr(pairs, "chunk_arrays", None)
    if chunk is not None:
        return chunk(lo, hi)
    return cache_pairs_arrays(pairs[lo:hi])


def _chunk_index(pairs, lo: int, hi: int) -> dict[int, int]:
    """{id: position in pairs[lo:hi]}; memoized on Rankings snapshots."""
    chunk = getattr(pairs, "chunk_index", None)
    if chunk is not None:
        return chunk(lo, hi)
    return {p[0]: j for j, p in enumerate(pairs[lo:hi])}


def _chunk_blocks(pairs, lo: int, hi: int, frag) -> tuple[int, bool]:
    """(nonempty container blocks of pairs[lo:hi]'s rows in ``frag``,
    whether they were counted now): kept on Rankings snapshots with the
    fragment generation they were counted at, counted afresh for plain
    lists."""
    chunk = getattr(pairs, "chunk_blocks", None)
    if chunk is not None:
        return chunk(lo, hi, frag)
    return frag.sparse_block_count([p[0] for p in pairs[lo:hi]]), True


class _ChunkedLazyScores:
    """Shared chunk-walk skeleton for cross-shard lazy TopN scoring:
    the next pow2 chunk of every shard's candidate list is staged and
    scored the first time any shard's ranked walk reads past the
    scored prefix. Chunk staging keys are content-derived (the
    per-shard candidate id tuples), so repeated queries reuse the
    HBM-resident blocks.

    The FIRST chunk is small: on skewed data the walk prunes within the
    hot head (reference threshold break, fragment.go:969), so staging
    4096 candidates x S shards up front wastes HBM upload — at the 1B
    scale that is the difference between ~0.5 GB and ~2.3 GB of cold
    staging. A later chunk ends where the caller's walk says it reads
    no further (``need``: the vectorized walk knows it from the fixed
    thresholds and the cached counts), and grows along the ladder
    (_chunk_size) to amortize dispatch count where no end is known.

    ``srcs`` may be a thunk: it resolves only when a chunk actually
    dispatches, so a pass 2 fully covered by the cross-pass carry pays
    no device work at all (not even re-folding a compound source).
    Subclasses define _stage (host packing, memoized by the stager)
    and _score (kernel dispatch returning a (shard_i, j) -> int
    accessor)."""

    def __init__(self, ex, frags, pairs_by_shard, srcs, shards=None, carry=None) -> None:
        self._ex = ex
        self._frags = frags
        self._pairs = pairs_by_shard
        self._srcs = srcs
        self._scores: list[dict[int, int]] = [{} for _ in frags]
        self._pos = 0  # scored prefix length (per shard)
        self._max_len = max((len(p) for p in pairs_by_shard), default=0)
        # per-chunk score matrices [S, size] + their candidate ids; the
        # vectorized cross-shard walk consumes these directly, and the
        # per-id dict fanout (only needed by the scalar fallback walk)
        # happens lazily in _fanout()
        self._mats: list[np.ndarray] = []
        self._chunk_meta: list[tuple] = []  # (lo, size, ids_by_shard)
        self._fanned = 0
        self._smat = None
        self._mat_cache = None
        # cross-pass score carry: TopN pass 2 re-reads counts pass 1
        # already computed (same source bitmap, same fragment snapshot —
        # both constant within one _execute_topn) — seeding from the
        # carry makes a per-id pass 2 dispatch only for (shard, id)
        # pairs no pass-1 chunk covered
        self._shards = list(shards) if shards is not None else list(range(len(frags)))
        self._prefetching = False  # one prefetch in flight at a time
        if carry:
            for i, s in enumerate(self._shards):
                seed = carry.seed(s, [rid for rid, _ in pairs_by_shard[i]])
                if seed:
                    self._scores[i].update(seed)
        if carry is not None:
            carry.attach(self)

    def _stage(self, ids_by_shard, size: int, peek: bool = False):
        """The staged bundle for a chunk; ``peek``: whether the stager
        holds (or is building) it, staging nothing."""
        raise NotImplementedError

    def _score(self, staged, size: int):
        raise NotImplementedError

    def _resolved_srcs(self):
        if callable(self._srcs):
            self._srcs = self._srcs()
        return self._srcs

    def _score_next(self, need: Optional[int] = None) -> None:
        """Stage and score the next chunk. ``need``: no shard's walk
        reads a candidate at or past this position (_walk_ends); a
        caller that knows no such bound leaves it out. A chunk that
        reaches ``need`` is the walk's last, so the one after it is not
        staged ahead."""
        lo = self._pos
        size = _bounded_chunk_size(lo, need)
        ends_walk = need is not None and need <= lo + size
        metrics.count(
            metrics.TOPN_CHUNKS,
            how="head" if lo == 0 else "bounded" if size < _chunk_size(lo) else "ladder",
        )
        hi = lo + size
        self._pos = hi
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            ids_by_shard = tuple(_chunk_ids(ps, lo, hi) for ps in self._pairs)
        staged = self._stage(ids_by_shard, size)
        # overlap: while this chunk's kernel runs + fetches, pre-stage
        # the NEXT chunk on a side thread (the stager memoizes by
        # content key, so the walk's next _score_next finds it hot).
        # Deep walks thus pipeline host packing with device compute
        # instead of alternating them serially. NOT from the head
        # chunk (lo == 0): most walks prune inside it on skewed data —
        # eagerly staging the ladder's chunk behind it would
        # re-introduce exactly the cold-staging cost the small head
        # chunk was measured to avoid (class docstring). The decision
        # runs here, on the request's thread, before this chunk's
        # kernel is launched: it has to cost what the walk reads (one
        # number a shard), not what the next chunk holds (_prefetch).
        if lo > 0 and hi < self._max_len and not ends_walk:
            with trace.leg(trace.WF_TOPN_CANDIDATES):
                self._prefetch(hi, need)
        if staged is None:  # no shard contributed blocks — all score 0
            mat = np.zeros((len(self._frags), size), dtype=np.int32)
        else:
            mat = self._score(staged, size)
        self._mats.append(mat)
        self._chunk_meta.append((lo, size, ids_by_shard))

    def _fanout(self) -> None:
        """Populate the per-shard id->score dicts from chunk matrices
        (scalar-walk fallback path only; zip over .tolist() is C-speed)."""
        while self._fanned < len(self._mats):
            _, _, ids_by_shard = self._chunk_meta[self._fanned]
            mat = self._mats[self._fanned]
            for i, ids in enumerate(ids_by_shard):
                if ids:
                    self._scores[i].update(zip(ids, mat[i].tolist()))
            self._fanned += 1

    def scored(self):
        """What the cross-pass carry reads (_ScoreCarry.attach): scores
        i32[S, P] over the scored prefix, memoized per chunk count."""
        k = len(self._mats)
        if self._smat is None or self._smat[0] != k:
            smat = None
            if k:
                smat = np.concatenate(self._mats, axis=1) if k > 1 else self._mats[0]
            self._smat = (k, smat)
        return self._shards, self._frags, self._pairs, self._smat[1]

    def matrices(self):
        """(scores i32[S, P], ids i64[S, P], counts i64[S, P],
        valid bool[S, P]) over the scored prefix; memoized per chunk
        count. Padding columns carry id -1 / count 0 / score 0."""
        k = len(self._mats)
        if self._mat_cache is not None and self._mat_cache[0] == k:
            return self._mat_cache[1]
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            S = len(self._frags)
            smat = self.scored()[3]
            P = smat.shape[1]
            idm = np.full((S, P), -1, dtype=np.int64)
            cntm = np.zeros((S, P), dtype=np.int64)
            col = 0
            for (lo, size, ids_by_shard), m in zip(self._chunk_meta, self._mats):
                for i, ids in enumerate(ids_by_shard):
                    L = len(ids)
                    if L:
                        a_ids, a_cnts = _chunk_arrays(self._pairs[i], lo, lo + L)
                        idm[i, col : col + L] = a_ids
                        cntm[i, col : col + L] = a_cnts
                col += size
            out = (smat, idm, cntm, idm >= 0)
        self._mat_cache = (k, out)
        return out

    def _bundle_blocks(self, blocks_by_shard: list[int]) -> int:
        """Container blocks _stage would put on the device for a chunk
        whose shards hold ``blocks_by_shard`` nonempty blocks."""
        raise NotImplementedError

    def _prefetch(self, lo: int, need: Optional[int] = None) -> None:
        """Stage the chunk at ``lo`` ahead on a side thread, where that
        pushes nothing out; ``need`` ends it where it will end the
        walk's own (_score_next), or another bundle than the one the
        walk asks for is built. Advisory means it may not evict: staging
        ahead a chunk that does not fit would push out the chunks this
        walk is scoring, and every later query would stage all of them
        again. Every request of a deep walk asks, so the question is
        answered from what the rankings snapshots keep: a lower bound
        first (a ranked candidate has a bit, so a block; has_room is
        monotone), then the chunk's block counts, which are counted
        once per snapshot and fragment generation. A count stale by a
        racing write can mis-stage, never mis-answer."""
        if self._prefetching:
            return
        size = _bounded_chunk_size(lo, need)
        hi = lo + size
        block_bytes = ops.packed.CONTAINER_WORDS * 4
        has_room = self._ex.stager.has_room
        lens = [
            max(min(len(ps), hi) - lo, 0) if f is not None else 0
            for f, ps in zip(self._frags, self._pairs)
        ]
        if not has_room(self._bundle_blocks(lens) * block_bytes):
            metrics.count(metrics.TOPN_PREFETCH_DECISIONS, how="bound")
            return
        counts = [
            _chunk_blocks(ps, lo, hi, f) if n else (0, False)
            for f, ps, n in zip(self._frags, self._pairs, lens)
        ]
        metrics.count(
            metrics.TOPN_PREFETCH_DECISIONS,
            how="counted" if any(c[1] for c in counts) else "memo",
        )
        blocks = self._bundle_blocks([c[0] for c in counts])
        if not has_room(blocks * block_bytes):
            return
        ids_by_shard = tuple(_chunk_ids(ps, lo, hi) for ps in self._pairs)
        if self._stage(ids_by_shard, size, peek=True):
            return
        self._prefetching = True

        def warm():
            try:
                self._stage(ids_by_shard, size)
            except Exception:
                pass  # purely advisory; the real call surfaces errors
            finally:
                self._prefetching = False

        metrics.count(metrics.TOPN_PREFETCH_STARTS)
        threading.Thread(
            target=warm, name="stage-prefetch", daemon=True
        ).start()

    def view(self, shard_index: int) -> "_ShardScoreView":
        return _ShardScoreView(self, shard_index)


class _StackedLazyScores(_ChunkedLazyScores):
    """Single-device form: each chunk is one merged block-sparse
    sparse_intersection_counts_stacked dispatch covering all shards
    (global segment ids), coalesced with concurrent queries through
    the BatchedScorer."""

    def _stage(self, ids_by_shard, size: int, peek: bool = False):
        return self._ex.stager.sparse_rows_stacked(
            self._frags, ids_by_shard, size, peek=peek
        )

    def _bundle_blocks(self, blocks_by_shard: list[int]) -> int:
        return _next_pow2(max(sum(blocks_by_shard), 1))

    def _score(self, staged, size: int):
        blocks, brow, bslot, bshard, num_rows = staged
        # route through the coalescing scorer: key on the staged arrays'
        # identity (same live objects ⇔ same snapshot — the BatchedScorer
        # contract), so concurrent queries over this chunk share one
        # kernel launch and one fetch
        scores = self._ex.stacked_scorer.score(
            (id(blocks), id(brow)),
            (blocks, brow, bslot, bshard, num_rows),
            self._resolved_srcs(),
        )
        return _fetch(scores)[: len(self._frags) * size].reshape(
            len(self._frags), size
        )


class _ShardScoreView:
    __slots__ = ("_p", "_i")

    def __init__(self, provider: _StackedLazyScores, i: int) -> None:
        self._p = provider
        self._i = i

    def __getitem__(self, row_id: int) -> int:
        p = self._p
        sc = p._scores[self._i]
        if row_id in sc:
            return sc[row_id]
        p._fanout()
        while row_id not in sc and p._pos < p._max_len:
            p._score_next()
            p._fanout()
        return sc[row_id]


class _SpmdLazyScores(_ChunkedLazyScores):
    """Mesh form: each chunk is ONE shard_map program
    (topn_scores_sparse_spmd) over block-sparse candidate stacks
    sharded across the mesh. The eager predecessor staged EVERY
    ranked-cache candidate densely (k × S × 128 KB — tens of GB at a
    50k-candidate cache); here a skewed walk that prunes in the hot
    head pays only the head chunk, and bytes staged scale with set
    containers (reference threshold walk semantics preserved by
    _ranked_walk; fragment.go:870-1002)."""

    def _stage(self, ids_by_shard, size: int, peek: bool = False):
        return self._ex.stager.sparse_rows_stack(
            self._frags, ids_by_shard, size, peek=peek
        )

    def _bundle_blocks(self, blocks_by_shard: list[int]) -> int:
        return len(blocks_by_shard) * _next_pow2(max(max(blocks_by_shard), 1))

    def _score(self, staged, size: int):
        blocks, brow, bslot = staged
        dev = self._ex._spmd_kernel("topn_scores_sparse", size)(
            self._resolved_srcs(), blocks, brow, bslot
        )
        # the kernel's [S, k] is the chunk's shape already (frags is the
        # mesh-padded plan, k the chunk size), gathered to every device:
        # no trim, an eager launch over the whole mesh, and the copy
        # reads one replica
        return _mesh_fetch(dev)


class _LazyScores:
    """Chunked on-demand candidate scoring for the device TopN walk.

    The walk consumes candidates in cached-count order and breaks as
    soon as counts fall below the running threshold (reference
    fragment.go:960-1002) — on skewed data it touches only the hot
    head. Scoring every cache candidate eagerly therefore wastes both
    HBM (50k candidates × 128 KB dense) and kernel time at the 1B-row
    scale. This provider scores pow2-sized chunks of the candidate
    list the first time the walk reads past them:

      * chunk staging keys depend only on (fragment state, chunk ids),
        so repeated queries hit the stager's HBM cache;
      * each chunk independently picks block-sparse vs dense staging by
        container occupancy (sparse wins below half-full);
      * dense chunks still coalesce through the BatchedScorer;
      * the first chunk is small (the walk usually prunes within the
        hot head — see _StackedLazyScores), later ones grow.
    """

    def __init__(self, ex, frag, pairs, src_words, shard=0, carry=None) -> None:
        self._ex = ex
        self._frag = frag
        self._pairs = pairs
        self._src = src_words
        self._scores: dict[int, int] = {}
        self._next = 0
        # cross-pass carry, same contract as _StackedLazyScores: pass 2
        # reads counts pass 1 computed for this (shard, src) pair
        self._shard = shard
        self._chunks: list[np.ndarray] = []
        if carry:
            self._scores.update(carry.seed(shard, [rid for rid, _ in pairs]))
        if carry is not None:
            carry.attach(self)

    def scored(self):
        """One shard's _ChunkedLazyScores.scored(): a chunk's scores end
        with its ids, and only the list's last chunk is short."""
        scores = np.concatenate(self._chunks)[None, :] if self._chunks else None
        return (self._shard,), (self._frag,), (self._pairs,), scores

    def _score_chunk(self) -> None:
        # ids materialise per chunk, never as one huge tuple — on a 50k-
        # candidate cache only the chunks the walk reaches pay anything
        size = _chunk_size(self._next)
        frag = self._frag
        lo = self._next
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            ids = _chunk_ids(self._pairs, lo, lo + size)
            occupied, _ = _chunk_blocks(self._pairs, lo, lo + size, frag)
        self._next += size
        if occupied * 2 < len(ids) * (SHARD_WIDTH >> 16):
            blocks, brow, bslot, num_rows = self._ex.stager.sparse_rows(frag, ids)
            dev = ops.sparse_intersection_counts(
                self._src, blocks, brow, bslot, num_rows
            )
            # trim on device: num_rows is pow2-padded, so fetching the
            # full vector and slicing on host transfers up to 2x the
            # real candidate scores
            scores = _fetch(dev[: len(ids)])
        else:
            # pow2-padded rows bound recompiles; trailing zero rows fall
            # off the zip below. Key on the staged array identity (not
            # frag.generation, which a concurrent import may bump
            # between staging and here): same live array object ⇔ same
            # snapshot, so coalesced peers can never mix matrices.
            mat = self._ex.stager.rows(frag, ids, pad_pow2=True)
            scores = self._ex.scorer.score(
                (id(frag), id(mat)), mat, self._src, trim=len(ids)
            )
        self._scores.update(zip(ids, (int(s) for s in scores)))
        self._chunks.append(scores)

    def __getitem__(self, row_id: int) -> int:
        while row_id not in self._scores and self._next < len(self._pairs):
            self._score_chunk()
        return self._scores[row_id]


def _candidate_pairs(frags, shards, row_ids, carry) -> list:
    """Each shard's candidate list for a cross-shard TopN pass; none for
    a shard without a fragment or one pass 2 is already answered for."""
    answered = carry.answered if carry is not None else ()
    return [
        f._top_bitmap_pairs(row_ids) if f is not None and s not in answered else []
        for f, s in zip(frags, shards)
    ]


def _plain_threshold_walk(c: Call) -> bool:
    """Does TopN call ``c`` pick by cached count and score against the
    threshold alone? A tanimoto or attribute filter looks at each
    (shard, id) again in pass 2 (_ranked_walk)."""
    tanimoto, _ = c.uint_arg("tanimotoThreshold")
    attr_name, _ = c.string_arg("attrName")
    return not tanimoto and not (attr_name and c.args.get("attrValues"))


def _vectorized_topn_walk(pairs_by_shard, provider, opt_: TopOptions):
    """All shards' ranked walks in one numpy pass, or None when the
    scalar fallback is required (tanimoto / attr filters).

    Exactness argument (mirrors _ranked_walk below, reference
    fragment.go:870-1002): the scalar walk's heap never pops, so once
    the first n qualifying candidates are pushed the heap minimum — the
    walk's threshold T — is FIXED: later pushes require count >= T.
    The walk therefore reduces to closed form per shard:
      phase 1: the first n candidates in cache order with
               cached>=min_threshold and score>=min_threshold;
               T = min of their scores;
      break:   the first later candidate with cached<T ends the walk;
      phase 2: candidates before the break with score >= T.
    Shards with fewer than n qualifying candidates scan their whole
    pairs list (the scalar loop never leaves phase 1). The cross-shard
    merge (pairs_add + final sort_pairs) is order-insensitive, so the
    picked SETS being identical makes the result bit-identical.

    The break needs no score: cached counts are in the ranked lists.
    So where the scored prefix holds no shard's break yet, the lists
    say how far each walk can still read (_walk_ends): a shard whose
    end is the prefix's is done without its break candidate ever being
    scored, and the furthest end over the shards bounds the next chunk
    (_score_next)."""
    if opt_.tanimoto_threshold > 0:
        return None
    if opt_.filter_name and opt_.filter_values:
        return None
    n = 0 if opt_.row_ids else opt_.n
    mth = max(int(opt_.min_threshold), 1)
    lengths = np.array([len(p) for p in pairs_by_shard], dtype=np.int64)
    max_len = int(lengths.max()) if lengths.size else 0
    if max_len == 0:
        return []

    if n == 0:
        # exhaustive mode (pass 2 / n=0): every eligible candidate is
        # scored; pairs lists here are the explicit id set — small —
        # and usually fully covered by the cross-pass carry, so the
        # dict lookups below dispatch nothing
        ids_out: list[int] = []
        cnts_out: list[int] = []
        for i, pairs in enumerate(pairs_by_shard):
            if not pairs:
                continue
            view = provider.view(i)
            for rid, cnt in pairs:
                if cnt < mth:
                    continue
                sc = view[rid]
                if sc >= mth:
                    ids_out.append(rid)
                    cnts_out.append(sc)
        return _merge_picked(
            np.asarray(ids_out, dtype=np.int64),
            np.asarray(cnts_out, dtype=np.int64),
        )

    big = np.int64(1) << np.int64(62)
    while True:
        if provider._pos == 0:
            provider._score_next()
        smat, idm, cntm, vmask = provider.matrices()
        P = smat.shape[1]
        elig = vmask & (cntm >= mth)
        ok = elig & (smat >= mth)
        cum = np.cumsum(ok, axis=1)
        total_ok = cum[:, -1]
        has_n = total_ok >= n
        sel = ok & (cum <= n)
        T = np.where(has_n, np.where(sel, smat, big).min(axis=1), big)
        nth_pos = np.where(has_n, np.argmax(cum >= n, axis=1), P)
        colr = np.arange(P, dtype=np.int64)[None, :]
        after = colr > nth_pos[:, None]
        brk_mask = elig & after & (cntm < T[:, None])
        has_brk = brk_mask.any(axis=1)
        exhausted = P >= lengths
        done = (has_n & has_brk) | exhausted
        need = None
        if not done.all():
            ends = _walk_ends(pairs_by_shard, P, done, has_n, T, mth)
            done |= ends <= P
            need = int(ends.max())
        if done.all():
            brk = np.where(has_brk, np.argmax(brk_mask, axis=1), P)
            phase2 = (
                elig
                & after
                & (colr < brk[:, None])
                & (smat >= T[:, None])
            )
            picked = np.where(has_n[:, None], sel | phase2, ok)
            s_idx, c_idx = np.nonzero(picked)
            return _merge_picked(
                idm[s_idx, c_idx], smat[s_idx, c_idx].astype(np.int64)
            )
        if provider._pos >= max_len:
            # unreachable (P == provider._pos >= every shard's length
            # implies exhausted.all()); bail to the scalar walk rather
            # than risk looping
            return None
        provider._score_next(need)


def _walk_ends(pairs_by_shard, pos: int, done, has_n, T, mth: int) -> np.ndarray:
    """Per shard, the position at and past which its walk reads (scores)
    no candidate, whatever the candidates from ``pos`` on score:
    int64[S], never under ``pos``. A shard that is ``done`` reads none.
    One whose threshold is fixed (``has_n``) reads up to the first
    candidate whose cached count is under it: its break, or, under the
    minimum too, the first of a tail it skips to the list's end. One
    that has not pushed n candidates yet reads every candidate that
    reaches the minimum: nothing tighter is known of it. Cached counts
    fall along a ranked list (cache.sort_pairs, RankCache.recalculate),
    so one search a shard finds the place."""
    ends = np.full(len(pairs_by_shard), pos, dtype=np.int64)
    for i, (pairs, over, fixed, t) in enumerate(
        zip(pairs_by_shard, done.tolist(), has_n.tolist(), T.tolist())
    ):
        if not over and len(pairs) > pos:
            # descending by count: the first position whose count is
            # under the threshold is where the negated counts pass its
            # negation
            ends[i] = bisect.bisect_right(
                pairs, -(t if fixed else mth), pos, len(pairs), key=_neg_count
            )
    return ends


def _neg_count(pair) -> int:
    return -pair[1]


def _merge_picked(ids: np.ndarray, counts: np.ndarray) -> list[tuple[int, int]]:
    """Cross-shard merge: sum counts per id (pairs_add semantics; final
    ordering is applied by the caller's sort_pairs)."""
    if ids.size == 0:
        return []
    uids, inv = np.unique(ids, return_inverse=True)
    sums = np.bincount(inv, weights=counts.astype(np.float64))
    return list(zip(uids.tolist(), sums.astype(np.int64).tolist()))


def _ranked_walk(frag, opt_: TopOptions, pairs, score_by_id) -> list[tuple[int, int]]:
    """Replay fragment.top's ranked walk (reference fragment.go:870-1002)
    with precomputed intersection counts — identical pruning, threshold,
    tanimoto, and attr-filter behavior, so device scoring stays
    bit-identical to the CPU path."""
    import heapq
    import math

    n = 0 if opt_.row_ids else opt_.n
    filters = set(opt_.filter_values) if (opt_.filter_name and opt_.filter_values) else None
    tanimoto_threshold = 0
    min_tanimoto = max_tanimoto = 0.0
    src_count = 0
    if opt_.tanimoto_threshold > 0:
        tanimoto_threshold = opt_.tanimoto_threshold
        src_count = opt_.src.count()
        min_tanimoto = float(src_count * tanimoto_threshold) / 100
        max_tanimoto = float(src_count * 100) / float(tanimoto_threshold)

    results: list[tuple[int, int]] = []
    for row_id, cnt in pairs:
        if cnt <= 0:
            continue
        if tanimoto_threshold > 0:
            if float(cnt) <= min_tanimoto or float(cnt) >= max_tanimoto:
                continue
        elif cnt < opt_.min_threshold:
            continue
        if filters is not None:
            attr = frag.row_attr_store.attrs(row_id) if frag.row_attr_store else None
            if not attr:
                continue
            value = attr.get(opt_.filter_name)
            if value is None or value not in filters:
                continue
        if n == 0 or len(results) < n:
            count = score_by_id[row_id]
            if count == 0:
                continue
            if tanimoto_threshold > 0:
                t = math.ceil(float(count * 100) / float(cnt + src_count - count))
                if t <= float(tanimoto_threshold):
                    continue
            elif count < opt_.min_threshold:
                continue
            heapq.heappush(results, (count, row_id))
            continue
        threshold = results[0][0]
        if threshold < opt_.min_threshold or cnt < threshold:
            break
        count = score_by_id[row_id]
        if count < threshold:
            continue
        heapq.heappush(results, (count, row_id))

    out = []
    while results:
        count, row_id = heapq.heappop(results)
        out.append((row_id, count))
    out.reverse()
    return out


def _row_from_device(words, shard: int) -> Row:
    w32 = _fetch(words)
    with trace.leg(trace.WF_TRANSFER_DECODE):
        w64 = np.ascontiguousarray(w32).view("<u8")
        seg = Bitmap.from_words_range(w64, start=shard * SHARD_WIDTH)
    return Row.from_segment(shard, seg)


def _pairs_result(pairs: list[tuple[int, int]]) -> list[dict]:
    """JSON-shaped Pair list (reference Pair, cache.go:360)."""
    return [{"id": p[0], "count": p[1]} for p in pairs]
