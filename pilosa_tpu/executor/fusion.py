"""Whole-query device fusion — one lowered program per multi-call read.

The per-call executor crosses the host↔device boundary once per call:
each Count/Sum/TopN in a multi-call query — and every query a dispatch
wave coalesces into one combined Query — launches its own kernel and
fetches its own result.

This module collapses that to ONE jitted program per query: every
fusable call lowers to a unit (Count → popcount-of-tree, Sum → BSI
plane counts, TopN → head-chunk candidate scoring), the units trace
into a single XLA program keyed by the tuple of unit descriptors (the
canonical plan/canon signatures of the lowered trees), and one fenced
launch returns only the final scalars / count vectors / score heads.
Intermediates — folded bitmaps, BSI planes, candidate blocks — never
leave HBM. Because the dispatch engine's wave combiner already routes a
wave's items through ``Executor._execute`` as one multi-call Query,
wave fusion falls out of the same hook: a wave of N coalesced queries
costs one launch, with per-item results split positionally on host
from the per-call outputs.

Determinism contract (PR 5/6): gang, cluster, remote, and serial
execution bypass fusion exactly as they bypass the dispatch engine —
the per-call paths those legs rely on are untouched. Bit-identity:
every unit reuses the SAME kernels and host finishers as the per-call
device path (the TopN head matrix is injected as the walk's first
chunk, then the existing ranked walk runs unchanged), so fused results
are bit-identical to both the unfused device path and the CPU oracle.

Calls that cannot lower (Min/Max, bitmap-valued top-level calls,
tanimoto TopN, non-deviceable subtrees) stay on the classic per-call
path; the fuser serves the rest and ``_execute`` merges positionally.
Any failure inside the fuser degrades to the classic path — reads are
pure, so re-execution is always safe.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Optional

import numpy as np

from pilosa_tpu.utils import chaos, metrics, trace

# Deliberately a module-load import (executor.py only imports this
# module lazily, inside Executor.__init__, so there is no cycle): the
# fuser reuses the executor's lowering helpers and kernels verbatim —
# that shared code is the bit-identity argument.
from pilosa_tpu.executor import analytics, executor as _ex
from pilosa_tpu.executor.executor import (
    FIRST_CHUNK,
    ValCount,
    _chunk_ids,
    _fetch,
    _timed_kernel,
)
from pilosa_tpu import ops
from pilosa_tpu.core import VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD
from pilosa_tpu.core.fragment import FragmentQuarantinedError

# call names the fuser can lower; everything else is residual
_ANALYTIC = analytics.ANALYTIC_CALLS
_FUSABLE = ("Count", "Sum", "TopN") + _ANALYTIC


class _Unit:
    """One lowered call: a static descriptor (part of the program key),
    the device input arrays consumed at the descriptor's flat offset,
    and a host finisher mapping the fetched output to the call result.
    ``extra_bytes`` charges transients the input sum cannot see (the
    GroupBy [K, S·W] cross-product stack) to the HBM admission check."""

    __slots__ = ("call_index", "desc", "inputs", "finish", "extra_bytes")

    def __init__(
        self, call_index: int, desc, inputs, finish, extra_bytes: int = 0
    ) -> None:
        self.call_index = call_index
        self.desc = desc
        self.inputs = inputs
        self.finish = finish
        self.extra_bytes = extra_bytes


class QueryFuser:
    """Lowers the fusable calls of one read query into a single jitted
    program. Owned by an Executor; invoked from ``_execute`` after the
    CSE rewrite, before the per-call fan-out."""

    def __init__(self, ex, max_calls: int = 64) -> None:
        self.ex = ex
        self.max_calls = int(max_calls)
        # program cache: (unit descriptors, input shapes) -> timed jit.
        # Bounded by distinct fused query shapes, like _tree_jits.
        self._programs: dict = {}
        self._mu = threading.Lock()
        # telemetry (monotonic counters, read by stats())
        self.fused_launches = 0
        self.fused_calls = 0
        self.cache_served = 0
        self.bytes_returned = 0
        self.admission_splits = 0
        self.bypasses: dict[str, int] = {}

    # -- eligibility ---------------------------------------------------------

    def _bypass(self, reason: str) -> None:
        self.bypasses[reason] = self.bypasses.get(reason, 0) + 1
        metrics.count(metrics.FUSION_BYPASSES, reason=reason)

    def try_execute(
        self, index: str, calls, shards, opt
    ) -> Optional[dict[int, Any]]:
        """Results for the call positions this fuser served (fused
        launch or plan-cache hit), or None/{} when everything should
        take the classic path. Never raises: reads are pure, so any
        internal failure degrades to per-call re-execution."""
        ex = self.ex
        if ex.gang is not None or ex.cluster is not None:
            self._bypass("topology")
            return None
        if ex.mesh is not None:
            # the SPMD path fuses per call via shard_map; whole-query
            # fusion across a mesh is future work
            self._bypass("mesh")
            return None
        if opt.remote or opt.serial:
            self._bypass("opt")
            return None
        if ex.device_policy == "never" or ex._cpu_forced():
            self._bypass("cpu")
            return None
        if not shards:
            self._bypass("no_shards")
            return None
        if len(calls) > self.max_calls:
            self._bypass("too_many_calls")
            return None
        candidates = [
            (i, c) for i, c in enumerate(calls) if c.name in _FUSABLE
        ]
        if len(candidates) < 2 and not any(
            c.name in _ANALYTIC for _, c in candidates
        ):
            # an analytic call is itself a K-way panel — one fused
            # launch replaces K point queries, so it fuses alone
            self._bypass("too_few_calls")
            return None
        if ex.device_policy != "always":
            # auto crossover on the AGGREGATE: the whole point of fusion
            # is that N calls share one dispatch, so the per-call
            # container estimate sums across the query before comparing
            # against the device crossover
            try:
                total = sum(
                    ex._touched_containers(index, c, s)
                    for _, c in candidates
                    for s in shards
                )
            except Exception:
                total = 0
            if total < ex.auto_min_containers:
                self._bypass("auto_policy")
                return None
        try:
            return self._run(index, calls, candidates, shards, opt)
        except Exception:
            # includes DeviceDown from the health guard: the gate is now
            # tripped, so the classic path re-runs these reads on CPU
            self._bypass("error")
            return {}

    # -- probe + lower + launch ---------------------------------------------

    def _run(self, index, calls, candidates, shards, opt) -> dict[int, Any]:
        ex = self.ex
        pc = ex.plan_cache if opt.cache else None
        out: dict[int, Any] = {}
        # plan-cache probe per candidate; capture (key, genvec, epoch)
        # BEFORE any build so fused inserts keep the over-invalidation
        # race direction (plan/cache.py module docstring)
        cacheinfo: dict[int, tuple] = {}
        lower = []
        for i, c in candidates:
            if pc is not None and ex._local_batchable(opt):
                from pilosa_tpu.plan import planner

                keyinfo = planner.call_cache_key(ex, index, c, shards, opt)
                if keyinfo is not None:
                    key, gvfn = keyinfo
                    genvec = gvfn()
                    hit = pc.get(key, gvfn)
                    if hit is not None:
                        out[i] = hit
                        self.cache_served += 1
                        continue
                    cacheinfo[i] = (key, genvec, pc.epoch)
            lower.append((i, c))
        if not lower:
            return out

        def fused():
            return self._lower_and_launch(index, lower, shards, opt)

        if ex.health is not None:
            served = ex.health.guard(fused)
        else:
            served = fused()
        bycall = dict(lower)
        for i, result, cost in served:
            out[i] = result
            # calls served by the fused launch never enter _map_reduce;
            # account their per-shard read legs here (cache hits above
            # short-circuit before the classic path records, so they
            # stay unrecorded on both routes). Analytic calls attribute
            # to the fields they actually read (dimension rows +
            # aggregate planes), not the first non-underscore arg key.
            if bycall[i].name in _ANALYTIC:
                ex._analytics_heat_legs(
                    index, analytics.heat_fields(bycall[i]), shards
                )
            else:
                ex._heat_read_legs(index, bycall[i], shards)
            info = cacheinfo.get(i)
            if info is not None and pc is not None:
                key, genvec, epoch0 = info
                pc.put(key, genvec, result, cost=cost, epoch0=epoch0)
        return out

    def _lower_and_launch(self, index, lower, shards, opt) -> list[tuple]:
        ex = self.ex
        units: list[_Unit] = []
        bycall = dict(lower)
        for i, c in lower:
            try:
                if c.name == "Count":
                    u = self._lower_count(index, i, c, shards)
                elif c.name == "Sum":
                    u = self._lower_sum(index, i, c, shards)
                elif c.name == "GroupBy":
                    with trace.leg(trace.WF_GROUPBY_LOWER):
                        u = self._lower_groupby(index, i, c, shards)
                elif c.name == "Distinct":
                    u = self._lower_distinct(index, i, c, shards)
                elif c.name == "Percentile":
                    u = self._lower_percentile(index, i, c, shards)
                else:
                    u = self._lower_topn(index, i, c, shards, opt)
            except FragmentQuarantinedError:
                # quarantined fragment staged into the batch: degrade
                # THIS call to the classic path (which surfaces the
                # clean 503) instead of poisoning the fused launch
                if c.name in _ANALYTIC:
                    metrics.count(metrics.ANALYTICS_DEGRADED_LEGS, call=c.name)
                u = None
            except Exception:
                # malformed args / missing fields / _NotDeviceable: the
                # classic path owns producing the (identical) error
                u = None
            if u is not None:
                units.append(u)
        launch = [u for u in units if u.desc is not None]
        zero_only = [u for u in units if u.desc is None]
        if len(launch) < 2 and not any(
            bycall[u.call_index].name in _ANALYTIC for u in launch
        ):
            # a single device call gains nothing over the per-call
            # batched path; keep classic routing (and its telemetry).
            # A lone analytic panel DOES launch — it already replaces K
            # point queries.
            self._bypass("too_few_fusable")
            return [(u.call_index, u.finish(None), 0.0) for u in zero_only]
        served = self._launch_units(launch)
        for u in zero_only:
            served.append((u.call_index, u.finish(None), 0.0))
        return served

    def _launch_units(self, launch: list, depth: int = 0) -> list[tuple]:
        """Launch lowered units as one fused program, under HBM
        admission (ISSUE 14): the governor is asked whether the wave's
        estimated transient peak fits current headroom BEFORE the
        launch. A wave that does not fit splits in half (each half
        re-admits — the estimate shrinks with the input set) instead of
        launching into an OOM; a unit that cannot fit even alone is NOT
        served, which routes it to the classic per-call path (bypass
        reason "admission")."""
        ex = self.ex
        flat: list = []
        descs: list = []
        for u in launch:
            descs.append(u.desc)
            flat.extend(u.inputs)
        # transient-peak estimate: inputs live in HBM for the whole
        # program and XLA holds roughly another copy in intermediates
        # (the fold chain rewrites in place but fetch buffers, padding
        # and fusion temporaries are real) — 2× summed input bytes,
        # plus per-unit declared transients (GroupBy's [K, S·W] stack)
        est = 2 * sum(int(getattr(a, "nbytes", 0)) for a in flat) + sum(
            u.extra_bytes for u in launch
        )
        gov = getattr(ex, "governor", None)
        if gov is not None and est > 0 and not gov.admit(est):
            if len(launch) >= 2 and depth < 4:
                self.admission_splits += 1
                metrics.count(metrics.FUSION_ADMISSION_SPLITS)
                mid = len(launch) // 2
                return self._launch_units(
                    launch[:mid], depth + 1
                ) + self._launch_units(launch[mid:], depth + 1)
            self._bypass("admission")
            return []
        shapes = tuple(
            (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "")))
            for a in flat
        )
        fn = self._program(tuple(descs), shapes)
        t0 = time.monotonic()
        with trace.child(metrics.STAGE_DEVICE_BATCH, call="Fused"):
            outs = fn(*flat)
            fetched = [_fetch(o) for o in outs]
        dt = time.monotonic() - t0
        nbytes = sum(int(o.nbytes) for o in fetched)
        self.fused_launches += 1
        self.fused_calls += len(launch)
        self.bytes_returned += nbytes
        metrics.count(metrics.FUSION_FUSED_LAUNCHES)
        metrics.observe(metrics.FUSION_FUSED_CALLS_PER_LAUNCH, len(launch))
        metrics.count(metrics.FUSION_BYTES_RETURNED, nbytes)
        for d in descs:
            if d[0] == "topn":
                metrics.count(
                    metrics.TOPN_SCORER_LAUNCHES, how=ops.stacked_scorer_how(d[2])
                )
            if d[0] in ("groupby_count", "groupby_sum"):
                metrics.count(metrics.FUSION_GROUPBY_LAUNCHES)
                k = 1
                for r in d[1]:
                    k *= r
                metrics.observe(metrics.FUSION_GROUPBY_GROUPS, k)
        cost = dt / max(len(launch), 1)
        return [
            (u.call_index, u.finish(fetched[k]), cost)
            for k, u in enumerate(launch)
        ]

    # -- per-call lowering ---------------------------------------------------

    def _lower_count(self, index, i, c, shards) -> Optional[_Unit]:
        if len(c.children) != 1:
            return None
        inputs, tree = self.ex._tree_leaves(index, c.children[0], shards)
        return _Unit(
            i,
            ("count", tree, len(inputs)),
            tuple(inputs),
            lambda res: int(np.asarray(res).reshape(-1)[0]),
        )

    def _lower_sum(self, index, i, c, shards) -> Optional[_Unit]:
        ex = self.ex
        field_name, ok = c.string_arg("field")
        if not ok or not field_name or len(c.children) > 1:
            return None
        f = ex.holder.field(index, field_name)
        bsig = f.bsi_group(field_name) if f is not None else None
        if bsig is None:
            return None
        depth = bsig.bit_depth()
        frags = tuple(
            ex.holder.fragment(
                index, field_name, VIEW_BSI_GROUP_PREFIX + field_name, s
            )
            for s in shards
        )
        if not any(frags):
            return None
        inputs, tree = ex._filter_tree(index, c, shards)
        planes = ex.stager.planes_stack(frags, depth)

        def finish(counts):
            vsum = sum(int(counts[j]) << j for j in range(depth))
            vcount = int(counts[depth])
            if vcount == 0:
                return ValCount()
            return ValCount(vsum + vcount * bsig.min, vcount)

        return _Unit(
            i, ("sum", depth, tree, len(inputs)), (planes, *inputs), finish
        )

    def _lower_groupby(self, index, i, c, shards) -> Optional[_Unit]:
        """Whole GroupBy panel as one segmented-reduction unit: every
        dimension's rows stack once, the cross-product AND + popcount
        (and BSI plane intersections for a Sum aggregate) trace into the
        fused program, and only the K-vector (or [K, depth+1] counts
        matrix) crosses back to host. The caller times this lowering as
        the request's ``groupby.lower``; the finisher's assembly is its
        ``groupby.finalize``."""
        import jax.numpy as jnp

        ex = self.ex
        plan = analytics.parse_groupby(c)
        dims = analytics.resolve_dims(
            ex.holder, index, plan, shards, ex.analytics_max_groups
        )
        if not all(ids for _, ids in dims):
            return _Unit(i, None, (), lambda _res: [])
        wf = len(shards) * _ex._W32
        inputs: list = []
        k = 1
        for field, ids in dims:
            frags = tuple(
                ex.holder.fragment(index, field, VIEW_STANDARD, s)
                for s in shards
            )
            rows = [ex.stager.row_stack(frags, rid) for rid in ids]
            inputs.append(jnp.stack(rows).reshape(len(ids), wf))
            k *= len(ids)
        # the filter's inputs follow the dimensions', its structure
        # rides in the descriptor
        finputs, tree = (
            ex._tree_leaves(index, plan.filter, shards)
            if plan.filter is not None
            else ([], None)
        )
        inputs.extend(finputs)
        rcounts = tuple(len(ids) for _, ids in dims)
        extra = k * wf * 4  # the [K, S·W] cross-product transient
        if plan.agg_field is None:

            def finish(counts):
                metrics.count(metrics.ANALYTICS_QUERIES, call="GroupBy")
                with trace.leg(trace.WF_GROUPBY_FINALIZE):
                    return analytics.finalize_groups(
                        plan, analytics.emit_device_groups(dims, counts)
                    )

            return _Unit(
                i,
                ("groupby_count", rcounts, tree, len(finputs)),
                tuple(inputs),
                finish,
                extra_bytes=extra,
            )
        f = ex.holder.field(index, plan.agg_field)
        bsig = f.bsi_group(plan.agg_field) if f is not None else None
        if bsig is None:
            return None  # classic path owns the error
        depth = bsig.bit_depth()
        afrags = tuple(
            ex.holder.fragment(
                index, plan.agg_field, VIEW_BSI_GROUP_PREFIX + plan.agg_field, s
            )
            for s in shards
        )
        if not any(afrags):
            return None  # no value fragments: classic path emits sum=0
        inputs.append(
            jnp.transpose(
                ex.stager.planes_stack(afrags, depth), (1, 0, 2)
            ).reshape(depth + 1, wf)
        )

        def finish(out):
            metrics.count(metrics.ANALYTICS_QUERIES, call="GroupBy")
            with trace.leg(trace.WF_GROUPBY_FINALIZE):
                sums = analytics.assemble_sums(out[:, 1:], depth, bsig.min)
                return analytics.finalize_groups(
                    plan,
                    analytics.emit_device_groups(dims, out[:, 0], sums=sums),
                )

        return _Unit(
            i,
            ("groupby_sum", rcounts, tree, len(finputs), depth),
            tuple(inputs),
            finish,
            extra_bytes=extra,
        )

    def _lower_distinct(self, index, i, c, shards) -> Optional[_Unit]:
        ex = self.ex
        field, ok = c.string_arg("field")
        if not ok or not field or len(c.children) > 1:
            return None
        f = ex.holder.field(index, field)
        bsig = f.bsi_group(field) if f is not None else None
        if bsig is None:
            return None
        depth = bsig.bit_depth()
        if depth > analytics.DISTINCT_DEVICE_MAX_DEPTH:
            return None  # presence domain too large — classic walk wins
        frags = tuple(
            ex.holder.fragment(index, field, VIEW_BSI_GROUP_PREFIX + field, s)
            for s in shards
        )
        if not any(frags):
            return _Unit(i, None, (), lambda _res: [])
        inputs, tree = ex._filter_tree(index, c, shards)
        planes = ex.stager.planes_stack(frags, depth)

        def finish(words):
            metrics.count(metrics.ANALYTICS_QUERIES, call="Distinct")
            return analytics.decode_presence_words(words, bsig.min)

        return _Unit(
            i, ("distinct", depth, tree, len(inputs)), (planes, *inputs), finish
        )

    def _lower_percentile(self, index, i, c, shards) -> Optional[_Unit]:
        ex = self.ex
        field, nth_bp = analytics.parse_percentile(c)
        f = ex.holder.field(index, field)
        bsig = f.bsi_group(field) if f is not None else None
        if bsig is None:
            return None
        depth = bsig.bit_depth()
        frags = tuple(
            ex.holder.fragment(index, field, VIEW_BSI_GROUP_PREFIX + field, s)
            for s in shards
        )
        if not any(frags):
            return _Unit(i, None, (), lambda _res: ValCount())
        inputs, tree = ex._filter_tree(index, c, shards)
        planes = ex.stager.planes_stack(frags, depth)
        # nth rides as a TRACED i32 input so every percentile of the
        # same (depth, filter structure) shares one compiled program
        nth = np.asarray(nth_bp, dtype=np.int32)

        def finish(out):
            metrics.count(metrics.ANALYTICS_QUERIES, call="Percentile")
            count = int(out[depth])
            if count == 0:
                return ValCount()
            val = sum(1 << j for j in range(depth) if int(out[j]))
            return ValCount(val + bsig.min, count)

        return _Unit(
            i,
            ("percentile", depth, tree, len(inputs)),
            (planes, nth, *inputs),
            finish,
        )

    def _lower_topn(self, index, i, c, shards, opt) -> Optional[_Unit]:
        ex = self.ex
        if len(c.children) != 1:
            return None
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 0:
            return None  # tanimoto pruning needs per-shard CPU counts
        field, ok = c.string_arg("_field")
        if not ok:
            return None
        row_ids, _ = c.uint_slice_arg("ids")
        frags = tuple(
            ex.holder.fragment(index, field, VIEW_STANDARD, s) for s in shards
        )
        size = FIRST_CHUNK
        with trace.leg(trace.WF_TOPN_CANDIDATES):
            pairs_by_shard = [
                f._top_bitmap_pairs(row_ids) if f is not None else []
                for f in frags
            ]
            ids_by_shard = tuple(
                _chunk_ids(ps, 0, size) for ps in pairs_by_shard
            )
        if not any(pairs_by_shard):
            return None  # classic path answers [] with no device work
        srcs = ex._device_bitmap_stack(index, c.children[0], shards)
        staged = ex.stager.sparse_rows_stacked(frags, ids_by_shard, size)
        n_shards = len(shards)

        def finish(mat):
            if mat is None:  # no shard contributed blocks: all score 0
                mat = np.zeros((n_shards, size), dtype=np.int32)
            # inject the fused head as the walk's first chunk; the
            # existing two-pass ranked walk then runs unchanged — the
            # bit-identity argument for fused TopN
            return ex._execute_topn(
                index,
                c,
                shards,
                opt,
                prescored=(frags, pairs_by_shard, ids_by_shard, mat, srcs),
            )

        if staged is None:
            return _Unit(i, None, (), finish)
        blocks, brow, bslot, bshard, num_rows = staged
        return _Unit(
            i,
            ("topn", num_rows, n_shards, size),
            (srcs, blocks, brow, bslot, bshard),
            finish,
        )

    # -- the fused program ---------------------------------------------------

    def _program(self, descs: tuple, shapes: tuple):
        key = (descs, shapes)
        with self._mu:
            fn = self._programs.get(key)
        if fn is None:
            import jax

            cf = chaos.FAULTS
            if cf is not None:
                # injected poisoned-jit fault: raising here lands in
                # try_execute's error bypass → the whole query re-runs
                # on the classic path, bit-identically
                cf.on_lowering()
            fn = _timed_kernel(
                "fused_query",
                jax.jit(_build_program(descs)),
                signature=key,
                recovery=self.ex._oom,
            )
            with self._mu:
                self._programs.setdefault(key, fn)
                fn = self._programs[key]
        return fn

    def stats(self) -> dict:
        ex = self.ex
        launches = self.fused_launches
        return {
            "enabled": True,
            "max_calls": self.max_calls,
            "fused_launches": launches,
            "fused_calls": self.fused_calls,
            "avg_calls_per_launch": (
                round(self.fused_calls / launches, 2) if launches else None
            ),
            "bytes_returned": self.bytes_returned,
            "cache_served": self.cache_served,
            "admission_splits": self.admission_splits,
            "programs": len(self._programs),
            "bypasses": dict(self.bypasses),
            "device_cache": (
                ex.device_cache.stats()
                if ex.device_cache is not None
                else {"enabled": False}
            ),
        }


def _build_program(descs: tuple):
    """The traced body of one fused query: consumes the flat input list
    by per-unit offset and returns one output per unit, each unit traced
    under ``jax.named_scope("fused/<unit kind>")`` so its operations
    carry its name in a device trace. Pure — traced under jax.jit, so
    no host effects (lint: jit-purity)."""

    def run(*flat):
        import jax

        outs = []
        off = 0
        for d in descs:
            with jax.named_scope(f"fused/{d[0]}"):
                out, off = _trace_unit(d, flat, off)
            outs.append(out)
        return tuple(outs)

    return run


def _trace_unit(d: tuple, flat: tuple, off: int):
    """One unit of a fused program: (its output, the next offset)."""
    import jax.numpy as jnp

    kind = d[0]
    if kind == "count":
        tree, nleaves = d[1], d[2]
        leaves = flat[off : off + nleaves]
        return ops.count_bits(_ex._eval_tree(tree, leaves))[None], off + nleaves
    if kind == "sum":
        depth, tree, n = d[1], d[2], d[3]
        end = off + 1 + n
        return _ex._trace_bsi_sum(depth, tree, flat[off], flat[off + 1 : end]), end
    if kind in ("groupby_count", "groupby_sum"):
        rcounts, tree, n = d[1], d[2], d[3]
        nd = len(rcounts)
        dims = tuple(flat[off : off + nd])
        off += nd
        filt = None
        if tree is not None:
            filt = _ex._eval_tree(tree, flat[off : off + n]).reshape(-1)
        off += n
        if kind == "groupby_count":
            return ops.groupby_counts(dims, filt), off
        counts, pc = ops.groupby_sum_reduce(dims, filt, flat[off])
        # one output per unit: [K, depth+2] with the group popcounts in
        # column 0, plane counts after
        return jnp.concatenate([counts[:, None], pc], axis=1), off + 1
    if kind == "distinct":
        depth, tree, n = d[1], d[2], d[3]
        end = off + 1 + n
        planes = flat[off]
        filt, has_filter = _ex._eval_filter(tree, flat[off + 1 : end], planes)
        out = ops.bsi_distinct_presence(
            planes, filt, bit_depth=depth, has_filter=has_filter
        )
        return out, end
    if kind == "percentile":
        depth, tree, n = d[1], d[2], d[3]
        end = off + 2 + n
        planes, nth = flat[off], flat[off + 1]
        filt, has_filter = _ex._eval_filter(tree, flat[off + 2 : end], planes)
        bits, count = ops.bsi_percentile_batched(
            planes, filt, nth, bit_depth=depth, has_filter=has_filter
        )
        out = jnp.concatenate(
            [bits.astype(jnp.int32), count[None].astype(jnp.int32)]
        )
        return out, end
    # topn head-chunk scoring
    num_rows, n_shards, chunk = d[1], d[2], d[3]
    srcs, blocks, brow, bslot, bshard = flat[off : off + 5]
    out = ops.sparse_intersection_counts_stacked_mat(
        srcs,
        blocks,
        brow,
        bslot,
        bshard,
        num_rows=num_rows,
        n_shards=n_shards,
        chunk=chunk,
    )
    return out, off + 5
