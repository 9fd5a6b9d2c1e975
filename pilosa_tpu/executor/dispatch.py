"""Continuous-batching async device dispatch — the executor↔device
boundary as a persistent feed loop (ISSUE 8).

The round-7 profile was blunt: the chip answers thousands of TopN qps
batched while serving delivered ~123 at c32, because every query still
parked a thread on a blocking dispatch and only *identical* queries
ever shared a launch (pipeline gangs) or *homogeneous* TopN scoring
coalesced (BatchedScorer). TPU/GPU inference servers close exactly
this gap with continuous batching (Orca/vLLM iteration-level
scheduling): one persistent dispatch loop owns the device and admits
whatever is queued into the next wave, so the device never idles
between launches. This module is that loop for bitmap queries:

* **Submit; lead when nobody is queued.** ``Executor.execute`` hands
  eligible local reads to ``submit()`` and gets a future back. Who
  runs the wave follows from two things the engine sees under its own
  lock. With the queue empty and a runner slot free the submitter
  *leads*: it takes the slot and runs a wave of its one item on its
  own thread, so the future is resolved when ``submit()`` returns and
  the request crossed no thread (the loop's wake-up, a wave thread's
  start and the hand-back were a millisecond of every request that no
  other request waited beside). With every slot taken, or somebody
  already queued (FIFO: nobody overtakes), the item queues and the
  loop *hands* the backlog to a wave thread; the caller waits on the
  future. Both enter one code path (``_begin_wave_locked`` →
  ``_run_wave_slot``); ``dispatch.waves{how=led|handed}`` counts
  which. Ineligible work (writes, gang/multihost, cluster fan-out,
  remote legs, traced queries, ``serial``) keeps the old inline path —
  the PR 5/6 gang determinism contract holds because gang execution
  is ``serial`` and never reaches the engine.
* **Heterogeneous waves.** The loop drains up to ``max_wave`` queued
  items per wave: backlog alone widens a wave, and backlog forms only
  while every slot computes (a led wave holds a slot like any other).
  Within a wave, items group by execution context
  (index, shard set, exec-opt bits) and dedup by canonical plan
  signature (plan/canon.py) — wave-level singleflight, so duplicate
  plans (including argument-order permutations) execute once and share
  results. Each group then becomes ONE combined multi-call query
  through ``executor._execute``: *mixed* TopN/Count/Sum/chain plans
  ride one wave, fan through the executor's read pool together, and
  the BatchedScorer / stacked scorers coalesce their kernel work into
  batched launches — generalizing both the pipeline's identical-query
  gangs and the scorer's homogeneous micro-batches.
* **Overlap.** ``max_inflight`` waves execute concurrently (double /
  triple buffering at the serving layer), led on their submitters'
  threads or handed to wave threads: while the slots compute, the
  loop is already building the next handed wave out of what queued
  meanwhile and firing advisory stage-ahead warms
  (``stager.stage_ahead``) so operand uploads overlap kernel
  execution, and earlier waves' waiters consume results as each
  runner finishes.
* **Deadlines.** Items whose deadline expired while queued are
  cancelled at wave build — before any parse/translate/kernel work —
  and their wave-mates are unaffected (a led item is checked there
  too, and from then on at ``_execute``'s own stage boundaries, like
  an inline execution: only the waiter of a handed wave can give up
  on its own clock while the wave still runs); a combined execution
  that fails (one bad member, a deadline, anything) falls back to per-item
  execution so each member gets ITS OWN outcome, mirroring the
  pipeline's gang fallback.
* **Shutdown by construction.** ``close()`` flips ``_closing`` under
  the queue lock; from then on ``submit()`` returns ``None`` and the
  caller executes inline — there is no submit/close race to lose. The
  loop drains what was already queued within the ``drain`` budget and
  fails the rest; a led wave counts in flight like a handed one, so
  ``close()`` waits for it too.

Observability: ``dispatch.waves{how}``, ``dispatch.wave_size``,
``dispatch.inflight_depth``, ``dispatch.device_idle_fraction`` (1 −
fraction of wall time with at least one wave executing, since first
submit), and
``dispatch.queue_wait_seconds``; snapshot at ``/debug/dispatch``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Optional

from pilosa_tpu.analysis.locks import OrderedLock
from pilosa_tpu.pql import Query
from pilosa_tpu.utils import heat, metrics, trace

# Request-deadline seam (server/deadline.py), imported lazily for the
# same L4→L6 layering reason as executor.py.
_deadline_mod = None


def _deadline():
    global _deadline_mod
    if _deadline_mod is None:
        from pilosa_tpu.server import deadline as _m

        _deadline_mod = _m
    return _deadline_mod


class _Item:
    """One submitted query: the future its caller blocks on."""

    __slots__ = (
        "index",
        "query",
        "shards",
        "opt",
        "deadline",
        "signature",
        "n_calls",
        "event",
        "value",
        "error",
        "t_enq",
        "t_done",
        "wait_s",
        "trace_ctx",
        "attrib",
        "wave_no",
        "req",
    )

    def __init__(
        self, index, query, shards, opt, deadline, signature, trace_ctx=None
    ) -> None:
        self.index = index
        self.query = query
        self.shards = shards
        self.opt = opt
        self.deadline = deadline
        self.signature = signature
        self.n_calls = len(query.calls)
        self.event = threading.Event()
        self.value: Any = None
        self.error: Optional[BaseException] = None
        self.t_enq = 0.0
        self.t_done = 0.0
        self.wait_s = 0.0
        # distributed trace context (utils/trace.py tuple): a deduped
        # item span-links the executed item it shared results with
        self.trace_ctx = trace_ctx
        # waterfall legs measured inside the wave, apportioned to this
        # item; result() merges them into the waiter's attribution ctx
        self.attrib: Optional[dict] = None
        self.wave_no = 0
        # the submitter's request id: the wave's legs are annotated with
        # it on the profiler's clock (a combined wave's with its head's)
        d = trace.attrib_current()
        self.req = d.get("_req", 0) if d else 0

    def finish(self, result=None, error=None) -> None:
        self.value = result
        self.error = error
        self.t_done = time.monotonic()
        self.event.set()

    def result(self) -> Any:
        """Block until the wave resolves this item. A waiter whose own
        deadline passes first raises (the runner's dequeue-time check
        skips its queued work; a launched item completes harmlessly on
        the abandoned future)."""
        dl = self.deadline
        if dl is None:
            self.event.wait()
        else:
            while not self.event.is_set():
                rem = dl.remaining()
                if rem <= 0:
                    dl.check("dispatch")  # raises (and counts)
                self.event.wait(timeout=min(rem, 0.5))
        d = trace.attrib_current()
        if d is not None:
            # the waiter's waterfall: queue wait + this item's share of
            # the wave's measured legs (+ the wave id for log joins) +
            # the hand-back, the wave's finishing stamp → running again
            trace.book(trace.WF_HANDOFF_WAKE, time.monotonic() - self.t_done)
            trace.book(trace.WF_DISPATCH_QUEUE, self.wait_s)
            if self.attrib:
                for k, v in self.attrib.items():
                    if k != "_req":
                        d[k] = d.get(k, 0.0) + v
            if self.wave_no:
                d["_wave"] = self.wave_no
        if self.error is not None:
            raise self.error
        return self.value


class DispatchEngine:
    """The persistent per-device dispatch loop. One per Executor; the
    loop thread starts lazily with the first item that has to queue, so
    idle executors (and every executor whose submitters all lead) pay
    nothing."""

    def __init__(
        self,
        executor,
        max_wave: int = 16,
        max_inflight: int = 2,
        stage_ahead: int = 1,
    ) -> None:
        self.executor = executor
        self.max_wave = max(1, int(max_wave))
        self.max_inflight = max(1, int(max_inflight))
        self.stage_ahead_depth = max(0, int(stage_ahead))
        self._mu = OrderedLock("dispatch.mu")
        self._cond = threading.Condition(self._mu)
        self._q: deque[_Item] = deque()
        self._closing = False
        self._loop_thread: Optional[threading.Thread] = None
        # wave runner slots: the loop blocks here BEFORE dequeuing, so
        # while all slots compute the queue keeps accumulating and the
        # next wave comes out wider — backlog IS the batching window,
        # exactly like the pipeline's gang dequeue. A submitter that
        # leads takes one without blocking, under _mu
        self._slots = threading.Semaphore(self.max_inflight)
        self._inflight = 0
        self._in_wave = threading.local()
        # busy/idle accounting: busy = wall time with >=1 wave
        # executing, measured from first submit. The exported
        # dispatch.device_idle_fraction is 1 - busy/wall — the number
        # continuous batching exists to drive down.
        self._t_start: Optional[float] = None
        self._busy_total = 0.0
        self._busy_since: Optional[float] = None
        # counters (ints under _mu; snapshot is consistent)
        self.waves = 0
        self.led = 0  # of them, run by their submitter (the rest: handed)
        self.items = 0
        self.dedup_hits = 0
        self.combined_items = 0
        self.fallbacks = 0
        self.expired = 0
        # per-tenant rollup (index = tenant, server/tenancy.py): who is
        # filling the waves, who is expiring in queue — the dispatch
        # leg of the per-tenant attribution story
        self.by_tenant: dict[str, dict[str, int]] = {}

    def _tenant_row_locked(self, index: str) -> dict:
        row = self.by_tenant.get(index)
        if row is None:
            row = self.by_tenant[index] = {
                "items": 0,
                "dedup_hits": 0,
                "expired": 0,
            }
        return row

    # -- admission -----------------------------------------------------------

    def submit(
        self,
        index: str,
        query: Query,
        shards,
        opt,
        deadline=None,
        text: Optional[str] = None,
        trace_ctx=None,
    ) -> Optional[_Item]:
        """Admit a read-only query and return its future — or ``None``
        when the engine is closing, in which case the caller executes
        inline (shutdown can never strand a submit). With nobody queued
        and a runner slot free the caller leads its own wave here, and
        the future it gets back is resolved; else the item queues for
        the loop's next wave."""
        sig = None
        if text is not None:
            from pilosa_tpu.plan import canon

            sig = canon.query_signature(text)
        item = _Item(index, query, shards, opt, deadline, sig, trace_ctx=trace_ctx)
        with self._mu:
            if self._closing:
                return None
            item.t_enq = time.monotonic()
            if self._t_start is None:
                self._t_start = item.t_enq
            self.items += 1
            self._tenant_row_locked(index)["items"] += 1
            # FIFO holds: somebody queued is served first, by the loop
            lead = not self._q and self._slots.acquire(blocking=False)
            if lead:
                wave_no = self._begin_wave_locked(led=True)
            else:
                if self._loop_thread is None:
                    t = threading.Thread(
                        target=self._loop, name="dispatch-loop", daemon=True
                    )
                    self._loop_thread = t
                    t.start()
                self._q.append(item)
                self._cond.notify_all()
        if lead:
            # nothing is queued behind a led wave, so there is nothing
            # for _stage_ahead_peek to warm
            self._run_wave_slot([item], wave_no)
        return item

    def in_wave(self) -> bool:
        """True on a thread currently executing a wave (re-entry
        guard: anything inside a wave that reaches execute() again must
        run inline, not deadlock against its own runner slot)."""
        return getattr(self._in_wave, "active", False)

    # -- the dispatch loop ---------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._mu:
                while not self._q and not self._closing:
                    self._cond.wait()
                if not self._q:
                    return  # closing and drained
            # acquire a runner slot BEFORE dequeuing: with every slot
            # busy the backlog keeps growing and the next wave is wider
            self._slots.acquire()
            with self._mu:
                n = min(self.max_wave, len(self._q))
                wave = [self._q.popleft() for _ in range(n)]
                if not wave:
                    self._slots.release()
                    continue
                wave_no = self._begin_wave_locked(led=False)
            # overlap: operand staging for what is STILL queued runs on
            # the stager's side thread while this wave computes
            self._stage_ahead_peek()
            threading.Thread(
                target=self._run_wave_slot,
                args=(wave, wave_no),
                name="dispatch-wave",
                daemon=True,
            ).start()

    def _begin_wave_locked(self, led: bool) -> int:
        """A wave starts, its slot already taken: the wave number, the
        in-flight count, the busy clock and the gauges. ``led``: by its
        submitter, on the submitter's thread; else handed by the loop to
        a wave thread. Whoever runs it releases through
        ``_run_wave_slot``."""
        self.waves += 1
        self.led += led
        metrics.count(metrics.DISPATCH_WAVES, how="led" if led else "handed")
        self._inflight += 1
        if self._inflight == 1:
            self._busy_since = time.monotonic()
        metrics.gauge(metrics.DISPATCH_INFLIGHT_DEPTH, self._inflight)
        return self.waves

    def _run_wave_slot(self, wave: list[_Item], wave_no: int = 0) -> None:
        try:
            self._run_wave(wave, wave_no)
        finally:
            with self._mu:
                self._inflight -= 1
                if self._inflight == 0 and self._busy_since is not None:
                    self._busy_total += time.monotonic() - self._busy_since
                    self._busy_since = None
                metrics.gauge(metrics.DISPATCH_INFLIGHT_DEPTH, self._inflight)
                metrics.gauge(
                    metrics.DISPATCH_DEVICE_IDLE_FRACTION,
                    self._idle_fraction_locked(),
                )
                if self._closing:
                    self._cond.notify_all()  # close() waits on inflight==0
            self._slots.release()

    def _idle_fraction_locked(self) -> float:
        if self._t_start is None:
            return 1.0
        now = time.monotonic()
        wall = now - self._t_start
        if wall <= 0:
            return 0.0
        busy = self._busy_total
        if self._busy_since is not None:
            busy += now - self._busy_since
        return max(0.0, min(1.0, 1.0 - busy / wall))

    # -- wave execution ------------------------------------------------------

    def _run_wave(self, wave: list[_Item], wave_no: int = 0) -> None:
        self._in_wave.active = True
        # wave id rides the contextvar so the logger's correlation
        # suffix (wave=N) joins this wave's log lines to its items'
        # waterfalls
        wtok = trace.set_wave(wave_no)
        try:
            now = time.monotonic()
            metrics.observe(metrics.DISPATCH_WAVE_SIZE, len(wave))
            live: list[_Item] = []
            for it in wave:
                it.wait_s = now - it.t_enq
                it.wave_no = wave_no
                metrics.observe(metrics.DISPATCH_QUEUE_WAIT_SECONDS, it.wait_s)
                if it.deadline is not None and it.deadline.expired():
                    # expired while queued: cancelled before any
                    # parse/translate/kernel work; wave-mates unaffected
                    with self._mu:
                        self.expired += 1
                        self._tenant_row_locked(it.index)["expired"] += 1
                    metrics.count(
                        metrics.PIPELINE_DEADLINE_EXPIRED, stage="dispatch"
                    )
                    it.finish(error=_deadline().DeadlineExceeded("dispatch"))
                    continue
                live.append(it)
            if heat.LEDGER.enabled:
                # wave-membership heat: one count per (index, shard)
                # admitted into this wave (fused launches ride the same
                # membership — a deduped item still occupied a slot)
                for it in live:
                    for s in it.shards or ():
                        heat.record_wave(it.index, "", s)
            groups: dict[tuple, list[_Item]] = {}
            for it in live:
                o = it.opt
                key = (
                    it.index,
                    tuple(it.shards) if it.shards is not None else None,
                    o.remote,
                    o.exclude_row_attrs,
                    o.exclude_columns,
                    o.cache,
                )
                groups.setdefault(key, []).append(it)
            for members in groups.values():
                self._run_group(members, wave_no)
        finally:
            trace.reset_wave(wtok)
            self._in_wave.active = False

    def _run_group(self, members: list[_Item], wave_no: int = 0) -> None:
        """Dedup by canonical signature, then execute the distinct
        plans as one combined multi-call query."""
        leaders: list[_Item] = []
        by_sig: dict[str, _Item] = {}
        dups: dict[int, list[_Item]] = {}
        for it in members:
            lead = by_sig.get(it.signature) if it.signature is not None else None
            if lead is not None and lead.n_calls == it.n_calls:
                dups.setdefault(id(lead), []).append(it)
                with self._mu:
                    self.dedup_hits += 1
                    self._tenant_row_locked(it.index)["dedup_hits"] += 1
                if it.trace_ctx is not None and it.trace_ctx[2]:
                    # wave-level singleflight: the deduped item's trace
                    # span-links the executed item and names the wave
                    lctx = lead.trace_ctx
                    trace.record_link(
                        metrics.STAGE_DISPATCH_DEDUP,
                        it.trace_ctx,
                        lctx if lctx is not None else ("", ""),
                        wave=wave_no,
                        signature=it.signature,
                    )
                continue
            if it.signature is not None:
                by_sig[it.signature] = it
            leaders.append(it)
        if len(leaders) > 1:
            if not self._try_combined(leaders):
                for it in leaders:
                    self._run_single(it)
        elif leaders:
            self._run_single(leaders[0])
        for lead in leaders:
            for d in dups.get(id(lead), ()):
                d.attrib = lead.attrib  # served by the leader's work
                d.finish(result=lead.value, error=lead.error)

    def _try_combined(self, leaders: list[_Item]) -> bool:
        """One combined execution for the whole group: the calls fan
        through the executor's read pool together, so the batched
        scorers coalesce heterogeneous members' kernel work. Runs under
        the group-minimum deadline; any failure reports False and the
        caller re-runs members individually (a bad member can never
        fail its wave-mates)."""
        head = leaders[0]
        combined = Query(calls=[c for it in leaders for c in it.query.calls])
        dls = [it.deadline for it in leaders if it.deadline is not None]
        gang_dl = min(dls, key=lambda d: d.at) if dls else None
        dm = _deadline()
        # fresh attribution scope for the combined execution: the legs
        # measured inside (fenced device compute, transfer, stager, ...)
        # are apportioned to the members by call count — one wave, one
        # measurement, each waiter sees its share
        measured: dict = {"_req": head.req}
        try:
            with dm.activate(gang_dl), trace.attrib_activate(measured):
                results = self.executor._execute(
                    head.index, combined, head.shards, head.opt
                )
        except BaseException:
            with self._mu:
                self.fallbacks += 1
            return False
        with self._mu:
            self.combined_items += len(leaders)
        total_calls = sum(it.n_calls for it in leaders) or 1
        legs = {k: v for k, v in measured.items() if k != "_req"}
        waited = sum(legs.values())
        off = 0
        for it in leaders:
            # a member waited through the whole wave; what was measured
            # beyond its own share was its wave-mates' work
            w = it.n_calls / total_calls
            it.attrib = {k: v * w for k, v in legs.items()}
            it.attrib[trace.WF_WAVE_MATES] = waited * (1.0 - w)
            it.finish(result=results[off : off + it.n_calls])
            off += it.n_calls
        return True

    def _run_single(self, it: _Item) -> None:
        if it.event.is_set():
            return
        dm = _deadline()
        if it.deadline is not None and it.deadline.expired():
            # the deadline lapsed during a FAILED combined attempt: the
            # waiter already raised 504 on its own clock — re-executing
            # here would burn a full solo run on an abandoned future
            # (the fault's blast radius leaking into device time). Give
            # the item its honest outcome instead.
            with self._mu:
                self.expired += 1
            metrics.count(metrics.PIPELINE_DEADLINE_EXPIRED, stage="dispatch")
            it.finish(error=dm.DeadlineExceeded("dispatch"))
            return
        measured: dict = {"_req": it.req}
        try:
            with dm.activate(it.deadline), trace.attrib_activate(measured):
                result = self.executor._execute(
                    it.index, it.query, it.shards, it.opt
                )
            it.attrib = measured
            it.finish(result=result)
        except BaseException as err:
            it.finish(error=err)

    # -- stage-ahead overlap -------------------------------------------------

    def _stage_ahead_peek(self) -> None:
        """Advisory operand prefetch for queued-but-unlaunched items:
        while the launched wave computes, the stager's side thread
        uploads the NEXT waves' Row operands (staging overlapped with
        compute). Bounded, best-effort, and idempotent — the real
        execution re-stages whatever this missed.

        With a plan-driven prefetcher wired (executor/tiering.py), the
        queued items' plans go to the scheduler instead: it extracts
        Row operands itself, promotes their blocks T1/T2 → T0, and
        marks them for accuracy attribution — replacing the opaque
        warm-thunk path."""
        ex = self.executor
        pf = getattr(ex, "prefetcher", None)
        if pf is not None and pf.enabled:
            with self._mu:
                peek = list(self._q)[: pf.depth * self.max_wave]
            if peek:
                pf.schedule(peek)
            return
        if self.stage_ahead_depth <= 0:
            return
        stage = getattr(ex.stager, "stage_ahead", None)
        if stage is None:
            return
        with self._mu:
            peek = list(self._q)[: self.stage_ahead_depth * self.max_wave]
        seen: set = set()
        for it in peek:
            key = (it.index, it.signature)
            if it.signature is not None and key in seen:
                continue
            seen.add(key)
            stage(lambda it=it: ex._warm_query(it.index, it.query, it.shards))

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain: float = 5.0) -> bool:
        """Stop admission (``submit`` returns None → callers run
        inline), drain queued + in-flight waves within ``drain``
        seconds, fail whatever remains. Returns True when everything
        drained in time."""
        t0 = time.monotonic()
        with self._mu:
            self._closing = True
            self._cond.notify_all()
            loop = self._loop_thread
        if loop is not None:
            loop.join(timeout=max(0.0, drain - (time.monotonic() - t0)))
        leftovers: list[_Item] = []
        with self._mu:
            deadline = t0 + drain
            while self._inflight > 0 and time.monotonic() < deadline:
                self._cond.wait(timeout=0.05)
            clean = self._inflight == 0 and not self._q
            while self._q:
                leftovers.append(self._q.popleft())
        for it in leftovers:
            it.finish(error=RuntimeError("dispatch engine shut down"))
        return clean

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        """The /debug/dispatch snapshot."""
        with self._mu:
            return {
                "enabled": True,
                "closing": self._closing,
                "max_wave": self.max_wave,
                "max_inflight": self.max_inflight,
                "stage_ahead": self.stage_ahead_depth,
                "queued": len(self._q),
                "inflight_waves": self._inflight,
                "waves": self.waves,
                "led": self.led,
                "handed": self.waves - self.led,
                "items": self.items,
                "dedup_hits": self.dedup_hits,
                "combined_items": self.combined_items,
                "fallbacks": self.fallbacks,
                "deadline_expired": self.expired,
                "tenants": {idx: dict(row) for idx, row in self.by_tenant.items()},
                "device_idle_fraction": self._idle_fraction_locked(),
                "fusion": (
                    self.executor.fuser.stats()
                    if getattr(self.executor, "fuser", None) is not None
                    else {"enabled": False}
                ),
                "prefetch": (
                    self.executor.prefetcher.stats()
                    if getattr(self.executor, "prefetcher", None) is not None
                    else {"enabled": False}
                ),
            }
