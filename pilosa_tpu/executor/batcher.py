"""Continuous micro-batching of TopN scoring dispatches.

A TPU serving system's throughput lever is batching: one kernel launch
scoring Q query sources against a staged fragment matrix costs barely
more than scoring one, because the scan is HBM-bound on the matrix read
(ops.intersection_counts_matrix_batch reads the matrix once for all Q).
The reference has no analog — each Go query runs its own heap loop
(fragment.go:985); batching is the TPU-native replacement for "one
goroutine per query".

Batching is *continuous* (the pattern TPU inference servers use): there
is no artificial wait window. Concurrent callers enqueue; the first to
find no active dispatcher is promoted to leader and drains the queue in
rounds until it is empty, launching one batched kernel per staged
matrix per round. A lone caller dispatches immediately — the sequential
path pays only one uncontended lock acquisition. While a round's fetch
is in flight, new arrivals accumulate for the next round, so batch
width self-tunes to the fetch latency.

This scorer is the *intra-wave* coalescing mechanism that the
continuous-batching dispatch engine (executor/dispatch.py) composes:
the engine widens the concurrency funnel at the executor boundary
(heterogeneous plans per wave, submit-don't-block), and the TopN calls
inside one wave still funnel through this scorer so homogeneous
scoring dispatches merge into single batched kernel launches.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from pilosa_tpu import ops
from pilosa_tpu.utils import metrics, profiler, trace


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _trim_device(dev, rows: Optional[int] = None, cols: Optional[int] = None):
    """Slice a still-on-device score array down to what callers will
    read, so the subsequent fetch only moves live lanes/columns.

    Lazy-slicing a jax array is a cheap device op; anything without an
    ``ndim`` (or an unexpected rank — the chain scorer's batch output
    is 1-D) passes through untouched.
    """
    try:
        nd = dev.ndim
    except AttributeError:
        return dev
    if nd == 1:
        if rows is not None:
            dev = dev[:rows]
        return dev
    if nd == 2:
        if rows is not None:
            dev = dev[:rows]
        if cols is not None:
            dev = dev[:, :cols]
    return dev


class _Slot:
    __slots__ = ("src", "event", "result", "error", "trim")

    def __init__(self, src, trim: Optional[int] = None) -> None:
        self.src = src
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        # rows of the score vector the caller will actually read (the
        # staged matrix is pow2-padded); set ⇒ _launch trims on device
        # before the fetch so pad lanes never cross the host boundary
        self.trim = trim

    def finish(self, scorer: "BatchedScorer" = None) -> np.ndarray:
        if scorer is None:
            self.event.wait()
        else:
            # bounded wait + rescue: if the queue is orphaned (leader
            # exited in the narrow window between waking its round's
            # waiters and a new arrival promoting itself), any blocked
            # waiter picks the work up within one poll interval
            while not self.event.wait(timeout=0.1):
                scorer._rescue()
        if self.error is not None:
            raise self.error
        return self.result


class BatchedScorer:
    """Coalesces concurrent ``score`` calls with the same key (same
    staged matrix) into batched kernel launches.

    The kernel pair is pluggable: the default scores a dense staged
    matrix; the executor's stacked-sparse TopN path supplies the
    block-sparse kernels instead (same drain/coalesce machinery, the
    staged operand is opaque to it).
    ``single_fn(src, staged) -> i32[R]``;
    ``batch_fn([src] * Q, staged) -> i32[Q, R]`` — a LIST of sources,
    so the kernel can stack inside its jit (one dispatch per
    coalesced batch).
    """

    def __init__(
        self,
        max_batch: int = 32,
        single_fn=None,
        batch_fn=None,
        kind: str = "topn_score_dense",
    ) -> None:
        self.max_batch = max_batch
        # the kernel's name in spmd.execute_seconds{kind} and
        # kernel.operand_bytes{kind}, launch → fetched
        self.kind = kind
        self._single_fn = single_fn or (
            lambda src, staged: ops.intersection_counts_matrix(src, staged)
        )
        self._batch_fn = batch_fn or (
            lambda srcs, staged: ops.intersection_counts_matrix_batch_list(
                srcs, staged
            )
        )
        # pow2 padding zeros, cached per (shape, dtype): a fresh
        # jnp.zeros_like per launch is an extra dispatch RPC
        self._pad_zeros: dict = {}
        # process-wide HBM governor (executor/hbm.py): the pad scratch
        # is device-resident, so its bytes are accounted against the
        # "batcher" tenant — one ledger sees every resident byte
        self.governor = None
        self._lock = threading.Lock()  # protects _pending/_dispatching
        # key -> (staged operand, waiting slots); the operand rides with
        # the queue because the dispatching leader may not be the thread
        # that enqueued this key's work
        self._pending: dict[tuple, tuple] = {}
        self._dispatching = False
        # telemetry (read by tests; no lock — monotonic counters)
        self.dispatches = 0
        self.batched_queries = 0

    def score(self, key: tuple, mat, src, trim: Optional[int] = None) -> np.ndarray:
        """popcount(src & row) per matrix row → i32[R].

        key MUST be derived from the live staged array's identity
        (e.g. ``(id(frag), id(mat))`` — see executor._top_device), so
        same key ⇔ same array object: keying on mutable metadata like
        frag.generation reintroduces a race where coalesced peers hold
        different matrices.

        Leader-promotion continuous batching: the first caller to find
        no active dispatcher becomes one and drains the WHOLE queue
        (all keys) in rounds until it is empty; everyone else just
        waits on their slot. While the leader's device→host fetch is
        in flight (GIL released) new arrivals pile into the queue and
        the next round drains them as one wide launch — batch width
        self-tunes to the fetch latency. Whether that latency bounds
        throughput is not measured on the current machine.
        """
        sp = trace.current()
        slot = _Slot(src, trim=trim)
        # enqueue → result is the host's wait on the device. The leader's
        # covers async launch + fetch (and at most one extra round served
        # for peers); fenced legs inside its loop (the chain scorer's
        # _timed_kernel) nest, so a second is credited once. A non-lead
        # waiter's work ran inside the leader's launch, which credited
        # only the leader's request: its slot wait is its device leg.
        with trace.leg(trace.WF_DEVICE_COMPUTE) as lg:
            with self._lock:
                ent = self._pending.get(key)
                if ent is None:
                    self._pending[key] = (mat, [slot])
                else:
                    ent[1].append(slot)
                if self._dispatching:
                    lead = False
                else:
                    self._dispatching = lead = True
            if lead:
                self._dispatch_loop(own=slot)
            out = slot.finish(self)
        metrics.observe(metrics.BATCHER_SLOT_WAIT_SECONDS, lg.seconds)
        if sp is not None:
            # backfill a span covering enqueue -> result (the wait was
            # spent inside finish(), so enter/exit timing can't be used)
            sp.record(metrics.STAGE_BATCH_SCORE, lg.t0, lg.seconds, lead=lead)
        return out

    def _rescue(self) -> None:
        """Adopt an orphaned queue (no active dispatcher but pending
        work) — called by blocked waiters on their poll interval."""
        with self._lock:
            if self._dispatching or not self._pending:
                return
            self._dispatching = True
        metrics.count(metrics.BATCHER_RESCUES)
        self._dispatch_loop(own=None)

    def _dispatch_loop(self, own: Optional[_Slot] = None) -> None:
        """Drain-launch-fetch rounds until the queue is empty or this
        leader's own request has been served (whoever its last round
        woke — or any still-blocked waiter via _rescue — takes over the
        remainder, bounding one caller's time served as leader). Within
        a round, every key's kernels launch (async) before any key's
        results are fetched, so independent staged matrices pipeline
        their device work behind one fetch chain. Errors land on the
        affected slots (finish() re-raises them per waiter); one key's
        failure doesn't abandon other keys' work.

        Rounds are DOUBLE-BUFFERED: round N+1's kernels launch before
        round N's results are fetched, so the fetch of round N overlaps
        round N+1's dispatch, device compute,
        and readiness — two rounds in flight instead of strict
        launch→fetch alternation. Correctness is unaffected (each
        slot's result is still fetched exactly once, just one round
        later); the leader serves at most one extra round past its own
        request before handing off."""
        prev: list = []
        launched_all: list = []

        def fetch(launched_rounds: list) -> None:
            for launched in launched_rounds:
                try:
                    self._finish(launched)
                except BaseException:
                    pass  # every slot of the batch carries the error
        try:
            while True:
                with self._lock:
                    if not self._pending or (own is not None and own.event.is_set()):
                        self._dispatching = False
                        break
                    work = self._pending
                    self._pending = {}
                launched_all = []
                for mat, batch in work.values():
                    try:
                        launched_all.append(self._launch(batch, mat))
                    except BaseException:
                        pass  # every slot of the batch carries the error
                fetch(prev)
                prev = launched_all
            # the final round's results are fetched after the dispatcher
            # flag clears; a new leader draining fresh arrivals touches
            # different slots, so the concurrent _finish is safe
            fetch(prev)
            # every round this leader launched has now been fetched, so
            # its pad lanes are no longer referenced by in-flight device
            # work — re-zero them through a donated jit so the scratch
            # buffer is recycled in place on TPU (no-op zeros on CPU)
            self._recycle_pads()
        except BaseException:
            # never leave the scorer wedged: a leader death outside the
            # per-key guards (KeyboardInterrupt, MemoryError) must not
            # strand the dispatcher flag — and never leave launched
            # rounds unfetched (their slots left _pending, so _rescue
            # can't adopt them; unfetched waiters would block forever).
            # prev and the round launched THIS iteration are distinct
            # objects whenever an async exception lands between the
            # fetch and the prev=launched_all swap; _finish is
            # idempotent per slot, so fetching both is always safe.
            with self._lock:
                self._dispatching = False
            fetch(prev)
            if launched_all is not prev:
                fetch(launched_all)
            raise

    def set_governor(self, governor) -> None:
        self.governor = governor
        if governor is None:
            return
        # accounting-only tenant: the scratch is a handful of pow2
        # zero arrays, never worth an eviction tier of its own
        governor.register("batcher", share_bytes=0, evict_fn=None, tier=99)
        held = sum(
            int(getattr(z, "nbytes", 0)) for z in self._pad_zeros.values()
        )
        if held:
            governor.reserve("batcher", held)

    def _recycle_pads(self) -> None:
        """Recycle the cached pow2 pad zeros through a donated re-zero
        (ops.zeros_like_donated). Called only after the leader's final
        fetch, when no round launched by this leader still holds the
        pads; a concurrent fresh leader is possible but rare, so a
        donation conflict just drops the entry for _launch to rebuild."""
        for zkey in list(self._pad_zeros):
            zero = self._pad_zeros.get(zkey)
            if zero is None:
                continue
            nbytes = int(getattr(zero, "nbytes", 0))
            try:
                self._pad_zeros[zkey] = ops.zeros_like_donated(zero)
            except BaseException:
                self._pad_zeros.pop(zkey, None)
                if self.governor is not None:
                    self.governor.release("batcher", nbytes)

    def _fill(self, batch: list[_Slot], mat) -> None:
        # compatibility seam (tests/instrumentation wrap this): launch +
        # fetch back-to-back, lock management is the caller's business
        self._finish(self._launch(batch, mat))

    def _launch(self, batch: list[_Slot], mat) -> list[tuple]:
        """Dispatch kernels for every chunk of ``batch`` asynchronously;
        returns [(chunk, device_scores, launch time)] for _finish to
        fetch. On error, fails EVERY not-yet-finished slot of the batch
        — including ones whose chunk already launched (their device
        results are discarded): a waiter must never be left blocked."""
        import jax.numpy as jnp

        launched: list[tuple] = []
        try:
            self.dispatches += 1
            metrics.count(metrics.BATCHER_DISPATCHES)
            metrics.observe(metrics.BATCHER_BATCH_SIZE, len(batch))
            if len(batch) == 1:
                src, trim = batch[0].src, batch[0].trim
                dev, t0 = self._timed_launch(self._single_fn, src, mat)
                launched.append((batch, _trim_device(dev, rows=trim), t0))
                return launched
            for start in range(0, len(batch), self.max_batch):
                chunk = batch[start : start + self.max_batch]
                self.batched_queries += len(chunk)
                # Pad Q to a power of two so compile cache stays bounded;
                # a zero source scores 0 everywhere and is sliced off.
                q = _next_pow2(len(chunk))
                srcs = [s.src for s in chunk]
                if q > len(chunk):
                    proto = srcs[0]
                    zkey = (getattr(proto, "shape", None), str(getattr(proto, "dtype", "")))
                    zero = self._pad_zeros.get(zkey)
                    if zero is None:
                        zero = self._pad_zeros[zkey] = jnp.zeros_like(proto)
                        if self.governor is not None:
                            self.governor.reserve(
                                "batcher", int(getattr(zero, "nbytes", 0))
                            )
                    srcs = srcs + [zero] * (q - len(chunk))
                dev, t0 = self._timed_launch(self._batch_fn, srcs, mat)
                # transfer hygiene: pad query lanes never reach the
                # host, and when every slot declared its read width the
                # score columns trim device-side too (the fetch then
                # moves exactly what the callers will consume)
                trims = [s.trim for s in chunk]
                keep = max(trims) if all(t is not None for t in trims) else None
                launched.append((chunk, _trim_device(dev, rows=len(chunk), cols=keep), t0))
            return launched
        except BaseException as e:
            for s in batch:
                if not s.event.is_set():
                    s.error = e
                    s.event.set()
            raise

    def _timed_launch(self, fn, srcs, mat) -> tuple:
        """One launch under the scorer's kernel name: the jit call up to
        its return of the not-yet-ready scores is ``device.launch``
        (nested in the leader's ``device.compute``) and
        ``spmd.launch_seconds``, the operand bytes (padding included)
        counted in it; returns the scores and the launch time for
        _finish."""
        with trace.leg(trace.WF_DEVICE_LAUNCH) as launch:
            profiler.count_operands(self.kind, (srcs, mat))
            dev = fn(srcs, mat)
        metrics.observe(metrics.SPMD_LAUNCH_SECONDS, launch.seconds, kind=self.kind)
        return dev, launch.t0

    def _finish(self, launched: list[tuple]) -> None:
        """Fetch launched device results and wake the coalesced slots.
        Runs outside the dispatch lock so fetches pipeline with the next
        batch's launch."""
        try:
            for chunk, dev_scores, t0 in launched:
                scores = np.asarray(dev_scores)
                metrics.observe(
                    metrics.SPMD_EXECUTE_SECONDS,
                    time.monotonic() - t0,
                    kind=self.kind,
                )
                if len(chunk) == 1 and scores.ndim == 1:
                    chunk[0].result = scores
                    chunk[0].event.set()
                    continue
                for i, s in enumerate(chunk):
                    s.result = scores[i]
                    s.event.set()
        except BaseException as e:
            # every coalesced peer must see the real error, not None
            for chunk, _, _ in launched:
                for s in chunk:
                    if not s.event.is_set():
                        s.error = e
                        s.event.set()
            raise
