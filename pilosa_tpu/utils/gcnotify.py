"""The collector's pauses, into the metric registry (reference
gcnotify/gcnotify.go:25-43, consumed at server.go:702-704, counts Go's
GC cycles; here each one is timed too).

CPython's cyclic collector stops every thread of the process while it
runs: a collection is a pause of every request in flight. ``gc.callbacks``
fire with phase "start"/"stop" around each one, on the thread whose
allocation began it; start → stop is ``runtime.gc_pause_seconds``
{generation}, and each "stop" counts to ``garbage_collection``.
"""

from __future__ import annotations

import gc
import time

from pilosa_tpu.utils import metrics


class GCNotifier:
    """Times completed garbage collections, by generation.

    ``close()`` unregisters the callback; instances are independent so a
    server owns one for its lifetime (the reference's AfterGC channel is
    likewise per-server). Collections never overlap (the interpreter
    starts none while one runs), so the callback's state needs no lock.
    """

    def __init__(self) -> None:
        self._t0 = 0.0
        self._unbooked: list[tuple[int, float]] = []
        self._closed = False
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._t0 = now
            return
        if not self._t0:  # hooked while a collection ran: no start seen
            return
        self._unbooked.append((info["generation"], now - self._t0))
        self._t0 = 0.0
        # this thread may be inside the registry's lock: what cannot be
        # booked now goes with the next collection
        while self._unbooked:
            generation, seconds = self._unbooked[0]
            if not metrics.REGISTRY.try_observe(
                metrics.GC_PAUSE_SECONDS, seconds, generation=generation
            ):
                return
            del self._unbooked[0]
            metrics.count(metrics.GARBAGE_COLLECTION)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass
