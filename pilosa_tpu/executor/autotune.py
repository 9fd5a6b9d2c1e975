"""Self-tuning device policy: measure, don't guess.

The executor's "auto" policy routes a query to the device when its
estimated touched-container count crosses a threshold. The right
threshold is a property of the DEPLOYMENT, not the code: it is the
ratio of what one device dispatch costs to what one roaring container
costs on the host, and both differ from machine to machine. Neither has
been measured on the current machine. Shipping a constant mis-routes
some deployment, so the server measures BOTH costs at open:

* dispatch_ms — p50 of a few tiny device round-trips (device_put +
  reduce + fetch: the same shape DeviceHealth probes use);
* cpu_ms_per_container — p50 cost of one roaring container
  intersection-count on this host (the CPU path's unit of work,
  reference fragment.go:985 / roaring intersectionCount loops).

crossover = dispatch_ms / cpu_ms_per_container, clamped to sane
bounds. The measurement runs on a side thread with a deadline so a
wedged device can never stall startup; explicit config/env overrides
win (they're operator statements, not guesses).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

# clamp bounds for the computed crossover: below 16 the estimate noise
# dominates; above 100k the device would practically never engage and
# the operator should look at the deployment instead
MIN_CROSSOVER = 16
MAX_CROSSOVER = 100_000

# containers in the calibration bitmap (big enough to amortize call
# overhead, small enough to build in milliseconds)
_CAL_CONTAINERS = 64


def measure_dispatch_ms(reps: int = 5, timeout_s: float = 10.0) -> Optional[float]:
    """p50 of a tiny device round-trip (dispatch + completion + fetch),
    in ms. None when the device never answers inside the deadline —
    callers keep their current threshold."""
    import numpy as np

    out: list[float] = []
    done = threading.Event()

    def run():
        try:
            import jax

            x = np.arange(64, dtype=np.uint32)
            # warm the backend + any compile outside the timed reps
            np.asarray(jax.device_put(x).sum())
            for _ in range(reps):
                t0 = time.perf_counter()
                got = np.asarray(jax.device_put(x).sum())
                out.append((time.perf_counter() - t0) * 1000)
                assert int(got) == int(x.sum())
            done.set()
        except Exception:
            pass  # leave `done` unset → treated as no answer

    t = threading.Thread(target=run, daemon=True)
    t.start()
    if not done.wait(timeout=timeout_s) or not out:
        return None
    out.sort()
    return out[len(out) // 2]


def measure_cpu_container_ms(reps: int = 7) -> float:
    """p50 per-container cost of a roaring intersection count on this
    host, measured live."""
    import numpy as np

    from pilosa_tpu.roaring import Bitmap

    rng = np.random.default_rng(7)
    # _CAL_CONTAINERS bitmap containers at ~30% density: dense enough
    # that the word loops (not the container walk) dominate, like the
    # hot rows the CPU path actually reads
    positions = []
    for c in range(_CAL_CONTAINERS):
        vals = rng.choice(1 << 16, size=20_000, replace=False).astype(np.uint64)
        positions.append(np.uint64(c << 16) + np.sort(vals))
    bits = np.concatenate(positions)
    a = Bitmap.from_sorted(bits)
    b = Bitmap.from_sorted(bits[::2].copy())
    a.intersection_count(b)  # warm any lazy setup
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        a.intersection_count(b)
        samples.append((time.perf_counter() - t0) * 1000)
    samples.sort()
    return samples[len(samples) // 2] / _CAL_CONTAINERS


def tuned_min_containers(
    dispatch_ms: Optional[float] = None,
    cpu_ms_per_container: Optional[float] = None,
) -> Optional[int]:
    """Crossover threshold from measured costs; None when the device
    could not be measured (keep the current threshold)."""
    if dispatch_ms is None:
        dispatch_ms = measure_dispatch_ms()
    if dispatch_ms is None:
        return None
    if cpu_ms_per_container is None:
        cpu_ms_per_container = measure_cpu_container_ms()
    if cpu_ms_per_container <= 0:
        return None
    raw = int(dispatch_ms / cpu_ms_per_container)
    return max(MIN_CROSSOVER, min(MAX_CROSSOVER, raw))


def autotune_executor(
    executor,
    logger=None,
    blocking: bool = False,
    measure: Optional[Callable[[], Optional[int]]] = None,
) -> Optional[threading.Thread]:
    """Tune ``executor.auto_min_containers`` from live measurements.

    Non-blocking by default: the server keeps serving on the shipped
    default and adopts the measured crossover when it lands (the
    attribute is read per-query). Returns the measuring thread (or
    None when run inline)."""
    measure = measure or tuned_min_containers

    def run():
        got = measure()
        if got is None:
            if logger is not None:
                logger.printf(
                    "device autotune: device unmeasurable; keeping "
                    "crossover=%d", executor.auto_min_containers,
                )
            return
        before = executor.auto_min_containers
        executor.auto_min_containers = got
        if logger is not None:
            logger.printf(
                "device autotune: crossover %d -> %d touched containers "
                "(measured)", before, got,
            )

    if blocking:
        run()
        return None
    t = threading.Thread(target=run, name="device-autotune", daemon=True)
    t.start()
    return t
