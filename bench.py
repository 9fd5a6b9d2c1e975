"""Benchmark: TopN queries/sec on the north-star workload.

Synthetic fragment (BASELINE.json config 4 style): R rows × 2^20 columns
per shard at ~2% density; queries are TopN(field, Row(src)) — the
reference's hot path (per-candidate IntersectionCount over the ranked
cache, fragment.go:985) executed as one batched intersection-count
matrix kernel + top_k on the TPU.

The source bitmap of TopN(Row(r)) is a row of the fragment, which the
HBM stager keeps device-resident (executor/stager.py) — so the query
step indexes the staged matrix rather than re-uploading the source from
host each time, exactly as the server's executor does. QPS is measured
with pipelined dispatch and then a forced host-side fetch of every
result (the fetch is what proves the query finished); p50 latency is
a true dispatch+completion+fetch round-trip per query. The batched
path mirrors the executor's continuous micro-batching
(executor/batcher.py): PILOSA_BENCH_BATCH sources per kernel launch.

Baseline: the same queries through this framework's CPU roaring path
(the reference's algorithm shape — per-candidate container popcount
loops). The reference Go binary itself can't run here (no Go toolchain
in the image); the roaring CPU path is the stand-in and is labeled as
such. vs_baseline = TPU QPS / CPU QPS.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import threading
import time

import numpy as np

# process-start clock for the child's self-enforced deadline: the
# parent's subprocess timeout runs from spawn, so measuring from inside
# main() (after the jax import) would silently eat the guard margin
_T_PROC_START = time.monotonic()

# ---- sub-result checkpointing -------------------------------------------
# Each completed sub-bench (tall full-path, kernel microbench) persists
# to disk the moment it finishes, tagged with the git revision it
# measured. A device wedge mid-run then costs only the unfinished
# parts: the next attempt (same invocation or a retry) reuses fresh
# same-revision parts instead of replaying a whole prior round
# (an earlier round's failure mode). Parts from a DIFFERENT revision are never
# reused — stale-replay remains the explicitly-labeled last resort.

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
PARTS_PATH = os.path.join(_REPO_DIR, ".bench_cache", "bench_parts.json")
PART_MAX_AGE_S = 3 * 3600.0


def _git_rev() -> str:
    import subprocess

    try:
        return subprocess.run(
            ["git", "-C", _REPO_DIR, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def save_part(name: str, obj: dict) -> None:
    try:
        os.makedirs(os.path.dirname(PARTS_PATH), exist_ok=True)
        try:
            with open(PARTS_PATH) as f:
                parts = json.load(f)
        except (OSError, ValueError):
            parts = {}
        parts[name] = {
            "data": obj,
            "ts": time.time(),
            "rev": _git_rev(),
        }
        tmp = PARTS_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(parts, f)
        os.replace(tmp, PARTS_PATH)
    except OSError as e:
        print(f"could not checkpoint part {name}: {e}", file=sys.stderr)


def load_part(name: str):
    """A fresh part measured on THIS code revision, or None."""
    try:
        with open(PARTS_PATH) as f:
            parts = json.load(f)
        p = parts.get(name)
        if not p:
            return None
        if p.get("rev") != _git_rev():
            return None
        age = time.time() - p.get("ts", 0)
        if age > PART_MAX_AGE_S:
            return None
        data = dict(p["data"])
        data["checkpointed_age_s"] = round(age, 1)
        return data
    except (OSError, ValueError):
        return None


def best_closed_loop(d: dict, prefix: str):
    """(key, qps) of the best measured closed-loop number among
    ``prefix``-keyed fields (topn_qps_c8/_c32/...), or (None, None).
    One definition — the live headline, the checkpoint-assembly
    headline, and the core-scaled margin block all use it."""
    best = (None, None)
    for k, v in d.items():
        if k.startswith(prefix) and isinstance(v, (int, float)):
            if best[0] is None or v > best[1]:
                best = (k, v)
    return best


def headline_mode(tall: dict):
    """(mode_label, qps) for the artifact headline: the best measured
    closed-loop serving number, falling back to sequential when no
    concurrency window ran — or when none beat the sequential number
    (a degraded window must not lower the published headline below
    what the run actually achieved)."""
    seq = tall.get("topn_qps") or 0.0
    bk, bv = best_closed_loop(tall, "topn_qps_c")
    if bk is not None and bv > seq:
        return f"{bk.rsplit('c', 1)[1]} closed-loop clients", bv
    return "sequential", seq


def vs_baseline_fields(
    mode: str, headline: float, cpu_qps, cpu_closed_qps=None, seq_qps=None
) -> dict:
    """The vs_baseline fields, identical from the live and the
    checkpoint-assembly paths: ratio + denominator + a note stating
    which convention the ratio uses. A closed-loop headline divides by
    the CPU path's best MEASURED throughput (max of its sequential and
    closed-loop windows — bench_tall measures a short CPU closed loop
    so the denominator is data, not the asserted "sequential is the
    1-core ceiling"); the sequential-vs-sequential ratio always rides
    alongside as vs_baseline_seq when seq_qps is known."""
    if not cpu_qps:
        return {}
    out = {}
    base = cpu_qps
    if mode != "sequential":
        if cpu_closed_qps:
            base = max(cpu_qps, cpu_closed_qps)
            out["baseline_cpu_closed_qps"] = cpu_closed_qps
            note = (
                "headline serving qps vs the CPU full path's best "
                "measured throughput (max of sequential and closed-loop "
                "windows)"
            )
        else:
            note = (
                "headline serving qps vs the CPU full path's sequential "
                "qps (no CPU closed-loop window measured this run)"
            )
    else:
        note = "sequential qps both sides (no concurrency window measured)"
    out.update(
        vs_baseline=round(headline / base, 2),
        baseline_cpu_qps=cpu_qps,
        vs_baseline_note=note,
    )
    if seq_qps and mode != "sequential":
        out["vs_baseline_seq"] = round(seq_qps / cpu_qps, 2)
    return out


# -- bench window self-qualification (VERDICT item 4) -----------------------
# A measurement window can degrade (slow dispatch round trips, shallow
# request pipelining) without failing outright; a headline measured in such a
# window must not silently overwrite the last-good artifact.

# a run whose RTT is this much worse than the last-good's is degraded
DEGRADED_RTT_FACTOR = 2.5
# a run achieving under this fraction of the last-good pipelining depth
# (concurrent round-trips in flight = qps x RTT) is degraded
DEGRADED_DEPTH_FACTOR = 0.4


def window_quality(tall: dict):
    """Measured quality of the window the headline came from: the
    sustained device RTT (median of the tiny round-trip probe) and the
    achieved pipelining depth (headline qps x RTT = concurrent round
    trips actually in flight). None when the run measured no RTT
    profile — a run that can't prove its window must not displace one
    that could."""
    prof = (tall or {}).get("profile") or {}
    rtt_ms = prof.get("device_rtt_ms")
    if not isinstance(rtt_ms, (int, float)) or rtt_ms <= 0:
        return None
    mode, qps = headline_mode(tall)
    if not qps:
        return None
    out = {
        "sustained_rtt_ms": rtt_ms,
        "pipelining_depth": round(qps * rtt_ms / 1000.0, 2),
        "headline_qps": qps,
        "headline_mode": mode,
    }
    # chain windows ride the same qualification as TopN (VERDICT chain-
    # margin instability): a degraded window must not overwrite the
    # last-good chain numbers either
    seq_chain = tall.get("chain_qps") or 0.0
    ck, cv = best_closed_loop(tall, "chain_qps_c")
    if ck is not None and cv > seq_chain:
        chain_mode, chain_qps = f"{ck.rsplit('c', 1)[1]} closed-loop clients", cv
    else:
        chain_mode, chain_qps = "sequential", seq_chain
    if chain_qps:
        out.update(
            chain_headline_qps=chain_qps,
            chain_headline_mode=chain_mode,
            chain_pipelining_depth=round(chain_qps * rtt_ms / 1000.0, 2),
        )
    # fused-execution window (ISSUE 13): how many device RTTs a warm
    # fused multi-call query costs end to end, and that it really ran
    # as ONE launch. Carried so window_degraded can reject a run where
    # fusion regressed to per-call round trips.
    fr = prof.get("fused_rtt") or {}
    fm = fr.get("rtt_multiple")
    if isinstance(fm, (int, float)) and fm > 0:
        out["fused_rtt_multiple"] = fm
        fl = fr.get("fused_launches_per_query")
        if isinstance(fl, (int, float)):
            out["fused_launches_per_query"] = fl
    return out


def window_degraded(new_wq, old_wq):
    """(degraded, reason) for overwriting an artifact whose window was
    ``old_wq`` with one whose window is ``new_wq``. No old quality
    record (pre-gating artifact) accepts anything — the first qualified
    run seeds the baseline."""
    if not old_wq:
        return False, None
    if not new_wq:
        return True, "no window_quality measured this run (last-good has one)"
    rtt, old_rtt = new_wq["sustained_rtt_ms"], old_wq["sustained_rtt_ms"]
    if old_rtt and rtt > old_rtt * DEGRADED_RTT_FACTOR:
        return True, (
            f"sustained RTT {rtt:.2f} ms > {DEGRADED_RTT_FACTOR}x "
            f"last-good {old_rtt:.2f} ms"
        )
    depth, old_depth = new_wq["pipelining_depth"], old_wq["pipelining_depth"]
    if old_depth and depth < old_depth * DEGRADED_DEPTH_FACTOR:
        return True, (
            f"pipelining depth {depth:.2f} < {DEGRADED_DEPTH_FACTOR}x "
            f"last-good {old_depth:.2f}"
        )
    # symmetric chain-window check: a run whose chain window is shallow
    # (or absent) must not displace qualified chain numbers
    old_cd = old_wq.get("chain_pipelining_depth")
    if old_cd:
        new_cd = new_wq.get("chain_pipelining_depth")
        if not new_cd:
            return True, (
                "no chain window measured this run (last-good has one)"
            )
        if new_cd < old_cd * DEGRADED_DEPTH_FACTOR:
            return True, (
                f"chain pipelining depth {new_cd:.2f} < "
                f"{DEGRADED_DEPTH_FACTOR}x last-good {old_cd:.2f}"
            )
    # symmetric fused-window check (ISSUE 13): once a last-good run has
    # proven one-launch multi-call execution, a run whose fused query
    # costs many more RTTs (fusion off / regressed to per-call round
    # trips) — or that didn't measure it — must not displace it
    old_fm = old_wq.get("fused_rtt_multiple")
    if old_fm:
        new_fm = new_wq.get("fused_rtt_multiple")
        if not new_fm:
            return True, (
                "no fused-query window measured this run (last-good has one)"
            )
        if new_fm > old_fm * DEGRADED_RTT_FACTOR:
            return True, (
                f"fused query costs {new_fm:.2f} RTTs > "
                f"{DEGRADED_RTT_FACTOR}x last-good {old_fm:.2f}"
            )
    return False, None


def _pipeline_serving_probe(budget_s: float) -> dict:
    """Closed-loop HTTP throughput THROUGH the serving pipeline
    (ISSUE 2): boots a real server on :0 with the pipeline enabled over
    a small CPU-path index and drives it with closed-loop HTTP clients.
    Chip-independent — it measures the serving layer (admission, queue,
    coalescing, HTTP glue), the part that bounded round 5 at ~120 qps
    while the kernel sustained thousands. Also runs a short OVERLOAD
    segment (injected per-query delay + shrunken queue so offered load
    exceeds capacity) showing goodput holds near unloaded capacity
    while the excess sheds as 503 + Retry-After."""
    import json as _json
    import shutil as _shutil
    import tempfile
    import urllib.error
    import urllib.request

    from pilosa_tpu.server import Config, Server

    out = {
        "note": (
            "closed-loop HTTP qps through the serving pipeline on a "
            "small CPU-path index (chip-independent: measures the "
            "serving layer, not the kernel)"
        )
    }
    tmp = tempfile.mkdtemp(prefix="pilosa_pipeline_probe_")
    cfg = Config(
        data_dir=tmp,
        bind="127.0.0.1:0",
        device_policy="never",
        device_timeout=0,
        metric="none",
    )
    s = Server(cfg)
    s.open()
    try:
        def post(path, body):
            r = urllib.request.Request(s.uri + path, data=body, method="POST")
            with urllib.request.urlopen(r, timeout=30) as resp:
                return resp.read()

        post("/index/pb", b"{}")
        post("/index/pb/field/f", b"{}")
        rows, cols = [], []
        for r_ in range(8):
            for c in range(256):
                rows.append(r_)
                cols.append((c * 2654435761 + r_ * 97) % (1 << 20))
        post(
            "/index/pb/field/f/import",
            _json.dumps({"rowIDs": rows, "columnIDs": cols}).encode(),
        )
        queries = [f"Count(Row(f={r_}))".encode() for r_ in range(8)]

        def closed_loop(n_clients, seconds):
            stop = time.perf_counter() + seconds
            counts = [0] * n_clients
            shed = [0] * n_clients
            errors = []

            def client(ci):
                i = ci
                try:
                    while time.perf_counter() < stop and not errors:
                        try:
                            post("/index/pb/query", queries[i % len(queries)])
                            counts[ci] += 1
                        except urllib.error.HTTPError as e:
                            if e.code in (429, 503):
                                shed[ci] += 1
                            else:
                                raise
                        except (ConnectionError, urllib.error.URLError):
                            # transport-level drop under overload (RST
                            # before the pipeline could shed politely):
                            # a shed in effect — count it as one
                            shed[ci] += 1
                        i += 1
                except BaseException as e:
                    errors.append(e)

            ts = [
                threading.Thread(target=client, args=(ci,))
                for ci in range(n_clients)
            ]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errors:
                raise errors[0]
            dt = time.perf_counter() - t0
            return sum(counts) / dt, sum(shed) / dt

        closed_loop(8, min(2.0, budget_s * 0.15))  # warm
        qps, _ = closed_loop(8, min(4.0, budget_s * 0.3))
        out["closed_loop_qps_c8"] = round(qps, 1)
        if budget_s > 10 and s.pipeline is not None:
            # Overload segment. Reads won't do: singleflight + gang
            # batching legitimately ABSORB a read flood (the c8 window
            # above shows it), so overload is driven with unique writes
            # — never coalesced or combined, each occupies a worker for
            # the injected delay — at 4x more clients than workers. The
            # delay (GIL-released) must dwarf the per-request Python
            # overhead of this 1-core host, or the GIL — not the worker
            # pool — becomes the bottleneck, the queue never fills, and
            # the ratio measures scheduler noise instead of shedding.
            real = s.executor.execute

            def slow(*a, **k):
                time.sleep(0.02)
                return real(*a, **k)

            seq = [0]
            seq_lock = threading.Lock()

            def write_loop(n_clients, seconds):
                stop = time.perf_counter() + seconds
                ok = [0] * n_clients
                shed = [0] * n_clients
                errors = []

                def client(ci):
                    try:
                        while time.perf_counter() < stop and not errors:
                            with seq_lock:
                                seq[0] += 1
                                col = seq[0]
                            try:
                                post(
                                    "/index/pb/query",
                                    f"Set({col % (1 << 20)}, f=30)".encode(),
                                )
                                ok[ci] += 1
                            except urllib.error.HTTPError as e:
                                if e.code in (429, 503):
                                    shed[ci] += 1
                                    # brief backoff (well under the
                                    # advertised Retry-After): a shed
                                    # client that re-fires instantly
                                    # melts the 1-core host with shed
                                    # churn; offered load still far
                                    # exceeds capacity
                                    time.sleep(0.01)
                                else:
                                    raise
                            except (ConnectionError, urllib.error.URLError):
                                shed[ci] += 1
                    except BaseException as e:
                        errors.append(e)

                ts = [
                    threading.Thread(target=client, args=(ci,))
                    for ci in range(n_clients)
                ]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errors:
                    raise errors[0]
                dt = time.perf_counter() - t0
                return sum(ok) / dt, sum(shed) / dt

            s.executor.execute = slow
            icq = s.pipeline._classes["interactive"]
            old_limit = icq.limit
            icq.limit = 4
            try:
                # unloaded = clients == workers (saturated, no queueing)
                cap, _ = write_loop(8, min(3.0, budget_s * 0.2))
                good, shed_rate = write_loop(32, min(4.0, budget_s * 0.25))
            finally:
                s.executor.execute = real
                icq.limit = old_limit
            out["overload"] = {
                "unloaded_qps_c8": round(cap, 1),
                "goodput_qps_c32": round(good, 1),
                "shed_per_s": round(shed_rate, 1),
                "goodput_vs_unloaded": round(good / cap, 2) if cap else None,
                "note": (
                    "unique writes (non-coalescable) + 20 ms/query delay "
                    "+ interactive queue shrunk to 4, offered load ~4x "
                    "capacity; goodput should hold near unloaded "
                    "capacity while the excess sheds as 503"
                ),
            }
        with urllib.request.urlopen(s.uri + "/debug/pipeline", timeout=30) as r:
            out["debug_pipeline"] = _json.loads(r.read())
    finally:
        s.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _rw_mix_probe(budget_s: float) -> dict:
    """Read/write-mix steady state (ISSUE 3): c8 closed-loop TopN/chain
    reads through the device executor with 1% interleaved single-bit
    writes, in three arms — read-only (denominator), writes absorbed by
    delta staging, and writes with delta staging disabled (every write
    cold-invalidates and the next read re-uploads full blocks). Reports
    steady-state read qps, re-staged bytes, and delta-apply counts per
    arm. Chip-independent (the contrast is staging economics, not
    kernel speed)."""
    import shutil as _shutil
    import tempfile

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import DeviceStager, Executor
    from pilosa_tpu.utils import metrics as _metrics

    # 256 rows × 4000 bits: big enough that a full chunk re-stage costs
    # ~20 ms host packing (the cost a write used to impose on the next
    # read) while a warm delta apply is ~1-4 ms; at chip scale the gap
    # is upload-bound and orders of magnitude wider
    R, BITS = 256, 4000
    WRITE_FRAC = 0.01
    tmp = tempfile.mkdtemp(prefix="pilosa_rwmix_")
    out = {
        "note": (
            "c8 closed-loop TopN/chain reads on the device executor, 1% "
            "single-bit writes; rw_delta absorbs writes as HBM scatter "
            "deltas, rw_full_restage rebuilds staged blocks per write"
        ),
        "write_frac": WRITE_FRAC,
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("rw")
        fld = idx.create_field("f")
        rng = np.random.default_rng(42)
        rows, cols = [], []
        for r_ in range(R):
            rows += [r_] * BITS
            cols += rng.integers(0, 1 << 20, size=BITS).tolist()
        fld.import_bits(rows, cols)
        queries = [
            "TopN(f, n=10)",
            "TopN(f, Row(f=3), n=8)",
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Union(Row(f=4), Row(f=5), Row(f=6)))",
        ]

        def arm(write_frac, delta_enabled, seconds, nonce):
            # nonce keys every rng: arms writing the SAME (row, col)
            # sequence as a previous arm would set already-set bits —
            # no-op writes that never bump the generation and fake a
            # write-free steady state
            ex = Executor(
                h,
                device_policy="always",
                stager=DeviceStager(delta_enabled=delta_enabled),
            )
            for q in queries:  # warm: compile + stage
                ex.execute("rw", q)
            if write_frac:
                # absorb the write-path compiles too (delta scatter
                # kernel shapes / restage packing) so the measured
                # window is steady state, not first-write JIT
                wrng = np.random.default_rng(7000 + nonce)
                for w in range(4):
                    fld.set_bit(w % 16, int(wrng.integers(0, 1 << 20)))
                    for q in queries:
                        ex.execute("rw", q)
            snap0 = _metrics.snapshot()
            stop = time.perf_counter() + seconds
            reads = [0] * 8
            writes = [0] * 8
            errors: list = []

            def worker(ci):
                wr = np.random.default_rng(1000 + nonce * 8 + ci)
                i = ci
                try:
                    while time.perf_counter() < stop and not errors:
                        if write_frac and wr.random() < write_frac:
                            # writes land on the rows the read mix keeps
                            # staged (chain sources + TopN candidates) —
                            # the worst case for staging, which is the
                            # point of the probe
                            fld.set_bit(
                                int(wr.integers(0, 16)),
                                int(wr.integers(0, 1 << 20)),
                            )
                            writes[ci] += 1
                        else:
                            ex.execute("rw", queries[i % len(queries)])
                            reads[ci] += 1
                        i += 1
                except BaseException as e:
                    errors.append(e)

            ts = [
                threading.Thread(target=worker, args=(ci,)) for ci in range(8)
            ]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errors:
                raise errors[0]
            dt = time.perf_counter() - t0
            snap1 = _metrics.snapshot()

            def delta_of(name):
                tot = 0.0
                for k, v in snap1.items():
                    if isinstance(v, dict) or k.split(";")[0] != name:
                        continue
                    tot += v - (snap0.get(k) or 0)
                return tot

            return {
                "read_qps": round(sum(reads) / dt, 1),
                "writes_per_s": round(sum(writes) / dt, 1),
                "delta_applied": int(delta_of("stager.delta_applied")),
                "delta_fallback": int(delta_of("stager.delta_fallback")),
                "invalidation_misses": int(
                    delta_of("stager.misses_invalidation")
                ),
                "restaged_bytes": int(delta_of("stager.restaged_bytes")),
            }

        seg = max(2.0, min(7.0, budget_s / 4))
        out["read_only"] = arm(0.0, True, seg, nonce=0)
        out["rw_delta"] = arm(WRITE_FRAC, True, seg, nonce=1)
        out["rw_full_restage"] = arm(WRITE_FRAC, False, seg, nonce=2)
        ro = out["read_only"]["read_qps"]
        if ro:
            out["rw_delta_vs_read_only"] = round(
                out["rw_delta"]["read_qps"] / ro, 3
            )
            out["rw_full_vs_read_only"] = round(
                out["rw_full_restage"]["read_qps"] / ro, 3
            )
        full = out["rw_full_restage"]
        nwrites = full["writes_per_s"] * seg
        if nwrites:
            # the per-write re-upload burden delta staging removes; on
            # the CPU back end re-staging only costs host packing, but
            # on a chip these bytes ride the host→HBM link — divide
            # by link bandwidth for the wall-clock a write mix would
            # add without delta staging
            out["restaged_bytes_per_write_without_delta"] = int(
                full["restaged_bytes"] / nwrites
            )
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _hist_count_delta(snap0: dict, snap1: dict, name: str) -> int:
    """Observation-count delta of a summary metric between two
    ``metrics.snapshot()`` calls, summed over label sets."""
    tot = 0
    for k, v in snap1.items():
        if not isinstance(v, dict) or k.split(";")[0] != name:
            continue
        prev = snap0.get(k)
        tot += v.get("count", 0) - (prev.get("count", 0) if prev else 0)
    return tot


def _ingest_sustained_probe(budget_s: float) -> dict:
    """Durable streaming ingest steady state (ISSUE 11): c12
    closed-loop TopN/chain reads on the device executor while >=10% of
    operations submit 16-mutation batches through the write-ahead
    IngestQueue — each submit blocks until its wave is group-committed
    + fsynced — interleaved with read-only segments on the same warm
    state (median of adjacent-pair ratios, because this rig's core
    speed drifts 2x within a minute). Reports the read-qps ratio
    (acceptance: >=0.8x at >=10% writes), write-ack p50/p99, wave
    coalescing stats, and the bounded-staleness figure (coalesce window
    + observed ack p99). The post-ingest state is checked bit-for-bit
    against an uncached CPU oracle, and a federated sub-arm drives
    write waves through a replicated-solo leader while a follower
    rejoins mid-stream and must converge. Chip-independent (the
    contrast is queue/commit economics, not kernel speed)."""
    import shutil as _shutil
    import tempfile

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import DeviceStager, Executor
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.ingest import IngestQueue
    from pilosa_tpu.utils import metrics as _metrics

    R, BITS = 128, 3000
    WRITE_FRAC = 0.10  # fraction of ops that are batch submits
    BATCH = 16  # mutations per submit
    # a much wider coalesce window than the server default (2 ms):
    # this rig is 1-core, and every wave carries a fixed read-side tax
    # (full-matrix delta scatter on next TopN + fsync + commit, ~5-8 ms
    # total) — at 10% batch submits the wave rate, not the mutation
    # count, decides the read hit, so coalescing harder trades ack
    # latency for most of the read throughput
    WAVE_INTERVAL = 0.050
    # enough closed-loop workers that ack waits (mostly coalesce-window
    # sleep, GIL-free) overlap with reads instead of idling the core;
    # both arms run the same count so the baseline is comparable
    N_WORKERS = 12
    tmp = tempfile.mkdtemp(prefix="pilosa_ingest_probe_")
    out = {
        "note": (
            "c12 closed-loop device reads with 10% of ops submitting "
            "16-mutation batches through the durable IngestQueue (ack "
            "= group commit + fsync), interleaved with read-only "
            "segments on the same warm state; ratio = median of "
            "adjacent pairs; staleness bound = coalesce window + ack "
            "p99"
        ),
        "write_frac": WRITE_FRAC,
        "batch_size": BATCH,
        "wave_interval_s": WAVE_INTERVAL,
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("ing")
        fld = idx.create_field("f")
        rng = np.random.default_rng(53)
        rows, cols = [], []
        for r_ in range(R):
            rows += [r_] * BITS
            cols += rng.integers(0, 1 << 20, size=BITS).tolist()
        fld.import_bits(rows, cols)
        queries = [
            "TopN(f, n=10)",
            "TopN(f, Row(f=3), n=8)",
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Union(Row(f=4), Row(f=5), Row(f=6)))",
        ]

        def _batch_muts(wrng):
            # streaming-shaped writes: uniform over the whole row space
            # (an event stream lands anywhere, unlike rw_mix's
            # adversarial hot-row writes), mostly sets plus some clears
            # so OP_REMOVE coalescing and replay ride along. The staged
            # read set still pays — the full-matrix TopN entry absorbs
            # every wave, per-row entries only the waves touching them
            rs = wrng.integers(0, R, size=BATCH)
            cs = wrng.integers(0, 1 << 20, size=BATCH)
            ss = wrng.random(BATCH) > 0.2
            return rs.tolist(), cs.tolist(), ss.tolist()

        # one executor + one queue for the WHOLE probe: segments
        # toggle the write mix on warm shared state, so pairing adjacent
        # segments cancels the rig's drift (this shared core's speed
        # moves 2x+ within a minute — a single A/B split mismeasures)
        ex = Executor(
            h,
            device_policy="always",
            stager=DeviceStager(delta_enabled=True),
        )
        for qq in queries:  # warm: compile + stage
            ex.execute("ing", qq)
        iq = IngestQueue(API(h, ex), wave_max=2048, wave_interval=WAVE_INTERVAL)
        wrng = np.random.default_rng(9000)
        for _ in range(40):
            # absorb the write-path compiles (wave apply, delta scatter
            # shapes) AND drive the fragment's ranked cache to its
            # written-to steady state — wave applies maintain the rank
            # cache, which makes the filtered-TopN read ~3x cheaper, so
            # a cold-cache read-only baseline would understate the
            # denominator and flatter the ratio
            rs, cs, ss = _batch_muts(wrng)
            iq.submit("ing", "f", rs, cs, ss)
            for qq in queries:
                ex.execute("ing", qq)

        ack_lat: list = []
        lat_mu = threading.Lock()

        def run_seg(write_frac, seconds, nonce):
            stop = time.perf_counter() + seconds
            reads = [0] * N_WORKERS
            acked = [0] * N_WORKERS
            errors: list = []

            def worker(ci):
                wr = np.random.default_rng(2000 + nonce * N_WORKERS + ci)
                i = ci
                try:
                    while time.perf_counter() < stop and not errors:
                        if write_frac and wr.random() < write_frac:
                            rs, cs, ss = _batch_muts(wr)
                            t1 = time.perf_counter()
                            iq.submit("ing", "f", rs, cs, ss)
                            lat = time.perf_counter() - t1
                            acked[ci] += BATCH
                            with lat_mu:
                                ack_lat.append(lat)
                        else:
                            ex.execute("ing", queries[i % len(queries)])
                            reads[ci] += 1
                        i += 1
                except BaseException as e:
                    errors.append(e)

            ts = [
                threading.Thread(target=worker, args=(ci,))
                for ci in range(N_WORKERS)
            ]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errors:
                raise errors[0]
            dt = time.perf_counter() - t0
            return sum(reads) / dt, sum(acked) / dt

        # interleaved pairs: ro seg then ingest seg, repeated; the
        # reported ratio is the MEDIAN of per-pair ratios
        n_pairs = 3
        seg = max(1.5, min(4.0, budget_s / (2 * n_pairs + 1)))
        snap0 = _metrics.snapshot()
        st0_waves = iq.stats()["waves"]
        ro_qps, ing_qps, ing_mut = [], [], []
        for k in range(n_pairs):
            r_qps, _ = run_seg(0.0, seg, nonce=2 * k)
            w_qps, w_mut = run_seg(WRITE_FRAC, seg, nonce=2 * k + 1)
            ro_qps.append(round(r_qps, 1))
            ing_qps.append(round(w_qps, 1))
            ing_mut.append(w_mut)
        snap1 = _metrics.snapshot()
        st = iq.stats()
        iq.close()

        def delta_of(name):
            tot = 0.0
            for k, v in snap1.items():
                if isinstance(v, dict) or k.split(";")[0] != name:
                    continue
                tot += v - (snap0.get(k) or 0)
            return tot

        lats = np.array(ack_lat)
        waves = st["waves"] - st0_waves
        acked_total = seg * sum(ing_mut)
        out["read_only"] = {
            "read_qps": round(float(np.median(ro_qps)), 1),
            "segments": ro_qps,
        }
        out["sustained_ingest"] = {
            "read_qps": round(float(np.median(ing_qps)), 1),
            "segments": ing_qps,
            "acked_mutations_per_s": round(sum(ing_mut) / len(ing_mut), 1),
            "submits": len(ack_lat),
            "write_ack_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
            "write_ack_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
            "waves": waves,
            "mean_wave_size": round(acked_total / waves, 1) if waves else None,
            "fsyncs": _hist_count_delta(
                snap0, snap1, "ingest.fsync_seconds.hist"
            ),
            "delta_applied": int(delta_of("stager.delta_applied")),
            "restaged_bytes": int(delta_of("stager.restaged_bytes")),
            # readers lag a submitted mutation by at most the coalesce
            # window + one wave commit — the observed ack p99 bounds
            # the latter
            "staleness_bound_ms": round(
                WAVE_INTERVAL * 1e3 + float(np.percentile(lats, 99)) * 1e3, 2
            ),
        }
        out["ingest_vs_read_only"] = round(
            float(np.median([w / r for r, w in zip(ro_qps, ing_qps) if r])), 3
        )
        # post-ingest oracle: the warm device path (staged deltas from
        # all committed waves) must match a fresh uncached CPU executor
        oracle = Executor(h, device_policy="never")
        checks = queries + [f"Count(Row(f={r_}))" for r_ in range(16)]
        mism = 0
        for qq in checks:
            (got,) = ex.execute("ing", qq)
            (want,) = oracle.execute("ing", qq)
            if str(got) != str(want):
                mism += 1
        out["oracle_checks"] = len(checks)
        out["result_mismatches_vs_uncached_oracle"] = mism
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)

    # federated sub-arm: write waves through a replicated-solo leader
    # (one KIND_WRITE_WAVE descriptor per wave) while a follower
    # rejoins mid-stream; the follower must re-stage the pre-rejoin
    # waves and receive the post-rejoin ones through replication
    if budget_s > 12:
        try:
            out["federated"] = _ingest_federated_subarm()
        except Exception as e:
            out["federated"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def _ingest_federated_subarm() -> dict:
    """Boot a replicated-solo federated leader in-process, ingest write
    waves through HTTP, rejoin a follower mid-stream, and verify the
    follower converges to the leader's bit state — the wave-replication
    leg of the durability story (tests/test_federation.py exercises
    the full lifecycle; this records the numbers)."""
    import json as _json
    import shutil as _shutil
    import socket as _socket
    import tempfile
    import urllib.request

    from pilosa_tpu.server import ClusterConfig, Config, Server

    tmp = tempfile.mkdtemp(prefix="pilosa_ingest_fed_")
    out: dict = {}
    servers: list = []
    try:
        # the leader needs the cluster plane wired (federation.wire
        # installs the gang's replicate hook on it) — a 1-node cluster
        # is enough, the follower rides the gang plane only
        with _socket.socket() as _s:
            _s.bind(("127.0.0.1", 0))
            pa = _s.getsockname()[1]
        a = Server(
            Config(
                data_dir=os.path.join(tmp, "lead"),
                bind=f"127.0.0.1:{pa}",
                device_policy="never",
                metric="none",
                federation_leader=True,
                cluster=ClusterConfig(
                    disabled=False,
                    coordinator=True,
                    hosts=[f"127.0.0.1:{pa}"],
                    probe_interval=0,
                ),
            )
        )
        a.open()
        servers.append(a)

        def post(uri, path, body):
            r = urllib.request.Request(uri + path, data=body, method="POST")
            with urllib.request.urlopen(r, timeout=30) as resp:
                return _json.loads(resp.read() or b"{}")

        post(a.uri, "/index/i", b"{}")
        post(a.uri, "/index/i/field/f", b"{}")
        rng = np.random.default_rng(31)

        def ingest_waves(n_batches, batch=32):
            total = 0
            for _ in range(n_batches):
                rs = rng.integers(0, 64, size=batch).tolist()
                cs = rng.integers(0, 1 << 20, size=batch).tolist()
                body = _json.dumps({"rowIDs": rs, "columnIDs": cs}).encode()
                r = post(a.uri, "/index/i/field/f/ingest", body)
                total += r["acked"]
            return total

        out["pre_rejoin_acked"] = ingest_waves(8)
        f = Server(
            Config(
                data_dir=os.path.join(tmp, "fol"),
                bind="127.0.0.1:0",
                device_policy="never",
                metric="none",
                federation_rejoin=a.uri,
            )
        )
        f.open()
        servers.append(f)
        t0 = time.perf_counter()
        t_end = time.monotonic() + 30
        while a.multihost.state != "ACTIVE" and time.monotonic() < t_end:
            time.sleep(0.05)
        out["rejoin_seconds"] = round(time.perf_counter() - t0, 2)
        out["gang_state"] = a.multihost.state
        out["post_rejoin_acked"] = ingest_waves(8)

        def count_on(uri):
            r = post(uri, "/index/i/query", b"Count(Union(Row(f=0), Row(f=1)))")
            return r["results"][0]

        want = count_on(a.uri)
        t0 = time.perf_counter()
        t_end = time.monotonic() + 30
        while count_on(f.uri) != want and time.monotonic() < t_end:
            time.sleep(0.05)
        got = count_on(f.uri)
        out["follower_convergence_seconds"] = round(time.perf_counter() - t0, 2)
        out["follower_converged"] = got == want
        out["leader_count"] = want
        out["follower_count"] = got
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _continuous_batching_probe(budget_s: float) -> dict:
    """Continuous-batching dispatch engine A/B (ISSUE 8): closed-loop
    c8/c32 heterogeneous reads (TopN/Count/Intersect/chain) against two
    bare device executors over the same holder — one routing through
    the async dispatch engine, one blocking per call — recording qps
    per concurrency plus the measured device-idle fraction per arm.
    Chip-independent for the CONTRAST (the engine's wave grouping,
    dedup, and in-flight overlap all exercise on the CPU backend); the
    absolute gap on a chip is not measured."""
    import shutil as _shutil
    import tempfile

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils import metrics as _metrics

    R, BITS = 256, 4000
    tmp = tempfile.mkdtemp(prefix="pilosa_dispatch_probe_")
    out = {
        "note": (
            "closed-loop heterogeneous reads on bare device executors: "
            "dispatch engine (async waves) vs blocking per-call "
            "execution; device_idle_fraction = wall time with no device "
            "work in flight"
        )
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("cb")
        fld = idx.create_field("f")
        rng = np.random.default_rng(77)
        rows, cols = [], []
        for r_ in range(R):
            rows += [r_] * BITS
            cols += rng.integers(0, 1 << 20, size=BITS).tolist()
        fld.import_bits(rows, cols)
        # heterogeneous mix — distinct canonical signatures coexist in
        # one wave; closed-loop round-robin also produces exact
        # duplicates in the backlog, which the engine collapses
        queries = [
            "TopN(f, n=10)",
            "TopN(f, Row(f=3), n=8)",
            "Count(Row(f=1))",
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Union(Row(f=4), Row(f=5), Row(f=6)))",
            "Count(Difference(Row(f=7), Row(f=8)))",
        ]

        def exec_sum(snap):
            tot = 0.0
            for k, v in snap.items():
                if k.split(";")[0] == "spmd.execute_seconds.hist":
                    tot += (v or {}).get("sum", 0.0)
            return tot

        def arm(engine: bool, n_clients: int, seconds: float):
            ex = Executor(h, device_policy="always", dispatch_enabled=engine)
            try:
                for q in queries:  # warm: compile + stage
                    ex.execute("cb", q)
                counts = [0] * n_clients
                errors: list = []
                stop = time.perf_counter() + seconds

                def client(ci):
                    i = ci
                    try:
                        while time.perf_counter() < stop and not errors:
                            ex.execute("cb", queries[i % len(queries)])
                            counts[ci] += 1
                            i += 1
                    except BaseException as e:
                        errors.append(e)

                snap0 = _metrics.snapshot()
                ts = [
                    threading.Thread(target=client, args=(ci,))
                    for ci in range(n_clients)
                ]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errors:
                    raise errors[0]
                dt = time.perf_counter() - t0
                if engine:
                    idle = ex.dispatch_engine.stats()["device_idle_fraction"]
                else:
                    # blocking arm has no engine accounting: idle =
                    # 1 - (device execute seconds / wall). Whatever a
                    # dispatch round trip costs rides INSIDE the
                    # blocking call, so this flatters the blocking arm
                    # if anything.
                    busy = exec_sum(_metrics.snapshot()) - exec_sum(snap0)
                    idle = max(0.0, min(1.0, 1.0 - busy / dt))
                return sum(counts) / dt, idle
            finally:
                ex.close()

        seg = max(2.0, min(6.0, budget_s / 7))
        for n in (8, 32):
            qps_b, idle_b = arm(False, n, seg)
            qps_e, idle_e = arm(True, n, seg)
            out[f"c{n}_qps"] = round(qps_e, 1)
            out[f"c{n}_qps_blocking"] = round(qps_b, 1)
            out[f"c{n}_speedup"] = round(qps_e / qps_b, 2) if qps_b else None
            out[f"c{n}_device_idle_fraction"] = round(idle_e, 4)
            out[f"c{n}_device_idle_fraction_blocking"] = round(idle_b, 4)
        # hot-set arm: 4 distinct TopN-heavy queries (the dashboard /
        # head-of-Zipf shape the plan cache targets) — wave dedup can
        # collapse c clients toward 4 executions. On a 1-core CPU rig
        # the speedup ceiling at c8 is clients/distinct = 2x; on chip
        # the ceiling is the RTT overlap instead.
        queries[:] = [
            "TopN(f, n=10)",
            "TopN(f, Row(f=3), n=8)",
            "TopN(f, Row(f=5), n=8)",
            "Count(Row(f=1))",
        ]
        for n in (8, 32):
            qps_b, _ = arm(False, n, seg)
            qps_e, _ = arm(True, n, seg)
            out[f"hotset_c{n}_qps"] = round(qps_e, 1)
            out[f"hotset_c{n}_qps_blocking"] = round(qps_b, 1)
            out[f"hotset_c{n}_speedup"] = (
                round(qps_e / qps_b, 2) if qps_b else None
            )
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _tiering_oversub_probe(budget_s: float) -> dict:
    """Hot-set latency under HBM oversubscription (ISSUE 17): the same
    cyclic hot-set read loop served from a stager whose T0 budget holds
    the whole set (1x arm) vs one-third of it (3x arm — every lap
    re-enters most rows, with the T1 host compressed tier, the
    compressed-upload expansion path, and plan-driven prefetch
    absorbing the cost). Reports per-arm p50/p95, T0 hit rate and
    restaged bytes, T1 hit rate, compressed-upload PCIe savings, and
    prefetch accuracy. Chip-independent (the contrast is residency
    economics, not kernel speed)."""
    import shutil as _shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu import SHARD_WIDTH
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import DeviceStager, Executor
    from pilosa_tpu.utils import metrics as _metrics

    R, BITS = 18, 1200
    ROW_BYTES = (SHARD_WIDTH // 32) * 4

    def msum(snap, name):
        return sum(
            v
            for k, v in snap.items()
            if not isinstance(v, dict) and k.startswith(name)
        )

    tmp = tempfile.mkdtemp(prefix="pilosa_tiering_")
    out = {
        "note": (
            "Zipf hot-set Count loop, 4 clients with think time (sub-"
            "saturation, so latency measures service + interference, not "
            "closed-loop queueing); the 1x arm's T0 holds the whole "
            "working set (each row stages as both the row and row_stack "
            "forms), the 3x arm a third of it (CPU executor; the "
            "contrast is residency economics, not kernel speed)"
        ),
        "rows": R,
        "row_bytes": ROW_BYTES,
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("tv")
        fld = idx.create_field("f")
        rng = np.random.default_rng(29)
        rows, cols = [], []
        for r_ in range(R):
            rows += [r_] * BITS
            cols += rng.integers(0, SHARD_WIDTH, size=BITS).tolist()
        fld.import_bits(rows, cols)
        queries = [f"Count(Row(f={k}))" for k in range(R)]
        # one fixed Zipf draw sequence shared by both arms: a head-heavy
        # hot set (the dashboard shape), so the hot-set p50 measures the
        # resident head while the tail exercises T1 re-entry
        zdraw = (np.random.default_rng(31).zipf(1.3, size=100_000) - 1) % R
        # the "hot set" for the headline percentile: the Zipf head small
        # enough that both arms can keep it T0-resident (2 staged forms
        # per row x HOT rows < the 3x arm's budget)
        HOT = 4

        def arm(budget_rows, tiered, seconds):
            st = DeviceStager(
                budget_bytes=budget_rows * ROW_BYTES,
                tier1_max_bytes=(128 << 20) if tiered else 0,
                compressed_min_ratio=1.5 if tiered else 0.0,
            )
            # max_wave=1 keeps cold restages out of hot queries' waves
            # (no wave-mate inflation) while arrival bursts still leave
            # a backlog for the plan-driven prefetcher to stage ahead
            ex = Executor(
                h, device_policy="always", stager=st, dispatch_max_wave=1
            )
            try:
                for q in queries[:4]:  # warm the compile caches
                    ex.execute("tv", q)
                snap0 = _metrics.snapshot()
                lats: list = []
                mu = threading.Lock()
                stop = time.perf_counter() + seconds

                def client(cid):
                    mine = []
                    i = cid * 7919  # offset so clients spread over the draw
                    while time.perf_counter() < stop:
                        r_ = int(zdraw[i % len(zdraw)])
                        i += 1
                        t0 = time.perf_counter()
                        ex.execute("tv", queries[r_])
                        mine.append((r_, time.perf_counter() - t0))
                        # think time keeps the arms below saturation so
                        # p50 measures service (+ restage interference),
                        # not closed-loop queue depth
                        time.sleep(0.008)
                    with mu:
                        lats.extend(mine)

                with ThreadPoolExecutor(max_workers=4) as pool:
                    for f in [pool.submit(client, c * 5) for c in range(4)]:
                        f.result()
                arr = np.asarray(lats)
                lat = np.sort(arr[:, 1])
                hot = np.sort(arr[arr[:, 0] < HOT, 1])

                def pct(a, p):
                    return round(
                        float(a[min(len(a) - 1, int(p * len(a)))]) * 1e3, 3
                    )

                snap1 = _metrics.snapshot()
                total = st.hits + st.misses
                res = {
                    "queries": len(lat),
                    "p50_ms": pct(lat, 0.50),
                    "p95_ms": pct(lat, 0.95),
                    "hot_queries": len(hot),
                    "hot_p50_ms": pct(hot, 0.50),
                    "t0_hit_rate": round(st.hits / max(total, 1), 4),
                    "restaged_bytes": int(
                        msum(snap1, _metrics.STAGER_RESTAGED_BYTES)
                        - msum(snap0, _metrics.STAGER_RESTAGED_BYTES)
                    ),
                }
                if tiered and st.tier1 is not None:
                    t1 = st.tier1.stats()
                    res["t1_hit_rate"] = round(
                        t1["hits"] / max(t1["hits"] + t1["misses"], 1), 4
                    )
                    res["compressed_upload_bytes_saved"] = int(
                        msum(snap1, _metrics.TIERING_UPLOAD_BYTES_SAVED)
                        - msum(snap0, _metrics.TIERING_UPLOAD_BYTES_SAVED)
                    )
                    pf = (
                        ex.prefetcher.stats()
                        if ex.prefetcher is not None
                        else {}
                    )
                    res["prefetch_issued"] = pf.get("issued", 0)
                    res["prefetch_accuracy"] = pf.get("accuracy", 0.0)
                return res
            finally:
                ex.close()

        seg = max(2.0, min(8.0, budget_s / 2.5))
        # the hot working set is ~2 rows' bytes per row (row + row_stack
        # forms) — the 1x arm holds all of it plus transient slack, the
        # 3x arm a third
        ws_rows = 2 * R + 4
        out["oversub_1x"] = arm(ws_rows, tiered=True, seconds=seg)
        out["oversub_3x"] = arm(ws_rows // 3, tiered=True, seconds=seg)
        p50_1x = out["oversub_1x"]["p50_ms"]
        p50_3x = out["oversub_3x"]["p50_ms"]
        out["p50_1x_over_3x"] = round(p50_1x / p50_3x, 3) if p50_3x else None
        # the headline: how much of the fully-resident arm's hot-set p50
        # the 3x oversubscribed arm keeps — tiering + prefetch must hold
        # the Zipf head resident while the tail churns through T1
        # (1.0 = no penalty; the tiering acceptance bar is >= 0.9)
        h1 = out["oversub_1x"]["hot_p50_ms"]
        h3 = out["oversub_3x"]["hot_p50_ms"]
        out["hot_p50_1x_over_3x"] = round(h1 / h3, 3) if h3 else None
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _dashboard_mix_probe(budget_s: float) -> dict:
    """Interactive latency under an analytics panel load (ISSUE 18):
    the same fixed-concurrency TopN/Count interactive loop measured
    alone (analytics-off arm) and with a GroupBy dashboard panel loop
    running alongside (analytics-on arm). The analytic panels execute
    as fused segmented reductions in their own launches, so the
    headline is the interactive p50 ratio between the arms (the
    acceptance bar is < 1.10 — panels must not burn interactive p50)
    plus fused launches per panel (the one-launch-per-panel proof
    under concurrency). Chip-independent (the contrast is isolation,
    not kernel speed)."""
    import shutil as _shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu import SHARD_WIDTH
    from pilosa_tpu.core import FieldOptions, Holder
    from pilosa_tpu.core.field import FIELD_TYPE_INT
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.utils import metrics as _metrics

    NSHARDS, BITS = 2, 1500
    SEG_ROWS, DEV_ROWS = 6, 4

    def msum(snap, name):
        return sum(
            v
            for k, v in snap.items()
            if not isinstance(v, dict) and k.startswith(name)
        )

    tmp = tempfile.mkdtemp(prefix="pilosa_dashmix_")
    out = {
        "note": (
            "4 interactive clients (TopN/Count mix, think time, sub-"
            "saturation) measured alone vs with one GroupBy(seg x dev, "
            "Sum) panel loop alongside on the same executor; "
            "interactive_p50_ratio = with-panels / without (< 1.10 = "
            "panels don't burn interactive p50), fused_launches_per_"
            "panel proves each panel stays one segmented-reduction "
            "launch under concurrency"
        ),
        "shards": NSHARDS,
        "panel_groups": SEG_ROWS * DEV_ROWS,
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("dm")
        seg = idx.create_field("seg")
        dev = idx.create_field("dev")
        val = idx.create_field(
            "v", FieldOptions(type=FIELD_TYPE_INT, min=0, max=1000)
        )
        rng = np.random.default_rng(37)
        ncols = NSHARDS * SHARD_WIDTH
        rows, cols = [], []
        for r_ in range(SEG_ROWS):
            rows += [r_] * BITS
            cols += rng.integers(0, ncols, size=BITS).tolist()
        seg.import_bits(rows, cols)
        rows, cols = [], []
        for r_ in range(DEV_ROWS):
            rows += [r_] * BITS
            cols += rng.integers(0, ncols, size=BITS).tolist()
        dev.import_bits(rows, cols)
        vcols = rng.choice(ncols, size=4000, replace=False).tolist()
        val.import_values(vcols, rng.integers(0, 1000, size=4000).tolist())

        interactive = [f"Count(Row(seg={k}))" for k in range(SEG_ROWS)] + [
            "TopN(seg, n=4)",
            "TopN(dev, n=3)",
            f"Count(Intersect(Row(seg=1), Row(dev=2)))",
        ]
        panel = "GroupBy(Rows(seg), Rows(dev), Sum(field=v))"

        ex = Executor(h, device_policy="always", fusion_enabled=True)
        try:
            for q in interactive:  # warm the compile caches
                ex.execute("dm", q)
            ex.execute("dm", panel)

            def arm(with_panels: bool, seconds: float):
                snap0 = _metrics.snapshot()
                lats: list = []
                mu = threading.Lock()
                stop = time.perf_counter() + seconds
                panels = [0]

                def client(cid):
                    mine, i = [], cid * 3
                    while time.perf_counter() < stop:
                        q = interactive[i % len(interactive)]
                        i += 1
                        t0 = time.perf_counter()
                        ex.execute("dm", q)
                        mine.append(time.perf_counter() - t0)
                        # think time keeps the interactive side below
                        # saturation so p50 measures service +
                        # panel interference, not queue depth
                        time.sleep(0.006)
                    with mu:
                        lats.extend(mine)

                def panel_loop():
                    while time.perf_counter() < stop:
                        ex.execute("dm", panel)
                        panels[0] += 1
                        # dashboard refresh cadence (~4 Hz): panels are
                        # periodic redraws, not a saturating loop — the
                        # contrast measured is fused-launch interference
                        # on interactive traffic, not core starvation
                        time.sleep(0.25)

                with ThreadPoolExecutor(max_workers=5) as pool:
                    futs = [pool.submit(client, c) for c in range(4)]
                    if with_panels:
                        futs.append(pool.submit(panel_loop))
                    for f in futs:
                        f.result()
                snap1 = _metrics.snapshot()
                lat = np.sort(np.asarray(lats))

                def pct(a, p):
                    return round(
                        float(a[min(len(a) - 1, int(p * len(a)))]) * 1e3, 3
                    )

                res = {
                    "interactive_queries": len(lat),
                    "interactive_p50_ms": pct(lat, 0.50),
                    "interactive_p95_ms": pct(lat, 0.95),
                    "panels": panels[0],
                }
                if with_panels and panels[0]:
                    launches = msum(
                        snap1, _metrics.FUSION_GROUPBY_LAUNCHES
                    ) - msum(snap0, _metrics.FUSION_GROUPBY_LAUNCHES)
                    res["fused_launches_per_panel"] = round(
                        launches / panels[0], 3
                    )
                return res

            seg_s = max(2.0, min(8.0, budget_s / 2.5))
            arm(True, min(2.0, seg_s))  # throwaway: thread-pool +
            # allocator steady state, so the off arm isn't flattered
            # by a cold first lap
            out["analytics_off"] = arm(False, seg_s)
            out["analytics_on"] = arm(True, seg_s)
            p_off = out["analytics_off"]["interactive_p50_ms"]
            p_on = out["analytics_on"]["interactive_p50_ms"]
            out["interactive_p50_ratio"] = (
                round(p_on / p_off, 3) if p_off else None
            )
        finally:
            ex.close()
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _plan_cache_probe(budget_s: float) -> dict:
    """Plan result cache under Zipf-repeated traffic (ISSUE 4): a
    TopN/Intersect query mix drawn from a Zipf distribution (the
    dashboard / hot-query traffic shape the serving stack targets) runs
    through an executor with and without the generation-stamped result
    cache. Reports hot vs cold qps, the achieved hit ratio, and bytes
    resident — then a 1%-write arm proving invalidation correctness:
    every read in the write arm is compared bit-for-bit against an
    uncached oracle executor over the same holder, and the arm must
    observe > 0 generation invalidations. Chip-independent (the
    contrast is cache economics, not kernel speed)."""
    import shutil as _shutil
    import tempfile

    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.plan.cache import PlanCache

    R, BITS = 128, 2000
    N_DISTINCT = 48  # distinct queries in the pool
    ZIPF_A = 1.3  # Zipf exponent: ~85-90% of draws hit the head
    WRITE_FRAC = 0.01
    tmp = tempfile.mkdtemp(prefix="pilosa_plancache_")
    out = {
        "note": (
            "Zipf-repeated TopN/Intersect mix through the plan result "
            "cache (CPU executor; the contrast is cache vs recompute, "
            "not kernel speed); write arm compares every cached read "
            "against an uncached oracle"
        ),
        "zipf_a": ZIPF_A,
        "distinct_queries": N_DISTINCT,
        "write_frac": WRITE_FRAC,
    }
    h = Holder(tmp)
    h.open()
    try:
        idx = h.create_index("zc")
        fld = idx.create_field("f")
        rng = np.random.default_rng(17)
        rows, cols = [], []
        for r_ in range(R):
            rows += [r_] * BITS
            cols += rng.integers(0, 1 << 20, size=BITS).tolist()
        fld.import_bits(rows, cols)
        pool = []
        for i in range(N_DISTINCT):
            a, b, c = i % R, (i * 7 + 1) % R, (i * 13 + 2) % R
            pool.append(
                [
                    f"TopN(f, Row(f={a}), n=10)",
                    f"Count(Intersect(Row(f={a}), Row(f={b})))",
                    f"Count(Union(Row(f={a}), Row(f={b}), Row(f={c})))",
                ][i % 3]
            )
        # one fixed Zipf draw sequence, shared by all arms
        zdraw = (np.random.default_rng(23).zipf(ZIPF_A, size=200_000) - 1) % N_DISTINCT

        def arm(ex, seconds, write_frac=0.0, oracle=None, wnonce=0):
            wrng = np.random.default_rng(5000 + wnonce)
            stop = time.perf_counter() + seconds
            # oracle-checked arms run at the ORACLE's qps, so a pure
            # time budget can finish before 1% of ops were writes —
            # writes fire deterministically every 1/write_frac ops and
            # the arm runs on until a few landed (bounded at 3x budget)
            hard_stop = time.perf_counter() + seconds * 3
            every = int(1 / write_frac) if write_frac else 0
            reads = writes = mismatches = i = 0
            t0 = time.perf_counter()
            while time.perf_counter() < stop or (
                every and writes < 5 and time.perf_counter() < hard_stop
            ):
                if every and i % every == every - 1:
                    # writes land on the rows the hot queries read —
                    # the worst case for the cache, which is the point
                    fld.set_bit(
                        int(wrng.integers(0, 16)),
                        int(wrng.integers(0, 1 << 20)),
                    )
                    writes += 1
                else:
                    q = pool[zdraw[i % len(zdraw)]]
                    (got,) = ex.execute("zc", q)
                    if oracle is not None:
                        (want,) = oracle.execute("zc", q)
                        if str(got) != str(want):
                            mismatches += 1
                    reads += 1
                i += 1
            dt = time.perf_counter() - t0
            return reads / dt, writes / dt, mismatches

        cold_ex = Executor(h, device_policy="never")
        cached_ex = Executor(h, device_policy="never", plan_cache=PlanCache())
        seg = max(1.5, min(6.0, budget_s / 5))
        for q in pool[:6]:  # warm both paths' Python/JIT overheads
            cold_ex.execute("zc", q)
            cached_ex.execute("zc", q)
        cold_qps, _, _ = arm(cold_ex, seg)
        hot_qps, _, _ = arm(cached_ex, seg)
        st = cached_ex.plan_cache.stats()
        out["cold_qps"] = round(cold_qps, 1)
        out["hot_qps"] = round(hot_qps, 1)
        out["speedup"] = round(hot_qps / cold_qps, 2) if cold_qps else None
        out["hit_ratio"] = st["hit_ratio"]
        out["bytes_resident"] = st["bytes"]
        out["entries"] = st["entries"]
        # write arm: cached executor + 1% writes, every read checked
        # bit-for-bit against an uncached oracle on the same holder
        inv0 = cached_ex.plan_cache.stats()["invalidations"]
        w_qps, wps, mism = arm(
            cached_ex, seg, write_frac=WRITE_FRAC, oracle=cold_ex, wnonce=1
        )
        st = cached_ex.plan_cache.stats()
        out["write_arm"] = {
            # oracle double-execution halves qps; correctness arm, not
            # a throughput claim
            "read_qps_with_oracle_check": round(w_qps, 1),
            "writes_per_s": round(wps, 1),
            "invalidations": st["invalidations"] - inv0,
            "result_mismatches_vs_uncached_oracle": mism,
        }
    finally:
        h.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _tenant_mix_probe(budget_s: float) -> dict:
    """Multi-tenant isolation under an abusive neighbor (ISSUE 19):
    dozens of index tenants with Zipf-distributed offered load share
    one server, then one extra tenant goes flat-out at >=10x the rate
    its weight entitles it to. The abuser's excess must be refused with
    per-tenant 429s (admitted rate tracks its cap), and the p50 of the
    well-behaved population must move <10% vs a no-abuser baseline
    segment — its burst is invisible to everyone else."""
    import json as _json
    import shutil as _shutil
    import tempfile
    import urllib.error
    import urllib.request

    from pilosa_tpu.server import Config, Server

    n_tenants = int(os.environ.get("PILOSA_BENCH_TENANTS", 24))
    zipf_s = 1.1
    abuser = "noisy"
    abuser_qps = 5.0  # explicit cap == what its weight-1 share buys it

    out = {
        "note": (
            f"{n_tenants} Zipf-traffic tenants + 1 abusive tenant on one "
            "server (chip-independent: measures per-tenant admission and "
            "weighted-fair scheduling, not the kernel)"
        ),
        "tenants": n_tenants,
        "zipf_s": zipf_s,
    }
    tenants = [f"t{i}" for i in range(n_tenants)]
    tmp = tempfile.mkdtemp(prefix="pilosa_tenant_probe_")
    cfg = Config(
        data_dir=tmp,
        bind="127.0.0.1:0",
        device_policy="never",
        device_timeout=0,
        metric="none",
        tenant_weights=f"*=4,{abuser}=1",
        tenant_qps=f"{abuser}={abuser_qps:g}",
        tenant_objectives="*=500@0.99",
    )
    s = Server(cfg)
    s.open()
    try:
        def post(path, body):
            r = urllib.request.Request(s.uri + path, data=body, method="POST")
            with urllib.request.urlopen(r, timeout=30) as resp:
                return resp.read()

        for idx in tenants + [abuser]:
            post(f"/index/{idx}", b"{}")
            post(f"/index/{idx}/field/f", b"{}")
            post(f"/index/{idx}/query", b"Set(1, f=1)")

        # Zipf offered load: tenant i trickles at base/(i+1)^s qps. One
        # thread per tenant — a paced open-ish loop (sleep between
        # queries) so slow tenants don't block fast ones.
        paces = [
            1.0 / max(0.5, 8.0 / ((i + 1) ** zipf_s))
            for i in range(n_tenants)
        ]

        def drive(seconds, with_abuser):
            stop = time.perf_counter() + seconds
            lats: dict[str, list] = {t: [] for t in tenants}
            codes: dict[int, int] = {}
            non200: dict[str, int] = {}
            codes_lock = threading.Lock()
            errors = []

            def well_behaved(ti):
                t = tenants[ti]
                body = b"Count(Row(f=1))"
                try:
                    while time.perf_counter() < stop and not errors:
                        t0 = time.perf_counter()
                        try:
                            post(f"/index/{t}/query", body)
                            lats[t].append(time.perf_counter() - t0)
                        except urllib.error.HTTPError as e:
                            # a well-behaved tenant should never be
                            # shed; record it rather than abort the run
                            with codes_lock:
                                k = f"wb_{e.code}"
                                non200[k] = non200.get(k, 0) + 1
                        time.sleep(paces[ti])
                except BaseException as e:
                    errors.append(e)

            def abuse():
                body = b"Count(Row(f=1))"
                try:
                    while time.perf_counter() < stop and not errors:
                        try:
                            post(f"/index/{abuser}/query", body)
                            with codes_lock:
                                codes[200] = codes.get(200, 0) + 1
                        except urllib.error.HTTPError as e:
                            with codes_lock:
                                codes[e.code] = codes.get(e.code, 0) + 1
                            if e.code not in (429, 503):
                                raise
                            # nudge under the advertised Retry-After so
                            # shed churn doesn't melt the 1-core host
                            time.sleep(0.005)
                except BaseException as e:
                    errors.append(e)

            ts = [
                threading.Thread(target=well_behaved, args=(ti,))
                for ti in range(n_tenants)
            ]
            if with_abuser:
                ts.append(threading.Thread(target=abuse))
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errors:
                raise errors[0]
            dt = time.perf_counter() - t0
            return lats, codes, non200, dt

        def p50(xs):
            return sorted(xs)[len(xs) // 2] if xs else 0.0

        seg = max(3.0, min(9.0, (budget_s - 4.0) / 2.0))
        drive(min(2.0, budget_s * 0.1), with_abuser=False)  # warm
        base_lats, _, _, _ = drive(seg, with_abuser=False)
        mix_lats, codes, non200, dt = drive(seg, with_abuser=True)

        pool_base = [v for xs in base_lats.values() for v in xs]
        pool_mix = [v for xs in mix_lats.values() for v in xs]
        b50, m50 = p50(pool_base), p50(pool_mix)
        admitted = codes.get(200, 0)
        throttled = codes.get(429, 0)
        offered_rate = (admitted + throttled) / dt
        admitted_rate = admitted / dt
        out["abuser"] = {
            "weight": 1,
            "qps_cap": abuser_qps,
            "offered_rate": round(offered_rate, 1),
            "admitted_rate": round(admitted_rate, 2),
            "throttled_429": throttled,
            "offered_x_cap": round(offered_rate / abuser_qps, 1),
            "codes": dict(codes),
        }
        out["well_behaved_p50_ms"] = {
            "no_abuser": round(b50 * 1000.0, 3),
            "with_abuser": round(m50 * 1000.0, 3),
            "delta_pct": round((m50 - b50) / b50 * 100.0, 1) if b50 else 0.0,
        }
        out["per_tenant_p50_ms_with_abuser"] = {
            t: round(p50(xs) * 1000.0, 3) for t, xs in mix_lats.items()
        }
        out["well_behaved_non_200s"] = dict(non200)
        snap = _json.loads(
            urllib.request.urlopen(s.uri + "/debug/tenancy", timeout=30).read()
        )
        out["isolated"] = bool(
            throttled > 0
            and not non200
            and offered_rate >= abuser_qps * 10
            and admitted_rate <= abuser_qps * (1.0 + 2.0 / seg) * 1.5
            and snap.get("pipeline", {}).get("weighted_fair")
        )
    finally:
        s.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def _keyed_mix_probe(budget_s: float) -> dict:
    """Keyed-vs-raw serving cost (ISSUE 20): the same warm TopN traffic
    through a keyed index (string keys resolved pre-canonicalization,
    results reverse-translated through the bounded LRU) and through its
    raw-id twin. Acceptance: keyed warm qps >= 0.9x raw (the translate
    layer must be a lookup, not a tax); the LRU hit ratio is reported —
    warm reverse translation should be ~all hits."""
    import json as _json
    import shutil as _shutil
    import tempfile
    import urllib.request

    from pilosa_tpu.server import Config, Server

    n_cols = int(os.environ.get("PILOSA_BENCH_KEYED_COLS", 2000))
    n_rows = 16

    out = {
        "note": (
            "warm TopN qps through a keyed index vs its raw-id twin "
            "(chip-independent: measures the translate layer, not the "
            "kernel)"
        ),
        "columns": n_cols,
        "rows": n_rows,
    }
    tmp = tempfile.mkdtemp(prefix="pilosa_keyed_probe_")
    cfg = Config(
        data_dir=tmp,
        bind="127.0.0.1:0",
        device_policy="never",
        device_timeout=0,
        metric="none",
    )
    s = Server(cfg)
    s.open()
    try:
        def post(path, body):
            r = urllib.request.Request(s.uri + path, data=body, method="POST")
            with urllib.request.urlopen(r, timeout=60) as resp:
                return resp.read()

        post("/index/k", _json.dumps({"options": {"keys": True}}).encode())
        post(
            "/index/k/field/f",
            _json.dumps({"options": {"keys": True}}).encode(),
        )
        post("/index/r", b"{}")
        post("/index/r/field/f", b"{}")

        # keyed load (mints every key), then the identical bits by
        # pre-translated raw ids into the twin
        batch = 500
        for at in range(0, n_cols, batch):
            cols = [f"user-{j:05d}" for j in range(at, min(at + batch, n_cols))]
            rows = [f"seg-{j % n_rows:02d}" for j in range(at, min(at + batch, n_cols))]
            post(
                "/index/k/field/f/ingest",
                _json.dumps({"rowKeys": rows, "columnKeys": cols}).encode(),
            )
        ts = s.translate_store
        for at in range(0, n_cols, batch):
            cols = [f"user-{j:05d}" for j in range(at, min(at + batch, n_cols))]
            rows = [f"seg-{j % n_rows:02d}" for j in range(at, min(at + batch, n_cols))]
            cids = ts.translate_columns_to_ids("k", cols, create=False)
            rids = ts.translate_rows_to_ids("k", "f", rows, create=False)
            post(
                "/index/r/field/f/ingest",
                _json.dumps({"rowIDs": rids, "columnIDs": cids}).encode(),
            )

        # bulk ingest bypasses the ranked TopN cache — force the
        # recalculation so TopN serves real candidate rows (and the
        # keyed side really pays/amortizes reverse translation)
        post("/recalculate-caches", b"")
        q = b"TopN(f, n=10)"

        def drive(index, seconds):
            # warm first (stager fill + LRU fill), then a timed
            # closed loop; ?cache=false so the plan cache doesn't
            # collapse the measurement into one lookup
            path = f"/index/{index}/query?cache=false"
            for _ in range(5):
                post(path, q)
            n = 0
            t0 = time.perf_counter()
            stop = t0 + seconds
            while time.perf_counter() < stop:
                post(path, q)
                n += 1
            return n / (time.perf_counter() - t0)

        seg = max(2.0, min(8.0, (budget_s - 4.0) / 2.0))
        raw_qps = drive("r", seg)
        keyed_qps = drive("k", seg)
        ratio = keyed_qps / raw_qps if raw_qps else 0.0
        dbg = _json.loads(
            urllib.request.urlopen(s.uri + "/debug/translate", timeout=30).read()
        )
        out["raw_qps"] = round(raw_qps, 1)
        out["keyed_qps"] = round(keyed_qps, 1)
        out["keyed_vs_raw"] = round(ratio, 3)
        out["lru_hit_ratio"] = dbg["cache"].get("hitRatio")
        out["keys"] = dbg["keys"]
        out["acceptance"] = ">=0.9 warm"
        out["pass"] = ratio >= 0.9
    finally:
        s.close()
        _shutil.rmtree(tmp, ignore_errors=True)
    return out


def main():
    import os

    import jax
    import jax.numpy as jnp

    # persist XLA compiles so a cold run pays them only once
    from pilosa_tpu.utils.jaxplatform import bootstrap

    bootstrap()

    import os

    # Self-enforced deadline: the parent SIGKILLs this child at its
    # timeout, which would lose everything measured so far. A guard
    # thread prints the progressively-filled result dict (marked
    # partial) and exits just before that happens — cold compiles and
    # first-time HBM staging at the 1B scale are the usual overrunners.
    child_budget = float(os.environ.get("PILOSA_BENCH_CHILD_BUDGET", 400))
    result: dict = {
        "metric": "TopN queries/sec (measurement incomplete)",
        "value": 0.0,
        "unit": "queries/s",
        "vs_baseline": None,
    }
    printed = threading.Event()
    emit_lock = threading.Lock()

    def emit(final: bool) -> None:
        with emit_lock:
            if printed.is_set():
                return
            # dict(result) is one C-level copy (atomic under the GIL);
            # dumping the live dict could race a concurrent update
            snapshot = dict(result)
            # the same metric names the server exports at /metrics
            # (pilosa_tpu/utils/metrics.py) — whatever the in-process
            # executor/batcher/stager instrumentation observed this run
            try:
                from pilosa_tpu.utils import metrics as _metrics

                snapshot["metrics"] = _metrics.snapshot()
            except Exception:
                pass
            # workload heat + placement skew (ISSUE 16): top-K hot
            # shards and imbalance ratio, the baseline curve future
            # tiering/rebalancing PRs compare against
            try:
                from pilosa_tpu.utils import heat as _heat

                hs = _heat.snapshot(dim="reads")
                snapshot["heat"] = {
                    "cells": len(hs["cells"]),
                    "skew": hs["skew"],
                }
            except Exception:
                pass
            # a result without a measured headline must never be
            # persisted over the last COMPLETE measurement
            if not final or snapshot.get("value", 0.0) == 0.0:
                snapshot["partial"] = True
            line = json.dumps(snapshot)
            printed.set()
            # print INSIDE the lock: the guard may os._exit immediately
            # after observing printed — the line must be out by then
            print(line, flush=True)

    def guard():
        remaining = child_budget - (time.monotonic() - _T_PROC_START) - 15
        if remaining > 0 and printed.wait(timeout=remaining):
            return
        emit(final=False)
        os._exit(0)

    threading.Thread(target=guard, daemon=True).start()
    result["platform"] = jax.devices()[0].platform

    # ---- Full-path north-star config FIRST (BASELINE config 4: 1B
    # rows, 64 shards) — it is the headline metric and must not starve
    # behind the kernel microbench when the budget is tight. The data
    # dir builds resumably into .bench_cache/; a kernel-bench reserve is
    # held back so the secondary numbers still get measured.
    tall = None
    if os.environ.get("PILOSA_BENCH_TALL", "1") != "0":
        try:
            import bench_tall

            # resume: a complete same-revision tall part from an attempt
            # wedged later in ITS run (or an earlier attempt of this
            # invocation) is this round's measurement — reuse it instead
            # of burning the budget again
            cached = load_part("tall")
            if cached and cached.get("topn_qps") and cached.get(
                "platform"
            ) == result["platform"]:
                tall = cached
                # top-level marker: the headline below comes from a
                # same-revision checkpoint of an earlier attempt, not
                # a measurement taken by THIS invocation
                result["tall_checkpointed"] = True
                result["tall_checkpoint_age_s"] = cached.get(
                    "checkpointed_age_s"
                )
            else:
                spent = time.monotonic() - _T_PROC_START
                # the full-path number is what matters: it gets the
                # budget minus a small reserve; the kernel microbench
                # below only runs if time is left (its numbers also
                # live in BENCH_r* history)
                tall_deadline = child_budget - spent - 70
                if tall_deadline > 75:
                    tall = bench_tall.run(deadline_s=tall_deadline)
                    if tall.get("topn_qps") and not tall.get("error"):
                        save_part("tall", tall)
            if tall is not None:
                result["tall"] = tall
                if tall.get("topn_qps"):
                    rows = tall["build"]["rows"]
                    # Headline = the best measured closed-loop serving
                    # number: the baseline (reference server / native
                    # proxy x cores) is concurrent server throughput,
                    # so the apples-to-apples headline is ours under
                    # concurrency too. Sequential qps always rides
                    # in seq_qps. A budget-cut run that only measured
                    # sequential reports that, labeled.
                    mode, headline = headline_mode(tall)
                    result["metric"] = (
                        f"TopN queries/sec (full path, {rows:,} rows x "
                        f"{tall['shards']} shards, single chip, {mode})"
                    )
                    result["value"] = headline
                    result["seq_qps"] = tall["topn_qps"]
                    # explicitly SEQUENTIAL p50 (one in-flight query)
                    # — named so the artifact can't be misread as
                    # closed-loop latency
                    result["seq_p50_ms"] = tall["topn_p50_ms"]
                    bk, _ = best_closed_loop(tall, "topn_qps_c")
                    if mode != "sequential" and bk:
                        cp = tall.get(
                            "topn_p50_ms_c" + bk.rsplit("c", 1)[1]
                        )
                        if cp is not None:
                            # per-query latency AT the headline
                            # concurrency (queueing included)
                            result["closed_p50_ms"] = cp
                    result.update(
                        vs_baseline_fields(
                            mode,
                            headline,
                            tall.get("cpu_topn_qps"),
                            cpu_closed_qps=tall.get("cpu_topn_qps_c4"),
                            seq_qps=tall.get("topn_qps"),
                        )
                    )
                    # window self-qualification rides next to the
                    # headline (VERDICT item 4): sustained RTT +
                    # achieved pipelining depth, consumed by the
                    # last-good gating in _guarded_main
                    wq = window_quality(tall)
                    if wq is not None:
                        result["window_quality"] = wq
        except Exception as e:  # keep the JSON line flowing
            print(f"tall bench failed: {type(e).__name__}: {e}", file=sys.stderr)

    # ---- native C++ baseline (the Go-reference proxy, measured offline
    # by native/baseline_topn.cpp): attach before any early return — it
    # costs only a local file read and belongs with the tall headline.
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE_NATIVE.json")) as f:
            _native = json.load(f)["measured"]
        result["native_baseline"] = {
            k: v.get("native_cpu_qps") for k, v in _native.items()
        }
        _tall_rows = result.get("tall", {}).get("build", {}).get("rows", 0)
        # only compare against the native 1B numbers when THIS run was
        # actually at (or near) the 1B scale
        if _tall_rows >= 900_000_000:
            for native_key, tall_key, out_key in (
                ("tall_1Bx64shards", "topn_qps", "vs_native_baseline"),
                ("tall_chains_1Bx64shards", "chain_qps", "chain_vs_native_baseline"),
            ):
                nv = _native.get(native_key, {}).get("native_cpu_qps")
                tv = result.get("tall", {}).get(tall_key)
                if nv and tv:
                    result[out_key] = round(tv / nv, 2)
            # Serving margin vs the CORE-SCALED baseline (BASELINE.md
            # convention: native single-core x8 ~= the reference server
            # parallelizing shards over an 8-core box). The serving
            # number is the best measured concurrency level — the
            # closed-loop concurrent number is what a deployment sees.
            # prefix matches ONLY the closed-loop concurrency keys
            # (topn_qps_c8/_c32/_c64...) — a budget-cut run that only
            # measured the sequential number must not publish
            # it under a serving label
            for native_key, prefix, out_key in (
                ("tall_1Bx64shards", "topn_qps_c", "topn_vs_native_core8"),
                ("tall_chains_1Bx64shards", "chain_qps_c", "chain_vs_native_core8"),
            ):
                nv = _native.get(native_key, {}).get("native_cpu_qps")
                _, best = best_closed_loop(result.get("tall", {}), prefix)
                if nv and best:
                    result[out_key] = {
                        "serving_qps": best,
                        "native_core8_qps": round(nv * 8, 2),
                        "margin": round(best / (nv * 8), 2),
                    }
            # per-window chain margins (VERDICT chain-margin
            # instability): the margin at EVERY measured chain
            # concurrency window, not just the best — so a single good
            # window can't mask degraded siblings in the artifact
            _cnv = _native.get("tall_chains_1Bx64shards", {}).get(
                "native_cpu_qps"
            )
            if _cnv:
                _cm = {
                    k: round(v / (_cnv * 8), 2)
                    for k, v in result.get("tall", {}).items()
                    if k.startswith("chain_qps_c")
                    and isinstance(v, (int, float))
                }
                if _cm:
                    result["chain_margins_per_window"] = _cm
    except Exception as e:  # any malformed baseline file — keep the JSON flowing
        print(f"native baseline unavailable: {type(e).__name__}: {e}", file=sys.stderr)

    # ---- serving pipeline probe (ISSUE 2): closed-loop HTTP qps
    # through the new admission/batching layer + overload shed
    # behavior. Cheap (~15 s, CPU path) and chip-independent.
    if os.environ.get("PILOSA_BENCH_PIPELINE", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 60:
            try:
                result["serving_pipeline"] = _pipeline_serving_probe(
                    min(20.0, rem - 35)
                )
            except Exception as e:
                print(
                    f"pipeline probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- read/write-mix probe (ISSUE 3): steady-state read qps under
    # 1% single-bit writes, delta staging vs forced full re-stage.
    if os.environ.get("PILOSA_BENCH_RWMIX", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 55:
            try:
                result["rw_mix"] = _rw_mix_probe(min(28.0, rem - 35))
            except Exception as e:
                print(
                    f"rw_mix probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- plan-cache probe (ISSUE 4): Zipf-repeated TopN/Intersect mix
    # through the generation-stamped result cache — hot vs cold qps,
    # hit ratio, bytes resident, and a 1%-write invalidation-
    # correctness arm checked against an uncached oracle.
    if os.environ.get("PILOSA_BENCH_PLANCACHE", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 50:
            try:
                result["cached_qps"] = _plan_cache_probe(min(25.0, rem - 30))
            except Exception as e:
                print(
                    f"plan-cache probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- continuous-batching probe (ISSUE 8): closed-loop c8/c32 qps
    # + device-idle fraction, dispatch engine vs blocking execution.
    if os.environ.get("PILOSA_BENCH_DISPATCH", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 60:
            try:
                result["continuous_batching"] = _continuous_batching_probe(
                    min(30.0, rem - 30)
                )
            except Exception as e:
                print(
                    f"continuous-batching probe failed: "
                    f"{type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- durable ingest probe (ISSUE 11): sustained >=10% writes
    # through the write-ahead queue (ack = group commit + fsync) vs a
    # read-only baseline, write-ack p50/p99, bounded staleness, an
    # uncached oracle check, and a federated rejoin-mid-stream sub-arm.
    if os.environ.get("PILOSA_BENCH_INGEST", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 60:
            try:
                result["ingest_sustained"] = _ingest_sustained_probe(
                    min(30.0, rem - 35)
                )
            except Exception as e:
                print(
                    f"ingest probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- tiered-staging oversubscription probe (ISSUE 17): hot-set
    # p50 with T0 holding the whole set vs a third of it, T1 host tier
    # + compressed upload + plan-driven prefetch absorbing re-entry.
    if os.environ.get("PILOSA_BENCH_TIERING", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 55:
            try:
                result["tiering_oversub"] = _tiering_oversub_probe(
                    min(24.0, rem - 30)
                )
                try:
                    with open(
                        os.path.join(_REPO_DIR, "TIERING_r17.json"), "w"
                    ) as f:
                        json.dump(
                            {
                                "ts": time.time(),
                                "platform": result.get("platform"),
                                **result["tiering_oversub"],
                            },
                            f,
                            indent=1,
                        )
                except OSError as e:
                    print(
                        f"could not write TIERING_r17.json: {e}",
                        file=sys.stderr,
                    )
            except Exception as e:
                print(
                    f"tiering probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- dashboard-mix probe (ISSUE 18): interactive TopN/Count p50
    # with a fused GroupBy panel loop alongside vs analytics off, plus
    # fused launches per panel under concurrency.
    if os.environ.get("PILOSA_BENCH_ANALYTICS", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 50:
            try:
                result["dashboard_mix"] = _dashboard_mix_probe(
                    min(22.0, rem - 28)
                )
                try:
                    with open(
                        os.path.join(_REPO_DIR, "ANALYTICS_r18.json"), "w"
                    ) as f:
                        json.dump(
                            {
                                "ts": time.time(),
                                "platform": result.get("platform"),
                                **result["dashboard_mix"],
                            },
                            f,
                            indent=1,
                        )
                except OSError as e:
                    print(
                        f"could not write ANALYTICS_r18.json: {e}",
                        file=sys.stderr,
                    )
            except Exception as e:
                print(
                    f"dashboard-mix probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- tenant-mix probe (ISSUE 19): dozens of Zipf-traffic tenants
    # + one abusive tenant; abuser throttled to its weight's qps while
    # the well-behaved population's p50 holds vs a no-abuser baseline.
    if os.environ.get("PILOSA_BENCH_TENANCY", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 50:
            try:
                result["tenant_mix"] = _tenant_mix_probe(min(22.0, rem - 28))
                try:
                    with open(
                        os.path.join(_REPO_DIR, "TENANCY_r19.json"), "w"
                    ) as f:
                        json.dump(
                            {
                                "ts": time.time(),
                                "platform": result.get("platform"),
                                **result["tenant_mix"],
                            },
                            f,
                            indent=1,
                        )
                except OSError as e:
                    print(
                        f"could not write TENANCY_r19.json: {e}",
                        file=sys.stderr,
                    )
            except Exception as e:
                print(
                    f"tenant-mix probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # ---- keyed-mix probe (ISSUE 20): warm TopN through a keyed index
    # vs its raw-id twin; keyed must hold >=0.9x raw qps with the
    # reverse-translation LRU absorbing the id->key cost.
    if os.environ.get("PILOSA_BENCH_KEYED", "1") != "0":
        rem = child_budget - (time.monotonic() - _T_PROC_START)
        if rem > 45:
            try:
                result["keyed_mix"] = _keyed_mix_probe(min(18.0, rem - 25))
            except Exception as e:
                print(
                    f"keyed-mix probe failed: {type(e).__name__}: {e}",
                    file=sys.stderr,
                )

    # a fresh same-revision checkpointed kernel is free — use it even
    # when the remaining budget couldn't afford a fresh measurement
    cached_kernel = load_part("kernel")
    if not (
        cached_kernel and cached_kernel.get("platform") == result["platform"]
    ) and child_budget - (time.monotonic() - _T_PROC_START) < 210:
        # Not enough room for the kernel microbench (measured ~160 s
        # warm: matrix build + compile + three paths) — ship the
        # complete tall headline rather than risk the deadline guard
        # marking the whole line partial over the secondary numbers.
        result["kernel_bench"] = "skipped (budget)"
        emit(final=True)
        return

    if cached_kernel and cached_kernel.get("platform") == result["platform"]:
        result.update(
            {k: v for k, v in cached_kernel.items() if k != "platform"}
        )
        result["kernel_checkpointed"] = True
        if not (tall and tall.get("topn_qps")) and cached_kernel.get("kernel_qps"):
            result.update(
                {
                    "metric": "TopN queries/sec (kernel microbench, single chip)",
                    "value": cached_kernel["kernel_qps"],
                    "vs_baseline": cached_kernel.get("kernel_vs_baseline"),
                    "seq_p50_ms": cached_kernel.get("kernel_p50_ms"),
                    "baseline_cpu_qps": cached_kernel.get("kernel_cpu_qps"),
                }
            )
        emit(final=True)
        return

    R = int(os.environ.get("PILOSA_BENCH_ROWS", 4096))
    W64 = 16384  # uint64 words per row (2^20 columns)
    DENSITY = 0.015625  # 2^-6 via 6-way AND
    N_QUERIES = int(os.environ.get("PILOSA_BENCH_QUERIES", 64))
    TOPK = 10

    rng = np.random.default_rng(11)
    # Synthetic packed fragment at ~2^-6 ≈ 1.6% density: AND of 6
    # uniform word streams (vectorised; per-bit P(set) = 0.5^6).
    mat64 = rng.integers(0, 2**64, size=(R, W64), dtype=np.uint64)
    for _ in range(5):
        mat64 &= rng.integers(0, 2**64, size=(R, W64), dtype=np.uint64)
    mat32 = mat64.view("<u4")

    q_rows = rng.integers(0, R, size=N_QUERIES)  # source row ids per query

    # ---- TPU path: staged-source intersection-count + top_k ----
    # TopN(Row(r))'s source is row r of the staged fragment; index it
    # out of HBM instead of re-uploading from host (stager.row path).
    @jax.jit
    def topn_step(row_id, mat):
        src = mat[row_id]
        scores = jnp.sum(
            jax.lax.population_count(jnp.bitwise_and(mat, src[None, :])).astype(
                jnp.int32
            ),
            axis=-1,
        )
        counts, ids = jax.lax.top_k(scores, TOPK)
        return ids, counts

    def force(out):
        """True completion: fetch one element host-side, so
        everything below measures COMPLETED queries."""
        return np.asarray(out[0].ravel()[:1])

    dev_mat = jax.device_put(mat32)
    # warmup / compile
    force(topn_step(int(q_rows[0]), dev_mat))

    # Latency: true round-trip (dispatch + completion + fetch) per
    # query.
    lat = []
    for q in range(N_QUERIES):
        t0 = time.perf_counter()
        force(topn_step(int(q_rows[q]), dev_mat))
        lat.append(time.perf_counter() - t0)
    p50 = sorted(lat)[len(lat) // 2] * 1000

    # Throughput: pipelined dispatch, then force completion of every
    # query's result.
    t_all = time.perf_counter()
    outs = [topn_step(int(q_rows[q]), dev_mat) for q in range(N_QUERIES)]
    for o in outs:
        force(o)
    tpu_qps = N_QUERIES / (time.perf_counter() - t_all)

    # ---- Pallas-tiled variant (TPU only): keep whichever is faster ----
    from pilosa_tpu.ops.pallas_kernels import (
        intersection_counts_matrix_batch_pallas,
        intersection_counts_matrix_pallas,
        pad_for_pallas,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    # one padded staged copy shared by the pallas and batched paths
    try:
        padded, true_r = pad_for_pallas(mat32)
        dev_pmat = jax.device_put(padded)
        del padded
    except Exception as e:  # e.g. HBM OOM — keep the JSON line flowing
        print(f"pallas staging failed: {type(e).__name__}: {e}", file=sys.stderr)
        dev_pmat = None

    pallas_qps = 0.0
    if on_tpu and dev_pmat is not None:
        try:

            @jax.jit
            def topn_step_pallas(row_id, pmat):
                src = pmat[row_id]
                scores = intersection_counts_matrix_pallas(src, pmat)
                counts, ids = jax.lax.top_k(scores[:true_r], TOPK)
                return ids, counts

            force(topn_step_pallas(int(q_rows[0]), dev_pmat))
            t0 = time.perf_counter()
            pouts = [
                topn_step_pallas(int(q_rows[q]), dev_pmat) for q in range(N_QUERIES)
            ]
            for o in pouts:
                force(o)
            pallas_qps = N_QUERIES / (time.perf_counter() - t0)
        except Exception as e:  # keep the JSON line clean; surface the cause
            print(f"pallas path failed: {type(e).__name__}: {e}", file=sys.stderr)
            pallas_qps = 0.0
    # ---- Batched dispatch (server-style continuous batching): score
    # Q concurrent query sources per kernel launch; the matrix streams
    # from HBM once per batch instead of once per query (executor's
    # BatchedScorer coalesces concurrent requests the same way).
    batched_qps = 0.0
    BATCH = int(os.environ.get("PILOSA_BENCH_BATCH", 512))
    try:
        if dev_pmat is None:
            raise RuntimeError("staged matrix unavailable")
        dev_bmat = dev_pmat

        @jax.jit
        def topn_step_batch(row_ids, pmat):
            srcs = pmat[row_ids]
            if on_tpu:
                scores = intersection_counts_matrix_batch_pallas(srcs, pmat)
            else:
                from pilosa_tpu import ops as _ops

                scores = _ops.intersection_counts_matrix_batch(srcs, pmat)
            counts, ids = jax.lax.top_k(scores[:, :true_r], TOPK)
            return ids, counts

        n_batches = max(N_QUERIES // BATCH, 4)
        batch_ids = [
            jnp.asarray(rng.integers(0, R, size=BATCH)) for _ in range(n_batches)
        ]
        force(topn_step_batch(batch_ids[0], dev_bmat))
        t0 = time.perf_counter()
        bouts = [topn_step_batch(b, dev_bmat) for b in batch_ids]
        for o in bouts:
            force(o)
        batched_qps = n_batches * BATCH / (time.perf_counter() - t0)
    except Exception as e:
        print(f"batched path failed: {type(e).__name__}: {e}", file=sys.stderr)
        batched_qps = 0.0

    best_qps = max(tpu_qps, pallas_qps, batched_qps)

    # ---- CPU baseline: roaring per-candidate intersection counts ----
    # A TopN query walks every candidate row computing
    # src.intersection_count(row) (the reference's fragment.top hot loop).
    # Building all R roaring rows in Python is prohibitive, so measure a
    # SAMPLE of rows and extrapolate the per-query cost linearly in R —
    # the walk is embarrassingly linear in candidate count.
    from pilosa_tpu.roaring import Bitmap

    sample_n = 64
    rows_cpu = [Bitmap.from_words_range(mat64[i]) for i in range(sample_n)]
    src_b = Bitmap.from_words_range(mat64[q_rows[0]])
    t0 = time.perf_counter()
    reps = 2
    for _ in range(reps):
        for b in rows_cpu:
            src_b.intersection_count(b)
    per_row = (time.perf_counter() - t0) / (sample_n * reps)
    cpu_query_s = per_row * R
    cpu_qps = 1.0 / cpu_query_s

    # Roofline: each query's score pass reads the full packed matrix
    # (R x 16384 u64 words) as operands. Effective operand traffic =
    # qps x matrix bytes; compared against v5e HBM peak (~819 GB/s) it
    # shows WHERE the kernel sits — above peak means the staged tiles
    # are reused on-chip across the batch's sources (compute-bound),
    # below means HBM-bound.
    matrix_bytes = R * W64 * 8
    v5e_hbm_peak = 819e9
    kernel_fields = {
        "xla_qps": round(tpu_qps, 2),
        "pallas_qps": round(pallas_qps, 2),
        "batched_qps": round(batched_qps, 2),
        "batch_size": BATCH,
        "kernel_qps": round(best_qps, 2),
        "kernel_cpu_qps": round(cpu_qps, 3),
        "kernel_vs_baseline": round(best_qps / cpu_qps, 2),
        "kernel_p50_ms": round(p50, 3),
        "roofline": {
            "operand_bytes_per_query": matrix_bytes,
            "effective_operand_traffic_GBps": round(
                best_qps * matrix_bytes / 1e9, 1
            ),
            "v5e_hbm_peak_GBps": round(v5e_hbm_peak / 1e9),
            "fraction_of_hbm_peak": round(
                best_qps * matrix_bytes / v5e_hbm_peak, 2
            ),
            "arithmetic": (
                f"{R} rows x {W64} u64 words x 8 B = "
                f"{matrix_bytes / 1e6:.0f} MB operands/query; "
                "traffic = qps x that"
            ),
        },
    }
    result.update(kernel_fields)
    save_part("kernel", {**kernel_fields, "platform": result["platform"]})
    # the kernel microbench is the headline only when the full-path
    # north-star config didn't produce one
    if not (tall and tall.get("topn_qps")):
        result.update(
            {
                "metric": (
                    f"TopN queries/sec ({R} rows x 1M cols, ~2% density, "
                    "single chip)"
                ),
                "value": round(best_qps, 2),
                "vs_baseline": round(best_qps / cpu_qps, 2),
                "seq_p50_ms": round(p50, 3),
                "baseline_cpu_qps": round(cpu_qps, 3),
            }
        )

    try:
        kern_native = (
            result.get("native_baseline", {}).get("kernel_4096x1M")
        )
        if kern_native:
            result["kernel_vs_native_baseline"] = round(best_qps / kern_native, 2)
    except Exception as e:
        print(f"native kernel ratio unavailable: {type(e).__name__}: {e}", file=sys.stderr)

    emit(final=True)


def _cpu_fresh_main():
    """Child mode: measure every chip-independent metric fresh on the
    CPU backend (warm open, staging breakdown, CPU-path QPS). Run when
    the device never answers, so the artifact carries numbers measured
    by THIS code instead of a wholesale stale replay."""
    from pilosa_tpu.utils.jaxplatform import bootstrap

    bootstrap()
    import bench_tall

    budget = float(os.environ.get("PILOSA_BENCH_CHILD_BUDGET", 240))
    out = bench_tall.run_cpu_fresh(deadline_s=budget - 15)
    out["metric"] = "chip-independent fresh measurements (device unreachable)"
    out["measured_at_rev"] = _git_rev()
    print(json.dumps(out), flush=True)


def _probe_main():
    """Tiny device liveness check run in a disposable child: init the
    backend, round-trip one array. Exits 0 iff the device answered."""
    import jax

    d = jax.devices()[0]
    x = jax.device_put(np.arange(8, dtype=np.uint32))
    got = int(np.asarray(jax.numpy.sum(x)))
    assert got == 28, got
    print(f"probe ok: {d.platform}", file=sys.stderr)


LAST_GOOD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_last_good.json")


def _extract_json_line(text):
    """Last line of stdout that parses as a JSON object with 'metric'."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            return obj
    return None


def _guarded_main():
    """Run the measurement in a child process with a watchdog + retries.

    A device back end can wedge at client init (a hung PJRT
    make_c_api_client blocks SIGTERM-less in C code); without a guard
    the whole bench run would hang and emit nothing. Strategy:
      1. Probe the device with a short-timeout child; retry with
         backoff — a wedged device sometimes recovers between attempts.
      2. On a live device, run the real bench child (watchdog'd) and
         persist its JSON line to BENCH_last_good.json.
      3. If the device never answers (or the bench child dies), fall
         back to the last persisted good result marked stale=true —
         a flaky device degrades to stale-but-real instead of 0.0.
    """
    import subprocess
    import time as _time

    def _env_float(name, default):
        try:
            return float(os.environ.get(name, default))
        except ValueError:
            return float(default)

    # Everything — probes, backoff, the bench child, and printing the
    # JSON line — must finish inside this budget, because callers wrap
    # the whole run in an outer `timeout` that would kill us mid-write.
    budget_s = _env_float("PILOSA_BENCH_TIMEOUT", 520)
    deadline = _time.monotonic() + budget_s
    attempts = max(1, int(_env_float("PILOSA_BENCH_ATTEMPTS", 4)))
    me = os.path.abspath(__file__)

    def remaining(margin=10.0):
        return deadline - _time.monotonic() - margin

    def run_child(extra_env, child_timeout):
        env = dict(os.environ, **extra_env)
        try:
            return subprocess.run(
                [sys.executable, me],
                env=env,
                timeout=child_timeout,
                stdout=subprocess.PIPE,
                text=True,
            )
        except subprocess.TimeoutExpired:
            return None

    # Probes are short and ADAPTIVE (20s, 40s, 60s, ...): a healthy
    # backend answers a tiny round-trip in a few seconds even with a
    # cold init, so burning 75s per probe (the round-3 default) just
    # starves the measurement budget when the device is merely slow to
    # come up. Backoff between attempts gives a wedged device a chance
    # to recover without spending the whole budget waiting.
    probe_base = _env_float("PILOSA_BENCH_PROBE_TIMEOUT", 20)
    reason = "device probe never ran"
    alive = False
    for i in range(attempts):
        t = min(probe_base * (i + 1), remaining())
        if t <= 5:
            reason = "budget exhausted before device answered"
            break
        proc = run_child({"PILOSA_BENCH_PROBE": "1"}, t)
        if proc is not None and proc.returncode == 0:
            alive = True
            break
        reason = (
            f"device probe timed out after {t:.0f}s"
            if proc is None
            else f"device probe exited {proc.returncode}"
        )
        print(f"attempt {i + 1}/{attempts}: {reason}", file=sys.stderr)
        if i + 1 < attempts and remaining() > 30:
            _time.sleep(min(5 * (i + 1), 20))

    if alive and remaining() <= 60:
        alive = False
        reason = "device alive but budget too small to run the bench"
    # The bench child gets up to TWO attempts: sub-results checkpoint
    # to .bench_cache/bench_parts.json as they complete, so a child
    # that dies mid-run (device wedge) is resumed by the next attempt
    # reusing every fresh same-revision part instead of starting over.
    child_tries = 0
    while alive and child_tries < 2 and remaining() > 60:
        child_tries += 1
        child_timeout = remaining()
        proc = run_child(
            {
                "PILOSA_BENCH_CHILD": "1",
                "PILOSA_BENCH_CHILD_BUDGET": str(child_timeout),
            },
            child_timeout,
        )
        if proc is None:
            reason = f"bench child timed out after {child_timeout:.0f}s"
            continue
        if proc.returncode != 0:
            reason = f"bench child exited {proc.returncode}"
            continue
        obj = _extract_json_line(proc.stdout)
        if obj is None:
            reason = "bench child produced no JSON line"
            continue
        if obj.get("platform") == "tpu" and not obj.get("partial"):
            # a deadline-cut partial must never shadow the last
            # COMPLETE real-device measurement. Only a real-device
            # result is worth replaying later; a CPU smoke run must
            # not masquerade as the TPU number. Window gating (VERDICT
            # item 4): a run measured in a degraded window (slow RTT,
            # collapsed pipelining depth vs the last-good's recorded
            # window_quality) keeps ITS OWN JSON line but must not
            # displace the last-good artifact. Write-then-rename so a
            # killed writer can't truncate the previous good file.
            old_wq = None
            try:
                with open(LAST_GOOD) as f:
                    old_wq = (json.load(f) or {}).get("window_quality")
            except (OSError, ValueError):
                pass
            degraded, why = window_degraded(obj.get("window_quality"), old_wq)
            if degraded:
                obj["window_degraded"] = why
                print(
                    f"degraded window — keeping prior BENCH_last_good.json: {why}",
                    file=sys.stderr,
                )
            else:
                try:
                    tmp = LAST_GOOD + ".tmp"
                    with open(tmp, "w") as f:
                        json.dump(obj, f)
                        f.write("\n")
                    os.replace(tmp, LAST_GOOD)
                except OSError as e:
                    print(f"could not persist last-good: {e}", file=sys.stderr)
        print(json.dumps(obj))
        return
    print(reason, file=sys.stderr)

    # Before replaying a PRIOR run: assemble from this revision's fresh
    # checkpointed parts — numbers measured by THIS code minutes ago
    # beat a stale replay.
    tall_part = load_part("tall")
    kern_part = load_part("kernel")

    # The device never answered — but most of the system is HOST work
    # that can still be measured NOW: warm open, staging pack, CPU-path
    # QPS. Run them fresh on the CPU backend and partition them from
    # anything replayed below (VERDICT r4: a full-stale replay carried
    # open_warm_s=134.5 while the shipped code opened in ~4 s). Skipped
    # when a fresh same-session tall checkpoint already carries those
    # numbers — re-measuring them would burn the margin that protects
    # the final JSON write from the caller's outer timeout.
    fresh_cpu = None
    if remaining() > 120 and not (tall_part and tall_part.get("topn_qps")):
        proc = run_child(
            {
                "PILOSA_BENCH_CPU_FRESH": "1",
                "JAX_PLATFORMS": "cpu",
                "PILOSA_BENCH_CHILD_BUDGET": str(remaining(margin=20.0)),
            },
            remaining(margin=15.0),
        )
        if proc is not None and proc.returncode == 0:
            fresh_cpu = _extract_json_line(proc.stdout)
            if fresh_cpu:
                fresh_cpu.pop("metric", None)
        if fresh_cpu is None:
            print("cpu-fresh measurement failed", file=sys.stderr)

    def attach_fresh(out: dict) -> dict:
        if fresh_cpu:
            out["fresh_cpu"] = fresh_cpu
            out["note"] = (
                "fresh_cpu fields were measured by THIS run on the CPU "
                "backend and supersede the same-named fields inside any "
                "replayed/checkpointed section"
            )
        return out
    if not (tall_part and tall_part.get("topn_qps")) and kern_part and kern_part.get(
        "kernel_qps"
    ):
        # no tall part, but a fresh same-revision kernel measurement
        # still beats a prior revision's stale replay
        out = {
            "metric": "TopN queries/sec (kernel microbench, single chip)",
            "value": kern_part["kernel_qps"],
            "unit": "queries/s",
            "vs_baseline": kern_part.get("kernel_vs_baseline"),
            "seq_p50_ms": kern_part.get("kernel_p50_ms"),
            "platform": kern_part.get("platform"),
            "assembled_from_checkpoints": True,
            "error": f"final attempt failed ({reason}); kernel part is a "
            "fresh same-revision measurement from this session",
        }
        out.update({k: v for k, v in kern_part.items() if k != "platform"})
        print(json.dumps(attach_fresh(out)))
        return
    if tall_part and tall_part.get("topn_qps"):
        # same headline convention as the live path (one definition:
        # headline_mode): best closed-loop serving number when one was
        # measured and beat sequential, else sequential, labeled either way
        mode, headline = headline_mode(tall_part)
        out = {
            "metric": (
                f"TopN queries/sec (full path, "
                f"{tall_part.get('build', {}).get('rows', 0):,} rows x "
                f"{tall_part.get('shards')} shards, single chip, {mode})"
            ),
            "value": headline,
            "seq_qps": tall_part["topn_qps"],
            "unit": "queries/s",
            **vs_baseline_fields(
                mode,
                headline,
                tall_part.get("cpu_topn_qps"),
                cpu_closed_qps=tall_part.get("cpu_topn_qps_c4"),
                seq_qps=tall_part.get("topn_qps"),
            ),
            "platform": tall_part.get("platform"),
            "tall": tall_part,
            "seq_p50_ms": tall_part.get("topn_p50_ms"),
            "assembled_from_checkpoints": True,
            "error": f"final attempt failed ({reason}); parts are fresh "
            "same-revision measurements from this session",
        }
        wq = window_quality(tall_part)
        if wq is not None:
            out["window_quality"] = wq
        bk, _ = best_closed_loop(tall_part, "topn_qps_c")
        if mode != "sequential" and bk:
            cp = tall_part.get("topn_p50_ms_c" + bk.rsplit("c", 1)[1])
            if cp is not None:
                out["closed_p50_ms"] = cp
        if kern_part:
            out.update({k: v for k, v in kern_part.items() if k != "platform"})
        print(json.dumps(attach_fresh(out)))
        return

    # Fallback: replay the last good DEVICE measurement, marked as the
    # replayed partition — fresh_cpu (above) carries everything this
    # run could honestly re-measure without the chip.
    try:
        with open(LAST_GOOD) as f:
            obj = json.load(f)
        obj["stale"] = True
        obj["stale_device"] = True
        obj["error"] = (
            f"device fields replayed from last good on-chip run; this "
            f"run failed: {reason}"
        )
        print(json.dumps(attach_fresh(obj)))
        return
    except (OSError, ValueError):
        pass
    out = {
        "metric": "TopN queries/sec (backend unavailable)",
        "value": 0.0,
        "unit": "queries/s",
        "vs_baseline": 0.0,
        "error": reason,
    }
    if fresh_cpu and fresh_cpu.get("cpu_topn_qps"):
        # no device and nothing to replay: the CPU full path measured
        # NOW is the only honest headline
        out["metric"] = (
            "TopN queries/sec (CPU full path; device unreachable, no "
            "prior on-chip result to replay)"
        )
        out["value"] = fresh_cpu["cpu_topn_qps"]
        out["vs_baseline"] = 1.0
    print(json.dumps(attach_fresh(out)))


if __name__ == "__main__":
    if os.environ.get("PILOSA_BENCH_PROBE"):
        _probe_main()
    elif os.environ.get("PILOSA_BENCH_CPU_FRESH"):
        _cpu_fresh_main()
    elif os.environ.get("PILOSA_BENCH_CHILD"):
        main()
    else:
        _guarded_main()
