"""Full-path benchmark of BASELINE.json config 4: the 1B-row north star.

Synthetic index, true shape: 64 shards x 2^20 columns, 1,000,000,000
distinct rows (32 hot rows present in every shard at ~50k bits/shard;
the rest are singletons — the long tail that makes dense staging
impossible and is exactly what the mmap store + block-sparse staging
exist for). Queries run through the FULL stack (PQL parse -> executor
-> stager -> XLA kernels), not bare kernels:

  * TopN(f, Row(f=h), n=10)           — the driver's headline metric
  * Count(deep Intersect/Union chain) — config 4's second family

The data dir builds once into .bench_cache/ (resumable per fragment —
an interrupted build continues on the next run) and is reused across
rounds. Scale knobs: PILOSA_BENCH_TALL_SHARDS (default 64; each shard
adds ~15.6M rows, ~285 MB disk, ~190 MB resident occupancy index),
PILOSA_BENCH_TALL_BUILD_BUDGET seconds of build time per run.

Baseline: the same queries through this framework's CPU roaring path,
measured on a query sample (labelled; the reference Go binary cannot
run in this image — see BASELINE.md and bench JSON caveats).
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(REPO, ".bench_cache", "tall_v1")


def _effective_cache_dir(rows_per_shard: int) -> str:
    """Non-default scales (dev smokes) get their OWN directory — a smoke
    run must never wipe the 18 GB default-scale dataset. An explicitly
    overridden CACHE_DIR (the gauntlet points it at a tmp dir) is used
    as-is."""
    if rows_per_shard != ROWS_PER_SHARD and CACHE_DIR.endswith("tall_v1"):
        return CACHE_DIR + f"_rps{rows_per_shard}"
    return CACHE_DIR

SHARDS_DEFAULT = 64
ROWS_PER_SHARD = 15_625_000  # x64 shards = 1.0e9 rows
HOT_ROWS = 32
HOT_BITS = 50_000
SINGLES_BASE = 64  # first singleton row id (hot rows are 0..31)
SHARD_WIDTH = 1 << 20


def _fragment_chunks(shard: int, rows_per_shard: int):
    """Sorted-unique position stream for one fragment: hot rows first,
    then the singleton tail (one bit per row, column = row hash)."""
    for h in range(HOT_ROWS):
        # pseudo-random columns (NOT an arithmetic pattern: strided rows
        # barely intersect, which collapses TopN thresholds and makes
        # every chain Count 0 — unrepresentative)
        rng = np.random.default_rng(h * 100003 + shard)
        cols = np.unique(
            rng.integers(0, SHARD_WIDTH, size=HOT_BITS, dtype=np.uint64)
        )
        yield np.uint64(h * SHARD_WIDTH) + cols
    base = SINGLES_BASE + shard * rows_per_shard
    step = 4_000_000
    for i in range(0, rows_per_shard, step):
        rows = np.arange(i, min(i + step, rows_per_shard), dtype=np.uint64) + np.uint64(
            base
        )
        cols = (rows * np.uint64(2654435761)) % np.uint64(SHARD_WIDTH)
        yield rows * np.uint64(SHARD_WIDTH) + cols


def build_data(
    shards: int, rows_per_shard: int = ROWS_PER_SHARD, budget_s: float = 1e9
) -> dict:
    """Build (or resume building) the tall data dir; returns build stats.
    Each fragment file is written atomically, so a run cut short by the
    budget resumes at the next missing fragment."""
    from pilosa_tpu.roaring import build_fragment_file

    t0 = time.monotonic()
    cache_dir = _effective_cache_dir(rows_per_shard)
    # a cache built at a different scale is a different dataset — rebuild
    meta_path = os.path.join(cache_dir, "build_meta.json")
    meta = {"rows_per_shard": rows_per_shard, "v": 2}
    try:
        with open(meta_path) as f:
            if json.load(f) != meta:
                shutil.rmtree(cache_dir)
    except (OSError, ValueError):
        if os.path.isdir(cache_dir):
            shutil.rmtree(cache_dir)
    vdir = os.path.join(cache_dir, "tall", "f", "views", "standard", "fragments")
    os.makedirs(vdir, exist_ok=True)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    built = 0
    skipped = 0
    for s in range(shards):
        path = os.path.join(vdir, str(s))
        if os.path.exists(path) and os.path.exists(path + ".cache"):
            skipped += 1
            continue
        if time.monotonic() - t0 > budget_s:
            break
        build_fragment_file(path, _fragment_chunks(s, rows_per_shard))
        built += 1
    present = skipped + built
    return {
        "shards_present": present,
        "built_this_run": built,
        "build_s": round(time.monotonic() - t0, 1),
        "rows": present * rows_per_shard + (HOT_ROWS if present else 0),
    }


def _queries():
    topn = [f"TopN(f, Row(f={h}), n=10)" for h in range(0, HOT_ROWS, 2)]
    chains = []
    for r in range(8):
        a, b, c, d = r, (r + 5) % HOT_ROWS, (r + 11) % HOT_ROWS, (r + 17) % HOT_ROWS
        chains += [
            f"Count(Intersect(Union(Row(f={a}), Row(f={b})), Union(Row(f={c}), Row(f={d}))))",
            f"Count(Union(Intersect(Row(f={a}), Row(f={b})), Intersect(Row(f={c}), Row(f={d})), Row(f={a})))",
            f"Count(Difference(Union(Row(f={a}), Row(f={b}), Row(f={c})), Row(f={d})))",
        ]
    return topn, chains


def _measure(execute, queries, seconds: float):
    """(qps, p50_ms, n_timed) over repeated passes within a time budget."""
    lat = []
    t_all = time.perf_counter()
    n = 0
    while time.perf_counter() - t_all < seconds:
        for q in queries:
            t0 = time.perf_counter()
            execute(q)
            lat.append(time.perf_counter() - t0)
            n += 1
        if n >= 4 and time.perf_counter() - t_all >= seconds:
            break
    total = time.perf_counter() - t_all
    lat.sort()
    return n / total, lat[len(lat) // 2] * 1000, n


def _measure_closed_loop(
    dev, queries, n_clients: int, budget_s: float, return_p50: bool = False
):
    """QPS with ``n_clients`` closed-loop clients: each thread sends its
    next query the moment the previous one returns (how N concurrent
    HTTP clients actually behave). The earlier wave-barrier harness
    (submit N futures, join all, repeat) convoyed the pipeline: the
    slowest query of each wave idled every other client, and the
    continuous batcher never saw a full queue.

    With ``return_p50=True`` returns ``(qps, p50_ms)`` — the per-query
    round-trip latency AS EXPERIENCED AT THIS CONCURRENCY (queueing +
    batching included), which is the latency a serving deployment's
    clients actually see alongside the closed-loop qps headline."""
    import threading

    stop = time.perf_counter() + budget_s
    counts = [0] * n_clients
    lat: list[list[float]] = [[] for _ in range(n_clients)]
    errors: list[BaseException] = []

    def client(ci: int) -> None:
        i = ci  # offset so clients interleave different queries
        try:
            while time.perf_counter() < stop and not errors:
                t_q = time.perf_counter()
                dev.execute("tall", queries[i % len(queries)])
                lat[ci].append(time.perf_counter() - t_q)
                i += 1
                counts[ci] += 1
        except BaseException as e:  # surface, don't shrink QPS silently
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(ci,)) for ci in range(n_clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    qps = round(sum(counts) / (time.perf_counter() - t0), 2)
    if not return_p50:
        return qps
    all_lat = sorted(x for per in lat for x in per)
    p50_ms = round(all_lat[len(all_lat) // 2] * 1000, 2) if all_lat else None
    return qps, p50_ms


def _scale_from_env() -> tuple[int, int]:
    """(shards, rows_per_shard) from env, shrunk to available disk.
    Guard rails: building the full 64-shard config needs ~18 GB disk
    and ~13 GB resident occupancy index at query time. One definition —
    run() and run_cpu_fresh() must build the SAME dataset or the
    fresh-vs-replayed comparison is skewed."""
    shards = int(os.environ.get("PILOSA_BENCH_TALL_SHARDS", SHARDS_DEFAULT))
    rows_per_shard = int(
        os.environ.get("PILOSA_BENCH_TALL_ROWS_PER_SHARD", ROWS_PER_SHARD)
    )
    free_gb = shutil.disk_usage(REPO).free / 1e9
    need_gb = shards * rows_per_shard * 18e-9 + 5
    if free_gb < need_gb:
        shards = max(1, int((free_gb - 5) / (rows_per_shard * 18e-9)))
    return shards, rows_per_shard


def _open_warm(rows_per_shard: int):
    """(holder, open_warm_s): open the data dir and eager-open every
    fragment, like the reference's startup walk (holder.Open →
    fragment.Open incl. cache restore, fragment.go:167-266). That cost
    is storage open + occupancy sidecar mmap + cache restore — not
    device staging, which warms under its own clock."""
    from pilosa_tpu.core import Holder

    h = Holder(_effective_cache_dir(rows_per_shard))
    t_open = time.monotonic()
    h.open()
    view = h.view("tall", "f", "standard")
    for s in sorted(view.fragments):
        view.fragments[s].ensure_open()
    return h, round(time.monotonic() - t_open, 2)


def run(deadline_s: float = 1e9) -> dict:
    """Build/resume the data, run the full-path bench, return the
    result dict (never raises; errors land in the dict)."""
    t0 = time.monotonic()

    def remaining():
        return deadline_s - (time.monotonic() - t0)

    shards, rows_per_shard = _scale_from_env()
    # reserve time for open/warm/measure; the build resumes next run if cut
    reserve = min(200.0, remaining() * 0.5)
    build_budget = float(
        os.environ.get("PILOSA_BENCH_TALL_BUILD_BUDGET", remaining() - reserve)
    )
    build = build_data(shards, rows_per_shard, budget_s=build_budget)
    out = {"config": "tall_1b", "build": build, "shards": build["shards_present"]}
    if build["shards_present"] == 0:
        out["error"] = "no fragments built within budget"
        return out

    import jax

    from pilosa_tpu.executor import Executor

    h, out["open_warm_s"] = _open_warm(rows_per_shard)
    dev = Executor(h, device_policy="always")
    cpu = Executor(h, device_policy="never")
    topn, chains = _queries()

    try:
        if remaining() < 45:
            out["error"] = "budget too small to warm and measure"
            return out
        # warmup: staging + compiles (also the bit-identity check).
        # CPU-oracle queries at 1B rows cost seconds each — two suffice
        # for the identity check; the measure loops absorb remaining
        # cold samples (a few cold p50 samples out of ~100 are noise).
        # Deadline-checked between queries: the first device TopN pays
        # the whole chunk-0 staging upload and can take minutes cold.
        ident = True
        checked = 0
        for q in [topn[0], chains[0]]:
            got = dev.execute("tall", q)
            if remaining() < 90:
                break
            want = cpu.execute("tall", q)
            ident &= json.dumps(want) == json.dumps(got)
            checked += 1
        if checked == 2:
            out["bit_identical"] = ident
        elif checked == 1:
            out["bit_identical"] = ident and "partial (1/2)"
        else:
            out["bit_identical"] = "skipped (deadline)"
        warm_budget = min(remaining() - 80, 60)
        t_warm = time.monotonic()
        for q in topn + chains:
            if time.monotonic() - t_warm > warm_budget or remaining() < 25:
                break
            dev.execute("tall", q)
        # device-side warm cost (first-touch HBM staging + compiles),
        # reported separately from the storage open above
        out["device_warm_s"] = round(time.monotonic() - t_warm, 1)

        budget = max(min(remaining() - 20, 60), 6)
        topn_qps, topn_p50, topn_n = _measure(
            lambda q: dev.execute("tall", q), topn, budget / 2
        )
        chain_qps, chain_p50, chain_n = _measure(
            lambda q: dev.execute("tall", q), chains, budget / 2
        )
        out.update(
            topn_qps=round(topn_qps, 2),
            topn_p50_ms=round(topn_p50, 2),
            topn_queries_timed=topn_n,
            chain_qps=round(chain_qps, 2),
            chain_p50_ms=round(chain_p50, 2),
            chain_queries_timed=chain_n,
            platform=jax.devices()[0].platform,
        )
        # serving throughput: 8 concurrent clients — pipelined round
        # trips + the executor's continuous micro-batching; this is
        # the number a real serving deployment sees
        def measure_cn(queries, n, budget_c, prefix):
            # records qps AND the closed-loop p50 at that concurrency
            # (the latency clients actually see at the headline qps)
            qps, p50 = _measure_closed_loop(
                dev, queries, n, budget_c, return_p50=True
            )
            if p50 is not None:
                out[f"{prefix}_p50_ms_c{n}"] = p50
            return qps

        if remaining() > 30:
            # Batch-width compile warm: the stacked/grouped kernels
            # compile once per pow2 batch width, and a cold width costs
            # 20-40 s of XLA compile — inside a 15 s measure window that
            # reads as a 2x QPS loss (observed: c32 41.5 cold vs ~90
            # steady-state on the same revision). Touch each width the
            # measures below can reach (the scorer chunks launches at
            # max_batch, so wider widths compile nothing new) so they
            # observe steady state; the persistent compile cache makes
            # this a no-op on re-runs. Each warm call can block ~40 s
            # inside one cold compile (the closed-loop budget only
            # gates loop entry, not an in-flight execute), so only
            # attempt it while the budget could absorb that worst case
            # without starving the measurement windows below. Warmed
            # widths are recorded: a budget-cut artifact whose
            # c-numbers ran against cold compiles is distinguishable
            # ([] or a short list here, vs the full ladder).
            max_w = getattr(dev.stacked_scorer, "max_batch", 32)
            warmed = []
            for width in (8, 16, 32, 64):
                if width > max_w or remaining() < 110:
                    break
                try:  # best-effort: a transient device error during a
                    # throwaway warm must not abort the measurements
                    _measure_closed_loop(dev, topn, width, 2.0)
                    warmed.append(width)
                except Exception:
                    break
            out["warmed_widths"] = warmed

        if remaining() > 30:
            d0, q0 = dev.stacked_scorer.dispatches, dev.stacked_scorer.batched_queries
            out["topn_qps_c8"] = measure_cn(topn, 8, min(remaining() - 15, 20), "topn")
            # coalescing telemetry: how many concurrent queries shared a
            # stacked kernel launch during the c8 window
            out["c8_coalesced_queries"] = dev.stacked_scorer.batched_queries - q0
            out["c8_dispatches"] = dev.stacked_scorer.dispatches - d0
            if remaining() > 30:
                out["chain_qps_c8"] = measure_cn(chains, 8, min(remaining() - 15, 15), "chain")
            if remaining() > 40:
                # deeper concurrency: the BatchedScorer coalesces c32/c64
                # into wider stacked launches
                out["topn_qps_c32"] = measure_cn(
                    topn, 32, min(remaining() - 15, 20), "topn"
                )
                if remaining() > 35:
                    # chains are transport-bound sequentially (one fused
                    # dispatch ≈ one RTT) — c32 is the number that
                    # answers the chain 10x question
                    out["chain_qps_c32"] = measure_cn(
                        chains, 32, min(remaining() - 15, 15), "chain"
                    )
                if remaining() > 40:
                    # c64: closed-loop clients at the depth a fleet of
                    # HTTP frontends would drive; the continuous batcher
                    # self-tunes width to the fetch latency
                    out["topn_qps_c64"] = measure_cn(
                        topn, 64, min(remaining() - 15, 20), "topn"
                    )
                if remaining() > 35:
                    out["chain_qps_c64"] = measure_cn(
                        chains, 64, min(remaining() - 15, 15), "chain"
                    )
        # Latency decomposition: how much of a single query's p50 is
        # device round trips vs host work? One tiny device round-trip
        # bounds the dispatch floor; dispatch counts per query multiply
        # it.
        if remaining() > 15:
            try:
                x = np.arange(64, dtype=np.uint32)
                rtts = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    np.asarray(jax.device_put(x).sum())
                    rtts.append((time.perf_counter() - t0) * 1000)
                rtts.sort()
                rtt_ms = rtts[len(rtts) // 2]
                from pilosa_tpu.utils import profiler, trace

                d0 = dev.stacked_scorer.dispatches
                topn_wf: dict = {}
                with trace.attrib_activate(topn_wf):
                    t0 = time.perf_counter()
                    dev.execute("tall", topn[0])
                    one_topn_ms = (time.perf_counter() - t0) * 1000
                topn_disp = dev.stacked_scorer.dispatches - d0
                chain_wf: dict = {}
                with trace.attrib_activate(chain_wf):
                    t0 = time.perf_counter()
                    dev.execute("tall", chains[0])
                    one_chain_ms = (time.perf_counter() - t0) * 1000
                out["profile"] = {
                    "device_rtt_ms": round(rtt_ms, 2),
                    "one_topn_ms": round(one_topn_ms, 2),
                    "topn_dispatches": topn_disp,
                    "topn_rtt_fraction": round(
                        min(1.0, max(1, topn_disp) * rtt_ms / max(one_topn_ms, 1e-9)), 2
                    ),
                    "one_chain_ms": round(one_chain_ms, 2),
                    "chain_rtt_fraction": round(
                        min(1.0, rtt_ms / max(one_chain_ms, 1e-9)), 2
                    ),
                    "note": (
                        "a warm chain is ONE fused dispatch, so its "
                        "sequential floor is one device round-trip; "
                        "rtt_fraction ~1.0 means the single-stream "
                        "number is transport-bound and concurrency "
                        "(c8/c32) is the honest throughput metric"
                    ),
                    # cross-validation (ISSUE 12): the hand-timed probe
                    # above vs the always-on attribution layer measuring
                    # the SAME queries. The two disagree only when the
                    # waterfall taxonomy has a hole.
                    "topn_waterfall": profiler.WaterfallAggregator.summarize(
                        topn_wf, one_topn_ms / 1000.0
                    ),
                    "chain_waterfall": profiler.WaterfallAggregator.summarize(
                        chain_wf, one_chain_ms / 1000.0
                    ),
                }
                # fused_rtt (ISSUE 13): a warm multi-call read query must
                # execute as ONE fused launch, so its sequential p50
                # target is ~1 device round-trip including result
                # delivery.  Measure a 3-chain query end to end and
                # record how many RTTs it costs; window_quality carries
                # the multiple forward and window_degraded rejects a run
                # where fusion regressed to per-call round trips.
                if remaining() > 10:
                    fused_q = "".join(chains[:3])
                    fuser = getattr(dev, "fuser", None)
                    dev.execute("tall", fused_q)  # warm the fused program
                    l0 = fuser.fused_launches if fuser is not None else 0
                    times = []
                    for _ in range(7):
                        t0 = time.perf_counter()
                        dev.execute("tall", fused_q)
                        times.append((time.perf_counter() - t0) * 1000)
                    times.sort()
                    one_query_ms = times[len(times) // 2]
                    l1 = fuser.fused_launches if fuser is not None else 0
                    out["profile"]["fused_rtt"] = {
                        "calls": 3,
                        "one_query_ms": round(one_query_ms, 2),
                        "fused_launches_per_query": round((l1 - l0) / 7.0, 2),
                        "rtt_multiple": round(one_query_ms / max(rtt_ms, 1e-9), 2),
                        "chain_rtt_multiple": round(
                            one_chain_ms / max(rtt_ms, 1e-9), 2
                        ),
                    }
            except Exception as e:  # profile is best-effort telemetry
                out["profile"] = {"error": f"{type(e).__name__}: {e}"}
        # CPU full-path baseline on a small sample (labelled: this is
        # this repo's Python roaring path, not the reference Go binary)
        if remaining() > 20:
            cpu_topn_qps, _, _ = _measure(
                lambda q: cpu.execute("tall", q), topn[:2], min(remaining() - 10, 10)
            )
            cpu_chain_qps, _, _ = _measure(
                lambda q: cpu.execute("tall", q), chains[:2], min(remaining() - 5, 5)
            )
            out["cpu_topn_qps"] = round(cpu_topn_qps, 3)
            out["cpu_chain_qps"] = round(cpu_chain_qps, 3)
            if remaining() > 14:
                # short CPU CLOSED-LOOP window: the serving-vs-CPU
                # headline ratio divides a concurrent serving number by
                # this baseline, so its concurrency ceiling must be
                # measured, not asserted from "1-core host"
                out["cpu_topn_qps_c4"] = _measure_closed_loop(
                    cpu, topn[:2], 4, min(remaining() - 8, 6)
                )
            out["baseline_note"] = (
                "CPU = this repo's Python roaring full path; reference Go "
                "binary unavailable in image (see BASELINE.md)"
            )
    except Exception as e:  # noqa: BLE001 — bench must always return a dict
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        h.close()
    return out


def run_cpu_fresh(deadline_s: float = 300.0) -> dict:
    """Every chip-INDEPENDENT measurement of the tall config, fresh:
    warm open, staging-pack breakdown, CPU-path QPS. Run on the CPU
    backend when the device never answers, so the bench artifact
    degrades to partial-fresh (these numbers measured by THIS code,
    now) instead of replaying a whole stale round (VERDICT r4 weak #1:
    the replay reported open_warm_s=134.5 while the shipped code
    opened in ~4 s)."""
    t0 = time.monotonic()

    def remaining():
        return deadline_s - (time.monotonic() - t0)

    out: dict = {"config": "tall_1b_cpu_fresh"}
    shards, rows_per_shard = _scale_from_env()
    # resume-build only within half the budget: when the dataset is
    # already on disk (the normal case) this is a no-op stat pass
    build = build_data(shards, rows_per_shard, budget_s=remaining() * 0.5)
    out["build"] = build
    out["shards"] = build["shards_present"]
    if build["shards_present"] == 0:
        out["error"] = "no fragments on disk and none built within budget"
        return out

    from pilosa_tpu.executor import Executor

    h, out["open_warm_s"] = _open_warm(rows_per_shard)
    view = h.view("tall", "f", "standard")

    try:
        # staging-pack breakdown: the candidate staging cost that feeds
        # the device path, measured host-side (it IS host work). Cold =
        # first touch (page-in + native expand); warm = packed again
        # from the page cache.
        frag = view.fragments[min(view.fragments)]
        cand = [p[0] for p in frag.cache.top()[:4096]]
        if cand:
            t_c = time.perf_counter()
            frag.sparse_row_blocks(cand)
            cold_ms = (time.perf_counter() - t_c) * 1000
            warm = []
            for _ in range(3):
                t_c = time.perf_counter()
                frag.sparse_row_blocks(cand)
                warm.append((time.perf_counter() - t_c) * 1000)
            from pilosa_tpu import native_bridge

            out["staging"] = {
                "candidates": len(cand),
                "pack_cold_ms": round(cold_ms, 1),
                "pack_warm_ms": round(sorted(warm)[1], 1),
                "native_kernel": native_bridge.available(),
            }
        # CPU full-path QPS (the reference-shaped roaring walk through
        # PQL parse -> executor -> fragment.top), measured fresh
        cpu = Executor(h, device_policy="never")
        topn, chains = _queries()
        if remaining() > 30:
            qps, p50, _ = _measure(
                lambda q: cpu.execute("tall", q), topn[:2],
                min(remaining() * 0.4, 25),
            )
            out["cpu_topn_qps"] = round(qps, 3)
            out["cpu_topn_p50_ms"] = round(p50, 1)
        if remaining() > 20:
            # same closed-loop CPU window as run(): the ratio
            # denominator stays measured even on the device-less path
            out["cpu_topn_qps_c4"] = _measure_closed_loop(
                cpu, topn[:2], 4, min(remaining() * 0.3, 6)
            )
        if remaining() > 15:
            qps, p50, _ = _measure(
                lambda q: cpu.execute("tall", q), chains[:2],
                min(remaining() * 0.5, 15),
            )
            out["cpu_chain_qps"] = round(qps, 3)
            out["cpu_chain_p50_ms"] = round(p50, 1)
        out["baseline_note"] = (
            "CPU = this repo's Python roaring full path; reference Go "
            "binary unavailable in image (see BASELINE.md)"
        )
    except Exception as e:  # noqa: BLE001 — bench must always return a dict
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        h.close()
    return out


if __name__ == "__main__":
    from pilosa_tpu.utils.jaxplatform import bootstrap

    bootstrap()
    deadline = float(os.environ.get("PILOSA_BENCH_TALL_DEADLINE", 1e9))
    print(json.dumps(run(deadline)))
