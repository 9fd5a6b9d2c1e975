#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration from the seed, starts ``python -m
pilosa_tpu server`` on it as a child, warms up the cell's own requests,
drives the cell's traffic over HTTP for ``--seconds``, stops the server,
and only then computes the plain reference's answers and compares every
answer of the window with them. The last line of standard output is the
result; earlier lines are facts of the run, one JSON object each.

This process never imports JAX: the chip belongs to the server child.
Without a TPU the run fails and prints no result; ``--allow-cpu`` (with
``--shards``, ``--clients``) is for rehearsals and trials by hand, and
the driver never passes them.
"""

from __future__ import annotations

import os
import sys
import time

T_PROCESS = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from dataclasses import dataclass  # noqa: E402

from benchmark import datagen, layer_metrics, load, roofline, trace_reduce, traffic  # noqa: E402
from benchmark import server as srv  # noqa: E402
from benchmark.reference import Undecidable, same_answer  # noqa: E402
from benchmark.server import BenchFailure  # noqa: E402

PLATFORM = "tpu"
SCRATCH = os.path.join(ROOT, ".bench_cache", "benchmark")
WARM_ROUND_S = 3.0
WARM_ROUNDS_MAX = 10
TRACE_SLICE_S = 5.0


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def read_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    return [
        m for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest rank: the smallest value with at least q of the sample at
    or below it."""
    return sorted_values[max(0, math.ceil(q * len(sorted_values)) - 1)]


# -- the traced slice ---------------------------------------------------------


class TraceSlice(threading.Thread):
    """Start the server's own ``jax.profiler`` capture a quarter into the
    window and stop it ``TRACE_SLICE_S`` later (or a third of a short
    window). Only the process that holds the chip can trace it."""

    def __init__(self, server, trace_dir: str, seconds: float) -> None:
        super().__init__(daemon=True)
        self.server, self.trace_dir = server, trace_dir
        self.wait_s = seconds / 4
        self.slice_s = min(TRACE_SLICE_S, seconds / 3)
        self.t0 = self.t1 = None
        self.started = self.stopped = None

    def run(self) -> None:
        time.sleep(self.wait_s)
        self.started = self.server.get_json(
            f"/debug/profile?capture=start&dir={self.trace_dir}", timeout=120
        )["capture"]
        self.t0 = time.monotonic()
        time.sleep(self.slice_s)
        self.t1 = time.monotonic()
        self.stopped = self.server.get_json("/debug/profile?capture=stop", timeout=300)["capture"]


def reduce_trace(trace_dir: str, device_prefix: str) -> dict:
    """The reduction runs in a child held to the CPU, once the server
    has exited and released the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "trace_reduce.py"), trace_dir, device_prefix],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=240,
    )
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return {"error": f"trace reduction failed: rc {out.returncode}: {out.stderr[-1500:]}"}


# -- one run ------------------------------------------------------------------


def warm_up(server, path: str, bodies: list[bytes], pairs: list[bytes], deck: list[int],
            clients: int, seed: int, cache_dir: str) -> dict:
    """Every distinct request once, one at a time (this stages the data
    and compiles each single-request shape); with two clients or more,
    every ordered pair of templates as one two-call query (the shapes of
    the waves two concurrent requests form); then rounds of the closed
    loop at the cell's own client count, until two rounds in a row
    compile nothing."""
    t0 = time.monotonic()
    once = bodies + (pairs if clients > 1 else [])
    first, _, _ = load.run_closed(
        server.host, server.port, path, once, iter(range(len(once))), 1, 3600.0
    )
    bad = [s for s in first if s.status != 200]
    if bad:
        raise BenchFailure(
            f"warm-up: {len(bad)} of {len(first)} requests were not answered 200; first: "
            f"{bad[0].status} {bad[0].body[:300]!r} for {once[bad[0].request][:200]!r}"
        )
    cold_s = time.monotonic() - t0
    rounds: list[tuple[float, int]] = []  # (tracked compiles, new cache entries) of each round
    before = (srv.compiles(server.scrape()), srv.cache_entries(cache_dir))
    while len(rounds) < WARM_ROUNDS_MAX and rounds[-2:] != [(0, 0), (0, 0)]:
        sched = traffic.schedule(deck, seed + 1 + len(rounds))
        load.run_closed(server.host, server.port, path, bodies, sched, clients, WARM_ROUND_S)
        metrics = server.scrape()
        after = (srv.compiles(metrics), srv.cache_entries(cache_dir))
        rounds.append((after[0] - before[0], after[1] - before[1]))
        before = after
    return {
        "cold_pass_seconds": round(cold_s, 2),
        "rounds_compiled": rounds,
        "compiles_by_kind": srv.by_label(metrics, "profiler.compiles", "kind"),
        "seconds": round(time.monotonic() - t0, 2),
    }


@dataclass
class Window:
    """What serving left behind, read once the server has exited."""

    info: dict  # build_info's labels
    on_chip: bool
    setup_s: float
    samples: list
    t_end: float
    before: list  # /metrics just before the window
    after: list  # and once its last answer had come
    cache_entries_added: int
    server_exit_code: int
    tracer: TraceSlice | None


def serve(args, cell: dict, config: dict, mix: dict, bodies: list[bytes], deck: list[int],
          scratch: str, cache_dir: str, server_module: str, server_flags) -> Window:
    """Start the server child on the built data, warm up, drive the
    window, stop the child. Whatever happens, no process is left."""
    clients = args.clients or mix["clients"]
    params = f"timeout={load.REQUEST_TIMEOUT_S}" + ("" if mix["cache"] else "&cache=false")
    path = f"/index/{config['index']}/query?{params}"
    server = srv.ServerChild(
        os.path.join(scratch, "data"), list(server_flags or config["server_flags"]),
        os.path.join(scratch, "server.log"), server_module,
    )
    try:
        ready_s = server.wait_ready()
        info = srv.samples(server.scrape(), "build_info")[0][0]
        emit("serve", ready_seconds=round(ready_s, 1), build_info=info, cache_dir=cache_dir)
        on_chip = info.get("backend") == PLATFORM
        if not on_chip and not args.allow_cpu:
            raise BenchFailure(f"server runs on backend {info.get('backend')!r}, not {PLATFORM!r}")
        if on_chip and int(info.get("device_count", 0)) < cell["chips"]:
            raise BenchFailure(f"{info.get('device_count')} chips visible, the cell asks for {cell['chips']}")

        pairs = [b.encode() for b in traffic.pairs(config, mix)]
        warm = warm_up(server, path, bodies, pairs, deck, clients, args.seed, cache_dir)
        emit("warm_up", distinct_requests=len(bodies), clients=clients, **warm)

        before, entries = server.scrape(), srv.cache_entries(cache_dir)
        tracer = None
        if args.trace:
            tracer = TraceSlice(server, os.path.join(scratch, "trace"), args.seconds)
            tracer.start()
        setup_s = time.monotonic() - T_PROCESS
        samples, _, t_end = load.run_closed(
            server.host, server.port, path, bodies,
            traffic.schedule(deck, args.seed), clients, args.seconds,
        )
        if tracer is not None:
            tracer.join(timeout=330)
        time.sleep(0.5)  # stage-ahead errors are counted on a side thread
        after = server.scrape()
        entries = srv.cache_entries(cache_dir) - entries
        rc = server.stop()
    finally:
        server.kill()
        shutil.rmtree(os.path.join(scratch, "data"), ignore_errors=True)
    return Window(info, on_chip, setup_s, samples, t_end, before, after, entries, rc, tracer)


def window_facts(w: Window) -> dict:
    """For an earlier line of every run: what must be 0, and the memory."""
    grew = lambda name, **match: srv.total(w.after, name, **match) - srv.total(w.before, name, **match)  # noqa: E731
    was = srv.fallbacks(w.before)
    stages = srv.by_label(w.after, "latency.stage_seconds_sum", "stage")
    stages_before = srv.by_label(w.before, "latency.stage_seconds_sum", "stage")
    return {
        "server_exit_code": w.server_exit_code,
        "compiles_in_window": grew("profiler.compiles"),
        "cache_entries_added_in_window": w.cache_entries_added,
        "fallbacks_in_window": {k: v - was[k] for k, v in srv.fallbacks(w.after).items() if v - was[k]},
        "device_launch_decisions": srv.device_work(w.after) - srv.device_work(w.before),
        "hbm_bytes_in_use": srv.by_label(w.after, "hbm.bytes_in_use", "device"),
        "hbm_peak_bytes": srv.by_label(w.after, "hbm.peak_bytes", "device"),
        "hbm_bytes_limit": srv.by_label(w.after, "hbm.bytes_limit", "device"),
        "stager_bytes": srv.total(w.after, "stager.bytes"),
        "stager_restaged_bytes_in_window": grew("stager.restaged_bytes"),
        "latency_ms_max": round(1000 * max((s.done - s.sent for s in w.samples), default=0.0), 1),
        "requests_over_1s": sum(1 for s in w.samples if s.done - s.sent > 1.0),
        "stage_ms_per_request": {
            k: round(1000 * (v - stages_before.get(k, 0.0)) / max(1, len(w.samples)), 4)
            for k, v in stages.items()
        },
    }


def compare(ref, calls: list, bodies: list[bytes], samples: list) -> dict:
    """Every answer of the window against the plain reference, which
    computes each distinct request once."""
    t0 = time.monotonic()
    used = sorted({s.request for s in samples})
    with ThreadPoolExecutor(max_workers=max(1, min(8, (os.cpu_count() or 2) - 1))) as pool:
        expected = dict(zip(used, pool.map(lambda i: _reference_answer(ref, calls[i]), used)))
    verdicts: dict[tuple[int, bytes], bool] = {}
    good, wrong, unanswered, undecided, first_wrong = [], 0, 0, 0, None
    for s in samples:
        want = expected[s.request]
        if s.status != 200:
            unanswered += 1
            first_wrong = first_wrong or f"status {s.status}: {s.body[:200]!r} for {bodies[s.request][:200]!r}"
        elif isinstance(want, Undecidable):
            undecided += 1
        else:
            key = (s.request, s.body)
            if key not in verdicts:
                verdicts[key] = _same(calls[s.request], s.body, want)
            if verdicts[key]:
                good.append(s)
            else:
                wrong += 1
                first_wrong = first_wrong or (
                    f"{bodies[s.request].decode()[:200]}: got {s.body[:400]!r}, want {json.dumps(want)[:400]}"
                )
    emit("compare", seconds=round(time.monotonic() - t0, 2), distinct_requests=len(used),
         compared=len(good) + wrong, undecided=undecided, first_wrong=first_wrong)
    return {"good": good, "wrong": wrong, "unanswered": unanswered,
            "calls": [calls[i] for i in used], "expected": [expected[i] for i in used]}


def run_cell(args, manifest: dict | None = None, server_module: str = srv.SERVER_MODULE,
             server_flags: list[str] | None = None, after_compare=None) -> dict:
    """Build, serve, compare, reduce. Returns the result line's object.
    The keywords are for ``benchmark/tests``: a manifest with a
    throw-away cell, a broken server underneath, the CPU path as a second
    witness (``--device-policy never``), and ``after_compare(ref, calls,
    expected)`` for the control's readings on the same data."""
    manifest = manifest or read_json("BENCHMARK.json")
    cell, config_entry = find_cell(manifest, args.workload)
    config = read_json(config_entry["file"])
    mix = traffic.load(os.path.join(ROOT, "benchmark", "traffic", f"{cell['traffic']}.json"))
    try:
        from pilosa_tpu import native_bridge
        from pilosa_tpu.utils.jaxplatform import bootstrap
    except ImportError as e:
        raise BenchFailure(f"the program is not in this checkout: {e}") from e
    cache_dir = bootstrap()  # the server child inherits the same directory
    native_bridge.require()

    scratch = os.path.join(SCRATCH, args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    ref, facts = datagen.build(config, args.seed, os.path.join(scratch, "data"),
                               args.shards or config["shards"])
    emit("build", config=config["name"], seed=args.seed, **facts)
    requests = traffic.pool(config, mix)
    calls = [c for c, _ in requests]
    bodies = [traffic.pql(c).encode() for c in calls]
    deck = traffic.deck(requests, mix["deck"])

    w = serve(args, cell, config, mix, bodies, deck, scratch, cache_dir, server_module, server_flags)
    emit("window", clients=args.clients or mix["clients"], seconds=args.seconds, **window_facts(w))

    # the reference runs only now: the window is closed, the peak read, the server gone
    c = compare(ref, calls, bodies, w.samples)
    if after_compare is not None:
        after_compare(ref, c["calls"], c["expected"])
    del ref
    compared = len(c["good"]) + c["wrong"]
    checks = {
        "wrong_answers": {"value": c["wrong"], "limit": 0},
        "unanswered": {"value": c["unanswered"], "limit": 0},
        "server_exit_code": {"value": w.server_exit_code, "limit": 0},
        "compared": {"value": compared, "at_least": 1},
    }
    correct = not (c["wrong"] or c["unanswered"] or w.server_exit_code) and compared >= 1

    hbm_peak = srv.by_label(w.after, "hbm.peak_bytes", "device")
    device = {
        "platform": w.info.get("backend"),
        "kind": w.info.get("device_kind"),
        "count": int(w.info.get("device_count", 0)),
        "memory_peak_bytes": int(max(hbm_peak.values(), default=0)),
    }
    breakdown = None
    if args.trace:
        values, breakdown = per_layer(args, manifest, config, calls, w, device, scratch)
    elif w.on_chip:
        lat = sorted(1000.0 * (s.done - s.sent) for s in w.samples)
        values = {
            "queries_per_s": sum(1 for s in c["good"] if s.done <= w.t_end) / args.seconds,
            "query_p50_ms": statistics.median(lat),
            "query_p95_ms": percentile(lat, 0.95),
            "setup_s": w.setup_s,
        }
    else:  # a rehearsal: no number of a CPU run stands under a device metric's name
        values = {"setup_s": w.setup_s}
    units = {m["name"]: m["unit"] for m in manifest["per_layer" if args.trace else "end_to_end"]}
    result = {
        "correct": correct,
        "attempted": len(w.samples),
        "failed": c["wrong"] + c["unanswered"],
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def per_layer(args, manifest: dict, config: dict, calls: list, w: Window, device: dict,
              scratch: str) -> tuple[dict, dict | None]:
    """The traced run's metrics: each from its own file under
    ``layer_metrics/``; ``device`` gains the slice's busy and window
    seconds. On the CPU nothing is read from the trace."""
    trace_dir = os.path.join(scratch, "trace")
    trace = _traced(w, trace_dir, config, calls, device)
    shutil.rmtree(trace_dir, ignore_errors=True)
    emit("trace", **{k: v for k, v in trace.items() if k not in ("device_ops", "idle_gaps")})
    breakdown = None
    if w.on_chip and trace.get("busy_s") is not None:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    answered = sum(1 for s in w.samples if s.status == 200)
    values = {}
    for m in metrics_of(manifest, "per_layer", args.workload):
        v = layer_metrics.evaluate(
            layer_metrics.load(m["name"]), w.before, w.after, answered, trace if w.on_chip else None
        )
        if v is not None:
            values[m["name"]] = v
    return values, breakdown


def _reference_answer(ref, call):
    try:
        return ref.answer(call)
    except Undecidable as e:
        return e


def _same(call, body: bytes, want) -> bool:
    try:
        results = json.loads(body)["results"]
    except (ValueError, KeyError, TypeError):
        return False
    return isinstance(results, list) and len(results) == 1 and same_answer(call, results[0], want)


def _traced(w: Window, trace_dir: str, config: dict, calls: list, device: dict) -> dict:
    """The slice's reduction plus what the parent counted in it."""
    t = w.tracer
    if t is None or t.t1 is None or not (t.started or {}).get("ok"):
        return {"error": f"capture did not start: {getattr(t, 'started', None)}"}
    if not (t.stopped or {}).get("ok"):
        return {"error": f"capture did not stop: {t.stopped}"}
    # on the CPU there is no device plane; reduce the host's, to rehearse the code
    trace = reduce_trace(trace_dir, trace_reduce.DEVICE_PLANE if w.on_chip else "/host:CPU$")
    inside = [s for s in w.samples if s.status == 200 and t.t0 <= s.done <= t.t1]
    trace["slice_s"] = t.t1 - t.t0
    trace["slice_requests"] = len(inside)
    trace["slice_bytes"] = sum(roofline.bytes_needed(config, calls[s.request]) for s in inside)
    if w.on_chip:
        trace["peak_hbm_bytes_per_s"] = roofline.peak(device["kind"])["hbm_bytes_per_s"]
    return trace


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true", help="rehearsal: print no device metric")
    p.add_argument("--shards", type=int, default=0, help="rehearsal: fewer shards than the configuration")
    p.add_argument("--clients", type=int, default=0, help="trial by hand: another client count")
    args = p.parse_args(argv)
    if args.shards and not args.allow_cpu:
        p.error("--shards is for rehearsals (--allow-cpu)")
    return args


def main(argv=None, **hooks) -> int:
    args = parse_args(argv)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLATFORM not in platforms.split(",") and not args.allow_cpu:
        # JAX would honour it and never look for the chip
        print(f"benchmark: FAILED: JAX_PLATFORMS={platforms} keeps JAX off the {PLATFORM}", file=sys.stderr)
        return 1
    try:
        result = run_cell(args, **hooks)
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} " + " ".join(f"{k}={v}" for k, v in c.items()), file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
