"""Mesh-vs-stacked TopN decision measurement at NON-TOY candidate scale.

VERDICT r4 weak #3: the round-4 meshed-default decision rested on a
200k-bit / 64-row executor measurement that contradicted the HTTP-level
gauntlet row (0.87x), and the eager mesh staging made the comparison
unrepeatable at 50k candidates. This script measures all three executor
paths AND the server (HTTP) level on the SAME dataset: 8 shards whose
ranked caches hold ~50k candidates each (the reference's default cache
size, field.go:41) — with the round-5 lazy chunked mesh staging.

Run on the 8-virtual-device CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python bench_spmd_measure.py

Writes SPMD_MEASURE_r5.json.
"""

from __future__ import annotations

import json
import os
import shutil
import time

# This experiment is DEFINED on the 8-virtual-device CPU mesh.
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from pilosa_tpu.utils.jaxplatform import force_cpu_mesh

force_cpu_mesh(8)

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(REPO, ".bench_cache", "spmd_measure_v1")
SHARD_WIDTH = 1 << 20
N_SHARDS = 8
HOT_ROWS = 32
HOT_BITS = 5_000
TAIL_ROWS = 50_000  # fills the reference-default ranked cache


def build() -> None:
    from pilosa_tpu.roaring import build_fragment_file

    vdir = os.path.join(DATA_DIR, "m", "f", "views", "standard", "fragments")
    if os.path.isdir(vdir) and len(os.listdir(vdir)) >= 2 * N_SHARDS:
        return
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    os.makedirs(vdir, exist_ok=True)

    def chunks(shard):
        for h in range(HOT_ROWS):
            rng = np.random.default_rng(h * 7919 + shard)
            cols = np.unique(
                rng.integers(0, SHARD_WIDTH, size=HOT_BITS, dtype=np.uint64)
            )
            yield np.uint64(h * SHARD_WIDTH) + cols
        rows = np.arange(TAIL_ROWS, dtype=np.uint64) + np.uint64(HOT_ROWS)
        cols = (rows * np.uint64(2654435761)) % np.uint64(SHARD_WIDTH)
        yield rows * np.uint64(SHARD_WIDTH) + cols

    for s in range(N_SHARDS):
        build_fragment_file(os.path.join(vdir, str(s)), chunks(s))


def _log(msg: str) -> None:
    import sys

    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _measure(execute, queries, reps=20, budget_s=30.0):
    lat = []
    t_all = time.perf_counter()
    for _ in range(reps):
        for q in queries:
            t0 = time.perf_counter()
            execute(q)
            lat.append(time.perf_counter() - t0)
        if time.perf_counter() - t_all > budget_s:
            break
    lat.sort()
    return round(lat[len(lat) // 2] * 1000, 2)


def executor_level(out: dict) -> None:
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel.spmd import make_mesh

    h = Holder(DATA_DIR)
    h.open()
    try:
        cpu = Executor(h, device_policy="never")
        stacked = Executor(h, device_policy="always")
        mesh = Executor(h, device_policy="always", mesh=make_mesh())
        # pruned walk (the serving-realistic case: skewed counts, the
        # ranked walk resolves inside the hot head) and a full scan
        # (n >= cache size: every candidate scored — the worst case the
        # eager mesh staging could not repeat at this scale)
        q_pruned = "TopN(f, Row(f=0), n=10)"
        q_full = f"TopN(f, Row(f=0), n={TAIL_ROWS + HOT_ROWS})"
        res = {}
        for name, ex in [("cpu", cpu), ("stacked", stacked), ("mesh", mesh)]:
            ident = None
            t_cold = {}
            for tag, q in [("pruned", q_pruned), ("full", q_full)]:
                t0 = time.perf_counter()
                got = ex.execute("m", q)
                t_cold[tag] = round((time.perf_counter() - t0) * 1000, 1)
                _log(f"{name} cold {tag}: {t_cold[tag]} ms")
                if name == "cpu":
                    res.setdefault("oracle", {})[tag] = json.dumps(got)
                else:
                    ident = (ident is not False) and (
                        json.dumps(got) == res["oracle"][tag]
                    )
            res[name] = {
                "cold_ms": t_cold,
                "pruned_ms": _measure(
                    lambda q, ex=ex: ex.execute("m", q), [q_pruned], budget_s=15
                ),
                "full_ms": _measure(
                    lambda q, ex=ex: ex.execute("m", q), [q_full], reps=5, budget_s=25
                ),
            }
            if name != "cpu":
                res[name]["bit_identical"] = ident
            _log(f"{name}: {res[name]}")
        res.pop("oracle", None)
        out["executor_level"] = res
    finally:
        h.close()


def server_level(out: dict) -> None:
    """Same dataset through the FULL HTTP stack (parse + handler +
    executor), one server meshless/CPU vs one meshed — the layer where
    the round-3/4 gauntlet saw the mesh lose."""
    import http.client
    import json as _json

    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.server import Server

    # keep-alive client (what the reference's Go client and every
    # production HTTP client use); the server speaks HTTP/1.1 with
    # TCP_NODELAY, so this measures the serving path without
    # per-request TCP setup
    conns: dict = {}

    def post(uri, path, body: str):
        host = uri.replace("http://", "")
        conn = conns.get(host)
        if conn is None:
            conn = conns[host] = http.client.HTTPConnection(host, timeout=60)
        conn.request("POST", path, body=body.encode())
        resp = conn.getresponse()
        return _json.loads(resp.read())

    q_pruned = "TopN(f, Row(f=0), n=10)"
    q_full = f"TopN(f, Row(f=0), n={TAIL_ROWS + HOT_ROWS})"
    res = {}
    for name, mesh_devices, policy in [
        ("cpu_http", 0, "never"),
        ("mesh_http", "all", "always"),
        ("stacked_http", 0, "always"),
    ]:
        # servers share the prebuilt data dir read-only (no writes here)
        cfg = Config(
            data_dir=DATA_DIR,
            bind="127.0.0.1:0",
            mesh_devices=mesh_devices,
            device_policy=policy,
            metric="none",
            anti_entropy_interval=0,
        )
        srv = Server(cfg)
        srv.open()
        try:
            uri = srv.uri
            post(uri, "/index/m/query", q_pruned)  # warm staging+compile
            post(uri, "/index/m/query", q_full)
            _log(f"{name}: warmed")
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < 6:
                post(uri, "/index/m/query", q_pruned)
                n += 1
            pruned_qps = round(n / (time.perf_counter() - t0), 1)
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < 6:
                post(uri, "/index/m/query", q_full)
                n += 1
            res[name] = {
                "pruned_qps": pruned_qps,
                "full_qps": round(n / (time.perf_counter() - t0), 2),
            }
            _log(f"{name}: {res[name]}")
        finally:
            srv.close()
    out["server_level"] = res


def main():
    from pilosa_tpu.utils.jaxplatform import bootstrap

    bootstrap()
    t0 = time.monotonic()
    build()
    out = {
        "what": (
            "Round-5 mesh-vs-batched decision at NON-TOY scale "
            f"(VERDICT r4 weak #3): {N_SHARDS} shards, ~{TAIL_ROWS + HOT_ROWS} "
            "ranked-cache candidates per shard (reference default cache "
            "size), lazy chunked mesh staging (executor._SpmdLazyScores). "
            "8-virtual-device CPU mesh; pruned = TopN n=10 on skewed "
            "counts (walk resolves in the hot head), full = n >= cache "
            "size (every candidate scored)."
        ),
        "build_s": round(time.monotonic() - t0, 1),
    }
    executor_level(out)
    server_level(out)
    # decision synthesis
    ex = out.get("executor_level", {})
    sv = out.get("server_level", {})
    try:
        out["decision"] = {
            "executor_pruned_mesh_vs_stacked": round(
                ex["stacked"]["pruned_ms"] / ex["mesh"]["pruned_ms"], 2
            ),
            "executor_full_mesh_vs_stacked": round(
                ex["stacked"]["full_ms"] / ex["mesh"]["full_ms"], 2
            ),
            "http_pruned_mesh_vs_stacked": round(
                sv["mesh_http"]["pruned_qps"] / sv["stacked_http"]["pruned_qps"], 2
            ),
            "http_full_mesh_vs_stacked": round(
                sv["mesh_http"]["full_qps"] / sv["stacked_http"]["full_qps"], 2
            ),
        }
    except (KeyError, ZeroDivisionError):
        pass
    with open(os.path.join(REPO, "SPMD_MEASURE_r5.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
