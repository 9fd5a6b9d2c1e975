"""Multi-host SERVING dryrun — the full Holder → Executor → HTTP path
on a 2-process jax.distributed CPU mesh (VERDICT r5 top next-round
item; the serving-level successor to dryrun_multiprocess.py's
kernel-only collectives).

Two worker processes each own 4 virtual CPU devices; one global
8-device mesh spans them. Rank 0 serves HTTP and gang-dispatches every
state-bearing operation (parallel/multihost.py); rank 1 runs the
follower worker loop and replays each descriptor into its own holder,
entering the identical shard_map collectives in lockstep. The parent:

  1. loads data over real HTTP (Set gangs + an import-value leg, so
     both the query and the import replication paths are exercised),
  2. answers Count / two-pass TopN / BSI Sum / a 3-op chain over HTTP,
  3. checks rank 0's HTTP results AND rank 1's replayed results
     bit-identical to a single-process CPU roaring oracle,
  4. SIGKILLs the follower mid-load and asserts rank 0 answers with a
     bounded clean failure (503 + degrade-to-local-mesh) — never a
     hang — and serves correct results again after the degrade,
  5. records everything in MULTIPROCESS_r6.json.

    python dryrun_multihost.py            # full run + artifact
    python dryrun_multihost.py --quick    # smaller load (CI smoke)

Worker mode (spawned): PILOSA_MH_DRYRUN_RANK set.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

RANK_ENV = "PILOSA_MH_DRYRUN_RANK"
COORD_ENV = "PILOSA_MH_DRYRUN_COORD"
HTTP_ENV = "PILOSA_MH_DRYRUN_HTTP"
DATA_ENV = "PILOSA_MH_DRYRUN_DATA"
TIMEOUT_ENV = "PILOSA_MH_DRYRUN_DISPATCH_TIMEOUT"

N_SHARDS = 6
SETS_PER_SHARD = 120
N_VALUES = 240
N_ROWS = 8

READ_QUERIES = [
    "Count(Row(f=1))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    # 3-op chain
    "Count(Difference(Union(Row(f=1), Row(f=2)), Intersect(Row(f=3), Row(f=4))))",
    "TopN(f, Row(f=1), n=5)",  # two-pass: pass 2 re-scores the winners
    "TopN(f, n=4)",
    "Sum(field=val)",
    "Sum(Row(f=1), field=val)",
    # a filter traced inside the mesh kernel, its predicates a host
    # vector identical on every rank
    "Sum(Intersect(Row(f=1), Range(val >< [100, 700])), field=val)",
    "Count(Union(Row(f=2), Range(val < 250)))",
]


def _dataset(quick: bool):
    """The one definition of the load — workers never see it (data
    arrives over HTTP); the parent replays it into the CPU oracle."""
    import numpy as np

    from pilosa_tpu import SHARD_WIDTH

    scale = 4 if quick else 1
    rng = np.random.default_rng(42)
    bits = []
    for shard in range(N_SHARDS):
        base = shard * SHARD_WIDTH
        for _ in range(SETS_PER_SHARD // scale):
            bits.append(
                (int(rng.integers(0, N_ROWS)), base + int(rng.integers(0, SHARD_WIDTH)))
            )
    cols = rng.choice(N_SHARDS * SHARD_WIDTH, size=N_VALUES // scale, replace=False)
    values = [(int(c), int(rng.integers(0, 1000))) for c in cols]
    return bits, values


# -- worker ------------------------------------------------------------------


def worker() -> None:
    rank = int(os.environ[RANK_ENV])

    import jax

    from pilosa_tpu.parallel import multihost
    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.http_handler import encode_result
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=os.path.join(os.environ[DATA_ENV], f"rank{rank}"),
        bind=f"127.0.0.1:{os.environ[HTTP_ENV] if rank == 0 else 0}",
        device_policy="always",
        metric="none",
        anti_entropy_interval=0,
        distributed_enabled=True,
        distributed_coordinator=os.environ[COORD_ENV],
        distributed_process_id=rank,
        distributed_num_processes=2,
        distributed_idle_interval=1.0,
        distributed_dispatch_timeout=float(os.environ.get(TIMEOUT_ENV, "20")),
        distributed_leader_timeout=60.0,
    )
    srv = Server(cfg)
    srv.open()

    def jsonable(r):
        return json.loads(json.dumps(encode_result(r)))

    if rank == 0:
        stop = []
        signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
        print(json.dumps({"event": "ready", "rank": 0}), flush=True)
        while not stop:
            time.sleep(0.1)
        stats = srv.multihost.stats()
        srv.close()
        print(json.dumps({"event": "exit", "rank": 0, "stats": stats}), flush=True)
        # linger: this process hosts the jax.distributed coordination
        # service — exiting the instant the poison lands can fatally
        # terminate the follower (coordination poll abort) before it
        # prints its results dump
        time.sleep(3.0)
        return

    # follower: record every replayed query's results so the parent can
    # verify rank 1's serving-level answers against the oracle
    records: list[dict] = []
    orig_apply = srv.multihost.apply_fn

    def recording_apply(kind, payload):
        result = orig_apply(kind, payload)
        if kind == multihost.KIND_QUERY:
            records.append(
                {
                    "query": payload["query"],
                    "plan": payload.get("plan"),
                    "results": [jsonable(r) for r in result],
                }
            )
        return result

    srv.multihost.apply_fn = recording_apply
    reason = srv.serve_follower()
    stats = srv.multihost.stats()
    # dump BEFORE closing: once the leader process exits, the dead
    # coordination service can fatally terminate this process mid-close
    # — the results must already be on stdout by then
    print(
        json.dumps(
            {
                "event": "exit",
                "rank": 1,
                "stop_reason": reason,
                "stats": stats,
                "queries": records,
            }
        ),
        flush=True,
    )
    try:
        srv.close()
    except Exception:
        pass


# -- parent ------------------------------------------------------------------


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, method: str, path: str, body: bytes = b"", timeout: float = 60):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _wait_ready(port: int, deadline_s: float = 120) -> None:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        try:
            status, _ = _http(port, "GET", "/status", timeout=2)
            if status == 200:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise TimeoutError("rank 0 HTTP never came up")


def _spawn(rank: int, env: dict, tmp: str, tag: str = ""):
    """Worker process with stdout/stderr to FILES, never pipes: a
    verbose child (the kill phase logs one re-map line per failed leg)
    would fill an undrained 64 KB pipe and block inside logger writes —
    observed as a total serving wedge that looked like a product bug."""
    import subprocess

    out = open(os.path.join(tmp, f"rank{rank}{tag}.out"), "w+")
    err = open(os.path.join(tmp, f"rank{rank}{tag}.err"), "w+")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env={**env, RANK_ENV: str(rank)},
        stdout=out,
        stderr=err,
        text=True,
    )
    p._outf, p._errf = out, err  # type: ignore[attr-defined]
    return p


def _finish(p, timeout: float):
    """(stdout, stderr, returncode) after the worker exits (killed on
    timeout); reads the spool files _spawn opened."""
    import subprocess

    try:
        p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.wait()
    out_text = err_text = ""
    for attr, store in (("_outf", "out"), ("_errf", "err")):
        f = getattr(p, attr, None)
        if f is None:
            continue
        f.flush()
        f.seek(0)
        if store == "out":
            out_text = f.read()
        else:
            err_text = f.read()
        f.close()
    return out_text, err_text, p.returncode


def _worker_env(tmp: str, coord: int, http_port: int, dispatch_timeout: float) -> dict:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        **{
            COORD_ENV: f"127.0.0.1:{coord}",
            HTTP_ENV: str(http_port),
            DATA_ENV: tmp,
            TIMEOUT_ENV: str(dispatch_timeout),
        },
    )
    return env


def _oracle(bits, values):
    """Single-process CPU roaring oracle over the same dataset."""
    from pilosa_tpu.core import FieldOptions, Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.server.http_handler import encode_result

    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    intf = idx.create_field("val", FieldOptions(type="int", min=0, max=1000))
    for row, col in bits:
        f.set_bit(row, col)
    for col, v in values:
        intf.set_value(col, v)
    for fld in idx.fields.values():
        for view in fld.views.values():
            for frag in view.fragments.values():
                frag.cache.recalculate()
    ex = Executor(h, device_policy="never")
    out = {}
    for q in READ_QUERIES:
        out[q] = [
            json.loads(json.dumps(encode_result(r))) for r in ex.execute("i", q)
        ]
    return out


def _load_over_http(port: int, bits, values) -> None:
    status, _ = _http(port, "POST", "/index/i", b"")
    assert status in (200, 409), status
    status, _ = _http(port, "POST", "/index/i/field/f", b"")
    assert status in (200, 409), status
    status, _ = _http(
        port,
        "POST",
        "/index/i/field/val",
        json.dumps({"options": {"type": "int", "min": 0, "max": 1000}}).encode(),
    )
    assert status in (200, 409), status
    sets = [f"Set({col}, f={row})" for row, col in bits]
    for i in range(0, len(sets), 200):
        status, body = _http(
            port, "POST", "/index/i/query", " ".join(sets[i : i + 200]).encode()
        )
        assert status == 200, (status, body[:300])
    # the import-value leg exercises gang import replication
    status, body = _http(
        port,
        "POST",
        "/index/i/field/val/import-value",
        json.dumps(
            {"columnIDs": [c for c, _ in values], "values": [v for _, v in values]}
        ).encode(),
    )
    assert status == 200, (status, body[:300])
    status, _ = _http(port, "POST", "/recalculate-caches", b"")
    assert status == 200, status


def parent(quick: bool) -> int:
    import subprocess
    import tempfile

    bits, values = _dataset(quick)
    oracle = _oracle(bits, values)
    summary: dict = {
        "what": (
            "2-process x 4-device jax.distributed CPU deployment serving "
            "PQL over real HTTP: rank 0 gang-dispatches every operation "
            "(parallel/multihost.py), rank 1 replays it in lockstep, and "
            "the SPMD Count/TopN/Sum collectives span the process "
            "boundary inside one global mesh — the serving-level "
            "successor to MULTIPROCESS_r5.json's kernel-only dryrun"
        ),
        "processes": 2,
        "devices_per_process": 4,
        "quick": quick,
        "queries": READ_QUERIES,
    }
    ok = True

    # -- phase 1: serving bit-identity ------------------------------------
    with tempfile.TemporaryDirectory() as tmp:
        coord, http_port = _free_port(), _free_port()
        env = _worker_env(tmp, coord, http_port, dispatch_timeout=30.0)
        procs = [_spawn(0, env, tmp), _spawn(1, env, tmp)]
        rank0_results = {}
        lat = {}
        mh_stats = None
        phase_error = None
        try:
            _wait_ready(http_port)
            _load_over_http(http_port, bits, values)
            for q in READ_QUERIES:  # warm (compiles), then timed/recorded
                _http(http_port, "POST", "/index/i/query", q.encode(), timeout=180)
            for q in READ_QUERIES:
                t0 = time.monotonic()
                status, body = _http(
                    http_port, "POST", "/index/i/query", q.encode(), timeout=180
                )
                lat[q] = round((time.monotonic() - t0) * 1000, 2)
                assert status == 200, (q, status, body[:300])
                rank0_results[q] = json.loads(body)["results"]
            status, body = _http(http_port, "GET", "/debug/multihost")
            mh_stats = json.loads(body)
        except Exception as e:
            phase_error = f"{type(e).__name__}: {e}"
            ok = False
        finally:
            try:
                procs[0].send_signal(signal.SIGTERM)
            except OSError:
                pass
            outs = [_finish(p, timeout=90) for p in procs]

        follower_dump = None
        for line in outs[1][0].splitlines():
            if line.startswith("{"):
                d = json.loads(line)
                if d.get("event") == "exit":
                    follower_dump = d
        rank0_ok = all(rank0_results.get(q) == oracle[q] for q in READ_QUERIES)
        # follower records key on the gang descriptor's re-serialized
        # PQL (Sum(field="val") for Sum(field=val)) — match by the
        # canonical plan signature instead, which is spelling-invariant
        from pilosa_tpu.plan.canon import query_signature

        by_plan = {}
        if follower_dump:
            for rec in follower_dump.get("queries", []):
                by_plan[rec.get("plan")] = rec["results"]
        follower_results = {q: by_plan.get(query_signature(q)) for q in READ_QUERIES}
        rank1_ok = bool(follower_dump) and all(
            follower_results.get(q) == oracle[q] for q in READ_QUERIES
        )
        ok &= rank0_ok and rank1_ok
        summary["serving"] = {
            "rank0_http_bit_identical": rank0_ok,
            "rank1_replay_bit_identical": rank1_ok,
            "latency_ms": lat,
            "rank0_results": rank0_results,
            "rank1_results": {q: follower_results.get(q) for q in READ_QUERIES},
            "oracle": oracle,
            "multihost_debug": mh_stats,
            "follower_stop_reason": (follower_dump or {}).get("stop_reason"),
            "follower_stats": (follower_dump or {}).get("stats"),
            "worker_rc": [rc for _, _, rc in outs],
            "error": phase_error,
        }
        if not (rank0_ok and rank1_ok):
            for i, (out, err, rc) in enumerate(outs):
                print(f"-- rank {i} rc={rc}\n{err[-4000:]}", file=sys.stderr)

    # -- phase 2: follower kill mid-load → bounded 503 + degrade ----------
    dispatch_timeout = 6.0
    with tempfile.TemporaryDirectory() as tmp:
        coord, http_port = _free_port(), _free_port()
        env = _worker_env(tmp, coord, http_port, dispatch_timeout)
        procs = [_spawn(0, env, tmp), _spawn(1, env, tmp)]
        kill = {}
        try:
            _wait_ready(http_port)
            small = bits[: len(bits) // 4]
            _load_over_http(http_port, small, values[: len(values) // 4])
            _http(http_port, "POST", "/index/i/query", b"Count(Row(f=1))", timeout=120)
            # kill the follower MID-LOAD: a write gang is in flight
            procs[1].kill()
            t0 = time.monotonic()
            status, body = _http(
                http_port,
                "POST",
                "/index/i/query",
                b"Count(Row(f=1))",
                timeout=dispatch_timeout * 3 + 30,
            )
            first_s = time.monotonic() - t0
            # bounded: either the gang already degraded (200, served on
            # the local mesh) or this request ate the dispatch timeout
            # and got the clean 503 — never a hang
            bounded = first_s < dispatch_timeout * 3
            # after the verdict, serving must be correct on the local mesh
            t0 = time.monotonic()
            deg_status, deg_body = _http(
                http_port, "POST", "/index/i/query", b"Count(Row(f=1))", timeout=60
            )
            second_s = time.monotonic() - t0
            status2, dbg = _http(http_port, "GET", "/debug/multihost")
            kill = {
                "dispatch_timeout_s": dispatch_timeout,
                "first_query_status": status,
                "first_query_seconds": round(first_s, 2),
                "first_query_bounded": bounded,
                "post_degrade_status": deg_status,
                "post_degrade_seconds": round(second_s, 2),
                "post_degrade_results": json.loads(deg_body).get("results")
                if deg_status == 200
                else deg_body.decode(errors="replace")[:500],
                "multihost_debug": json.loads(dbg) if status2 == 200 else None,
            }
            degraded = bool((kill["multihost_debug"] or {}).get("degraded"))
            kill["degraded"] = degraded
            kill_ok = (
                bounded
                and status in (200, 503)
                and deg_status == 200
                and degraded
            )
            kill["ok"] = kill_ok
            ok &= kill_ok
        except Exception as e:
            kill["error"] = f"{type(e).__name__}: {e}"
            ok = False
        finally:
            try:
                procs[0].send_signal(signal.SIGTERM)
            except OSError:
                pass
            for i, p in enumerate(procs):
                out, err, rc = _finish(p, timeout=60)
                if not kill.get("ok"):
                    print(
                        f"-- kill-phase rank {i} rc={rc}\n{err[-4000:]}",
                        file=sys.stderr,
                    )
        summary["follower_kill"] = kill

    summary["ok"] = bool(ok)
    print(json.dumps(summary, indent=2))
    if not quick:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "MULTIPROCESS_r6.json"
        )
        with open(path, "w") as f:
            json.dump(summary, f, indent=2)
    return 0 if ok else 1


if __name__ == "__main__":
    if os.environ.get(RANK_ENV) is not None:
        worker()
    else:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--quick", action="store_true", help="smaller load (CI smoke)")
        a = ap.parse_args()
        sys.exit(parent(a.quick))
