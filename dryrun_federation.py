"""Sharded gang FEDERATION dryrun — 2 gangs x 2 processes on CPU, one
index sharded across both gangs via the cluster plane (ISSUE 7; the
federation-level successor to dryrun_multihost.py's single gang).

Topology: gangs A and B each form their own 2-process jax.distributed
collective (2 virtual CPU devices per process). The two gang LEADERS
are the cluster nodes (``cluster.hosts``, replicas=2), so every query
splits across gangs — local legs replay on this gang's mesh, remote
legs fan out over InternalClient — and every shard has a replica on
the other gang. The parent then walks the whole lifecycle:

  1. serving: load over HTTP via A's leader, answer Count / two-pass
     TopN / BSI Sum / a 3-op chain on BOTH leaders, bit-identical to a
     single-process CPU roaring oracle,
  2. follower kill: SIGKILL A's follower mid-serving — bounded fence
     (503 no longer than the dispatch timeout), gang A DEGRADED into
     replicated-solo, reads correct on both leaders throughout (zero
     wrong answers),
  3. re-form: boot a fresh follower with ``federation-rejoin`` — the
     leader re-stages it (schema + fragments), bumps the epoch, and
     the gang returns to ACTIVE; new writes replicate to the rejoined
     follower,
  4. leader kill: SIGKILL B's leader — reads fail over to gang A's
     replica copies; restart the leader with ``federation-leader``
     (replicated-solo DEGRADED, heals its data from peers at the next
     rejoin) and a fresh follower; gang B back to ACTIVE,
  5. record per-gang unavailability windows + everything else in
     FEDERATION_r7.json.

    python dryrun_federation.py            # full run + artifact
    python dryrun_federation.py --quick    # smaller load (CI smoke)

Worker mode (spawned): PILOSA_FED_DRYRUN_MODE set.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

from dryrun_multihost import (
    READ_QUERIES,
    _dataset,
    _finish,
    _free_port,
    _http,
    _oracle,
    _wait_ready,
)

MODE_ENV = "PILOSA_FED_DRYRUN_MODE"  # gang | rejoin | leader
GANG_ENV = "PILOSA_FED_DRYRUN_GANG"
RANK_ENV = "PILOSA_FED_DRYRUN_RANK"
COORD_ENV = "PILOSA_FED_DRYRUN_COORD"
HTTP_A_ENV = "PILOSA_FED_DRYRUN_HTTP_A"
HTTP_B_ENV = "PILOSA_FED_DRYRUN_HTTP_B"
SELF_HTTP_ENV = "PILOSA_FED_DRYRUN_SELF_HTTP"
NAME_ENV = "PILOSA_FED_DRYRUN_NAME"
DATA_ENV = "PILOSA_FED_DRYRUN_DATA"
TIMEOUT_ENV = "PILOSA_FED_DRYRUN_DISPATCH_TIMEOUT"
REJOIN_ENV = "PILOSA_FED_DRYRUN_REJOIN"

REFORM_BUDGET = 30.0  # federation_reform_budget default; windows must fit


# -- worker ------------------------------------------------------------------


def worker() -> None:
    mode = os.environ[MODE_ENV]

    import jax

    from pilosa_tpu.server.config import ClusterConfig, Config
    from pilosa_tpu.server.server import Server

    hosts = [
        f"127.0.0.1:{os.environ[HTTP_A_ENV]}",
        f"127.0.0.1:{os.environ[HTTP_B_ENV]}",
    ]
    name = os.environ[NAME_ENV]
    common = dict(
        data_dir=os.path.join(os.environ[DATA_ENV], name),
        bind=f"127.0.0.1:{os.environ.get(SELF_HTTP_ENV, '0')}",
        device_policy="always",
        metric="none",
        anti_entropy_interval=0,
    )
    rank = 0
    if mode == "rejoin":
        # re-staged follower: no cluster plane, no jax.distributed —
        # it announces itself to its gang leader and gets re-formed in
        cfg = Config(**common, federation_rejoin=os.environ[REJOIN_ENV])
    elif mode == "leader":
        # restarted gang leader: replicated-solo DEGRADED, keeps its
        # cluster seat; data heals from peers at the next rejoin
        cfg = Config(
            **common,
            federation_leader=True,
            client_retries=2,
            cluster=ClusterConfig(
                disabled=False,
                coordinator=False,
                replicas=2,
                hosts=hosts,
                status_interval=30.0,
            ),
        )
    else:
        gang, rank = os.environ[GANG_ENV], int(os.environ[RANK_ENV])
        cfg = Config(
            **common,
            distributed_enabled=True,
            distributed_coordinator=f"127.0.0.1:{os.environ[COORD_ENV]}",
            distributed_process_id=rank,
            distributed_num_processes=2,
            distributed_idle_interval=1.0,
            distributed_dispatch_timeout=float(os.environ.get(TIMEOUT_ENV, "20")),
            distributed_leader_timeout=15.0,
            client_retries=2,
            cluster=ClusterConfig(
                disabled=False,
                coordinator=(gang == "A"),
                replicas=2,
                hosts=hosts,
                status_interval=30.0,
            ),
        )
    srv = Server(cfg)
    srv.open()

    if mode == "gang" and rank != 0:
        reason = srv.serve_follower()
        stats = srv.multihost.stats() if srv.multihost else None
        # dump BEFORE closing (see dryrun_multihost.py: the dead
        # coordination service can fatally terminate mid-close)
        print(
            json.dumps(
                {"event": "exit", "name": name, "stop_reason": reason, "stats": stats}
            ),
            flush=True,
        )
        try:
            srv.close()
        except Exception:
            pass
        return

    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    print(json.dumps({"event": "ready", "name": name}), flush=True)
    while not stop:
        time.sleep(0.1)
    stats = srv.multihost.stats() if srv.multihost else None
    try:
        srv.close()
    except Exception:
        pass
    print(json.dumps({"event": "exit", "name": name, "stats": stats}), flush=True)
    # gang leaders host their gang's jax.distributed coordination
    # service — linger so a follower poisoned on close can exit clean
    time.sleep(2.0)


# -- parent ------------------------------------------------------------------


def _spawn(env: dict, tmp: str, name: str, **overrides):
    """Worker with stdout/stderr to FILES, never pipes (64 KB pipe
    deadlock — see dryrun_multihost._spawn)."""
    import subprocess

    out = open(os.path.join(tmp, f"{name}.out"), "w+")
    err = open(os.path.join(tmp, f"{name}.err"), "w+")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env={**env, NAME_ENV: name, **overrides},
        stdout=out,
        stderr=err,
        text=True,
    )
    p._outf, p._errf = out, err  # type: ignore[attr-defined]
    return p


def _http_h(
    port: int,
    method: str,
    path: str,
    body: bytes = b"",
    headers: dict | None = None,
    timeout: float = 60,
):
    """Like dryrun_multihost._http, plus request headers (the
    observability phase sends a traceparent)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body, headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _collect_pids(d: dict, out: set) -> set:
    meta = d.get("meta") or {}
    if "pid" in meta:
        out.add(meta["pid"])
    for c in d.get("children") or []:
        _collect_pids(c, out)
    return out


def _gang_status(port: int) -> dict:
    status, body = _http(port, "GET", "/status", timeout=10)
    if status != 200:
        return {}
    return json.loads(body).get("gang") or {}


def _poll_gang_state(port: int, want: str, deadline_s: float) -> float:
    """Seconds until the leader on ``port`` reports gang state
    ``want``; raises on timeout."""
    t0 = time.monotonic()
    t_end = t0 + deadline_s
    while time.monotonic() < t_end:
        try:
            if _gang_status(port).get("state") == want:
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(0.25)
    raise TimeoutError(f"gang on :{port} never reached {want}")


def _query(port: int, q: str, timeout: float = 120):
    status, body = _http(port, "POST", "/index/i/query", q.encode(), timeout=timeout)
    return status, (json.loads(body).get("results") if status == 200 else body[:300])


def _serve_and_check(port: int, oracle: dict) -> tuple[dict, dict, bool]:
    results, lat = {}, {}
    for q in READ_QUERIES:  # warm (compiles), then timed/recorded
        _http(port, "POST", "/index/i/query", q.encode(), timeout=180)
    for q in READ_QUERIES:
        t0 = time.monotonic()
        status, body = _http(port, "POST", "/index/i/query", q.encode(), timeout=180)
        lat[q] = round((time.monotonic() - t0) * 1000, 2)
        assert status == 200, (q, status, body[:300])
        results[q] = json.loads(body)["results"]
    return results, lat, all(results[q] == oracle[q] for q in READ_QUERIES)


def _load(port: int, recalc_ports: list[int], bits, values) -> None:
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
    status, body = _http(
        port,
        "POST",
        "/index/i/field/val/import-value",
        json.dumps(
            {"columnIDs": [c for c, _ in values], "values": [v for _, v in values]}
        ).encode(),
    )
    assert status == 200, (status, body[:300])
    for p in recalc_ports:
        status, _ = _http(p, "POST", "/recalculate-caches", b"")
        assert status == 200, status


def parent(quick: bool) -> int:
    import tempfile

    dispatch_timeout = 8.0
    bits, values = _dataset(quick)
    oracle = _oracle(bits, values)
    summary: dict = {
        "what": (
            "2-gang x 2-process federation on CPU: each gang is its own "
            "jax.distributed collective, the gang leaders form the cluster "
            "plane (replicas=2), queries split across gangs and merge "
            "through the Row/TopN/BSI reducers (parallel/federation.py). "
            "Walks follower SIGKILL -> bounded fence -> DEGRADED "
            "replicated-solo -> rejoin re-form -> ACTIVE, then leader "
            "SIGKILL -> replica failover -> federation-leader restart -> "
            "rejoin -> ACTIVE. Zero wrong answers at every step."
        ),
        "gangs": 2,
        "processes_per_gang": 2,
        "devices_per_process": 2,
        "quick": quick,
        "dispatch_timeout_s": dispatch_timeout,
        "reform_budget_s": REFORM_BUDGET,
        "queries": READ_QUERIES,
    }
    ok = True

    with tempfile.TemporaryDirectory() as tmp:
        coord_a, coord_b = _free_port(), _free_port()
        http_a, http_b = _free_port(), _free_port()
        http_a1r, http_b1r = _free_port(), _free_port()
        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
        }
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            **{
                MODE_ENV: "gang",
                HTTP_A_ENV: str(http_a),
                HTTP_B_ENV: str(http_b),
                DATA_ENV: tmp,
                TIMEOUT_ENV: str(dispatch_timeout),
            },
        )

        def gang_worker(gang: str, rank: int):
            return _spawn(
                env,
                tmp,
                f"{gang}{rank}",
                **{
                    GANG_ENV: gang,
                    RANK_ENV: str(rank),
                    COORD_ENV: str(coord_a if gang == "A" else coord_b),
                    SELF_HTTP_ENV: str(
                        (http_a if gang == "A" else http_b) if rank == 0 else 0
                    ),
                },
            )

        procs = {f"{g}{r}": gang_worker(g, r) for g in "AB" for r in (0, 1)}
        harvested: dict = {}

        def harvest(name: str, timeout: float = 60):
            out, err, rc = _finish(procs.pop(name), timeout=timeout)
            dump = None
            for line in out.splitlines():
                if line.startswith("{"):
                    d = json.loads(line)
                    if d.get("event") == "exit":
                        dump = d
            harvested[name] = {"rc": rc, "dump": dump, "err_tail": err[-2000:]}
            return harvested[name]

        try:
            # -- phase 1: cross-gang serving bit-identity -----------------
            _wait_ready(http_a)
            _wait_ready(http_b)
            _load(http_a, [http_a, http_b], bits, values)
            res_a, lat_a, ok_a = _serve_and_check(http_a, oracle)
            res_b, lat_b, ok_b = _serve_and_check(http_b, oracle)
            ok &= ok_a and ok_b
            summary["serving"] = {
                "leader_a_bit_identical": ok_a,
                "leader_b_bit_identical": ok_b,
                "latency_ms": {"A": lat_a, "B": lat_b},
                "results": {"A": res_a, "B": res_b},
                "oracle": oracle,
                "gang_health": {
                    "A": _gang_status(http_a),
                    "B": _gang_status(http_b),
                },
            }

            # -- phase 1.5: fleet observability (ISSUE 10) ----------------
            # one traceparent-tagged cross-gang query must come back as
            # ONE stitched trace with spans from >=3 distinct processes
            # (A leader root+replay, A follower's pushed replay, B
            # leader's envelope), and the A leader's fleet scrape must
            # carry every rank's build_info, instance-labeled
            tid = os.urandom(16).hex()
            tp = f"00-{tid}-{os.urandom(8).hex()}-01"
            # a write + TopN chain NOT in READ_QUERIES (and cache=false
            # at ingress): a plan-cache hit on either leader would
            # short-circuit the dispatch and emit no gang replay spans.
            # Row 88 / column 9001 stay outside the oracle's rows so
            # later bit-identity checks are unaffected
            st_t, _ = _http_h(
                http_a,
                "POST",
                "/index/i/query?cache=false",
                b"Set(9001, f=88) TopN(f, n=3)",
                headers={"traceparent": tp},
                timeout=120,
            )
            pids: set = set()
            n_entries = 0
            t_end = time.monotonic() + 30
            while time.monotonic() < t_end:
                st, body = _http(http_a, "GET", f"/debug/traces?trace_id={tid}")
                if st == 200:
                    entries = json.loads(body).get("traces") or []
                    n_entries = len(entries)
                    pids = set()
                    for d in entries:
                        _collect_pids(d, pids)
                    if len(pids) >= 3:
                        break
                time.sleep(0.5)
            trace_ok = st_t == 200 and n_entries >= 1 and len(pids) >= 3
            fleet_instances: set = set()
            t_end = time.monotonic() + 30
            while time.monotonic() < t_end:
                st, body = _http(http_a, "GET", "/metrics?fleet=true")
                if st == 200:
                    fleet_instances = {
                        line.split('instance="', 1)[1].split('"', 1)[0]
                        for line in body.decode().splitlines()
                        if line.startswith("pilosa_build_info{")
                        and 'instance="' in line
                    }
                    if len(fleet_instances) >= 4:
                        break
                time.sleep(0.5)
            fleet_ok = len(fleet_instances) >= 4
            obs_ok = trace_ok and fleet_ok
            ok &= obs_ok
            summary["observability"] = {
                "ok": obs_ok,
                "trace_id": tid,
                "stitched_trace_found": n_entries >= 1,
                "distinct_pids_in_trace": sorted(pids),
                "trace_spans_from_3plus_processes": trace_ok,
                "fleet_build_info_instances": sorted(fleet_instances),
                "fleet_scrape_all_ranks": fleet_ok,
            }

            # -- phase 1.6: federated workload heat (ISSUE 16) ------------
            # a deliberately skewed index: 40 bits land in shard 0, 10
            # in shard 1 (SHARD_WIDTH apart). Write heat is recorded
            # once per applying rank, and the replication x gang-replay
            # multiplier is IDENTICAL for both shards (replicas=2 over
            # both nodes), so the fleet-merged ``writes`` dimension must
            # reproduce the 4:1 ratio and imbalance_ratio
            # max/mean = 40/25 = 1.6 exactly — a hand-computed
            # placement-skew oracle on raw integer counters.
            SW = 1 << 20  # pilosa_tpu.SHARD_WIDTH
            n0, n1 = 40, 10
            st, _ = _http(http_a, "POST", "/index/hx", b"")
            assert st in (200, 409), st
            st, _ = _http(http_a, "POST", "/index/hx/field/hf", b"")
            assert st in (200, 409), st
            hsets = [f"Set({c}, hf=1)" for c in range(n0)]
            hsets += [f"Set({SW + c}, hf=1)" for c in range(n1)]
            st, body = _http(
                http_a, "POST", "/index/hx/query", " ".join(hsets).encode(), timeout=120
            )
            assert st == 200, (st, body[:300])
            # read heat on both shards; cache=false so the plan cache
            # can't short-circuit the executor's per-shard map legs
            for _ in range(3):
                _http(
                    http_a,
                    "POST",
                    "/index/hx/query?cache=false",
                    b"Count(Row(hf=1))",
                    timeout=120,
                )
            heat_ok = False
            hx: dict = {}
            w0 = w1 = 0
            t_end = time.monotonic() + 30
            while time.monotonic() < t_end:
                st, body = _http(
                    http_a, "GET", "/debug/heat?fleet=true&dim=writes&index=hx"
                )
                if st == 200:
                    hx = json.loads(body)
                    by_shard: dict = {}
                    reads_by_shard: dict = {}
                    for c in hx.get("cells") or []:
                        by_shard[c["shard"]] = by_shard.get(c["shard"], 0) + c["writes"]
                        reads_by_shard[c["shard"]] = (
                            reads_by_shard.get(c["shard"], 0) + c["reads"]
                        )
                    w0, w1 = by_shard.get(0, 0), by_shard.get(1, 0)
                    skew = hx.get("skew") or {}
                    top = skew.get("top") or [{}]
                    if (
                        w1 > 0
                        and w0 == 4 * w1  # replication multiplier cancels
                        and skew.get("imbalance_ratio") == 1.6
                        and (top[0].get("index"), top[0].get("shard")) == ("hx", 0)
                        and len(hx.get("instances") or []) >= 4
                        and reads_by_shard.get(0, 0) > 0
                        and reads_by_shard.get(1, 0) > 0
                    ):
                        heat_ok = True
                        break
                time.sleep(0.5)
            ok &= heat_ok
            summary["heat"] = {
                "ok": heat_ok,
                "oracle": {"writes_ratio": 4.0, "imbalance_ratio": 1.6},
                "merged_writes": {"shard0": w0, "shard1": w1},
                "instances": hx.get("instances"),
                "skew": hx.get("skew"),
            }

            # -- phase 2: follower SIGKILL -> bounded fence + DEGRADED ----
            t_kill = time.monotonic()
            procs["A1"].kill()
            t0 = time.monotonic()
            status, _ = _query(
                http_a, "Set(701, f=90)", timeout=dispatch_timeout * 3 + 30
            )
            first_s = time.monotonic() - t0
            _poll_gang_state(http_a, "DEGRADED", dispatch_timeout * 3)
            # first write after the kill either ate the bounded fence
            # (503) or landed after the degrade (200) — never a hang
            bounded = first_s < dispatch_timeout * 3
            w_status, w_res = _query(http_a, "Set(701, f=90)")
            unavail_a = time.monotonic() - t_kill
            # a fenced 503 write may still have applied before the fence
            # (at-least-once), so the retry can see changed=False; the
            # contract is the retry SUCCEEDS and the bit is then visible
            rb_status, rb_res = _query(http_a, "Count(Row(f=90))")
            r_status, r_res = _query(http_a, "Count(Row(f=1))")
            # the other gang keeps answering correctly throughout
            res_b2, _, ok_b2 = _serve_and_check(http_b, oracle)
            follower_exit = harvest("A1", timeout=10)
            kill_ok = (
                bounded
                and status in (200, 503)
                and w_status == 200
                and w_res in ([True], [False])
                and rb_status == 200
                and rb_res == [1]
                and r_status == 200
                and r_res == oracle["Count(Row(f=1))"]
                and ok_b2
            )
            ok &= kill_ok
            summary["follower_kill"] = {
                "ok": kill_ok,
                "first_write_status": status,
                "first_write_seconds": round(first_s, 2),
                "first_write_bounded": bounded,
                "write_after_degrade": [w_status, w_res],
                "write_readback": [rb_status, rb_res],
                "read_after_degrade": [r_status, r_res],
                "write_unavailability_seconds": round(unavail_a, 2),
                "gang_a": _gang_status(http_a),
                "leader_b_bit_identical_during_degrade": ok_b2,
                "follower_rc": follower_exit["rc"],
            }

            # -- phase 3: rejoin -> re-form -> ACTIVE + replication -------
            t0 = time.monotonic()
            procs["A1r"] = _spawn(
                env,
                tmp,
                "A1r",
                **{
                    MODE_ENV: "rejoin",
                    REJOIN_ENV: f"http://127.0.0.1:{http_a}",
                    SELF_HTTP_ENV: str(http_a1r),
                },
            )
            # budget covers worker boot (jax import) + push + reform
            reform_a = _poll_gang_state(http_a, "ACTIVE", REFORM_BUDGET + 30)
            gang_a = _gang_status(http_a)
            _query(http_a, "Set(123, f=97)")
            t_end = time.monotonic() + 15
            repl = None
            while time.monotonic() < t_end:
                st, repl = _query(http_a1r, "Count(Row(f=97))")
                if st == 200 and repl == [1]:
                    break
                time.sleep(0.25)
            res_a3, _, ok_a3 = _serve_and_check(http_a, oracle)
            reform_ok = (
                reform_a < REFORM_BUDGET + 30
                and gang_a.get("epoch", 0) >= 1
                and f"http://127.0.0.1:{http_a1r}" in (gang_a.get("replicas") or [])
                and repl == [1]
                and ok_a3
            )
            ok &= reform_ok
            summary["reform"] = {
                "ok": reform_ok,
                "reform_seconds": round(reform_a, 2),
                "gang_a": gang_a,
                "write_replicated_to_rejoined_follower": repl == [1],
                "leader_a_bit_identical_after_reform": ok_a3,
            }

            # -- phase 3.5: the kill/rejoin cycle in the event journal ----
            # A's leader must journal ACTIVE->DEGRADED, then
            # DEGRADED->REFORMING, then REFORMING->ACTIVE, in seq order,
            # with the epoch bumped across the cycle
            st, body = _http(http_a, "GET", "/debug/events?kind=gang.transition")
            edges = [
                (e.get("frm"), e.get("to"), e.get("epoch", 0))
                for e in (json.loads(body).get("events") or [])
            ] if st == 200 else []

            def _edge_idx(frm: str, to: str) -> int:
                for i, (f, t, _) in enumerate(edges):
                    if f == frm and t == to:
                        return i
                return -1

            i_deg = _edge_idx("ACTIVE", "DEGRADED")
            i_ref = _edge_idx("DEGRADED", "REFORMING")
            i_act = _edge_idx("REFORMING", "ACTIVE")
            events_ok = (
                0 <= i_deg < i_ref < i_act
                and edges[i_act][2] > edges[i_deg][2]
            )
            ok &= events_ok
            summary["observability"]["events_ok"] = events_ok
            summary["observability"]["gang_a_transitions"] = edges

            # -- phase 4: leader SIGKILL -> failover -> solo restart ------
            t_kill = time.monotonic()
            procs["B0"].kill()
            t0 = time.monotonic()
            res_a4, _, ok_a4 = _serve_and_check(http_a, oracle)
            failover_s = time.monotonic() - t0
            b1_exit = harvest("B1", timeout=40)  # leader_timeout=15 + slack
            procs["B0r"] = _spawn(
                env,
                tmp,
                "B0",  # SAME data dir + port: a restarted leader
                **{MODE_ENV: "leader", SELF_HTTP_ENV: str(http_b)},
            )
            _wait_ready(http_b)
            solo = _gang_status(http_b)
            procs["B1r"] = _spawn(
                env,
                tmp,
                "B1r",
                **{
                    MODE_ENV: "rejoin",
                    REJOIN_ENV: f"http://127.0.0.1:{http_b}",
                    SELF_HTTP_ENV: str(http_b1r),
                },
            )
            reform_b = _poll_gang_state(http_b, "ACTIVE", REFORM_BUDGET + 30)
            unavail_b = time.monotonic() - t_kill
            # post-recovery: rank caches on the healed leader
            _http(http_b, "POST", "/recalculate-caches", b"")
            res_b5, _, ok_b5 = _serve_and_check(http_b, oracle)
            res_a5, _, ok_a5 = _serve_and_check(http_a, oracle)
            st97, r97 = _query(http_b, "Count(Row(f=97))")
            leader_ok = (
                ok_a4  # zero wrong answers while B's leader was dead
                and solo.get("state") == "DEGRADED"
                and solo.get("mode") == "replicated"
                and ok_b5
                and ok_a5
                and st97 == 200
                and r97 == [1]  # pre-kill write healed into the restarted B
            )
            ok &= leader_ok
            summary["leader_kill"] = {
                "ok": leader_ok,
                "leader_a_bit_identical_during_outage": ok_a4,
                "failover_first_pass_seconds": round(failover_s, 2),
                "b1_stop_reason": (b1_exit["dump"] or {}).get("stop_reason"),
                "solo_restart_gang": solo,
                "gang_b_reform_seconds": round(reform_b, 2),
                "gang_b_unavailability_seconds": round(unavail_b, 2),
                "gang_b": _gang_status(http_b),
                "leader_b_bit_identical_after_recovery": ok_b5,
                "healed_write_on_restarted_leader": r97 == [1],
            }
            summary["unavailability_windows_s"] = {
                "gang_a_follower_death": summary["follower_kill"][
                    "write_unavailability_seconds"
                ],
                "gang_a_reform": summary["reform"]["reform_seconds"],
                "gang_b_leader_death_to_active": round(unavail_b, 2),
            }
        except Exception as e:
            summary["error"] = f"{type(e).__name__}: {e}"
            ok = False
        finally:
            for name, p in list(procs.items()):
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
            for name in list(procs):
                harvest(name, timeout=60)
            summary["worker_rc"] = {n: h["rc"] for n, h in harvested.items()}
            if not ok:
                for n, h in harvested.items():
                    print(f"-- {n} rc={h['rc']}\n{h['err_tail']}", file=sys.stderr)

    summary["ok"] = bool(ok)
    print(json.dumps(summary, indent=2))
    if not quick:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "FEDERATION_r7.json"
        )
        with open(path, "w") as f:
            json.dump(summary, f, indent=2)
    return 0 if ok else 1


if __name__ == "__main__":
    if os.environ.get(MODE_ENV) is not None:
        worker()
    else:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--quick", action="store_true", help="smaller load (CI smoke)")
        a = ap.parse_args()
        sys.exit(parent(a.quick))
