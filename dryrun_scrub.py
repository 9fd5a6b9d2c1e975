"""End-to-end data-integrity soak (ISSUE 15) — a federated 2-node
cluster (replicas=2) with seeded bit rot in multiple owned fragments
under mixed read/write load:

  * seed 4+ fragments (multi-shard) on both replicas, snapshot them so
    every file carries its blake2b digest trailer,
  * install ``bitrot=1`` on node0 ONLY (separate process: the fault is
    process-global) and sweep — every owned fragment's verification
    flips a base byte on disk, so every corruption must be DETECTED,
    journaled (``scrub.corruption`` + ``scrub.quarantine``), and the
    fragment quarantined (reads 503 + Retry-After, never garbage),
  * clear the fault and sweep again — every quarantined fragment must
    be REPAIRED from its healthy replica over the checksummed
    fragment-backup plane, after which reads on both nodes must match
    the python oracle bit-for-bit,
  * holder backup → wipe (index delete) → restore on both nodes: the
    restored data must verify bit-identical (backup manifests equal),
    and a tampered archive must be refused with 400 before any byte
    is applied.

The invariant everywhere: a fault may cost latency or a retryable
error (status ⊆ {200, 429, 503, 504}) — NEVER a wrong answer.

    python dryrun_scrub.py            # full run + artifact
    python dryrun_scrub.py --quick    # smaller load (CI smoke)

Artifact: SCRUB_r15.json. Worker mode (spawned): PILOSA_SCRUB_MODE.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import sys
import tarfile
import tempfile
import time

from dryrun_chaos import (
    ALLOWED,
    Reader,
    Writer,
    _events,
    _ingest_acked,
    _journal_seq,
    _oracle_rows,
    _read_row_acked,
    _static_cells,
)
from dryrun_multihost import _free_port, _http, _wait_ready

MODE_ENV = "PILOSA_SCRUB_MODE"  # node
DATA_ENV = "PILOSA_SCRUB_DATA"
RANK_ENV = "PILOSA_SCRUB_RANK"
HOSTS_ENV = "PILOSA_SCRUB_HOSTS"

ARTIFACT = "SCRUB_r15.json"
SEED = 15
N_SHARDS = 4  # ≥3 owned fragments get rotted


# -- worker -------------------------------------------------------------------


def worker() -> None:
    from pilosa_tpu.server.config import ClusterConfig, Config
    from pilosa_tpu.server.server import Server

    rank = int(os.environ[RANK_ENV])
    hosts = os.environ[HOSTS_ENV].split(",")
    cfg = Config(
        data_dir=os.path.join(os.environ[DATA_ENV], f"node{rank}"),
        bind=hosts[rank],
        device_policy="never",
        metric="none",
        anti_entropy_interval=0,  # sweeps are driven explicitly
        scrub_interval=0,  # ditto — determinism over wall-clock
        chaos_enabled=True,
        cluster=ClusterConfig(
            disabled=False,
            coordinator=(rank == 0),
            replicas=2,
            hosts=hosts,
        ),
    )
    s = Server(cfg)
    s.open()
    print(f"scrub dryrun node{rank} up on {cfg.bind}", flush=True)
    while True:  # parent terminates us
        time.sleep(1.0)


def _spawn_node(tmp: str, rank: int, hosts: list) -> object:
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        JAX_PLATFORMS="cpu",
        **{
            MODE_ENV: "node",
            DATA_ENV: tmp,
            RANK_ENV: str(rank),
            HOSTS_ENV: ",".join(hosts),
        },
    )
    out = open(os.path.join(tmp, f"node{rank}.log"), "w+")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=out,
        stderr=subprocess.STDOUT,
        text=True,
    )
    p._outf = out  # type: ignore[attr-defined]
    return p


# -- phases -------------------------------------------------------------------


def _seed_shards(port: int) -> dict:
    """Rows spanning N_SHARDS shards so the rot phase has ≥3 distinct
    owned fragments to corrupt. Returns {row: set(cols)}. Row ids sit
    between the Writer rows (< 100) and the static rows (≥ 100_000) so
    the three oracles never collide."""
    from pilosa_tpu import SHARD_WIDTH

    rows: dict[int, set] = {}
    for r in (90_001, 90_002):
        cells = set()
        for shard in range(N_SHARDS):
            for j in range(40):
                cells.add(shard * SHARD_WIDTH + (r * 17 + j * 13) % 5000)
        rows[r] = cells
        _ingest_acked(port, [(r, c, True) for c in sorted(cells)])
    return rows


def _force_snapshots(ports: list) -> int:
    """Round-trip every fragment archive through the verify-before-
    apply restore on ITS OWN node: unmarshal snapshots, so every
    on-disk file gains its digest trailer (seed writes alone stay in
    the op log — MAX_OP_N is never reached here)."""
    n = 0
    for port in ports:
        st, body = _http(port, "GET", "/internal/fragments")
        assert st == 200, (st, body[:200])
        for e in json.loads(body):
            path = (
                f"/internal/fragment/data?index={e['index']}&field={e['field']}"
                f"&view={e['view']}&shard={e['shard']}"
            )
            st, archive = _http(port, "GET", path)
            assert st == 200
            st, body = _http(port, "POST", path, archive, timeout=60)
            assert st == 200, (st, body[:200])
            n += 1
    return n


def _quarantined(port: int) -> list:
    st, body = _http(port, "GET", "/status")
    assert st == 200
    return json.loads(body).get("integrity", {}).get("quarantined", [])


def _scrub(port: int, body: bytes = b"{}") -> dict:
    st, resp = _http(port, "POST", "/debug/scrub", body, timeout=120)
    assert st == 200, (st, resp[:200])
    return json.loads(resp)


def _chaos(port: int, storage: str) -> None:
    st, body = _http(
        port, "POST", "/debug/chaos",
        json.dumps({"storage": storage}).encode(),
    )
    assert st == 200, (st, body[:200])


def _manifest_of(archive: bytes) -> dict:
    with tarfile.open(fileobj=io.BytesIO(archive)) as tr:
        return json.loads(tr.extractfile("MANIFEST.json").read())


def _verify_rows(port: int, oracle: dict, failures: list, tag: str) -> None:
    for r, want in sorted(oracle.items()):
        got = _read_row_acked(port, r, deadline_s=60.0)
        if got != want:
            failures.append(
                f"{tag}: row {r} mismatch on port {port} "
                f"(+{len(got - want)}/-{len(want - got)} cols)"
            )


def _rot_phase(ports: list, oracle: dict, result: dict, quick: bool) -> list:
    failures: list = []
    port = ports[0]
    seq0 = _journal_seq(port)

    n_writers = 2 if quick else 4
    n_readers = 3 if quick else 5
    static = {r: c for r, c in oracle.items() if r >= 100_000}
    writers = [Writer(k, port) for k in range(n_writers)]
    readers = [Reader(k, port, static) for k in range(n_readers)]
    for t in writers + readers:
        t.thread.start()

    # -- corrupt: bitrot=1 flips a base byte at EVERY verification.
    # The detect sweep runs with repair DISABLED so every corruption
    # stays quarantined and observable (repair would otherwise succeed
    # even mid-rot: the replica pull installs in-memory storage, so
    # nothing re-reads the rotted mmap until the next snapshot) --
    _chaos(port, "bitrot=1")
    detect = _scrub(port, b'{"repair": false}')
    quarantined = _quarantined(port)
    result["detect_sweep"] = detect
    result["quarantined"] = quarantined
    print(f"== detect sweep: {detect} quarantined={len(quarantined)}")
    if detect["corrupt"] < 3:
        failures.append(f"only {detect['corrupt']} corruptions detected (< 3)")
    if len(quarantined) < 1:
        failures.append("no fragment left quarantined while rot is active")

    # quarantined reads answer 503 + Retry-After — never garbage
    qreads = {"checked": 0, "clean_503": 0}
    for q in quarantined[:2]:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
        try:
            conn.request(
                "POST", f"/index/{q['index']}/query",
                f"Row({q['field']}=90001)".encode(),
            )
            resp = conn.getresponse()
            resp.read()
            qreads["checked"] += 1
            if resp.status == 503 and resp.getheader("Retry-After"):
                qreads["clean_503"] += 1
            elif resp.status not in ALLOWED and resp.status != 200:
                failures.append(
                    f"quarantined read answered {resp.status} (not a clean 503)"
                )
        finally:
            conn.close()
    result["quarantined_reads"] = qreads

    # -- repair: clear the fault, sweep until every fragment heals --
    _chaos(port, "")
    repair_sweeps = []
    for _ in range(5):
        s = _scrub(port)
        repair_sweeps.append(s)
        if not _quarantined(port):
            break
    result["repair_sweeps"] = repair_sweeps
    left = _quarantined(port)
    if left:
        failures.append(f"{len(left)} fragments never repaired: {left}")
    if not any(s["repaired"] for s in repair_sweeps):
        failures.append("no fragment repaired from its replica")
    print(f"== repair sweeps: {repair_sweeps}")

    # a clean verification sweep after repair: zero corruption left
    final = _scrub(port)
    result["verify_sweep"] = final
    if final["corrupt"]:
        failures.append("corruption detected AFTER repair")

    for t in writers + readers:
        t.stop.set()
    for t in writers + readers:
        t.thread.join(timeout=60)

    bad = sorted({s for x in writers + readers for s in x.bad_statuses})
    wrong = [e for x in readers for e in x.wrong]
    result["load"] = {
        "write_requests": sum(x.requests for x in writers),
        "write_retries": sum(x.retries for x in writers),
        "read_requests": sum(x.requests for x in readers),
        "read_transient": sum(x.transient for x in readers),
        "wrong_answers": wrong,
        "bad_statuses": bad,
    }
    if wrong:
        failures.append("wrong answers during the rot window")
    if bad:
        failures.append(f"statuses outside {{200,429,503,504}}: {bad}")

    # journal assertions AFTER the soak: the durable backing (ISSUE 16)
    # pages past any ring eviction, so the counts no longer need to be
    # sampled the instant each sweep finishes
    ev_corrupt = len(_events(port, "scrub.corruption", seq0))
    ev_quar = len(_events(port, "scrub.quarantine", seq0))
    ev_repair = len(_events(port, "scrub.repair", seq0))
    result["journal"] = {
        "scrub_corruption": ev_corrupt,
        "scrub_quarantine": ev_quar,
        "scrub_repair": ev_repair,
    }
    if ev_corrupt < detect["corrupt"]:
        failures.append(
            f"journal under-reports corruption ({ev_corrupt} < {detect['corrupt']})"
        )
    if ev_quar < 1:
        failures.append("no scrub.quarantine journal event")
    if ev_repair < 1:
        failures.append("no scrub.repair journal event")
    print(f"== journal (counted after soak): {result['journal']}")

    # quiesce: writer rows + every seeded row verify on BOTH nodes
    oracle = dict(oracle)
    unknown: dict[int, set] = {}
    for x in writers:
        for r, c, _s in x.unknown:
            unknown.setdefault(r, set()).add(c)
    for r, want in _oracle_rows(writers).items():
        skip = unknown.get(r, set())
        for p in ports:
            got = _read_row_acked(p, r, deadline_s=60.0)
            if got - skip != want - skip:
                failures.append(f"quiesce: writer row {r} mismatch on {p}")
    for p in ports:
        _verify_rows(p, oracle, failures, f"quiesce node@{p}")
    return failures


def _backup_phase(ports: list, oracle: dict, result: dict) -> list:
    failures: list = []
    port = ports[0]
    seq0 = _journal_seq(port)

    st, archive = _http(port, "GET", "/backup", timeout=120)
    if st != 200:
        return [f"backup failed: {st}"]
    manifest0 = _manifest_of(archive)
    result["backup"] = {
        "bytes": len(archive),
        "entries": len(manifest0["entries"]),
        "sha256": hashlib.sha256(archive).hexdigest(),
    }
    print(f"== backup: {len(archive)}B, {len(manifest0['entries'])} entries")

    # tampered archive must be refused BEFORE any byte is applied.
    # Flip a byte INSIDE a fragment entry's payload (a flip at an
    # arbitrary offset can land in tar block padding and change
    # nothing).
    bad = bytearray(archive)
    with tarfile.open(fileobj=io.BytesIO(archive)) as tr:
        frag_off = next(
            m.offset_data
            for m in tr.getmembers()
            if m.name.startswith("fragments/") and m.size > 0
        )
    bad[frag_off] ^= 0x01
    st, body = _http(port, "POST", "/restore", bytes(bad), timeout=120)
    result["tampered_restore"] = {"status": st, "body": body[:200].decode("utf-8", "replace")}
    if st != 400:
        failures.append(f"tampered restore answered {st}, want 400")
    if not _events(port, "restore.refused", seq0):
        failures.append("refused restore left no restore.refused journal event")
    for p in ports:
        _verify_rows(p, oracle, failures, f"post-tamper node@{p}")

    # wipe (cluster-wide index delete), then restore EVERY node from
    # the archive — the holder-level disaster-recovery drill
    st, _ = _http(port, "DELETE", "/index/i")
    if st != 200:
        failures.append(f"index delete failed: {st}")
    restores = []
    for p in ports:
        st, body = _http(p, "POST", "/restore", archive, timeout=120)
        restores.append({"port": p, "status": st})
        if st != 200:
            failures.append(f"restore on {p} failed: {st} {body[:200]}")
    result["restores"] = restores
    for p in ports:
        _verify_rows(p, oracle, failures, f"post-restore node@{p}")

    # bit-identical: a fresh backup's manifest must equal the original
    st, archive2 = _http(port, "GET", "/backup", timeout=120)
    ok = st == 200 and _manifest_of(archive2)["entries"] == manifest0["entries"]
    result["bit_identical"] = ok
    if not ok:
        failures.append("post-restore backup manifest diverges from original")
    return failures


# -- main ---------------------------------------------------------------------


def main() -> int:
    quick = "--quick" in sys.argv
    tmp = tempfile.mkdtemp(prefix="scrub-")
    result: dict = {"quick": quick, "seed": SEED}
    failures: list = []

    ports = [_free_port(), _free_port()]
    hosts = [f"127.0.0.1:{p}" for p in ports]
    procs = [_spawn_node(tmp, r, hosts) for r in range(2)]
    try:
        for p in ports:
            _wait_ready(p)
        assert _http(ports[0], "POST", "/index/i", b"")[0] == 200
        assert _http(ports[0], "POST", "/index/i/field/f", b"")[0] == 200

        print("== seed static + multi-shard rows")
        oracle: dict = {}
        static = _static_cells()
        for r, cells in static.items():
            _ingest_acked(ports[0], [(r, c, True) for c in sorted(cells)])
        oracle.update(static)
        oracle.update(_seed_shards(ports[0]))
        for r, cells in oracle.items():
            assert _read_row_acked(ports[0], r) == cells, f"seed verify row {r}"
        n_snap = _force_snapshots(ports)
        result["fragments_snapshotted"] = n_snap
        print(f"== snapshotted {n_snap} fragment files (digest trailers on disk)")

        failures += _rot_phase(ports, oracle, result, quick)
        failures += _backup_phase(ports, oracle, result)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()

    result["failures"] = failures
    with open(ARTIFACT, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"artifact: {ARTIFACT}")
    if failures:
        print("FAIL: " + "; ".join(failures))
        return 1
    print(
        "PASS: every seeded corruption detected+journaled, quarantined "
        "fragments repaired from replicas, zero wrong answers, errors "
        "bounded to {429,503,504}, backup→wipe→restore bit-identical, "
        "tampered archive refused"
    )
    return 0


if __name__ == "__main__":
    if os.environ.get(MODE_ENV):
        worker()
    else:
        sys.exit(main())
