"""Ingest crash-recovery dryrun (ISSUE 11) — SIGKILL a server mid-ingest
under injected storage faults, restart it on the same data dir, and
prove the durability contract end to end:

  * ZERO acknowledged writes lost: every batch a client saw ack (HTTP
    200 — its write wave group-committed + fsynced) is present after
    the restart, bit-identical to a CPU oracle replaying only acked
    batches,
  * clean truncation: a record torn by the kill (or by the injected
    ``torn_at`` fault) truncates at reopen instead of failing the open
    or corrupting the replay,
  * batches in flight at the kill (no ack observed) are allowed either
    state — the contract is one-way.

Fault schedule while loading: ``fsync_fail_every=23,torn_at=9000`` —
periodic fsync EIO (waves nack, clients retry) plus one torn append
(the writer repairs the tail in-place). Clients retry nacked batches
until acked, so the oracle stays exact; only the kill itself creates
unknown-outcome batches.

    python dryrun_ingest_crash.py            # full run + artifact
    python dryrun_ingest_crash.py --quick    # smaller load (CI smoke)

Artifact: INGEST_CRASH_r11.json. Worker mode (spawned server):
PILOSA_INGEST_DRYRUN_MODE set.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

MODE_ENV = "PILOSA_INGEST_DRYRUN_MODE"
PORT_ENV = "PILOSA_INGEST_DRYRUN_PORT"
DATA_ENV = "PILOSA_INGEST_DRYRUN_DATA"
FAULTS_ENV = "PILOSA_INGEST_DRYRUN_FAULTS"

ARTIFACT = "INGEST_CRASH_r11.json"
FAULTS = "fsync_fail_every=23,torn_at=9000"


# -- worker (the server process) ---------------------------------------------


def worker() -> None:
    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=os.environ[DATA_ENV],
        bind=f"127.0.0.1:{os.environ[PORT_ENV]}",
        device_policy="never",
        storage_faults=os.environ.get(FAULTS_ENV, ""),
    )
    s = Server(cfg)
    s.open()
    print(f"ingest dryrun server up on {cfg.bind}", flush=True)
    while True:  # parent SIGKILLs / SIGTERMs us
        time.sleep(1.0)


# -- parent helpers ----------------------------------------------------------


def _free_port() -> int:
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
    raise TimeoutError("server HTTP never came up")


def _spawn(port: int, data_dir: str, faults: str, tmp: str, tag: str):
    env = dict(os.environ)
    env[MODE_ENV] = "server"
    env[PORT_ENV] = str(port)
    env[DATA_ENV] = data_dir
    env[FAULTS_ENV] = faults
    env["JAX_PLATFORMS"] = "cpu"
    outf = open(os.path.join(tmp, f"server-{tag}.log"), "w+")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=outf,
        stderr=subprocess.STDOUT,
    )
    p._outf = outf  # type: ignore[attr-defined]
    return p


# -- load generation ---------------------------------------------------------


class Writer:
    """One client thread owning a disjoint row range. Retries 429/5xx
    nacks until ack, so its oracle is exact; the batch in flight when
    the server dies is recorded as unknown-outcome."""

    def __init__(self, wid: int, port: int, batch: int, rows_per_writer: int):
        self.wid = wid
        self.port = port
        self.batch = batch
        self.row_base = wid * rows_per_writer
        self.rows_n = rows_per_writer
        self.acked_batches: list[list] = []
        self.unknown: list = []  # mutations with no observed outcome
        self.acked = 0
        self.retries = 0
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def _mutations(self, seq: int) -> list:
        # deterministic per (writer, seq): mostly sets, some clears of
        # previously set cells — exercises OP_REMOVE replay too
        out = []
        for i in range(self.batch):
            r = self.row_base + (seq * 7 + i) % self.rows_n
            c = (seq * self.batch + i) * 13 % 4096
            s = not (seq > 2 and i % 5 == 0)
            out.append((r, c, s))
        return out

    def run(self) -> None:
        seq = 0
        while not self.stop.is_set():
            muts = self._mutations(seq)
            body = json.dumps(
                {
                    "rowIDs": [m[0] for m in muts],
                    "columnIDs": [m[1] for m in muts],
                    "sets": [m[2] for m in muts],
                }
            ).encode()
            while not self.stop.is_set():
                try:
                    status, _ = _http(
                        self.port, "POST", "/index/i/field/f/ingest", body, timeout=10
                    )
                except OSError:
                    # connection died mid-request: outcome unknown (the
                    # kill); stop — every later batch would be unknown too
                    self.unknown.extend(muts)
                    self.stop.set()
                    break
                if status == 200:
                    self.acked_batches.append(muts)
                    self.acked += len(muts)
                    break
                self.retries += 1  # 429 shed or 5xx nacked wave: retry
                time.sleep(0.01)
            seq += 1


def _oracle_rows(writers) -> dict:
    """Replay acked batches in per-writer order → {row: set(cols)}.
    Rows are writer-disjoint, so cross-writer order can't matter."""
    rows: dict[int, set] = {}
    for w in writers:
        for batch in w.acked_batches:
            for r, c, s in batch:
                cells = rows.setdefault(r, set())
                (cells.add if s else cells.discard)(c)
    return rows


def main() -> int:
    quick = "--quick" in sys.argv
    n_writers = 4 if quick else 6
    batch = 24
    rows_per_writer = 32
    load_seconds = 2.5 if quick else 6.0

    tmp = tempfile.mkdtemp(prefix="ingest-crash-")
    data = os.path.join(tmp, "data")
    port = _free_port()
    result: dict = {"quick": quick, "faults": FAULTS, "writers": n_writers}

    print(f"== phase 1: server up (faults: {FAULTS}), concurrent ingest load")
    p = _spawn(port, data, FAULTS, tmp, "a")
    try:
        _wait_ready(port)
        assert _http(port, "POST", "/index/i", b"")[0] == 200
        assert _http(port, "POST", "/index/i/field/f", b"")[0] == 200

        writers = [Writer(w, port, batch, rows_per_writer) for w in range(n_writers)]
        for w in writers:
            w.thread.start()
        time.sleep(load_seconds)

        print("== phase 2: SIGKILL mid-ingest")
        p.send_signal(signal.SIGKILL)
        p.wait()
        for w in writers:
            w.stop.set()
        for w in writers:
            w.thread.join(timeout=15)

        acked_total = sum(w.acked for w in writers)
        retries_total = sum(w.retries for w in writers)
        unknown_total = sum(len(w.unknown) for w in writers)
        result["acked_mutations"] = acked_total
        result["nack_retries"] = retries_total
        result["unknown_mutations"] = unknown_total
        print(
            f"   acked={acked_total} retries={retries_total} "
            f"unknown-at-kill={unknown_total}"
        )
        if acked_total == 0:
            print("FAIL: no batch acked before the kill — nothing proven")
            return 1

        print("== phase 3: restart on the same data dir (no faults), verify")
        p2 = _spawn(port, data, "", tmp, "b")
        try:
            _wait_ready(port)
            # recovery telemetry: did the reopen truncate a torn tail?
            _, ev = _http(port, "GET", "/debug/events?kind=ingest.recovery")
            recov = json.loads(ev).get("events", [])
            result["recovery_events"] = recov
            result["truncated_bytes"] = sum(
                e.get("truncated_bytes", 0) for e in recov
            )

            oracle = _oracle_rows(writers)
            unknown_cells = {
                (r, c) for w in writers for (r, c, _s) in w.unknown
            }
            lost = []
            checked_rows = 0
            for w in writers:
                for r in range(w.row_base, w.row_base + w.rows_n):
                    st, body = _http(
                        port, "POST", "/index/i/query",
                        f"Row(f={r})".encode(),
                    )
                    assert st == 200, (st, body)
                    got = set(json.loads(body)["results"][0].get("columns", []))
                    want = oracle.get(r, set())
                    checked_rows += 1
                    for c in want - got:
                        if (r, c) not in unknown_cells:
                            lost.append((r, c, "acked set missing"))
                    for c in got - want:
                        if (r, c) not in unknown_cells:
                            lost.append((r, c, "acked clear resurfaced"))
            result["checked_rows"] = checked_rows
            result["lost"] = lost[:50]
            result["bit_identical"] = not lost
            print(
                f"   rows checked={checked_rows} "
                f"truncated_bytes={result['truncated_bytes']} lost={len(lost)}"
            )

            # the recovered server still serves durable writes
            st, body = _http(
                port, "POST", "/index/i/field/f/ingest",
                json.dumps({"rowIDs": [9999], "columnIDs": [1]}).encode(),
            )
            assert st == 200 and json.loads(body)["acked"] == 1
            result["post_recovery_ingest"] = True
        finally:
            p2.terminate()
            p2.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()

    with open(ARTIFACT, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"artifact: {ARTIFACT}")
    if result.get("lost"):
        print(f"FAIL: {len(result['lost'])} acked writes lost/corrupted")
        return 1
    print("PASS: zero acked writes lost; bit-identical to the acked oracle")
    return 0


if __name__ == "__main__":
    if os.environ.get(MODE_ENV):
        worker()
    else:
        sys.exit(main())
