"""Key-translation crash-recovery dryrun (ISSUE 20) — SIGKILL a server
mid KEYED ingest, restart it on the same data dir, and prove the
translate durability contract end to end:

  * every ACKED key→id assignment survives: a keyed ingest batch the
    client saw ack (200 — translate assignments group-committed ahead
    of the write wave's own fsync) resolves to the SAME id after the
    restart,
  * no duplicate ids: the recovered key→id map is injective per space
    (per column partition residue class, per field row space) — a
    replayed log never re-mints an id,
  * unacked tail truncated: a translate frame torn by the kill
    truncates cleanly at reopen (reported via /debug/translate
    ``truncatedBytes``) instead of failing the open,
  * the keyed query surface stays bit-identical to the acked oracle
    across the crash: Row(f="...") serves exactly the acked columns.

    python dryrun_translate_crash.py           # full run + artifact
    python dryrun_translate_crash.py --quick   # smaller load (CI smoke)

Artifact: TRANSLATE_r20.json. Worker mode (spawned server):
PILOSA_TRANSLATE_DRYRUN_MODE set.
"""

from __future__ import annotations

import json
import os
import http.client
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

MODE_ENV = "PILOSA_TRANSLATE_DRYRUN_MODE"
PORT_ENV = "PILOSA_TRANSLATE_DRYRUN_PORT"
DATA_ENV = "PILOSA_TRANSLATE_DRYRUN_DATA"

ARTIFACT = "TRANSLATE_r20.json"


# -- worker (the server process) ---------------------------------------------


def worker() -> None:
    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.server import Server

    cfg = Config(
        data_dir=os.environ[DATA_ENV],
        bind=f"127.0.0.1:{os.environ[PORT_ENV]}",
        device_policy="never",
    )
    s = Server(cfg)
    s.open()
    print(f"translate dryrun server up on {cfg.bind}", flush=True)
    while True:  # parent SIGKILLs / SIGTERMs us
        time.sleep(1.0)


# -- parent helpers ----------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, method: str, path: str, body: bytes = b"", timeout: float = 60):
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


def _spawn(port: int, data_dir: str, tmp: str, tag: str):
    env = dict(os.environ)
    env[MODE_ENV] = "server"
    env[PORT_ENV] = str(port)
    env[DATA_ENV] = data_dir
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


def _resolve(port: int, field: str, keys: list) -> list:
    """key → id through the owner mint endpoint (single node = owner
    of every space; idempotent for existing keys)."""
    st, body = _http(
        port,
        "POST",
        "/internal/translate/keys",
        json.dumps({"index": "i", "field": field, "keys": keys}).encode(),
    )
    assert st == 200, (st, body)
    return json.loads(body)["ids"]


# -- load generation ---------------------------------------------------------


class Writer:
    """One client thread minting a disjoint key namespace via keyed
    ingest. After each ack it resolves the batch's keys to ids and
    records them — the oracle the restarted server must reproduce
    exactly. The batch in flight at the kill is unknown-outcome."""

    def __init__(self, wid: int, port: int, batch: int):
        self.wid = wid
        self.port = port
        self.batch = batch
        # key -> id observed at ack time (never overwritten)
        self.acked_rows: dict = {}
        self.acked_cols: dict = {}
        # row key -> set of column keys acked into it
        self.oracle: dict = {}
        self.unknown_keys: set = set()
        self.acked_batches = 0
        self.retries = 0
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def _batch_keys(self, seq: int):
        rows = [f"w{self.wid}-r{(seq + i) % 8}" for i in range(self.batch)]
        cols = [f"w{self.wid}-c{seq}-{i}" for i in range(self.batch)]
        return rows, cols

    def run(self) -> None:
        seq = 0
        while not self.stop.is_set():
            rows, cols = self._batch_keys(seq)
            body = json.dumps({"rowKeys": rows, "columnKeys": cols}).encode()
            while not self.stop.is_set():
                try:
                    status, _ = _http(
                        self.port, "POST", "/index/i/field/f/ingest", body, timeout=10
                    )
                except (OSError, http.client.HTTPException):
                    # connection died mid-request: the kill — these
                    # keys may or may not have been assigned
                    self.unknown_keys.update(rows)
                    self.unknown_keys.update(cols)
                    self.stop.set()
                    break
                if status == 200:
                    try:
                        rids = _resolve(self.port, "f", rows)
                        cids = _resolve(self.port, "", cols)
                    except (OSError, http.client.HTTPException, AssertionError):
                        # killed between ack and resolve: the ASSIGNMENT
                        # is durable (the 200 proved it) but we never
                        # observed the id — treat as unknown
                        self.unknown_keys.update(rows)
                        self.unknown_keys.update(cols)
                        self.stop.set()
                        break
                    for k, id_ in zip(rows, rids):
                        self.acked_rows.setdefault(k, id_)
                    for k, id_ in zip(cols, cids):
                        self.acked_cols.setdefault(k, id_)
                    for rk, ck in zip(rows, cols):
                        self.oracle.setdefault(rk, set()).add(ck)
                    self.acked_batches += 1
                    break
                self.retries += 1  # 429 shed / 5xx nacked wave: retry
                time.sleep(0.01)
            seq += 1


def main() -> int:
    quick = "--quick" in sys.argv
    n_writers = 4 if quick else 6
    batch = 16
    load_seconds = 2.5 if quick else 6.0

    tmp = tempfile.mkdtemp(prefix="translate-crash-")
    data = os.path.join(tmp, "data")
    port = _free_port()
    result: dict = {"quick": quick, "writers": n_writers}

    print("== phase 1: server up, concurrent KEYED ingest load")
    p = _spawn(port, data, tmp, "a")
    try:
        _wait_ready(port)
        assert (
            _http(port, "POST", "/index/i", json.dumps({"options": {"keys": True}}).encode())[0]
            == 200
        )
        assert (
            _http(
                port,
                "POST",
                "/index/i/field/f",
                json.dumps({"options": {"keys": True}}).encode(),
            )[0]
            == 200
        )

        writers = [Writer(w, port, batch) for w in range(n_writers)]
        for w in writers:
            w.thread.start()
        time.sleep(load_seconds)

        print("== phase 2: SIGKILL mid keyed-ingest")
        p.send_signal(signal.SIGKILL)
        p.wait()
        for w in writers:
            w.stop.set()
        for w in writers:
            w.thread.join(timeout=15)

        acked_keys = sum(len(w.acked_rows) + len(w.acked_cols) for w in writers)
        result["acked_batches"] = sum(w.acked_batches for w in writers)
        result["acked_keys"] = acked_keys
        result["nack_retries"] = sum(w.retries for w in writers)
        result["unknown_keys"] = sum(len(w.unknown_keys) for w in writers)
        print(
            f"   acked-keys={acked_keys} "
            f"batches={result['acked_batches']} "
            f"unknown-at-kill={result['unknown_keys']}"
        )
        if acked_keys == 0:
            print("FAIL: no keyed batch acked before the kill — nothing proven")
            return 1

        print("== phase 3: restart on the same data dir, verify assignments")
        p2 = _spawn(port, data, tmp, "b")
        try:
            _wait_ready(port)
            st, body = _http(port, "GET", "/debug/translate")
            assert st == 200, (st, body)
            dbg = json.loads(body)
            result["recovered_keys"] = dbg["keys"]
            result["truncated_bytes"] = dbg["truncatedBytes"]

            # (1) every acked key resolves to the SAME id
            changed = []
            for w in writers:
                rks = sorted(w.acked_rows)
                for k, id_ in zip(rks, _resolve(port, "f", rks)):
                    if id_ != w.acked_rows[k]:
                        changed.append(("row", k, w.acked_rows[k], id_))
                cks = sorted(w.acked_cols)
                for k, id_ in zip(cks, _resolve(port, "", cks)):
                    if id_ != w.acked_cols[k]:
                        changed.append(("col", k, w.acked_cols[k], id_))
            result["changed_assignments"] = changed[:50]

            # (2) no duplicate ids per space (column ids are globally
            # unique across partitions by the residue-class layout)
            dup = []
            col_ids: dict = {}
            row_ids: dict = {}
            for w in writers:
                for k, id_ in w.acked_cols.items():
                    if col_ids.setdefault(id_, k) != k:
                        dup.append(("col", id_, col_ids[id_], k))
                for k, id_ in w.acked_rows.items():
                    if row_ids.setdefault(id_, k) != k:
                        dup.append(("row", id_, row_ids[id_], k))
            result["duplicate_ids"] = dup[:50]

            # (3) keyed reads bit-identical to the acked oracle
            lost = []
            checked = 0
            for w in writers:
                for rk, want_cols in sorted(w.oracle.items()):
                    st, body = _http(
                        port, "POST", "/index/i/query", f'Row(f="{rk}")'.encode()
                    )
                    assert st == 200, (st, body)
                    got = set(json.loads(body)["results"][0].get("keys") or [])
                    checked += 1
                    for ck in want_cols - got - w.unknown_keys:
                        lost.append((rk, ck, "acked keyed set missing"))
            result["checked_row_keys"] = checked
            result["lost"] = lost[:50]
            ok = not changed and not dup and not lost
            result["ok"] = ok
            print(
                f"   recovered-keys={dbg['keys']} "
                f"truncated-bytes={dbg['truncatedBytes']} "
                f"changed={len(changed)} dup={len(dup)} lost={len(lost)}"
            )

            # the recovered server still mints: fresh keys get fresh,
            # non-colliding ids
            (nid,) = _resolve(port, "f", ["post-recovery-row"])
            assert nid not in row_ids, "recovered mint reused a live id"
            result["post_recovery_mint"] = True
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
    if not result.get("ok"):
        print("FAIL: acked assignment changed, id duplicated, or keyed bits lost")
        return 1
    print("PASS: every acked key kept its id; no duplicates; clean recovery")
    return 0


if __name__ == "__main__":
    if os.environ.get(MODE_ENV):
        worker()
    else:
        sys.exit(main())
