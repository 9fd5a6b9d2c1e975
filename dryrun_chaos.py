"""Cross-layer chaos soak (ISSUE 14) — one seeded schedule composing
all three injected fault families against a LIVE server under mixed
read/write load, plus a federated sub-phase with gang-channel faults:

  * storage windows: fsync EIO on the durable-ingest op log
    (``fsync_fail_every=N`` via ``POST /debug/chaos``) — writes may
    shed/nack (429/503) but every acked batch stays durable,
  * device windows: injected RESOURCE_EXHAUSTED on every Nth kernel
    launch (``oom_every=N``) — the HBM governor's evict → retry
    recovery serves every read, DeviceHealth never trips,
  * bit-rot windows (ISSUE 15): ``bitrot=N`` flips a snapshot-base
    byte on disk under a dedicated ``rot`` index; a scoped scrub sweep
    must DETECT it (digest mismatch → quarantine + journal) while the
    main index's load is untouched,
  * a federated sub-phase: a 2-process gang booted with
    ``distributed-faults`` (frame delay + a deterministic drop) — the
    gang degrades to replicated-solo behind a bounded 503 fence and
    keeps answering correctly.

The invariant asserted everywhere: a fault may cost latency or a
retryable error (status ⊆ {200, 429, 503, 504}) — NEVER a wrong
answer. Static rows seeded before the first window have fixed truth,
so every 200 read DURING a fault window is checked bit-identical
against the python oracle; writer rows verify at the post-window
quiesce points; every window leaves ``chaos.window`` + fault/recovery
events in the journal.

    python dryrun_chaos.py            # full run + artifact
    python dryrun_chaos.py --quick    # smaller load (CI smoke)

Artifact: CHAOS_r14.json. Worker modes (spawned): PILOSA_CHAOS_MODE.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import tempfile
import threading
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

MODE_ENV = "PILOSA_CHAOS_MODE"  # server | gang
PORT_ENV = "PILOSA_CHAOS_PORT"
DATA_ENV = "PILOSA_CHAOS_DATA"
RANK_ENV = "PILOSA_CHAOS_RANK"
COORD_ENV = "PILOSA_CHAOS_COORD"
MH_FAULTS_ENV = "PILOSA_CHAOS_MH_FAULTS"

ARTIFACT = "CHAOS_r14.json"
SEED = 14
ALLOWED = {200, 429, 503, 504}
GANG_FAULTS = "drop_every=25,delay=0.001,after=30"

N_STATIC_ROWS = 8
STATIC_ROW_BASE = 100_000
ROWS_PER_WRITER = 16


# -- workers ------------------------------------------------------------------


def worker() -> None:
    import faulthandler

    faulthandler.register(signal.SIGUSR1)  # stack dump on demand

    from pilosa_tpu.server.config import Config
    from pilosa_tpu.server.server import Server

    mode = os.environ[MODE_ENV]
    if mode == "server":
        cfg = Config(
            data_dir=os.environ[DATA_ENV],
            bind=f"127.0.0.1:{os.environ[PORT_ENV]}",
            device_policy="always",
            metric="none",
            anti_entropy_interval=0,
            chaos_enabled=True,
        )
        s = Server(cfg)
        s.open()
        print(f"chaos dryrun server up on {cfg.bind}", flush=True)
        while True:  # parent terminates us
            time.sleep(1.0)

    # mode == "gang": one rank of the federated sub-phase, gang channel
    # faults installed at boot (they wrap the channel at construction —
    # the one family the runtime /debug/chaos endpoint can't arm)
    rank = int(os.environ[RANK_ENV])
    cfg = Config(
        data_dir=os.path.join(os.environ[DATA_ENV], f"rank{rank}"),
        bind=f"127.0.0.1:{os.environ[PORT_ENV] if rank == 0 else 0}",
        device_policy="always",
        metric="none",
        anti_entropy_interval=0,
        distributed_enabled=True,
        distributed_coordinator=os.environ[COORD_ENV],
        distributed_process_id=rank,
        distributed_num_processes=2,
        distributed_idle_interval=1.0,
        distributed_dispatch_timeout=6.0,
        distributed_leader_timeout=30.0,
        distributed_faults=os.environ.get(MH_FAULTS_ENV, ""),
    )
    srv = Server(cfg)
    srv.open()
    if rank == 0:
        stop = []
        signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
        print(json.dumps({"event": "ready", "rank": 0}), flush=True)
        while not stop:
            time.sleep(0.1)
        stats = srv.multihost.stats() if srv.multihost else None
        srv.close()
        print(json.dumps({"event": "exit", "rank": 0, "stats": stats}), flush=True)
        time.sleep(3.0)  # keep the coordination service up for rank 1
        return
    reason = srv.serve_follower()
    stats = srv.multihost.stats() if srv.multihost else None
    print(
        json.dumps({"event": "exit", "rank": 1, "stop_reason": reason, "stats": stats}),
        flush=True,
    )
    # hard-exit on desync: a clean interpreter exit would block in
    # jax.distributed's atexit barrier until the leader exits, keeping
    # this process's gloo connections OPEN — and the leader's
    # half-joined collective (the one whose descriptor frame the fault
    # dropped) blocks its whole device stream until those connections
    # reset. Real follower loss is process death; emulate it.
    os._exit(0)


def _spawn(mode: str, tmp: str, tag: str, **extra_env):
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **{MODE_ENV: mode, DATA_ENV: tmp}, **extra_env)
    if mode == "gang":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = open(os.path.join(tmp, f"{tag}.log"), "w+")
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=out,
        stderr=subprocess.STDOUT,
        text=True,
    )
    p._outf = out  # type: ignore[attr-defined]
    return p


# -- load generation ----------------------------------------------------------


_ROT_FRAG_PATH = "/internal/fragment/data?index=rot&field=f&view=standard&shard=0"


def _setup_rot_index(port: int) -> bytes:
    """Dedicated sacrificial index for bit-rot windows (ISSUE 15): a
    few bits, snapshotted so the file carries a digest trailer for the
    scrub sweep to verify. Returns the fragment's checksummed archive —
    the known-good copy each bit-rot window restores from (the repair
    role a replica would play in a federated deployment)."""
    for path in ("/index/rot", "/index/rot/field/f"):
        st, body = _http(port, "POST", path, b"{}")
        assert st in (200, 409), (st, body[:200])
    body = json.dumps(
        {
            "rowIDs": [1] * 64 + [2] * 64,
            "columnIDs": list(range(64)) + list(range(100, 164)),
            "sets": [True] * 128,
        }
    ).encode()
    st, body = _http(port, "POST", "/index/rot/field/f/ingest", body)
    assert st == 200, (st, body[:200])
    st, archive = _http(port, "GET", _ROT_FRAG_PATH)
    assert st == 200
    # round-trip through the verify-before-apply restore: unmarshal
    # forces a snapshot, so the on-disk file gains its digest trailer
    st, body = _http(port, "POST", _ROT_FRAG_PATH, archive)
    assert st == 200, (st, body[:200])
    return archive


def _static_cells() -> dict:
    """Deterministic seed rows written ONCE before the first window —
    their truth never changes, so reads during fault windows verify."""
    rows: dict[int, set] = {}
    for k in range(N_STATIC_ROWS):
        r = STATIC_ROW_BASE + k
        rows[r] = {(k * 31 + i * 17) % 4096 for i in range(40 + 8 * k)}
    return rows


def _ingest(port: int, muts: list, timeout: float = 30.0):
    body = json.dumps(
        {
            "rowIDs": [m[0] for m in muts],
            "columnIDs": [m[1] for m in muts],
            "sets": [m[2] for m in muts],
        }
    ).encode()
    return _http(port, "POST", "/index/i/field/f/ingest", body, timeout=timeout)


def _ingest_acked(port: int, muts: list, deadline_s: float = 60.0) -> None:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        st, _ = _ingest(port, muts)
        if st == 200:
            return
        assert st in ALLOWED, st
        time.sleep(0.02)
    raise TimeoutError("seed ingest never acked")


class Writer:
    """One writer thread with a disjoint row range; retries 429/5xx
    until ack so its oracle is exact. Any status outside the allowed
    set is a contract violation."""

    def __init__(self, wid: int, port: int):
        self.port = port
        self.row_base = wid * ROWS_PER_WRITER
        self.acked_batches: list[list] = []
        self.unknown: list = []  # mutations whose outcome is indeterminate
        self.requests = 0
        self.retries = 0
        self.bad_statuses: list[int] = []
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def _mutations(self, seq: int) -> list:
        return [
            (
                self.row_base + (seq * 5 + i) % ROWS_PER_WRITER,
                (seq * 24 + i) * 13 % 4096,
                not (seq > 2 and i % 5 == 0),
            )
            for i in range(24)
        ]

    def run(self) -> None:
        seq = 0
        while not self.stop.is_set():
            muts = self._mutations(seq)
            indeterminate = False  # saw a 504/connection loss for THIS batch
            acked = False
            while not self.stop.is_set():
                try:
                    st, _ = _ingest(self.port, muts, timeout=10)
                except OSError:
                    indeterminate = True
                    self.retries += 1
                    time.sleep(0.05)
                    continue
                self.requests += 1
                if st == 200:
                    self.acked_batches.append(muts)
                    acked = True
                    break
                if st not in ALLOWED:
                    self.bad_statuses.append(st)
                    self.stop.set()
                    break
                if st == 504:
                    # 504 means "commit wait lapsed", NOT "nacked" —
                    # the wave may still land; the same-batch retry is
                    # idempotent, but stopping here leaves it unknown
                    indeterminate = True
                self.retries += 1
                time.sleep(0.01)
            if indeterminate and not acked:
                self.unknown.extend(muts)
            seq += 1


class Reader:
    """Reads static rows (fixed truth) through the fused multi-call
    path during fault windows: every 200 must be bit-identical; every
    non-200 must be a clean retryable status."""

    def __init__(self, rid: int, port: int, static: dict):
        self.port = port
        self.static = static
        self.rid = rid
        self.requests = 0
        self.wrong: list = []
        self.bad_statuses: list[int] = []
        self.transient = 0
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def run(self) -> None:
        keys = sorted(self.static)
        i = self.rid
        while not self.stop.is_set():
            r1, r2 = keys[i % len(keys)], keys[(i + 3) % len(keys)]
            q = f"Count(Row(f={r1}))Count(Row(f={r2}))"
            want = [len(self.static[r1]), len(self.static[r2])]
            try:
                st, body = _http(self.port, "POST", "/index/i/query", q.encode(), 15)
            except OSError:
                self.transient += 1
                time.sleep(0.05)
                continue
            self.requests += 1
            if st == 200:
                got = json.loads(body)["results"]
                if got != want:
                    self.wrong.append({"q": q, "want": want, "got": got})
                    self.stop.set()
            elif st in ALLOWED:
                self.transient += 1
                time.sleep(0.01)
            else:
                self.bad_statuses.append(st)
                self.stop.set()
            i += 1


def _oracle_rows(writers) -> dict:
    rows: dict[int, set] = {}
    for w in writers:
        for batch in w.acked_batches:
            for r, c, s in batch:
                cells = rows.setdefault(r, set())
                (cells.add if s else cells.discard)(c)
    return rows


def _read_row_acked(port: int, r: int, deadline_s: float = 30.0) -> set:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        st, body = _http(port, "POST", "/index/i/query", f"Row(f={r})".encode())
        if st == 200:
            return set(json.loads(body)["results"][0].get("columns", []))
        assert st in ALLOWED, st
        time.sleep(0.05)
    raise TimeoutError(f"Row(f={r}) never served")


def _events(port: int, kind: str, since: int = 0) -> list:
    _, body = _http(
        port, "GET", f"/debug/events?kind={kind}&since={since}&limit=5000"
    )
    return json.loads(body).get("events", [])


def _journal_seq(port: int) -> int:
    """Newest journal seq — the per-window watermark. The journal is
    durable (segmented on-disk backing, ISSUE 16), so each window's
    events are counted AFTER its soak with ``since=<watermark>`` — no
    more sampling before load to beat ring eviction."""
    _, body = _http(port, "GET", "/debug/events?limit=1")
    ev = json.loads(body).get("events", [])
    return ev[-1]["seq"] if ev else 0


# -- the soak -----------------------------------------------------------------


def _window_phase(port: int, quick: bool, result: dict) -> list:
    from pilosa_tpu.utils.chaos import ChaosSchedule

    n_windows = 4 if quick else 8
    duration = 2.0 if quick else 4.0
    n_writers = 2 if quick else 4
    n_readers = 3 if quick else 5

    static = _static_cells()
    print("== seed static rows (fixed truth for in-window reads)")
    for r, cells in static.items():
        _ingest_acked(port, [(r, c, True) for c in sorted(cells)])
    for r, cells in static.items():
        assert _read_row_acked(port, r) == cells, f"static seed verify row {r}"
    rot_archive = _setup_rot_index(port)

    schedule = list(ChaosSchedule(seed=SEED, windows=n_windows, duration_s=duration))
    result["seed"] = SEED
    result["windows"] = []
    all_writers: list[Writer] = []
    wid = 0
    for w in schedule:
        bitrot = "bitrot" in w["name"]
        print(f"== window {w['name']}: storage={w['storage'] or '-'} "
              f"device={w['device'] or '-'} ({w['duration_s']}s)")
        if bitrot:
            # re-arm: a previous bit-rot window left the rot fragment
            # quarantined (no replica to repair from on one node);
            # restoring the known-good archive clears it so THIS
            # window's verification detects a FRESH flip
            st, body = _http(
                port, "POST", _ROT_FRAG_PATH, rot_archive, timeout=60
            )
            assert st == 200, (st, body[:200])
        seq0 = _journal_seq(port)
        st, body = _http(
            port, "POST", "/debug/chaos",
            json.dumps({"storage": w["storage"], "device": w["device"]}).encode(),
        )
        assert st == 200, (st, body[:200])

        scrub_res = None
        if bitrot:
            # scoped scrub sweeps on the rot index: the sweep's digest
            # verification is where the installed bitrot spec flips a
            # base byte — detection, quarantine, and journal all happen
            # against a LIVE server. bitrot=N fires every Nth
            # verification (N ≤ 3), so up to 4 sweeps arm it. Then the
            # storage fault is cleared BEFORE the mixed load: a main-
            # index snapshot also re-verifies its digest, and rotting
            # the load-bearing index would poison the soak's oracle.
            for _ in range(4):
                st, body = _http(
                    port, "POST", "/debug/scrub",
                    json.dumps({"index": "rot"}).encode(), timeout=60,
                )
                assert st == 200, (st, body[:200])
                scrub_res = json.loads(body)
                if scrub_res["corrupt"]:
                    break
            st, _ = _http(
                port, "POST", "/debug/chaos",
                json.dumps({"storage": "", "device": w["device"]}).encode(),
            )
            assert st == 200

        writers = [Writer(wid + k, port) for k in range(n_writers)]
        wid += n_writers
        readers = [Reader(k, port, static) for k in range(n_readers)]
        for t in writers + readers:
            t.thread.start()
        time.sleep(w["duration_s"])
        for t in writers + readers:
            t.stop.set()
        for t in writers + readers:
            t.thread.join(timeout=30)
        all_writers.extend(writers)

        # clear the window, then count this window's journal events
        # AFTER the soak — the durable backing pages past any ring
        # eviction, which is exactly what the before-load sampling
        # workaround existed to dodge
        st, _ = _http(port, "POST", "/debug/chaos", b"{}")
        assert st == 200
        fault_ev = {
            "ingest_fault": len(_events(port, "ingest.fault", seq0)),
            "device_oom": len(_events(port, "device.oom", seq0)),
            "device_oom_recovered": len(
                _events(port, "device.oom_recovered", seq0)
            ),
            "scrub_corruption": len(_events(port, "scrub.corruption", seq0)),
            "scrub_quarantine": len(_events(port, "scrub.quarantine", seq0)),
        }
        chaos_ev = len(_events(port, "chaos.window", seq0))
        oracle = _oracle_rows(writers)
        unknown: dict[int, set] = {}
        for x in writers:
            for r, c, _s in x.unknown:
                unknown.setdefault(r, set()).add(c)
        mismatches = []
        for r, want in oracle.items():
            got = _read_row_acked(port, r)
            skip = unknown.get(r, set())
            if got - skip != want - skip:
                mismatches.append(r)
        journal = {"chaos_window": chaos_ev, **fault_ev}
        wres = {
            "name": w["name"],
            "storage": w["storage"],
            "device": w["device"],
            "journal": journal,
            "scrub": scrub_res,
            "write_requests": sum(x.requests for x in writers),
            "write_retries": sum(x.retries for x in writers),
            "acked_batches": sum(len(x.acked_batches) for x in writers),
            "unknown_mutations": sum(len(x.unknown) for x in writers),
            "read_requests": sum(x.requests for x in readers),
            "read_transient": sum(x.transient for x in readers),
            "wrong_answers": [e for x in readers for e in x.wrong],
            "bad_statuses": sorted(
                {s for x in writers + readers for s in x.bad_statuses}
            ),
            "quiesce_mismatched_rows": mismatches,
        }
        result["windows"].append(wres)
        print(
            f"   writes={wres['write_requests']} (retries={wres['write_retries']}) "
            f"reads={wres['read_requests']} (transient={wres['read_transient']}) "
            f"wrong={len(wres['wrong_answers'])} bad={wres['bad_statuses']} "
            f"quiesce_mismatch={len(mismatches)}"
        )

    _, body = _http(port, "GET", "/debug/chaos")
    snap = json.loads(body)
    result["oom"] = snap["oom"]
    result["health_trips"] = snap["health_trips"]
    result["governor"] = snap["governor"]

    total_writes = sum(w["write_requests"] for w in result["windows"])
    total_reads = sum(w["read_requests"] for w in result["windows"])
    result["write_fraction"] = round(
        total_writes / max(1, total_writes + total_reads), 4
    )

    failures = []
    if any(w["wrong_answers"] for w in result["windows"]):
        failures.append("wrong answers during fault windows")
    if any(w["bad_statuses"] for w in result["windows"]):
        failures.append("statuses outside {200,429,503,504}")
    if any(w["quiesce_mismatched_rows"] for w in result["windows"]):
        failures.append("acked writes lost at quiesce")
    if result["write_fraction"] < 0.10:
        failures.append(f"write fraction {result['write_fraction']} < 10%")
    for w in result["windows"]:
        j = w["journal"]
        if j["chaos_window"] < 2:  # install + clear transitions
            failures.append(f"{w['name']}: missing chaos.window journal events")
        if w["storage"] and not j["ingest_fault"]:
            failures.append(f"{w['name']}: storage faults journaled no ingest.fault")
        if w["device"] and not j["device_oom"]:
            failures.append(f"{w['name']}: device faults journaled no device.oom")
        if "bitrot" in w["name"]:
            if not w["scrub"] or not w["scrub"]["corrupt"]:
                failures.append(f"{w['name']}: scrub detected no bit rot")
            if not j["scrub_corruption"] or not j["scrub_quarantine"]:
                failures.append(
                    f"{w['name']}: bit rot left no scrub journal events"
                )
    if any(w["device"] for w in result["windows"]) and result["oom"]["recovered"] < 1:
        failures.append("no injected OOM recovered in place")
    if result["health_trips"] != 0:
        failures.append("an injected OOM tripped DeviceHealth")
    return failures


def _post_acked(port: int, path: str, body: bytes, ok=(200, 409)) -> None:
    """POST with retry through the degrade fence: a frame dropped by
    the gang faults 503s the in-flight request while the gang fences
    and degrades — the retry must land on the local-mesh path."""
    t_end = time.monotonic() + 120
    while True:
        try:
            st, resp = _http(port, "POST", path, body, timeout=30)
        except OSError:
            st, resp = None, b""
        if st in ok:
            return
        assert st is None or st in ALLOWED, (st, resp[:300])
        if time.monotonic() > t_end:
            raise TimeoutError(f"POST {path} never acked (last={st})")
        time.sleep(0.25)


def _load_gang(port: int, bits, values) -> None:
    _post_acked(port, "/index/i", b"")
    _post_acked(port, "/index/i/field/f", b"")
    _post_acked(
        port,
        "/index/i/field/val",
        json.dumps({"options": {"type": "int", "min": 0, "max": 1000}}).encode(),
    )
    sets = [f"Set({col}, f={row})" for row, col in bits]
    for i in range(0, len(sets), 200):
        # Set is idempotent, so retrying a batch whose frame was
        # dropped mid-replication cannot corrupt the oracle
        _post_acked(port, "/index/i/query", " ".join(sets[i : i + 200]).encode(), (200,))
    _post_acked(
        port,
        "/index/i/field/val/import-value",
        json.dumps(
            {"columnIDs": [c for c, _ in values], "values": [v for _, v in values]}
        ).encode(),
        (200,),
    )
    _post_acked(port, "/recalculate-caches", b"", (200,))


def _federated_phase(tmp: str, quick: bool, result: dict) -> list:
    """2-process gang booted with frame delay + a deterministic drop on
    the control channel: the drop desyncs the follower, the gang
    degrades behind a bounded 503 fence, reads stay correct throughout."""
    print(f"== federated sub-phase: 2-process gang, faults {GANG_FAULTS}")
    bits, values = _dataset(quick=True)
    want = _oracle(bits, values)
    port, coord = _free_port(), _free_port()
    env = {
        PORT_ENV: str(port),
        COORD_ENV: f"127.0.0.1:{coord}",
        MH_FAULTS_ENV: GANG_FAULTS,
    }
    procs = [
        _spawn("gang", tmp, f"gang-rank{r}", **env, **{RANK_ENV: str(r)})
        for r in (0, 1)
    ]
    fed = {"faults": GANG_FAULTS, "reads": 0, "transient": 0}
    failures: list = []
    try:
        _wait_ready(port, deadline_s=180)
        _load_gang(port, bits, values)
        rounds = 10 if quick else 20
        wrong = []
        bad = []
        for i in range(rounds):
            for q in READ_QUERIES:
                t_end = time.monotonic() + 30
                while True:
                    try:
                        st, body = _http(
                            port, "POST", "/index/i/query", q.encode(), 30
                        )
                    except OSError:
                        st = None
                    fed["reads"] += 1
                    if st == 200:
                        got = json.loads(body)["results"]
                        if got != want[q]:
                            wrong.append({"q": q, "round": i})
                        break
                    if st is not None and st not in ALLOWED:
                        bad.append(st)
                        break
                    fed["transient"] += 1  # bounded degrade fence
                    if time.monotonic() > t_end:
                        failures.append(f"gang read {q!r} never recovered")
                        break
                    time.sleep(0.25)
            if failures:
                break
        fed["wrong_answers"] = wrong
        fed["bad_statuses"] = sorted(set(bad))
        if wrong:
            failures.append("wrong answers on the faulted gang")
        if bad:
            failures.append("gang statuses outside the allowed set")
    finally:
        procs[0].send_signal(signal.SIGTERM)
        out0, _, _ = _finish(procs[0], timeout=60)
        out1, _, _ = _finish(procs[1], timeout=60)
        for line in (out0 + out1).splitlines():
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if d.get("event") == "exit" and d.get("stats"):
                fed[f"rank{d.get('rank')}_stats"] = d["stats"]
    result["federated"] = fed
    return failures


def main() -> int:
    quick = "--quick" in sys.argv
    tmp = tempfile.mkdtemp(prefix="chaos-")
    result: dict = {"quick": quick}
    failures: list = []

    port = _free_port()
    p = _spawn("server", tmp, "server", **{PORT_ENV: str(port)})
    try:
        _wait_ready(port)
        assert _http(port, "POST", "/index/i", b"")[0] == 200
        assert _http(port, "POST", "/index/i/field/f", b"")[0] == 200
        failures += _window_phase(port, quick, result)
    finally:
        p.terminate()
        try:
            p.wait(timeout=30)
        except Exception:
            p.kill()

    failures += _federated_phase(tmp, quick, result)

    result["failures"] = failures
    with open(ARTIFACT, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"artifact: {ARTIFACT}")
    if failures:
        print("FAIL: " + "; ".join(failures))
        return 1
    print(
        "PASS: zero wrong answers, errors bounded to {429,503,504}, "
        "every window recovered, injected OOMs recovered without a "
        "health trip"
    )
    return 0


if __name__ == "__main__":
    if os.environ.get(MODE_ENV):
        worker()
    else:
        sys.exit(main())
