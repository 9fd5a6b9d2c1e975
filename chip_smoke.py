#!/usr/bin/env python3
"""The quickest proof that the served path runs on the chip.

Builds BASELINE config 4's shape from ``--seed`` (64 shards x 2^20
columns: one ranked set field whose hot rows sit in every shard at ~50k
bits each above a ~1M-row singleton tail per shard, plus one BSI int
field), starts ``python -m pilosa_tpu server`` on it as a child with
``--device-policy always``, drives it over HTTP, compares every answer
with a numpy reference computed here from the generated positions, and
reads the server's own counters to prove that the device, and not one of
the CPU fallbacks, produced them.

This process never imports JAX: a chip belongs to one process, and the
server child is that process. Device platform, kind and count come from
the server's ``build_info`` labels.

    python chip_smoke.py             # one chip, as the driver runs it
    python chip_smoke.py --chips 4   # only the 4-device mesh phase

One JSON object per phase goes to stdout; the last line is
``{"ok": true, "device": {...}}``. Any failure exits non-zero with the
reason on stderr and no such line. The numbers under "smoke" are
orientation, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import operator
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ProcessPoolExecutor
from dataclasses import asdict, dataclass
from multiprocessing import get_context
from typing import Callable

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")  # what a chip call brings back

PLATFORM = "tpu"  # what the served path must have run on
SHARD_WIDTH = 1 << 20
WORDS64 = SHARD_WIDTH // 64
INDEX, SET_FIELD, INT_FIELD = "smoke", "f", "v"
INT_VIEW = "bsig_" + INT_FIELD
INT_MIN, INT_MAX, INT_DEPTH = 0, 999, 10  # 999 < 2^10
GROUP = 16  # hot rows per correlated group
MIN_RESIDENT_BYTES = 2 << 30
REQUEST_TIMEOUT_S = 900


class SmokeFailure(Exception):
    """A phase failed; the message is the reason printed on stderr."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- data ---------------------------------------------------------------------


@dataclass(frozen=True)
class Spec:
    seed: int
    shards: int
    hot_rows: int  # multiple of GROUP
    hot_bits: int  # bits per hot row per shard
    tail_rows: int  # singleton rows per shard, numbered after the hot rows


def _shard_data(spec: Spec, shard: int):
    """One shard's generated content: (hot u64[R, WORDS64] packed rows,
    vals i32[W], exists bool[W]).

    Hot rows come in groups of GROUP: row j of a group keeps a share
    1 - j/(GROUP+4) of the group's base columns and fills up with fresh
    random ones, so a group's rows overlap its base row by clearly
    separated amounts in every shard. TopN answers then have no tie at
    the cut and each top row is a candidate in every shard, whatever a
    ranked cache chooses to show."""
    rng = np.random.default_rng([spec.seed, shard])
    density = spec.hot_bits / SHARD_WIDTH
    hot = np.empty((spec.hot_rows, WORDS64), dtype="<u8")
    for g in range(spec.hot_rows // GROUP):
        base = rng.random(SHARD_WIDTH) < density
        keep_rank = rng.random(SHARD_WIDTH)
        for j in range(GROUP):
            mask = base & (keep_rank < 1.0 - j / (GROUP + 4))
            fresh = spec.hot_bits - int(mask.sum())
            if fresh > 0:
                mask[rng.integers(0, SHARD_WIDTH, size=fresh)] = True
            hot[g * GROUP + j] = np.packbits(mask, bitorder="little").view("<u8")
    vals = rng.integers(INT_MIN, INT_MAX + 1, size=SHARD_WIDTH, dtype=np.int32)
    exists = rng.random(SHARD_WIDTH) < 0.5
    return hot, vals, exists


def _unpack(words: np.ndarray) -> np.ndarray:
    """u64[..., WORDS64] -> bool[..., SHARD_WIDTH]."""
    return np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), axis=-1, bitorder="little"
    ).astype(bool)


def _pack(mask: np.ndarray) -> np.ndarray:
    return np.packbits(mask, axis=-1, bitorder="little").view("<u8")


def _fragment_dir(data_dir: str, field: str, view: str) -> str:
    return os.path.join(data_dir, INDEX, field, "views", view, "fragments")


def build_shard(spec: Spec, shard: int, data_dir: str):
    """Write one shard's two fragment files; return what the reference
    needs. Runs in a builder process that must stay off JAX."""
    from pilosa_tpu.roaring import build_fragment_file

    hot, vals, exists = _shard_data(spec, shard)
    width = np.uint64(SHARD_WIDTH)

    def set_chunks():
        for r in range(spec.hot_rows):
            cols = np.flatnonzero(_unpack(hot[r])).astype(np.uint64)
            yield np.uint64(r) * width + cols
        rows = np.arange(spec.tail_rows, dtype=np.uint64) + np.uint64(
            spec.hot_rows + shard * spec.tail_rows
        )
        cols = (rows * np.uint64(2654435761)) % width
        yield rows * width + cols

    def int_chunks():
        base = (vals - INT_MIN).astype(np.uint32)
        for i in range(INT_DEPTH):
            cols = np.flatnonzero(exists & ((base >> i) & 1).astype(bool))
            yield np.uint64(i) * width + cols.astype(np.uint64)
        yield np.uint64(INT_DEPTH) * width + np.flatnonzero(exists).astype(np.uint64)

    stats = build_fragment_file(
        os.path.join(_fragment_dir(data_dir, SET_FIELD, "standard"), str(shard)),
        set_chunks(),
    )
    build_fragment_file(
        os.path.join(_fragment_dir(data_dir, INT_FIELD, INT_VIEW), str(shard)),
        int_chunks(),
        write_cache_file=False,
    )
    if "jax" in sys.modules:
        raise SmokeFailure("the data builder imported JAX")
    return shard, hot, vals, exists, stats["bits"], stats["rows"]


def build(spec: Spec, data_dir: str) -> "Reference":
    t0 = time.monotonic()
    workers = max(1, min(16, spec.shards, (os.cpu_count() or 2) - 1))
    if os.path.isdir(data_dir):
        shutil.rmtree(data_dir)
    for field, view in ((SET_FIELD, "standard"), (INT_FIELD, INT_VIEW)):
        os.makedirs(_fragment_dir(data_dir, field, view))
    with open(os.path.join(data_dir, INDEX, INT_FIELD, ".meta"), "w") as f:
        json.dump({"type": "int", "min": INT_MIN, "max": INT_MAX}, f)
    hot = np.empty((spec.shards, spec.hot_rows, WORDS64), dtype="<u8")
    vals = np.empty((spec.shards, SHARD_WIDTH), dtype=np.int32)
    exists = np.empty((spec.shards, SHARD_WIDTH), dtype=bool)
    bits = rows = 0
    # spawn, not fork: fresh interpreters, nothing inherited but the spec
    with ProcessPoolExecutor(
        max_workers=workers, mp_context=get_context("spawn")
    ) as pool:
        futures = [
            pool.submit(build_shard, spec, s, data_dir) for s in range(spec.shards)
        ]
        for fut in futures:
            s, h, v, e, nbits, nrows = fut.result()
            hot[s], vals[s], exists[s] = h, v, e
            bits += nbits
            rows += nrows
    disk = sum(
        os.path.getsize(os.path.join(d, name))
        for d, _, names in os.walk(data_dir)
        for name in names
    )
    emit(
        "build",
        seconds=round(time.monotonic() - t0, 1),
        workers=workers,
        spec=asdict(spec),
        set_field_bits=bits,
        set_field_rows=rows,
        hot_dense_bytes=spec.hot_rows * spec.shards * WORDS64 * 8,
        disk_bytes=disk,
        reduced=[f"rows cut from 1,000,000,000 to {rows:,} (the singleton tail)"]
        + ([] if spec.shards == 64 else [f"shards cut from 64 to {spec.shards}"]),
    )
    return Reference(spec, hot, vals, exists)


# -- the plain reference ------------------------------------------------------
#
# Expressions are tuples: ("Row", r), ("Range", op, x[, y]), and
# ("Intersect" | "Union" | "Difference" | "Xor", e1, e2, ...).


def pql(e) -> str:
    tag = e[0]
    if tag == "Row":
        return f"Row({SET_FIELD}={e[1]})"
    if tag == "Range":
        if e[1] == "><":
            return f"Range({INT_FIELD} >< [{e[2]}, {e[3]}])"
        return f"Range({INT_FIELD} {e[1]} {e[2]})"
    return f"{tag}({', '.join(pql(c) for c in e[1:])})"


_COMPARE = {
    ">": operator.gt, ">=": operator.ge, "<": operator.lt,
    "<=": operator.le, "==": operator.eq, "!=": operator.ne,
}


class Reference:
    """numpy over the generated positions: packed words, & | ^, popcount,
    argsort. Shares no code with the device path."""

    def __init__(self, spec: Spec, hot, vals, exists) -> None:
        self.spec = spec
        self.hot = hot  # u64[S, R, WORDS64]
        self.vals = vals  # i32[S, W]
        self.exists = exists  # bool[S, W]

    def words(self, e) -> np.ndarray:
        """u64[S, WORDS64] for a bitmap expression."""
        tag = e[0]
        if tag == "Row":
            return self.hot[:, e[1], :]
        if tag == "Range":
            if e[1] == "><":
                m = (self.vals >= e[2]) & (self.vals <= e[3])
            else:
                m = _COMPARE[e[1]](self.vals, e[2])
            return _pack(m & self.exists)
        acc = self.words(e[1])
        for c in e[2:]:
            w = self.words(c)
            if tag == "Intersect":
                acc = acc & w
            elif tag == "Union":
                acc = acc | w
            elif tag == "Xor":
                acc = acc ^ w
            elif tag == "Difference":
                acc = acc & ~w
            else:
                raise ValueError(tag)
        return acc

    def count(self, e) -> int:
        return int(np.bitwise_count(self.words(e)).sum())

    def columns(self, e) -> list[int]:
        mask = _unpack(self.words(e))
        shard, col = np.nonzero(mask)
        return (shard.astype(np.int64) * SHARD_WIDTH + col).tolist()

    def sum(self, e) -> dict:
        m = self.exists if e is None else _unpack(self.words(e)) & self.exists
        return {"value": int(self.vals[m].sum(dtype=np.int64)), "count": int(m.sum())}

    def topn(self, e, n: int) -> list[dict]:
        src = self.words(e)
        counts = np.array(
            [
                int(np.bitwise_count(self.hot[:, r, :] & src).sum())
                for r in range(self.spec.hot_rows)
            ]
        )
        order = np.lexsort((np.arange(counts.size), -counts))
        top, cut = order[:n], order[n]
        # a tail row holds one bit, so it can score at most 1
        if counts[top[-1]] <= max(int(counts[cut]), 1):
            raise SmokeFailure(f"reference TopN({pql(e)}, n={n}) has a tie at the cut")
        return [{"id": int(r), "count": int(counts[r])} for r in top]

    def groupby(self, ids_a, ids_b, filt, with_sum: bool) -> list[dict]:
        out = []
        fw = None if filt is None else self.words(filt)
        for a in ids_a:
            for b in ids_b:
                w = self.hot[:, a, :] & self.hot[:, b, :]
                if fw is not None:
                    w = w & fw
                cnt = int(np.bitwise_count(w).sum())
                if cnt == 0:
                    continue
                entry = {
                    "group": [
                        {"field": SET_FIELD, "rowID": a},
                        {"field": SET_FIELD, "rowID": b},
                    ],
                    "count": cnt,
                }
                if with_sum:
                    m = _unpack(w) & self.exists
                    entry["sum"] = int(self.vals[m].sum(dtype=np.int64))
                out.append(entry)
        return out

    def set_bit(self, row: int, column: int) -> None:
        shard, col = divmod(column, SHARD_WIDTH)
        self.hot[shard, row, col >> 6] |= np.uint64(1) << np.uint64(col & 63)

    def clear_column(self, row: int, shard: int) -> int:
        """A column of ``shard`` (global id) that ``row`` does not hold."""
        clear = np.flatnonzero(~_unpack(self.hot[shard, row]))
        return shard * SHARD_WIDTH + int(clear[len(clear) // 2])


# -- queries ------------------------------------------------------------------


def _row(r):
    return ("Row", r)


@dataclass(frozen=True)
class Q:
    """One request: its PQL body and a thunk computing the expected
    ``results`` list from the reference."""

    body: str
    want: Callable[["Reference"], list]


def _topn(e, n=10) -> Q:
    return Q(f"TopN({SET_FIELD}, {pql(e)}, n={n})", lambda ref: [ref.topn(e, n)])


def _count(e) -> Q:
    return Q(f"Count({pql(e)})", lambda ref: [ref.count(e)])


def _sum(e) -> Q:
    body = (
        f"Sum(field={INT_FIELD})"
        if e is None
        else f"Sum({pql(e)}, field={INT_FIELD})"
    )
    return Q(body, lambda ref: [ref.sum(e)])


def _groupby(ids_a, ids_b, filt=None, with_sum=True) -> Q:
    parts = [
        f"Rows({SET_FIELD}, ids={list(ids_a)})",
        f"Rows({SET_FIELD}, ids={list(ids_b)})",
    ]
    if filt is not None:
        parts.append(pql(filt))
    if with_sum:
        parts.append(f"Sum(field={INT_FIELD})")
    return Q(
        f"GroupBy({', '.join(parts)})",
        lambda ref: [ref.groupby(ids_a, ids_b, filt, with_sum)],
    )


def _multi(*qs: Q) -> Q:
    return Q(
        "".join(q.body for q in qs),
        lambda ref: [r for q in qs for r in q.want(ref)],
    )


def families(mesh: bool) -> dict[str, list[Q]]:
    """The request set, by family. Row ids are spread over groups so no
    two calls in the set are the same call (a repeat would be a plan-cache
    hit, which proves nothing about the device)."""

    def g(k, j=0):  # row j of group k
        return k * GROUP + j

    fam: dict[str, list[Q]] = {
        "topn": [_topn(_row(g(k))) for k in (0, 5, 11)],
        "chain": [
            _count(
                ("Intersect",
                 ("Union", _row(g(1)), _row(g(1, 3))),
                 ("Union", _row(g(1, 1)), _row(g(2))))
            ),
            _count(
                ("Union",
                 ("Intersect", _row(g(3)), _row(g(3, 2))),
                 ("Intersect", _row(g(3, 1)), _row(g(3, 4))),
                 _row(g(4, 7)))
            ),
            _count(
                ("Intersect",
                 ("Union",
                  ("Intersect", _row(g(6)), _row(g(6, 1))),
                  ("Difference", _row(g(6, 2)), _row(g(6, 3)))),
                 ("Union", _row(g(6, 4)), ("Xor", _row(g(6, 5)), _row(g(7)))))
            ),
        ],
        "bsi_sum": [_sum(None), _sum(_row(g(8))), _sum(("Union", _row(g(8, 1)), _row(g(9))))],
    }
    if mesh:
        return fam
    fam["fused"] = [
        _multi(
            _count(_row(g(10))),
            _count(("Intersect", _row(g(10)), _row(g(10, 1)))),
            _topn(_row(g(12))),
            _sum(_row(g(10, 2))),
        ),
        _multi(
            _count(("Union", _row(g(13)), _row(g(13, 1)))),
            _count(("Difference", _row(g(13, 2)), _row(g(13, 3)))),
        ),
    ]
    fam["bsi_range"] = [
        _count(("Range", ">", 500)),
        _count(("Intersect", _row(g(14)), ("Range", "<", 100))),
        _count(("Range", "><", 200, 300)),
    ]
    fam["groupby"] = [
        _groupby([g(15), g(15, 1), g(15, 2), g(15, 3)], [g(15, 4), g(15, 5), g(2, 1)]),
        _groupby([g(4), g(4, 1)], [g(4, 2), g(4, 3)], filt=_row(g(4, 4)), with_sum=False),
    ]
    e = ("Intersect", _row(g(14, 1)), ("Range", "==", 17))
    fam["bitmap"] = [
        Q(pql(e), lambda ref: [{"attrs": {}, "columns": ref.columns(e)}])
    ]
    return fam


# -- the server child ---------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerChild:
    """``python -m pilosa_tpu server`` as a child in its own session."""

    def __init__(self, data_dir: str, extra: list[str]) -> None:
        if "jax" in sys.modules:
            raise SmokeFailure("this process imported JAX: it would hold the chip")
        self.port = _free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        os.makedirs(OUT_DIR, exist_ok=True)
        # a file, not a pipe: an undrained pipe blocks the child's logger
        self.log_path = os.path.join(OUT_DIR, "chip_smoke_server.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "pilosa_tpu", "server",
                "--data-dir", data_dir,
                "--bind", f"127.0.0.1:{self.port}",
                *extra,
            ],
            cwd=ROOT,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"server child exited with code {rc}:\n{self.log_tail()}"
            )

    def get(self, path: str, timeout: float = 60) -> bytes:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as resp:
            return resp.read()

    def query(self, body: str, cache: bool) -> list:
        params = f"timeout={REQUEST_TIMEOUT_S}" + ("" if cache else "&cache=false")
        req = urllib.request.Request(
            f"{self.base}/index/{INDEX}/query?{params}",
            data=body.encode(),
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S + 30) as resp:
                return json.loads(resp.read())["results"]
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"HTTP {e.code} for {body[:200]}: {e.read()[:500]!r}"
            ) from e
        except OSError as e:
            self.check_alive()
            raise SmokeFailure(f"request failed for {body[:200]}: {e}") from e

    def wait_ready(self, timeout: float = 300) -> float:
        """Seconds until /status answers and build_info is published."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            self.check_alive()
            try:
                self.get("/status", timeout=5)
                if _samples(scrape(self), "build_info"):
                    return time.monotonic() - t0
            except OSError:
                pass
            time.sleep(0.5)
        raise SmokeFailure(
            f"server not ready after {timeout:.0f} s:\n{self.log_tail()}"
        )

    def stop(self) -> int:
        """SIGINT and wait; the clean-exit code, or a failure."""
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server child ignored SIGINT for 120 s")
        finally:
            self._log.close()
        return rc

    def kill(self) -> None:
        """Leave no process behind, whatever happened."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        if not self._log.closed:
            self._log.close()


# -- /metrics -----------------------------------------------------------------

_SAMPLE = re.compile(r"^pilosa_([a-zA-Z0-9_]+?)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'([a-zA-Z0-9_]+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> list[tuple[str, dict, float]]:
    out = []
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")), float(m.group(3))))
    return out


def scrape(server: ServerChild) -> list[tuple[str, dict, float]]:
    return parse_metrics(server.get("/metrics").decode())


def _samples(metrics, name: str, **match) -> list[tuple[dict, float]]:
    name = name.replace(".", "_")
    return [
        (labels, v)
        for n, labels, v in metrics
        if n == name and all(labels.get(k) == want for k, want in match.items())
    ]


def total(metrics, name: str, **match) -> float:
    return sum(v for _, v in _samples(metrics, name, **match))


def by_label(metrics, name: str, label: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for labels, v in _samples(metrics, name):
        key = labels.get(label, "")
        out[key] = out.get(key, 0) + v
    return out


# counters that mean an answer came from somewhere other than the device
FALLBACK_COUNTERS = (
    "executor.route.cpu",
    "executor.device_down_fallback",
    "executor.not_deviceable",
    "device.oom",
    "device.oom_cpu_degrades",
    "devicehealth.trips",
    "devicehealth.saturations",
    "stager.ahead_errors",
    "plancache.device_upload_errors",
)
# fusion bypass reasons that mean the same; the others route a query to
# the per-call device path and are only reported
FATAL_BYPASSES = ("error", "cpu")


def device_work(metrics) -> float:
    """Monotone count of device launches the executor decided on."""
    return (
        total(metrics, "executor.route.device")
        + total(metrics, "fusion.fused_launches")
        + total(metrics, "fusion.groupby_launches")
    )


def compiles(metrics) -> float:
    return total(metrics, "profiler.compiles")


def compile_seconds(metrics) -> float:
    return total(metrics, "spmd.compile_seconds_sum")


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


# -- phases -------------------------------------------------------------------


def ask(server, q_body: str, want, where: str, cache: bool) -> float:
    """One request; its wall seconds. Any difference from the reference
    fails the run."""
    t0 = time.monotonic()
    got = server.query(q_body, cache=cache)
    seconds = time.monotonic() - t0
    if got != want:
        raise SmokeFailure(
            f"{where}: answer differs from the numpy reference for "
            f"{q_body[:300]}\n got  {json.dumps(got)[:600]}\n"
            f" want {json.dumps(want)[:600]}"
        )
    return round(seconds, 4)


def run_pass(server, fam, wants, name: str, cache: bool) -> dict:
    """Send every family's requests once; compare; return per-family
    wall seconds and device-work growth."""
    out = {}
    for family, qs in fam.items():
        before = device_work(scrape(server))
        secs = [
            ask(server, q.body, want, f"{name} pass, {family}", cache)
            for q, want in zip(qs, wants[family])
        ]
        grew = device_work(scrape(server)) - before
        if grew <= 0:
            raise SmokeFailure(
                f"{name} pass, {family}: correct answers but no device launch "
                "was counted (executor.route.device / fusion.*_launches)"
            )
        out[family] = {"seconds": secs, "device_launch_decisions": grew}
    emit(name, cache=cache, families=out)
    return out


def collect(args) -> dict:
    """Build, serve, drive and compare. Returns the observations that
    ``problems`` decides on; raises SmokeFailure when a phase failed."""
    from pilosa_tpu import native_bridge
    from pilosa_tpu.utils.jaxplatform import bootstrap

    mesh = args.chips > 1
    cache_dir = bootstrap()  # children inherit the same directory
    native_bridge.require()
    entries_start = cache_entries(cache_dir)
    emit("native", loaded=True, cache_dir=cache_dir, cache_entries=entries_start)

    spec = Spec(args.seed, args.shards, args.hot_rows, args.hot_bits, args.tail_rows)
    ref = build(spec, args.data_dir)
    fam = families(mesh)
    t0 = time.monotonic()
    wants = {f: [q.want(ref) for q in qs] for f, qs in fam.items()}
    emit("reference", seconds=round(time.monotonic() - t0, 1),
         requests=sum(len(qs) for qs in fam.values()))

    extra = ["--device-policy", "always"]
    if mesh:
        extra += ["--mesh-devices", str(args.chips)]
    server = ServerChild(args.data_dir, extra)
    obs: dict = {
        "chips": args.chips,
        "cache_dir": cache_dir,
        "cache_entries_start": entries_start,
    }
    try:
        ready_s = server.wait_ready()
        m0 = scrape(server)
        info = _samples(m0, "build_info")[0][0]
        obs["build_info"] = info
        emit("serve", ready_seconds=round(ready_s, 1), build_info=info)
        if info.get("backend") != PLATFORM:
            # nothing below means anything on another back end
            raise SmokeFailure(
                f"server runs on backend {info.get('backend')!r}, not {PLATFORM!r}"
            )

        obs["cold"] = run_pass(server, fam, wants, "cold", cache=True)
        m_cold = scrape(server)
        entries_cold = cache_entries(cache_dir)
        obs["warm"] = run_pass(server, fam, wants, "warm", cache=False)
        m_warm = scrape(server)
        obs["warm_compiles"] = compiles(m_warm) - compiles(m_cold)
        obs["warm_storms"] = total(m_warm, "profiler.recompile_storms") - total(
            m_cold, "profiler.recompile_storms"
        )
        obs["warm_cache_entries_added"] = cache_entries(cache_dir) - entries_cold

        obs["hbm_after_warm"] = by_label(m_warm, "hbm.bytes_in_use", "device")

        # an acknowledged write is read back: one bit that moves a Count
        # and the counts of a TopN, through the default (cached) path
        row = GROUP  # base row of group 1
        column = ref.clear_column(row, shard=min(1, spec.shards - 1))
        count_row = _count(_row(row))
        before = count_row.want(ref)
        ask(server, count_row.body, before, "before the Set", cache=True)
        acked = server.query(f"Set({column}, {SET_FIELD}={row})", cache=True)
        if acked != [True]:
            raise SmokeFailure(f"Set({column}, {SET_FIELD}={row}) answered {acked}")
        ref.set_bit(row, column)
        if count_row.want(ref) != [before[0] + 1]:
            raise SmokeFailure("the reference's Set did not move Count by one")
        after_q = [count_row, _topn(_row(row))]
        if not mesh:
            after_q.append(_multi(_count(("Union", _row(row), _row(row + 1))),
                                  _sum(_row(row))))
        secs = [
            ask(server, q.body, q.want(ref), "read after the acknowledged Set", cache=True)
            for q in after_q
        ]
        time.sleep(0.5)  # stage-ahead errors are counted on a side thread
        m_end = scrape(server)
        emit("write", column=column, row=row, count_before=before[0],
             count_after=before[0] + 1, requery_seconds=secs)

        obs["metrics"] = m_end
        obs["compiles_cold"] = compiles(m_cold) - compiles(m0)
        obs["compile_seconds_cold"] = round(
            compile_seconds(m_cold) - compile_seconds(m0), 3
        )
        obs["cache_entries_end"] = cache_entries(cache_dir)
        rc = server.stop()
        obs["server_exit_code"] = rc
        emit("shutdown", exit_code=rc)
    finally:
        server.kill()
        shutil.rmtree(args.data_dir, ignore_errors=True)
    return obs


def problems(obs: dict) -> list[str]:
    """Every reason this run does not prove the chip; empty when it does."""
    m = obs["metrics"]
    info = obs["build_info"]
    chips = obs["chips"]
    why = []
    if info.get("backend") != PLATFORM:
        why.append(f"backend is {info.get('backend')!r}, not {PLATFORM!r}")
    if info.get("device_count") != str(chips):
        why.append(f"{info.get('device_count')} devices visible, {chips} asked for")
    if info.get("native") != "true":
        why.append("the server did not load the native host kernels")
    if obs["server_exit_code"] != 0:
        why.append(f"server child exited with code {obs['server_exit_code']}")
    for name in FALLBACK_COUNTERS:
        n = total(m, name)
        if n:
            why.append(f"{name} = {n:g} {by_label(m, name, 'call')}")
    bypasses = by_label(m, "fusion.bypasses", "reason")
    for reason in FATAL_BYPASSES:
        if bypasses.get(reason):
            why.append(f"fusion.bypasses{{reason={reason}}} = {bypasses[reason]:g}")
    if obs["warm_compiles"] or obs["warm_storms"] or obs["warm_cache_entries_added"]:
        why.append(
            f"the warm pass compiled: profiler.compiles +{obs['warm_compiles']:g}, "
            f"recompile_storms +{obs['warm_storms']:g}, cache entries "
            f"+{obs['warm_cache_entries_added']}"
        )
    in_use = obs["hbm_after_warm"]
    if chips == 1:
        if not total(m, "fusion.fused_launches"):
            why.append("fusion.fused_launches = 0")
        if not total(m, "profiler.compiles", kind="fused_query"):
            why.append('profiler.compiles{kind="fused_query"} = 0')
        if sum(in_use.values()) < MIN_RESIDENT_BYTES:
            why.append(
                f"device memory in use after warm-up is {sum(in_use.values()):g} "
                f"bytes, under {MIN_RESIDENT_BYTES}"
            )
    else:
        for kind in ("count", "topn_scores_sparse", "plane_counts"):
            if not total(m, "spmd.compile_seconds_count", kind=kind):
                why.append(f'spmd.compile_seconds{{kind="{kind}"}} never observed')
        if len(in_use) != chips:
            why.append(f"memory_stats() reported for {len(in_use)} devices, not {chips}")
        elif min(in_use.values()) * 2 < max(in_use.values()):
            why.append(f"staged bytes are not spread over the devices: {in_use}")
    return why


def report(obs: dict) -> None:
    """Orientation numbers. Not a benchmark: claim nothing from them."""
    m = obs["metrics"]
    emit(
        "smoke",
        chips=obs["chips"],
        hbm_bytes_in_use_after_warm=obs["hbm_after_warm"],
        hbm_bytes_in_use_at_end=by_label(m, "hbm.bytes_in_use", "device"),
        hbm_peak_bytes=by_label(m, "hbm.peak_bytes", "device"),
        hbm_bytes_limit=by_label(m, "hbm.bytes_limit", "device"),
        stager_bytes=total(m, "stager.bytes"),
        route_device=by_label(m, "executor.route.device", "call"),
        route_cpu=total(m, "executor.route.cpu"),
        fused_launches=total(m, "fusion.fused_launches"),
        groupby_launches=total(m, "fusion.groupby_launches"),
        fusion_bypasses=by_label(m, "fusion.bypasses", "reason"),
        devicehealth_slow_calls=total(m, "devicehealth.slow_calls"),
        stager_delta_applied=total(m, "stager.delta_applied"),
        stager_delta_fallback=by_label(m, "stager.delta_fallback", "reason"),
        stager_restaged_bytes=total(m, "stager.restaged_bytes"),
        compiles_cold=obs["compiles_cold"],
        compile_seconds_cold=obs["compile_seconds_cold"],
        compiles_by_kind=by_label(m, "profiler.compiles", "kind"),
        spmd_compile_seconds=by_label(m, "spmd.compile_seconds_sum", "kind"),
        compiles_warm=obs["warm_compiles"],
        cache_dir=obs["cache_dir"],
        cache_entries_start=obs["cache_entries_start"],
        cache_entries_end=obs["cache_entries_end"],
        cold_seconds={f: sum(v["seconds"]) for f, v in obs["cold"].items()},
        warm_seconds={f: sum(v["seconds"]) for f, v in obs["warm"].items()},
        previous_run=_previous_run(obs["cache_dir"], obs["chips"]),
    )


def _runs_log() -> str:
    return os.path.join(OUT_DIR, "chip_smoke.jsonl")


def _previous_run(cache_dir: str, chips: int):
    """The last record of an earlier run that shared this cache
    directory, to read its compile seconds beside this run's."""
    try:
        with open(_runs_log()) as f:
            runs = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return None
    same = [r for r in runs if r.get("cache_dir") == cache_dir and r.get("chips") == chips]
    return same[-1] if same else None


def finish(obs: dict) -> int:
    """Report, decide, record, and print the result line. The exit code."""
    report(obs)
    why = problems(obs)
    if why:
        print(f"chip_smoke: FAILED: {'; '.join(why)}", file=sys.stderr)
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(_runs_log(), "a") as f:
        record = {
            k: obs[k]
            for k in (
                "chips", "cache_dir", "compiles_cold", "compile_seconds_cold",
                "cache_entries_start", "cache_entries_end",
            )
        }
        f.write(json.dumps({"at": time.strftime("%Y-%m-%dT%H:%M:%S"), **record}) + "\n")
    info = obs["build_info"]
    device = {
        "platform": info["backend"],
        "kind": info["device_kind"],
        "count": int(info["device_count"]),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs only the mesh phase (--mesh-devices 4)")
    p.add_argument("--shards", type=int, default=64)
    p.add_argument("--hot-rows", type=int, default=256)
    p.add_argument("--hot-bits", type=int, default=50_000)
    p.add_argument("--tail-rows", type=int, default=1_000_000)
    p.add_argument("--data-dir", default=os.path.join(ROOT, ".smoke_data"))
    args = p.parse_args(argv)
    if args.hot_rows % GROUP or args.hot_rows < 16 * GROUP:
        # the request set names rows of 16 groups
        p.error(f"--hot-rows must be a multiple of {GROUP}, at least {16 * GROUP}")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLATFORM not in platforms.split(","):
        # JAX would honour it and never look for the chip
        print(
            f"chip_smoke: FAILED: JAX_PLATFORMS={platforms} keeps JAX off the "
            f"{PLATFORM}",
            file=sys.stderr,
        )
        return 1
    try:
        obs = collect(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    return finish(obs)


if __name__ == "__main__":
    sys.exit(main())
