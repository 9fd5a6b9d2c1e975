"""The server child and its ``/metrics``, copied from ``chip_smoke.py``
(PR 21) so that later PRs may change the smoke and not the yardstick.

The benchmark's parent never imports JAX: a chip belongs to one process,
and ``python -m pilosa_tpu server`` as a child is that process.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVER_MODULE = "pilosa_tpu"  # ``python -m <module> server ...``


class BenchFailure(Exception):
    """The run cannot give a result; the message goes to stderr."""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ServerChild:
    """``python -m pilosa_tpu server`` as a child in its own session."""

    def __init__(self, data_dir: str, extra: list[str], log_path: str,
                 module: str = SERVER_MODULE) -> None:
        if "jax" in sys.modules:
            raise BenchFailure("this process imported JAX: it would hold the chip")
        self.port = _free_port()
        self.host = "127.0.0.1"
        self.base = f"http://{self.host}:{self.port}"
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        # a file, not a pipe: an undrained pipe blocks the child's logger
        self.log_path = log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", module, "server",
                "--data-dir", data_dir,
                "--bind", f"{self.host}:{self.port}",
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
            raise BenchFailure(
                f"server child exited with code {rc}:\n{self.log_tail()}"
            )

    def get(self, path: str, timeout: float = 60) -> bytes:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as resp:
            return resp.read()

    def get_json(self, path: str, timeout: float = 60) -> dict:
        return json.loads(self.get(path, timeout))

    def wait_ready(self, timeout: float = 300) -> float:
        """Seconds until /status answers and build_info is published."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            self.check_alive()
            try:
                self.get("/status", timeout=5)
                if samples(self.scrape(), "build_info"):
                    return time.monotonic() - t0
            except OSError:
                pass
            time.sleep(0.25)
        raise BenchFailure(
            f"server not ready after {timeout:.0f} s:\n{self.log_tail()}"
        )

    def scrape(self) -> list[tuple[str, dict, float]]:
        return parse_metrics(self.get("/metrics").decode())

    def stop(self) -> int:
        """SIGINT and wait; the clean-exit code, or a failure."""
        self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise BenchFailure("server child ignored SIGINT for 120 s")
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


def samples(metrics, name: str, **match) -> list[tuple[dict, float]]:
    name = name.replace(".", "_")
    return [
        (labels, v)
        for n, labels, v in metrics
        if n == name and all(labels.get(k) == want for k, want in match.items())
    ]


def total(metrics, name: str, **match) -> float:
    return sum(v for _, v in samples(metrics, name, **match))


def by_label(metrics, name: str, label: str) -> dict[str, float]:
    out: dict[str, float] = {}
    for labels, v in samples(metrics, name):
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
# the per-call device path
FATAL_BYPASSES = ("error", "cpu")


def fallbacks(metrics) -> dict[str, float]:
    """Every non-zero fallback counter, by name."""
    out = {n: total(metrics, n) for n in FALLBACK_COUNTERS}
    bypasses = by_label(metrics, "fusion.bypasses", "reason")
    for reason in FATAL_BYPASSES:
        out[f"fusion.bypasses.{reason}"] = bypasses.get(reason, 0.0)
    return out


def device_work(metrics) -> float:
    """Monotone count of device launches the executor decided on."""
    return (
        total(metrics, "executor.route.device")
        + total(metrics, "fusion.fused_launches")
        + total(metrics, "fusion.groupby_launches")
    )


def compiles(metrics) -> float:
    return total(metrics, "profiler.compiles")


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
