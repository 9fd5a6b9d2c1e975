"""The closed loop: ``clients`` threads, each with one keep-alive
connection, each sending its next request when the last is answered.
Timed on the host's clock from just before a request's bytes are sent
to the last byte of its answer."""

from __future__ import annotations

import http.client
import threading
import time
from dataclasses import dataclass

REQUEST_TIMEOUT_S = 120


@dataclass
class Sample:
    request: int  # index into the pool
    sent: float  # time.monotonic()
    done: float
    status: int  # 0: no answer came
    body: bytes


def run_closed(host: str, port: int, path: str, bodies: list[bytes], schedule,
               clients: int, seconds: float) -> tuple[list[Sample], float, float]:
    """Drive the loop for ``seconds`` (or until ``schedule`` runs out);
    requests in flight at the close are waited for. Returns (samples,
    window start, window end)."""
    lock = threading.Lock()
    samples: list[Sample] = []
    start = threading.Barrier(clients + 1)
    t_end = [0.0]

    def client() -> None:
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        mine: list[Sample] = []
        start.wait()
        try:
            while True:
                with lock:
                    i = next(schedule, None)
                t0 = time.monotonic()
                if i is None or t0 >= t_end[0]:
                    break
                try:
                    conn.request("POST", path, body=bodies[i])
                    resp = conn.getresponse()
                    body = resp.read()
                    status = resp.status
                except (OSError, http.client.HTTPException) as e:
                    status, body = 0, repr(e).encode()
                    conn.close()
                    conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
                mine.append(Sample(i, t0, time.monotonic(), status, body))
        finally:
            conn.close()
            with lock:
                samples.extend(mine)

    threads = [threading.Thread(target=client, daemon=True) for _ in range(clients)]
    for t in threads:
        t.start()
    t_start = time.monotonic()
    t_end[0] = t_start + seconds
    start.wait()
    for t in threads:
        t.join(timeout=seconds + REQUEST_TIMEOUT_S + 60)
        if t.is_alive():
            raise RuntimeError("a client thread did not end")
    return samples, t_start, t_end[0]
