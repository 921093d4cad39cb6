"""Closed-loop load from this process: one thread and one keep-alive
connection per client, the client's clock around the whole request."""

from __future__ import annotations

import http.client
import threading
import time
from dataclasses import dataclass, field

from harness import traffic

WINDOW_HTTP_TIMEOUT_S = 120.0


@dataclass
class Record:
    template: str
    sem: dict
    t_sent: float      # perf_counter, seconds
    t_done: float
    ok: bool
    body: bytes | None  # kept for reads, to be compared after the window


@dataclass
class Window:
    records: list = field(default_factory=list)
    t_end: float = 0.0        # the deadline (perf_counter)
    cpu_seconds: float = 0.0  # CPU time of the generator's threads


def run(port: int, index: str, clients: list, seconds: float | None = None,
        requests_each: int | None = None, keep_bodies: bool = True) -> Window:
    """Drive every client until the deadline (or for a fixed number of
    requests each). A client sends no new request after the deadline; one
    in flight is awaited and recorded with its true completion time."""
    out = Window()
    per_client: list[list] = [[] for _ in clients]
    cpu = [0.0] * len(clients)
    gate = threading.Barrier(len(clients) + 1)
    path = f"/index/{index}/query"

    def loop(ci: int, client: traffic.Client) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=WINDOW_HTTP_TIMEOUT_S)
        records = per_client[ci]
        conn.connect()
        gate.wait()
        c0 = time.thread_time()
        n = 0
        while True:
            if requests_each is not None:
                if n >= requests_each:
                    break
            elif time.perf_counter() >= out.t_end:
                break
            name, pql, sem = client.next()
            n += 1
            t0 = time.perf_counter()
            try:
                conn.request("POST", path, body=pql.encode())
                resp = conn.getresponse()
                body = resp.read()
                ok = 200 <= resp.status < 300
            except (OSError, http.client.HTTPException):
                ok, body = False, None
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=WINDOW_HTTP_TIMEOUT_S)
            t1 = time.perf_counter()
            keep = body if (keep_bodies and ok) else None
            records.append(Record(name, sem, t0, t1, ok, keep))
        cpu[ci] = time.thread_time() - c0
        conn.close()

    threads = [threading.Thread(target=loop, args=(i, c), daemon=True)
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    out.t_end = time.perf_counter() + (seconds if seconds is not None
                                       else float("inf"))
    gate.wait()
    for t in threads:
        t.join()
    out.records = [r for recs in per_client for r in recs]
    out.cpu_seconds = sum(cpu)
    return out
