"""Offers a `traffic.Stream` to a server over HTTP from threads of this
process, and keeps one exact record per request sent.

closed   `clients` threads, each sending its next request when the last
         returned; the threads take requests from ONE shared stream, so the
         i-th request sent is the stream's i-th whichever client sends it.
poisson  requests sent at the stream's due times, however the server is
         doing (an open loop); how late each left is recorded.

Each request carries an `X-DL4J-Trace` header whose trace id is its index,
so that the program's request ledger record can be joined to it.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

TRACE_HEADER = "X-DL4J-Trace"


def trace_id(i: int) -> str:
    return f"{i + 1:032x}"


class Load:
    def __init__(self, stream, host: str, port: int, timeout_s: float = 300.0):
        self.stream = stream
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.records = []          # one dict per request sent
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()
        self._threads = []
        self._pool = None
        self.t_start = None

    # ------------------------------------------------------------ sending

    def _take(self):
        with self._lock:
            i = self._next
            self._next += 1
        return i

    def _send(self, conn, i: int, due=None):
        body = json.dumps(self.stream.request(i)).encode()
        headers = {"Content-Type": "application/json",
                   TRACE_HEADER: f"00-{trace_id(i)}-0000000000000001-01"}
        rec = {"i": i, "due": due, "t_send": time.perf_counter(),
               "t_done": None, "ok": False, "error": None, "n_ids": 0}
        with self._lock:
            self.records.append(rec)
        try:
            conn.request("POST", "/generate", body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            if resp.status == 200:
                rec["n_ids"] = len(json.loads(data)["ids"])
                rec["ok"] = True
            else:
                rec["error"] = f"{resp.status}: {data[:200]!r}"
        except Exception as e:  # a refused or broken connection is a failed request
            rec["error"] = f"{type(e).__name__}: {e}"
            conn.close()
        rec["t_done"] = time.perf_counter()
        return rec

    def _client(self, delay_s: float):
        if self._stop.wait(delay_s):
            return
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            while not self._stop.is_set():
                self._send(conn, self._take())
        finally:
            conn.close()

    def _one(self, i: int, due: float):
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            self._send(conn, i, due=due)
        finally:
            conn.close()

    def _dispatcher(self):
        while not self._stop.is_set():
            i = self._take()
            due = self.t_start + self.stream.due(i)
            if self._stop.wait(max(0.0, due - time.perf_counter())):
                return
            self._pool.submit(self._one, i, due)

    # ------------------------------------------------------------ control

    def start(self):
        arrival = self.stream.traffic["arrival"]
        self.t_start = time.perf_counter()
        if arrival["kind"] == "closed":
            n = int(arrival["clients"])
            stagger = float(arrival.get("stagger_s", 0.0))
            targets = [(self._client, (k * stagger / n,)) for k in range(n)]
        elif arrival["kind"] == "poisson":
            self._pool = ThreadPoolExecutor(
                max_workers=int(arrival.get("max_in_flight", 64)),
                thread_name_prefix="bench-open")
            targets = [(self._dispatcher, ())]
        else:
            raise ValueError(f"unknown arrival {arrival['kind']!r}")
        for k, (fn, a) in enumerate(targets):
            t = threading.Thread(target=fn, args=a, name=f"bench-load-{k}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop_sending(self):
        self._stop.set()

    def join(self, timeout_s: float = 30.0) -> bool:
        """Wait for every sender to end (the caller has stopped the server,
        which answers what is still open). True when all have ended."""
        deadline = time.perf_counter() + timeout_s
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
        for t in self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)

    def snapshot(self) -> list:
        with self._lock:
            return [dict(r) for r in self.records]


def window_records(sent: list, ledger: list, t0: float, t1: float):
    """Split what was sent by the window `[t0, t1]` (this process's clock).

    Returns `(completed, failed, attempted)`: `completed` are the ledger
    records (joined by trace id) of the requests that returned inside the
    window; `failed` counts requests that returned an error inside the
    window, or were sent in its first half and were still open at its end;
    `attempted` is completed + failed."""
    by_trace = {r.get("trace_id"): r for r in ledger
                if r.get("route") == "generate"}
    completed, failed = [], 0
    half = t0 + (t1 - t0) / 2
    for rec in sent:
        done = rec["t_done"]
        if done is not None and t0 <= done <= t1:
            row = by_trace.get(trace_id(rec["i"]))
            if (rec["ok"] and row is not None and row.get("outcome") == "ok"
                    and "first_token" in row.get("marks", {})):
                completed.append(dict(row, i=rec["i"]))
            else:
                failed += 1
        elif (done is None or done > t1) and t0 <= rec["t_send"] <= half:
            failed += 1
    return completed, failed, len(completed) + failed
