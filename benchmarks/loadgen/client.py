"""The load generator: a process of its own, standard library only.

Started by ``benchmarks/run.py`` as a script (never forked from the process
that holds the chip); it imports nothing of JAX or of the program, so the
server's Python does not share an interpreter lock with it. It reads one
command a line on standard input — ``{"schedule": <file>, "out": <file>}``
— runs the schedule against ``127.0.0.1:<port>``, writes every request's
due, send and reply time (``time.monotonic()``: one clock for every process
of the machine) with the reply's status and body to ``out``, and answers
``done`` on standard output. ``{"quit": true}`` ends it.

Open loop (``"mode": "open"``): a request is handed to the first free
keep-alive connection when it is due, whatever the server is doing; one
due while all connections are busy waits in the queue and is still timed
from when it was due. Closed loop (``"mode": "closed"``): each client sends
its own list back to back, wrapping round, and starts no new request once
``seconds`` have passed; the requests in flight then are finished
(``"once": true``: each client sends its list once and stops).
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time

HEADERS = {"Content-Type": "application/json"}


class Conn:
    """One keep-alive connection; reopened after a failure."""

    def __init__(self, port: int, timeout: float):
        self.port, self.timeout = port, timeout
        self.http = None

    def open(self):
        if self.http is None:
            self.http = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.timeout)
            self.http.connect()

    def call(self, method: str, path: str, body: bytes):
        """(status, text); status 0 where no reply came."""
        try:
            self.open()
            self.http.request(method, path, body=body, headers=HEADERS)
            resp = self.http.getresponse()
            return resp.status, resp.read().decode("utf-8", "replace")
        except (OSError, http.client.HTTPException) as e:
            self.close()
            return 0, f"{type(e).__name__}: {e}"

    def close(self):
        if self.http is not None:
            try:
                self.http.close()
            except OSError:
                pass
            self.http = None


def run_open(port: int, sched: dict) -> dict:
    reqs = sched["requests"]
    bodies = [r["body"].encode() for r in reqs]
    n_conn = int(sched["connections"])
    timeout = float(sched.get("reply_timeout_s", 60.0))
    conns = [Conn(port, timeout) for _ in range(n_conn)]
    for c in conns:
        c.open()
    todo: queue.Queue = queue.Queue()
    records = [None] * len(reqs)
    t0 = time.monotonic() + 0.05

    def worker(conn: Conn):
        while True:
            i = todo.get()
            if i is None:
                return
            r = reqs[i]
            sent = time.monotonic()
            status, text = conn.call(r["method"], r["path"], bodies[i])
            records[i] = [i, t0 + r["due"], sent, time.monotonic(), status,
                          text]

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for i, r in enumerate(reqs):
        wait = t0 + r["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put(i)
    for _ in threads:
        todo.put(None)
    deadline = time.monotonic() + timeout + 5.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    t_end = time.monotonic()
    for c in conns:
        c.close()
    for i, r in enumerate(reqs):
        if records[i] is None:  # never sent or never answered
            records[i] = [i, t0 + r["due"], None, None, 0, "no reply"]
    return {"t0": t0, "t_end": t_end, "records": records}


def run_closed(port: int, sched: dict) -> dict:
    lists = sched["requests"]  # one list of requests per client
    seconds = float(sched["seconds"])
    once = bool(sched.get("once"))  # each list once, however long it takes
    timeout = float(sched.get("reply_timeout_s", 120.0))
    conns = [Conn(port, timeout) for _ in lists]
    for c in conns:
        c.open()
    out = [[] for _ in lists]
    t0 = time.monotonic() + 0.05

    def client(ci: int):
        mine = lists[ci]
        bodies = [r["body"].encode() for r in mine]
        time.sleep(max(0.0, t0 - time.monotonic()))
        n = 0
        while (n < len(mine) if once else time.monotonic() < t0 + seconds):
            j = n % len(mine)
            r = mine[j]
            sent = time.monotonic()
            status, text = conns[ci].call(r["method"], r["path"], bodies[j])
            out[ci].append([[ci, j], sent, sent, time.monotonic(), status,
                            text])
            n += 1

    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(len(lists))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(None if once else seconds + timeout + 5.0)
    t_end = time.monotonic()
    for c in conns:
        c.close()
    return {"t0": t0, "t_end": t_end,
            "records": [rec for recs in out for rec in recs]}


def main(argv) -> int:
    port = int(argv[1])
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd.get("quit"):
            break
        with open(cmd["schedule"]) as fh:
            sched = json.load(fh)
        run = run_open if sched["mode"] == "open" else run_closed
        result = run(port, sched)
        with open(cmd["out"], "w") as fh:
            json.dump(result, fh)
        sys.stdout.write("done\n")
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
