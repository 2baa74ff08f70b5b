"""The load generator: it sends a schedule to the server over the loopback
and keeps, for every request, when it was due, when it was sent, when each
streamed delta arrived and what it held. One process, a thread a request in
flight, nothing else. Every time here is the harness's own clock.

The request and response shapes are those of ``chip_smoke.py``
(``event_stream_b64`` / ``post_generate``), copied: ``POST /v1/generate``
with ``{"query", "event_b64", "max_new_tokens", "stream": true}``; the
answer is newline-framed JSON events (``delta``: the text of the tokens a
segment committed, ``restart``: the whole text anew), ending in ``{"done":
true, "rid", "status"}``. The benchmark's tokenizer renders every token id
as one character (``benchmark/loader.py``), so a delta's length is its
count of tokens and its text is their ids.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import threading
import time
from typing import List, Optional

from benchmark.loader import ids_of
from benchmark.traffic import Request, Schedule


@dataclasses.dataclass
class Sent:
    req: Request
    t_due: float                     # perf_counter, absolute
    t_sent: float = 0.0
    t_done: Optional[float] = None
    code: int = 0
    rid: Optional[int] = None
    status: str = "unsent"
    error: str = ""
    deltas: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    text: str = ""                   # the answer so far, a character a token

    @property
    def token_ids(self) -> list:
        return ids_of(self.text)


def body_bytes(req: Request, stream_b64: bytes) -> bytes:
    head = json.dumps({"query": req.question, "max_new_tokens": req.budget,
                       "stream": True}).encode()
    return head[:-1] + b', "event_b64": "' + stream_b64 + b'"}'


def post(host: str, port: int, body: bytes, rec: Sent, timeout: float) -> None:
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        rec.t_sent = time.perf_counter()
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.code = resp.status
        ev = {}
        while True:
            line = resp.readline()
            if not line:
                break
            t = time.perf_counter()
            if not line.strip():
                continue
            ev = json.loads(line)
            new = ev.get("delta")
            if new is None and "restart" in ev:
                new = ev["restart"][len(rec.text):]
                rec.text = ev["restart"][:len(rec.text)]
            if new:
                rec.text += new
                rec.deltas.append((t, len(new)))
        rec.t_done = time.perf_counter()
        rec.rid = ev.get("rid")
        if resp.status != 200:
            rec.status, rec.error = f"http_{resp.status}", str(ev)[:200]
        elif ev.get("done") is not True:
            rec.status, rec.error = "no_done_event", str(ev)[:200]
        else:
            rec.status = ev.get("status", "fault" if "error" in ev else "ok")
            rec.error = str(ev.get("error", ""))[:200]
    except Exception as e:  # a request that fails is counted, not raised
        rec.t_done = time.perf_counter()
        rec.status, rec.error = "client_error", repr(e)[:200]
    finally:
        conn.close()


class Driver:
    """Sends schedules at one server. ``records`` keeps everything sent."""

    def __init__(self, host: str, port: int, pool: List[bytes],
                 timeout: float = 120.0):
        self.host, self.port, self.pool, self.timeout = host, port, pool, timeout
        self.records: List[Sent] = []
        self._lock = threading.Lock()

    def _fire(self, req: Request, t_due: float) -> Sent:
        rec = Sent(req, t_due)
        with self._lock:
            self.records.append(rec)
        post(self.host, self.port, body_bytes(req, self.pool[req.stream]),
             rec, self.timeout)
        return rec

    def run_open(self, sched: Schedule, t0: float, until_s: float) -> List[threading.Thread]:
        """Send each request at ``t0 + due_s``; returns the threads still
        reading answers. Never waits for an answer before the next send."""
        threads = []
        for req in sched.requests:
            if req.due_s >= until_s:
                break
            t_due = t0 + req.due_s
            delay = t_due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=self._fire, args=(req, t_due),
                                  daemon=True)
            th.start()
            threads.append(th)
        return threads


def join_all(threads: List[threading.Thread], limit_s: float) -> int:
    """Wait up to ``limit_s`` in all; returns how many are still running."""
    end = time.perf_counter() + limit_s
    for th in threads:
        th.join(max(0.0, end - time.perf_counter()))
    return sum(th.is_alive() for th in threads)
