"""The load client: a child process that imports no JAX, sends requests to
``serve`` over HTTP on the schedule it is given, stamps every streamed line
on the monotonic clock and writes its records to a file.

Rewritten from ``tools/loadgen.py``'s ``drive_http``: the wall clock from
the DUE time is kept; what is new is a process of its own (the server's
process holds the chip and the GIL), a fixed pool of threads made before
the window, a stamp per token, how late each request was sent, and a
closed loop.

  python3 benchmark/harness/client.py <spec.json> <records.json>

``spec``: ``{"base_url", "loop", "clients": [[request...]...], "t0",
"seconds", "temperature", "keep_tokens", "timeout_s"}`` with ``t0`` on
``time.monotonic()`` (one clock for every process of a Linux host). Times
in the records are seconds after ``t0``. A request is timed from when it
was DUE: an open loop's schedule, or the completion of the client's last
request in a closed loop.
"""

from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
from urllib.parse import urlparse

MAX_OPEN_THREADS = 48


def send(host: str, port: int, req: dict, spec: dict, due: float) -> dict:
    """One streamed ``/generate`` call. Only SAMPLED tokens are stamped:
    ``serve`` first echoes the prompt's forced tokens (``prompt_tokens - 1``
    lines, in one burst after the admission prefill)."""
    t0 = spec["t0"]
    rec = {"id": req["id"], "due": due - t0, "sent": None, "stamps": [],
           "done": None, "ok": False, "error": None,
           "prompt_tokens": req["prompt_tokens"],
           "output_tokens": req["output_tokens"]}
    tokens = [] if spec.get("keep_tokens") else None
    n_echo = req["prompt_tokens"] - 1
    body = json.dumps({
        "prompt": req["prompt"],
        # ``steps`` counts positions, the prompt's included, and the last
        # prompt position already samples: n + out - 1 positions give
        # ``out`` sampled tokens
        "steps": req["prompt_tokens"] + req["output_tokens"] - 1,
        "temperature": spec.get("temperature", 0), "stream": True})
    conn = http.client.HTTPConnection(host, port,
                                      timeout=spec.get("timeout_s", 120))
    try:
        rec["sent"] = time.monotonic() - t0
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json",
                      "Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return rec
        n_lines = 0
        while True:
            line = resp.readline()
            now = time.monotonic() - t0
            if not line:
                rec["error"] = "stream ended without a done line"
                return rec
            msg = json.loads(line)
            if msg.get("done"):
                rec["done"] = now
                if msg.get("error"):
                    rec["error"] = str(msg["error"])
                resp.read()     # the chunked body's end, so the close is clean
                break
            n_lines += 1
            if n_lines > n_echo:
                rec["stamps"].append(now)
            if tokens is not None:
                tokens.append(msg["token"])
        if rec["error"] is None:
            if len(rec["stamps"]) == req["output_tokens"]:
                rec["ok"] = True
            else:
                rec["error"] = (f"{len(rec['stamps'])} sampled tokens, not "
                                f"{req['output_tokens']} (ended early)")
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
        if tokens is not None:
            rec["tokens"] = tokens
    return rec


def run_open(host, port, spec, records, lock):
    """Requests leave on their due times whatever the server does. The
    threads exist before the window; a request that finds none free waits
    in the queue, and its lateness shows in ``sent - due``."""
    reqs = spec["clients"][0]
    work: queue.Queue = queue.Queue()

    def worker():
        while True:
            req = work.get()
            if req is None:
                return
            rec = send(host, port, req, spec, spec["t0"] + req["due_s"])
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(min(MAX_OPEN_THREADS, max(1, len(reqs))))]
    for t in threads:
        t.start()
    for req in reqs:
        delay = spec["t0"] + req["due_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        work.put(req)
    for _ in threads:
        work.put(None)
    return threads


def run_closed(host, port, spec, records, lock):
    """Each client sends its next request when its last completes, and
    starts none after the window's end."""
    end = spec["t0"] + spec["seconds"]

    def client(reqs):
        due = spec["t0"]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for req in reqs:
            if time.monotonic() >= end:
                return
            rec = send(host, port, req, spec, due)
            with lock:
                records.append(rec)
            due = time.monotonic()

    threads = [threading.Thread(target=client, args=(reqs,), daemon=True)
               for reqs in spec["clients"]]
    for t in threads:
        t.start()
    return threads


def main(argv) -> int:
    spec_path, out_path = argv
    with open(spec_path, encoding="utf-8") as fh:
        spec = json.load(fh)
    url = urlparse(spec["base_url"])
    records: list = []
    lock = threading.Lock()
    run = run_open if spec["loop"] == "open" else run_closed
    threads = run(url.hostname, url.port, spec, records, lock)
    deadline = (spec["t0"] + spec["seconds"]
                + spec.get("drain_s", 90))
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    stuck = sum(t.is_alive() for t in threads)
    with lock:
        records.sort(key=lambda r: r["id"])
        doc = {"records": list(records), "stuck_threads": stuck}
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    return 1 if stuck else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
