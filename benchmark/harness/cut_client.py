"""``harness/client.py``'s closed loop for traffic whose requests outlast the
window: a request still streaming when the window closes is CUT there by
its client (the connection is closed; ``serve`` sees a vanished consumer and
frees the row), and its record stands with the stamps it has, ``"cut":
true`` and ``ok`` true: nothing went wrong with it, the measurement ended.
The tokens it delivered inside the window count like any other's; a request
that was still queued has no stamp and counts nothing.

Why: ``reason-sat32``'s outputs are 1,100 to 6,000 tokens, two to four
minutes of decoding a request, against a window of 40 s. ``client.py``
waits for every request to complete (and gives up 90 s after the window,
dropping the records of those that have not): the run would take ten
minutes and count the tokens of whichever requests happened to be short.

  python3 benchmark/harness/cut_client.py <spec.json> <records.json>

``spec`` as ``client.py``'s, closed loop only, and ``first_wave``: that
many clients (the first of the list) send at ``t0``, a millisecond apart in
the list's order, and the others ``SECOND_WAVE_S`` later: with more clients
than slots the FIRST requests to reach the server decide which prompts the
window's one fill is made of, and a race between 64 threads waking at once
made that 231 to 253 chunks from seed to seed (3.2 % of the tokens a window:
my chip runs, PR 37); the driver puts clients whose first requests are the
mix in its exact proportions at the head of the list. A request that ends
inside the window is recorded as ``client.send`` records it (the function
is a copy of it up to the cut).
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time
from urllib.parse import urlparse

CUT_GRACE_S = 0.25   # past the window's end, so that its last stamps land
SECOND_WAVE_S = 0.5  # the clients past ``first_wave`` send this much later


class Cutter:
    """Closes every open stream once, ``CUT_GRACE_S`` past the window's end:
    a blocked read then returns, and ``send`` records the request as cut."""

    def __init__(self, cut_at: float):
        self.cut_at = cut_at
        self.done = False
        self._lock = threading.Lock()
        self._open: set = set()
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self) -> None:
        time.sleep(max(0.0, self.cut_at - time.monotonic()))
        with self._lock:
            self.done = True
            for sock in self._open:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def watch(self, sock) -> bool:
        """Register a connection's socket (the response takes it from the
        connection, so the socket itself is kept); False if the cut has
        been made."""
        with self._lock:
            if not self.done:
                self._open.add(sock)
            return not self.done

    def forget(self, sock) -> None:
        with self._lock:
            self._open.discard(sock)


def send(host: str, port: int, req: dict, spec: dict, due: float,
         cutter: Cutter) -> dict:
    t0 = spec["t0"]
    rec = {"id": req["id"], "due": due - t0, "sent": None, "stamps": [],
           "done": None, "ok": False, "error": None, "cut": False,
           "prompt_tokens": req["prompt_tokens"],
           "output_tokens": req["output_tokens"]}
    n_echo = req["prompt_tokens"] - 1
    body = json.dumps({
        "prompt": req["prompt"],
        "steps": req["prompt_tokens"] + req["output_tokens"] - 1,
        "temperature": spec.get("temperature", 0), "stream": True})
    conn = http.client.HTTPConnection(host, port, timeout=120)
    sock = None
    try:
        rec["sent"] = time.monotonic() - t0
        conn.connect()
        sock = conn.sock
        if not cutter.watch(sock):
            rec["cut"] = rec["ok"] = True
            return rec
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
                resp.read()
                break
            n_lines += 1
            if n_lines > n_echo:
                rec["stamps"].append(now)
        if rec["error"] is None:
            if len(rec["stamps"]) == req["output_tokens"]:
                rec["ok"] = True
            else:
                rec["error"] = (f"{len(rec['stamps'])} sampled tokens, not "
                                f"{req['output_tokens']} (ended early)")
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        cutter.forget(sock)
        conn.close()
        if cutter.done and not rec["ok"] and rec["done"] is None:
            # the cutter closed it (a read that was blocked returned empty
            # or raised): the measurement ended, nothing went wrong
            rec.update(cut=True, ok=True, error=None)
    return rec


def main(argv) -> int:
    spec_path, out_path = argv
    with open(spec_path, encoding="utf-8") as fh:
        spec = json.load(fh)
    url = urlparse(spec["base_url"])
    records: list = []
    lock = threading.Lock()
    end = spec["t0"] + spec["seconds"]
    cutter = Cutter(end + CUT_GRACE_S)

    first_wave = int(spec.get("first_wave", len(spec["clients"])))

    def client(index, reqs):
        due = spec["t0"] + 0.001 * index + (
            SECOND_WAVE_S if index >= first_wave else 0.0)
        time.sleep(max(0.0, due - time.monotonic()))
        for req in reqs:
            if time.monotonic() >= end:
                return
            rec = send(url.hostname, url.port, req, spec, due, cutter)
            with lock:
                records.append(rec)
            due = time.monotonic()

    threads = [threading.Thread(target=client, args=(i, reqs), daemon=True)
               for i, reqs in enumerate(spec["clients"])]
    for t in threads:
        t.start()
    deadline = end + 30
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
