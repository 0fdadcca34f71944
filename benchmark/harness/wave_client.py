"""``harness/cut_client.py``'s closed loop for a window that opens AFTER the
first wave's admissions: the clients send at ``t0`` (the first ``first_wave``
of the list, then the others, as ``cut_client`` has it) and nobody knows yet
when the window will close. The driver watches the program's own count of
requests that have streamed their first token, and when each of the first
wave has, writes the moment (its ``time.monotonic()``: one clock for every
process of a machine) to ``opened_path``; this client, which polls for that
file, then arms its cutter at that moment + ``seconds`` + the grace. A
request still streaming then is cut by its client as ``cut_client`` cuts it.
Stamps are relative to ``t0``; the driver moves them to the window's start.

Why: a cell whose judged number is decode at depth has to build that depth
first, and the fill (hundreds of admission chunks) is set-up, not window
(``traffic/swa-deep-sat32.json`` says who sends such traffic).

  python3 benchmark/harness/wave_client.py <spec.json> <records.json>

``spec`` as ``cut_client.py``'s, with ``opened_path`` and ``open_limit_s``
(the client gives up, cuts everything and exits 2 if the window has not
opened by then).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from urllib.parse import urlparse

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cut_client import CUT_GRACE_S, SECOND_WAVE_S, Cutter, send  # noqa: E402


class LateCutter(Cutter):
    """A ``Cutter`` that learns when to cut after it was made."""

    def __init__(self):
        self._armed = threading.Event()
        super().__init__(float("inf"))

    def _run(self) -> None:
        self._armed.wait()
        super()._run()

    def arm(self, cut_at: float) -> None:
        self.cut_at = cut_at
        self._armed.set()


def main(argv) -> int:
    spec_path, out_path = argv
    with open(spec_path, encoding="utf-8") as fh:
        spec = json.load(fh)
    url = urlparse(spec["base_url"])
    records: list = []
    lock = threading.Lock()
    cutter = LateCutter()
    first_wave = int(spec.get("first_wave", len(spec["clients"])))

    def client(index, reqs):
        due = spec["t0"] + 0.001 * index + (
            SECOND_WAVE_S if index >= first_wave else 0.0)
        time.sleep(max(0.0, due - time.monotonic()))
        for req in reqs:
            if cutter.done:
                return
            rec = send(url.hostname, url.port, req, spec, due, cutter)
            with lock:
                records.append(rec)
            due = time.monotonic()

    threads = [threading.Thread(target=client, args=(i, reqs), daemon=True)
               for i, reqs in enumerate(spec["clients"])]
    for t in threads:
        t.start()
    limit = spec["t0"] + float(spec["open_limit_s"])
    opened = None
    while opened is None and time.monotonic() < limit:
        try:
            with open(spec["opened_path"], encoding="utf-8") as fh:
                opened = float(json.load(fh)["opened_at"])
        except (OSError, ValueError, KeyError):
            time.sleep(0.005)
    end = (time.monotonic() if opened is None
           else opened + float(spec["seconds"]))
    cutter.arm(end + CUT_GRACE_S)
    deadline = end + 30
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    stuck = sum(t.is_alive() for t in threads)
    with lock:
        records.sort(key=lambda r: r["id"])
        doc = {"records": list(records), "stuck_threads": stuck,
               "opened_at": opened}
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    return 2 if opened is None else 1 if stuck else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
