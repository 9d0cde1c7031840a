"""Open-loop HTTP load generator — a child process that never imports jax.

It shares no interpreter lock with the server under test.  Reads a spec
(JSON: port, path, due times, bodies, connections, timeout), waits for
``go`` on stdin, sends each request when it is due whether or not earlier
ones have finished, and writes one JSON result file: per request the due
time, the time it was actually sent, the time the whole response had been
read (all seconds since ``go``), the HTTP status (0 = transport error or
timeout) and the response body.  Latency is later taken from the DUE
time, so a stall costs every request behind it; ``sent - due`` is how
late this generator ran.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time


def run(spec: dict, go=None) -> dict:
    due = spec["due_s"]
    bodies = [b.encode("utf-8") for b in spec["bodies"]]
    n = len(due)
    sent = [0.0] * n
    done = [0.0] * n
    status = [0] * n
    answers = [""] * n
    nxt = [0]
    lock = threading.Lock()
    t0_box = []
    started = threading.Event()

    def worker():
        conn = None
        started.wait()
        t0 = t0_box[0]
        while True:
            with lock:
                i = nxt[0]
                if i >= n:
                    break
                nxt[0] = i + 1
            wait = due[i] - (time.monotonic() - t0)
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.monotonic() - t0
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        spec.get("host", "127.0.0.1"), spec["port"],
                        timeout=spec.get("timeout_s", 30.0))
                conn.request("POST", spec.get("path", "/queries.json"),
                             body=bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                answers[i] = resp.read().decode("utf-8", "replace")
                status[i] = resp.status
            except (OSError, http.client.HTTPException):
                status[i] = 0
                if conn is not None:
                    conn.close()
                conn = None
            done[i] = time.monotonic() - t0
        if conn is not None:
            conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(spec.get("connections", 64)))]
    for t in threads:
        t.start()
    if go is not None:
        go()
    t0_box.append(time.monotonic())
    started.set()
    for t in threads:
        t.join()
    return {"due_s": due, "sent_s": sent, "done_s": done, "status": status,
            "answers": answers}


def main() -> int:
    spec_path, out_path = sys.argv[1], sys.argv[2]
    with open(spec_path, encoding="utf-8") as f:
        spec = json.load(f)

    def go():
        print("ready", flush=True)
        sys.stdin.readline()

    result = run(spec, go)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
