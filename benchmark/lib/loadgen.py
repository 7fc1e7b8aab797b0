"""The load generator: a child process that never imports jax.

    python benchmark/lib/loadgen.py <plan.json> <results.json>

The plan: {"url", "mode": "open" | "closed", "requests": [{"body", "want"}],
"due_s": [offset of each arrival, open loop], "clients": n (closed loop),
"seconds": window, "timeout_s"}. It prints "ready", waits for "go" on stdin,
and runs: open loop sends request i when it is DUE, on a thread of its own,
however the earlier ones fare; closed loop runs `clients` threads that each
send their next request when the last one answered, until the window ends.
Every request is timed on the wall clock (ns): due, sent (just before the
bytes go out), done (last byte read), and compared with its precomputed
answer. Results go to <results.json>; then it prints "done".
"""

from __future__ import annotations

import http.client
import itertools
import json
import sys
import threading
import time
from urllib.parse import urlparse


def main() -> int:
    plan = json.loads(open(sys.argv[1]).read())
    url = urlparse(plan["url"])
    payloads = [json.dumps(r["body"]).encode() for r in plan["requests"]]
    records: list = []
    lock = threading.Lock()

    def send(i: int, due_ns: int) -> None:
        rec = {"i": i, "due_ns": due_ns, "status": 0, "ok": False, "units": 0}
        conn = http.client.HTTPConnection(url.hostname, url.port, timeout=plan["timeout_s"])
        try:
            rec["sent_ns"] = time.time_ns()
            conn.request("POST", "/v1/query", body=payloads[i], headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            rec["done_ns"] = time.time_ns()
            rec["status"] = resp.status
            if resp.status == 200:
                got = json.loads(data)
                rec["units"] = got.get("units", 0)
                rec["ok"] = got.get("result") == plan["requests"][i]["want"]
                if not rec["ok"]:
                    rec["got"] = got.get("result")
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec.setdefault("done_ns", time.time_ns())
            rec["error"] = repr(e)
        finally:
            conn.close()
        with lock:
            records.append(rec)

    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    t0 = time.time_ns() + 20_000_000
    t_end = t0 + int(plan["seconds"] * 1e9)
    threads = []
    if plan["mode"] == "open":
        for k, off in enumerate(plan["due_s"]):
            due = t0 + int(off * 1e9)
            wait = (due - time.time_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            t = threading.Thread(target=send, args=(k % len(payloads), due))
            t.start()
            threads.append(t)
    else:
        ticket = itertools.count()

        def client() -> None:
            while (now := time.time_ns()) < t_end:
                send(next(ticket) % len(payloads), max(now, t0))

        time.sleep(max(0.0, (t0 - time.time_ns()) / 1e9))
        threads = [threading.Thread(target=client) for _ in range(plan["clients"])]
        for t in threads:
            t.start()
    for t in threads:
        t.join()
    with open(sys.argv[2], "w") as f:
        json.dump({"t0_ns": t0, "t_end_ns": t_end, "records": records}, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
