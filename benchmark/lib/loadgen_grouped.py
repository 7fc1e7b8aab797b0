"""The load generator for grouped answers: a child process that never imports
jax. lib/loadgen.py's sibling for a cell whose answers carry `groups` (the
closed loop only: a grouped cell has no open-loop traffic kind yet).

    python benchmark/lib/loadgen_grouped.py <plan.json> <results.json>

The plan: {"url", "mode": "closed", "requests": [{"body", "want"}], "clients",
"seconds": window, "timeout_s"}; `want` is the reference's `groups` for the
request — keys in order, every aggregate as the daemon renders it. It prints
"ready", waits for "go" on stdin, and runs `clients` threads that each send
their next request when the last one answered, until the window ends. Every
request is timed on the wall clock (ns): due, sent (just before the bytes go
out), done (last byte read). An answer is ok when its `groups` EQUAL `want`
(keys, their order, each aggregate's text and the count) and its `group_count`
is their number. Results go to <results.json> in lib/loadgen.py's form, so
that lib/serving.py's `run` reads them; then it prints "done".
"""

from __future__ import annotations

import http.client
import itertools
import json
import sys
import threading
import time
from urllib.parse import urlparse


def same_groups(got: dict, want: list) -> bool:
    """The comparison that decides `correct`: exact."""
    return got.get("groups") == want and got.get("group_count") == len(want)


def main() -> int:
    plan = json.loads(open(sys.argv[1]).read())
    if plan["mode"] != "closed":
        raise SystemExit("loadgen_grouped: closed loop only")
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
                rec["ok"] = same_groups(got, plan["requests"][i]["want"])
                if not rec["ok"]:
                    rec["got"] = got.get("groups")
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
