"""Open-loop load generator: a process of its own that never imports JAX.

    python3 chipbench/loadgen.py <schedule.json>

The schedule (written by ``traffic/open_loop_http.py``) holds the
server's address, the route, ``t0`` (a ``time.monotonic()`` value: on
Linux that clock is shared by all processes), the ``deadline_s`` after
``t0`` at which everything still open is abandoned, and the requests
with their due times.  Each request is sent at ``t0 + due_s`` whether or
not earlier ones have finished (open loop), on a thread and a
connection of its own, as a streamed ``POST /<route>/generate``.  Every
streamed chunk is stamped as it arrives.  Standard library only, so the
generator shares no interpreter lock with the engine's loop.

Prints one JSON object: for every request its id, when it was due and
when it was really sent (lateness = the generator's own delay), the
arrival time of every token, the tokens, and how it ended.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time


def fire(req: dict, sched: dict, out: dict) -> None:
    t0, deadline = sched["t0"], sched["t0"] + sched["deadline_s"]
    rec = {"id": req["id"], "due_s": req["due_s"], "sent_s": None,
           "token_s": [], "tokens": [], "ended": "unfinished", "detail": ""}
    out[req["id"]] = rec
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0, "stream": True}).encode()
    head = (f"POST /{sched['route']}/generate HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    try:
        with socket.create_connection((sched["host"], sched["port"]),
                                      timeout=10) as s:
            rec["sent_s"] = time.monotonic() - t0
            s.sendall(head + body)
            buf, status, done = b"", None, False
            while True:
                s.settimeout(max(0.05, deadline - time.monotonic()))
                got = s.recv(65536)
                now = time.monotonic() - t0
                if not got:
                    rec["ended"] = "truncated"
                    return
                buf += got
                if status is None:
                    if b"\r\n\r\n" not in buf:
                        continue
                    top, _, buf = buf.partition(b"\r\n\r\n")
                    status = int(top.split(b"\r\n")[0].split()[1])
                    if status != 200:
                        rec["ended"] = f"http_{status}"
                        rec["detail"] = buf[:200].decode("latin1")
                        return
                # chunked framing: <hex size>\r\n<data>\r\n ... 0\r\n\r\n
                while True:
                    size_end = buf.find(b"\r\n")
                    if size_end < 0:
                        break
                    size = int(buf[:size_end], 16)
                    if size == 0:
                        rec["ended"] = "done" if done else "truncated"
                        return
                    if len(buf) < size_end + 2 + size + 2:
                        break
                    doc = json.loads(buf[size_end + 2:size_end + 2 + size])
                    buf = buf[size_end + 2 + size + 2:]
                    if doc.get("done"):
                        done = True
                    else:
                        rec["tokens"].append(doc["token"])
                        rec["token_s"].append(now)
    except socket.timeout:
        rec["ended"] = "unfinished"
    except Exception as e:          # reported, never raised: one failed
        rec["ended"] = "error"      # request must not stop the others
        rec["detail"] = f"{type(e).__name__}: {e}"[:200]


def main() -> int:
    with open(sys.argv[1]) as f:
        sched = json.load(f)
    out, threads = {}, []
    for req in sched["requests"]:        # sorted by due time
        wait = sched["t0"] + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=fire, args=(req, sched, out),
                              daemon=True)
        th.start()
        threads.append(th)
    end = sched["t0"] + sched["deadline_s"] + 2.0
    for th in threads:
        th.join(timeout=max(0.0, end - time.monotonic()))
    json.dump({"requests": [out[r["id"]] for r in sched["requests"]
                            if r["id"] in out]}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
