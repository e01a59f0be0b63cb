#!/usr/bin/env python3
"""The closed-loop load generator: a process of its own that never
imports JAX (the chip belongs to the server's process), with one thread
a client.  Each client sends its next ``POST /generate`` (streamed) the
moment its previous reply is complete, taking requests off one shared
list in order.

Driven over stdin/stdout, one JSON object a line:
  {"op": "batch", "requests": [[prompt, steps], ...]}
      send them all at once, one thread each; reply when all are done;
  {"op": "loop", "requests": [...], "clients": n, "seconds": s}
      closed loop for s seconds (s null: until the list is used up);
      requests SENT inside the window are followed to their end.  Two
      lines come back: {"t0": ...} the moment the loop starts, so that
      the caller can act when the window closes, and the reply when the
      last request has ended;
  {"op": "quit"}.
Times are ``time.monotonic()`` seconds, comparable across processes.
"""

import http.client
import json
import sys
import threading
import time

TIMEOUT_S = 300.0


def one_request(host, port, prompt, steps):
    """-> record with the send time, the arrival time of every streamed
    token, the tokens, and the error if any."""
    rec = {"prompt_len": len(prompt), "steps": steps, "tokens": [],
           "arrivals": [], "error": None}
    body = json.dumps({"prompt": prompt, "steps": steps, "stream": True})
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = "HTTP %d" % resp.status
            return rec
        for line in resp:
            now = time.monotonic()
            line = line.strip()
            if line == b"data: [DONE]":
                break
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[6:])
            if "token" in event:
                rec["tokens"].append(event["token"])
                rec["arrivals"].append(now)
            elif "error" in event:
                rec["error"] = json.dumps(event["error"])[:300]
            elif event.get("done"):
                rec["final"] = event["tokens"][len(prompt):]
        rec["done"] = time.monotonic()
        if rec["error"] is None and len(rec["tokens"]) != steps:
            rec["error"] = "%d tokens of %d" % (len(rec["tokens"]), steps)
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = "%s: %s" % (type(e).__name__, e)
    finally:
        conn.close()
    rec.setdefault("done", time.monotonic())
    return rec


def run_batch(host, port, requests):
    out = [None] * len(requests)

    def work(i):
        out[i] = one_request(host, port, *requests[i])
    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def run_loop(host, port, requests, clients, seconds):
    lock, out, nxt = threading.Lock(), [], [0]
    think = []
    t0 = time.monotonic()
    print(json.dumps({"t0": t0}), flush=True)

    def client():
        last_done = None
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(requests) or (
                    seconds is not None
                    and time.monotonic() - t0 >= seconds):
                return
            rec = one_request(host, port, *requests[i])
            if last_done is not None:
                think.append(rec["sent"] - last_done)
            rec["index"] = i
            last_done = rec["done"]
            with lock:
                out.append(rec)
    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"t0": t0, "records": sorted(out, key=lambda r: r["index"]),
            "think_max_s": max(think) if think else 0.0,
            "used_up": nxt[0] > len(requests)}


def main():
    host, port = sys.argv[1], int(sys.argv[2])
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["op"] == "quit":
            break
        if cmd["op"] == "batch":
            reply = {"records": run_batch(host, port, cmd["requests"])}
        else:
            reply = run_loop(host, port, cmd["requests"], cmd["clients"],
                             cmd["seconds"])
        print(json.dumps(reply), flush=True)


if __name__ == "__main__":
    main()
