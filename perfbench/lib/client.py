"""The load generator: one process, one thread, off jax.

Started by the serving driver as `python client.py`, it reads one JSON
plan from stdin, answers `ready`, waits for `go`, drives the gateway over
HTTP/SSE on the plan's schedule, and writes one JSON result to stdout.
Clocks are CLOCK_MONOTONIC seconds, which this process shares with the
server's, so the plan's `t0` (when the measured window opens) means the
same instant on both sides. Times in the result are relative to t0.

open loop    every request is sent when it is due, whatever came back
closed loop  `clients` workers each post the pool's next document when
             their last one has ended, until the window closes; what is
             still in flight then is cut (the connection closed, which
             makes the gateway cancel it) and reported as `cut`
"""
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from traffic import prompt_tokens  # noqa: E402


async def _post_stream(port, body, rec, t0, stop_at=None):
    """POST /v1/generate and read the SSE stream to its end event.
    Fills rec: sent, code, first, events [(t, n_tokens)], status, tokens."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        payload = json.dumps(body).encode()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Type: application/json\r\nContent-Length: "
                     + str(len(payload)).encode() + b"\r\n\r\n" + payload)
        rec["sent"] = time.monotonic() - t0
        await writer.drain()
        status = await reader.readline()
        rec["code"] = int(status.split()[1]) if status else 0
        while True:                      # headers
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
        if rec["code"] != 200:
            rec["status"] = f"http_{rec['code']}"
            return
        etype, events = None, rec["events"]
        while True:
            if stop_at is not None:
                left = stop_at - time.monotonic()
                if left <= 0:
                    rec["status"] = "cut"
                    return
                try:
                    line = await asyncio.wait_for(reader.readline(), left)
                except asyncio.TimeoutError:
                    rec["status"] = "cut"
                    return
            else:
                line = await reader.readline()
            if not line:
                rec["status"] = "truncated"
                return
            if line.startswith(b"event:"):
                etype = line[6:].strip()
            elif line.startswith(b"data:"):
                now = time.monotonic() - t0
                if etype == b"token":
                    data = json.loads(line[5:])
                    events.append((now, len(data["tokens"])))
                elif etype == b"end":
                    data = json.loads(line[5:])
                    rec["status"] = data["status"]
                    rec["tokens"] = data["tokens"]
                    rec["end"] = now
                    return
    finally:
        writer.close()


def _body(plan, r):
    toks = prompt_tokens(plan["seed"], r["index"], r["prompt_len"],
                         plan["vocab"])
    return {"prompt": [int(t) for t in toks],
            "max_new_tokens": r["max_new_tokens"],
            "request_id": f"{plan['tag']}-{r['index']}"}


def _record(r):
    return dict(index=r["index"], doc=r.get("doc", r["index"]),
                phase=r["phase"], due=r.get("due_s"),
                prompt_len=r["prompt_len"],
                max_new_tokens=r["max_new_tokens"], events=[],
                status="unsent", tokens=None)


async def run_open(plan, t0):
    recs, tasks = [], []
    deadline = t0 + plan["seconds"] + plan["grace_s"]

    async def one(r, rec):
        delay = t0 + r["due_s"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        body = _body(plan, r)
        try:
            await _post_stream(plan["port"], body, rec, t0, stop_at=deadline)
        except (OSError, ValueError) as e:
            rec["status"] = f"error:{type(e).__name__}"

    for r in plan["requests"]:
        rec = _record(r)
        recs.append(rec)
        tasks.append(asyncio.ensure_future(one(r, rec)))
    window = [t for t, r in zip(tasks, plan["requests"])
              if r["phase"] != "lead_out"]
    # the run is over when every request due up to the window's end has
    # ended (or the grace ran out); the lead-out only keeps the load up
    await asyncio.wait(window, timeout=max(0.0, deadline - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs


async def run_closed(plan, t0):
    recs = []
    pool = plan["requests"]
    nxt = [0]
    start = t0 - plan["lead_in_s"]
    stop = t0 + plan["seconds"]

    async def worker():
        delay = start - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        while time.monotonic() < stop:
            k = nxt[0]
            nxt[0] += 1
            r = dict(pool[k % len(pool)])
            r["doc"] = r["index"]     # which document: the prompt's key
            r["index"] = k            # unique request ids past one cycle
            r["phase"] = "window" if time.monotonic() >= t0 else "lead_in"
            rec = _record(r)
            rec["due"] = time.monotonic() - t0
            recs.append(rec)
            body = _body(plan, dict(r, index=r["doc"]))
            body["request_id"] = f"{plan['tag']}-{k}"
            try:
                await _post_stream(plan["port"], body, rec, t0, stop_at=stop)
            except (OSError, ValueError) as e:
                rec["status"] = f"error:{type(e).__name__}"
                await asyncio.sleep(0.05)

    await asyncio.gather(*[worker() for _ in range(plan["clients"])])
    return recs


def main():
    plan = json.loads(sys.stdin.readline())
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    if not go or go[0] != "go":
        return 1
    t0 = float(go[1])
    run = run_open if plan["loop"] == "open" else run_closed
    recs = asyncio.run(run(plan, t0))
    json.dump({"records": recs}, sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
