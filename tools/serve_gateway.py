#!/usr/bin/env python
"""Serving-gateway gate (ISSUE 12): the HTTP/SSE front door as CI.

An in-process gateway (real TCP on 127.0.0.1, real asyncio client) is
driven through the full front-door contract, three passes on one
engine (cold -> prefix-cache-warm -> declare_warm -> the steady-state
gate):

* **concurrent SSE streams** (submitted under a stepper hold so all
  four land on one admission pass — the compiled-bucket sequence stays
  host-deterministic under wall-clock HTTP arrivals): streamed tokens
  must be BYTE-IDENTICAL to ``engine.generate()``, and the per-stream
  SSE token-event order must match the span ring (one event per
  prefill-completing chunk / decode span, same widths, same order);
* **one mid-stream cancel** — DELETE answers 200, the stream ends
  with a typed ``end`` event (status ``cancelled``), the partial
  tokens are an exact prefix of the reference, and the KV/refcount
  gauges return to baseline;
* **one deadline** — ``deadline_steps`` in the POST body, 504 +
  ``deadline_exceeded``, partial tokens an exact reference prefix
  (zero cold — the prompt cannot prefill inside the deadline — one
  once the prefix cache maps the whole prompt);
* **one shed** — a deterministic burn-rate flag flips the admission
  gate: the queued low-priority stream ends ``shed``/``slo_burn``,
  and ``/healthz`` answers 503 (reason ``slo_burn``) while the flag
  is up, 200 after;
* **one structured rejection** — ``spec_k`` wider than the engine's
  answers 422 with the engine's fixed reason label;
* **control plane parses** — ``/metrics`` through
  ``parse_prometheus`` (gateway + serve families present), ``/slo``
  through ``validate_report``, ``/healthz`` through
  ``validate_healthz``, ``/requests/{id}`` digest keys, ``/dumps`` +
  a dump download through the flight-recorder schema;
* **zero new compile buckets after warmup**, and the pass-3 stream
  schedule (statuses + per-event token widths) replays pass 2
  exactly.

Wall-clock shows up only in latencies (reported, not gated) and in
WHEN the cancel lands (its prefix length is asserted, not its value).

Usage:
  python tools/serve_gateway.py [--json OUT]
  python tools/serve_gateway.py --check tools/serve_gateway.json
"""
import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.serve_gateway/1"

DEFAULT_CONFIG = {
    "engine": {
        "seed": 0, "max_seq_len": 64, "num_blocks": 40, "block_size": 8,
        "max_batch": 4, "prefill_chunk": 8, "spec_k": 2,
        "prefix_cache": True, "shed_priority_min": 1,
    },
    "workload": {
        "seed": 0,
        # the four concurrent streams (prompt len, new tokens)
        "streams": [[5, 6], [11, 5], [16, 8], [7, 4]],
        # mid-stream cancel: long generation, DELETE after 2 token events
        "cancel": {"prompt_len": 9, "max_new_tokens": 24,
                   "after_events": 2},
        # deadline: a 16-token prompt cannot prefill (chunk=8) inside 1
        # step -> deadline_exceeded with zero tokens, deterministically
        "deadline": {"prompt_len": 16, "max_new_tokens": 4,
                     "deadline_steps": 1},
        # shed: priority-2 stream submitted while the burn flag is up
        "shed": {"prompt_len": 6, "max_new_tokens": 4, "priority": 2},
    },
    "slo": {
        "cadence_s": 60.0,
        "windows": [{"name": "fast", "window_s": 5.0,
                     "burn_threshold": 1000.0}],
        "objectives": [
            {"name": "ttft_p99", "kind": "quantile",
             "metric": "serve_ttft_seconds", "q": 0.99, "max": 600.0},
        ],
    },
}


class BurnFlagMonitor:
    """SLOMonitor wrapper whose ``last_report`` the gate can force into
    a burn: the engine's pressure-aware admission and the gateway's
    /healthz both read ``last_report["breaches"]`` — flipping the flag
    exercises the production shed + degrade paths on a deterministic
    trigger instead of a real latency regression."""

    def __init__(self, inner):
        self.inner = inner
        self.force_burn = False

    @property
    def last_report(self):
        if self.force_burn:
            return {"breaches": 1, "forced": True}
        return self.inner.last_report

    def tick(self, now=None):
        return self.inner.tick(now)

    def report(self, now=None):
        return self.inner.report(now)


# -- minimal asyncio HTTP/SSE client --------------------------------------

async def _request(port, method, path, body=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = b"" if body is None else json.dumps(body).encode()
    # one-shot client: opt out of HTTP/1.1 keep-alive so read-to-EOF
    # below terminates (the gateway honors Connection: close)
    head = (f"{method} {path} HTTP/1.1\r\nHost: gw\r\n"
            "Connection: close\r\n")
    if payload:
        head += ("Content-Type: application/json\r\n"
                 f"Content-Length: {len(payload)}\r\n")
    writer.write(head.encode() + b"\r\n" + payload)
    await writer.drain()
    data = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except OSError:
        pass
    headb, _, rest = data.partition(b"\r\n\r\n")
    return int(headb.split(None, 2)[1]), rest


async def _get_json(port, path):
    code, body = await _request(port, "GET", path)
    return code, json.loads(body)


async def _open_stream(port, body):
    """POST a streaming generate; returns (status, reader, writer)
    positioned after the response headers."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: gw\r\n"
                  "Content-Type: application/json\r\n"
                  f"Content-Length: {len(payload)}\r\n\r\n").encode()
                 + payload)
    await writer.drain()
    status = int((await reader.readline()).split(None, 2)[1])
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
    return status, reader, writer


async def _next_sse(reader):
    """One SSE frame -> (event, payload) or None on EOF."""
    etype, data = None, []
    while True:
        line = await reader.readline()
        if not line:
            return None
        line = line.decode().rstrip("\r\n")
        if line == "":
            if data:
                return etype or "message", json.loads("\n".join(data))
            continue
        field, _, value = line.partition(":")
        value = value[1:] if value.startswith(" ") else value
        if field == "event":
            etype = value
        elif field == "data":
            data.append(value)


async def _run_stream(port, body, cancel_after=None):
    """Drive one SSE stream to its `end` event; with `cancel_after`,
    DELETE the request after that many token events. Returns
    (http_status, events, delete_status)."""
    status, reader, writer = await _open_stream(port, body)
    events, ntok, del_code = [], 0, None
    if status == 200:
        while True:
            ev = await _next_sse(reader)
            if ev is None:
                break
            events.append(ev)
            if ev[0] == "token":
                ntok += 1
                if cancel_after is not None and ntok == cancel_after:
                    del_code, _ = await _request(
                        port, "DELETE",
                        f"/v1/requests/{body['request_id']}")
            if ev[0] == "end":
                break
    writer.close()
    try:
        await writer.wait_closed()
    except OSError:
        pass
    return status, events, del_code


# -- span-ring cross-check --------------------------------------------------

def _expected_emissions(rid, prompt_len):
    """Per-emission token widths the span ring predicts for `rid`: one
    1-token emission from the prefill chunk that reached the prompt's
    end, then each decode span's `emitted`. The SSE token-event widths
    must replay this exactly (same order, same counts)."""
    from paddle_tpu.observability import tracing

    out = []
    for s in tracing.get_tracer().spans(request=rid):
        a = s["args"] or {}
        if s["name"] == "prefill_chunk" and a.get("progress") == prompt_len:
            out.append(1)
        elif s["name"] == "decode":
            out.append(a.get("emitted", 1))
    return out


def _token_widths(events):
    return [len(p["tokens"]) for e, p in events if e == "token"]


def _stream_tokens(events):
    return [t for e, p in events if e == "token" for t in p["tokens"]]


def _end_event(events):
    ends = [p for e, p in events if e == "end"]
    return ends[0] if ends else None


# -- the pass ---------------------------------------------------------------

async def _drive_pass(gw, stepper, monitor, workload, refs, tag,
                      faulted):
    """One full scenario suite against a live gateway. `faulted` runs
    the cancel/deadline/shed variants; a plain pass runs those requests
    to completion instead (bucket warmup must see their full solo
    shapes). Returns the per-pass report dict."""
    from paddle_tpu.observability import tracing

    port = gw.port
    tracing.get_tracer().clear()
    report = {"tag": tag}

    # -- concurrent streams: hold the stepper so all four land on one
    # admission pass (deterministic schedule), then release
    stepper.hold()
    tasks = []
    for j, (p, n) in enumerate(workload["streams"]):
        body = {"prompt": refs[f"s{j}"]["prompt"], "max_new_tokens": n,
                "request_id": f"{tag}s{j}"}
        st, reader, writer = await _open_stream(port, body)
        assert st == 200, f"stream {j} HTTP {st}"
        first = await _next_sse(reader)
        assert first and first[0] == "accepted", first
        tasks.append((j, reader, writer))
    stepper.release()

    async def _drain(j, reader, writer):
        events = [("accepted", {})]
        while True:
            ev = await _next_sse(reader)
            if ev is None:
                break
            events.append(ev)
            if ev[0] == "end":
                break
        writer.close()
        return j, events

    drained = await asyncio.gather(
        *(_drain(j, r, w) for j, r, w in tasks))
    stream_ok, emissions, statuses = True, {}, {}
    for j, events in drained:
        rid = f"{tag}s{j}"
        end = _end_event(events)
        statuses[f"s{j}"] = end["status"] if end else None
        toks = _stream_tokens(events)
        ref = refs[f"s{j}"]["tokens"]
        if not (end and end["status"] == "finished" and toks == ref
                and end["tokens"] == ref):
            stream_ok = False
        widths = _token_widths(events)
        emissions[f"s{j}"] = widths
        if widths != _expected_emissions(
                rid, len(refs[f"s{j}"]["prompt"])):
            report.setdefault("sse_order_mismatch", []).append(rid)
    report["streams_token_exact"] = stream_ok
    report["stream_emissions"] = emissions
    report["sse_order_matches_spans"] = \
        "sse_order_mismatch" not in report

    # -- mid-stream cancel (or, unfaulted, a full solo run for warmup)
    c = workload["cancel"]
    body = {"prompt": refs["cancel"]["prompt"],
            "max_new_tokens": c["max_new_tokens"],
            "request_id": f"{tag}c0"}
    st, events, del_code = await _run_stream(
        port, body,
        cancel_after=c["after_events"] if faulted else None)
    end = _end_event(events)
    toks = _stream_tokens(events)
    ref = refs["cancel"]["tokens"]
    if faulted:
        statuses["cancel"] = end["status"] if end else None
        report["cancel_delete_code"] = del_code
        report["cancel_ok"] = bool(
            st == 200 and del_code == 200 and end
            and end["status"] == "cancelled"
            and len(toks) >= c["after_events"]
            and toks == ref[:len(toks)])
    else:
        statuses["cancel"] = end["status"] if end else None
        report["cancel_ok"] = bool(end and end["status"] == "finished"
                                   and toks == ref)

    # -- deadline (non-stream: the status must map to the HTTP code)
    d = workload["deadline"]
    body = {"prompt": refs["deadline"]["prompt"],
            "max_new_tokens": d["max_new_tokens"],
            "request_id": f"{tag}d0", "stream": False}
    if faulted:
        body["deadline_steps"] = d["deadline_steps"]
    code, resp = await _request(port, "POST", "/v1/generate", body)
    resp = json.loads(resp)
    statuses["deadline"] = resp["status"]
    if faulted:
        # partial tokens are KEPT at the deadline (cold, the 16-token
        # prompt can't prefill inside 1 step -> zero tokens; warm, the
        # prefix cache maps the whole prompt and one token lands
        # first) — either way an exact prefix of the reference
        ref_d = refs["deadline"]["tokens"]
        report["deadline_ok"] = bool(
            code == 504 and resp["status"] == "deadline_exceeded"
            and resp["tokens"] == ref_d[:len(resp["tokens"])])
    else:
        report["deadline_ok"] = bool(
            code == 200 and resp["status"] == "finished"
            and resp["tokens"] == refs["deadline"]["tokens"])

    # -- shed under a forced burn + /healthz degradation
    s = workload["shed"]
    body = {"prompt": refs["shed"]["prompt"],
            "max_new_tokens": s["max_new_tokens"],
            "request_id": f"{tag}h0", "priority": s["priority"]}
    if faulted:
        monitor.force_burn = True
        hcode, hz = await _get_json(port, "/healthz")
        st, events, _ = await _run_stream(port, body)
        end = _end_event(events)
        monitor.force_burn = False
        hcode2, hz2 = await _get_json(port, "/healthz")
        statuses["shed"] = end["status"] if end else None
        report["healthz_degraded"] = (hcode, hz.get("status"),
                                      hz.get("reason"))
        report["shed_ok"] = bool(
            st == 200 and end and end["status"] == "shed"
            and end["reason"] == "slo_burn")
        report["healthz_flips"] = bool(
            hcode == 503 and hz["status"] == "degraded"
            and hz["reason"] == "slo_burn" and hcode2 == 200
            and hz2["status"] == "ok")
    else:
        st, events, _ = await _run_stream(port, body)
        end = _end_event(events)
        statuses["shed"] = end["status"] if end else None
        report["shed_ok"] = bool(end and end["status"] == "finished")

    # -- structured rejection: spec_k wider than the engine
    code, resp = await _request(
        port, "POST", "/v1/generate",
        {"prompt": [1, 2, 3], "max_new_tokens": 2,
         "request_id": f"{tag}r0", "spec_k": 99})
    resp = json.loads(resp)
    statuses["reject"] = resp.get("status")
    report["reject_ok"] = bool(
        code == 422 and resp["status"] == "rejected"
        and resp["reason"] == "spec_k_exceeds_engine")

    # -- allocator back to baseline after every terminal
    def _baseline(cb):
        a = cb.allocator
        return (a.num_used == 0 and not a._ref
                and a.num_free + a.num_pooled
                == a.num_blocks - a.reserved)

    report["gauges_baseline"] = await asyncio.wrap_future(
        stepper.call(_baseline))
    report["statuses"] = statuses
    return report


async def _check_control_plane(gw, stepper, rid):
    """/metrics, /slo, /healthz, /requests, /dumps must all parse
    against their schemas."""
    from paddle_tpu.observability import (parse_prometheus,
                                          validate_report)
    from paddle_tpu.serving import validate_healthz

    out = {}
    port = gw.port
    code, body = await _request(port, "GET", "/metrics")
    fams = parse_prometheus(body.decode())
    needed = {"gateway_responses_total", "gateway_request_seconds",
              "gateway_stream_seconds", "gateway_live_connections",
              "gateway_live_streams", "gateway_sse_pending_events",
              "gateway_sse_events_total", "serve_ttft_seconds",
              "serve_tokens_total", "kv_blocks_free"}
    missing = sorted(needed - set(fams))
    out["metrics_parse"] = bool(code == 200 and not missing)
    if missing:
        out["metrics_missing"] = missing
    code, rep = await _get_json(port, "/slo")
    try:
        validate_report(rep)
        out["slo_parse"] = code == 200
    except ValueError as e:
        out["slo_parse"] = False
        out["slo_error"] = str(e)
    code, hz = await _get_json(port, "/healthz")
    try:
        validate_healthz(hz)
        out["healthz_parse"] = code == 200
    except ValueError as e:
        out["healthz_parse"] = False
        out["healthz_error"] = str(e)
    code, digest = await _get_json(port, f"/requests/{rid}")
    out["request_digest_parse"] = bool(
        code == 200 and digest.get("request") == rid
        and digest.get("retired") is True
        and {"ttft_s", "prefill_chunks", "decode_steps",
             "stalls"} <= set(digest))
    code, listing = await _get_json(port, "/requests")
    out["requests_list_parse"] = bool(
        code == 200 and listing.get("count", 0) >= 1
        and any(d["request"] == rid for d in listing["requests"]))
    code, dumps = await _get_json(port, "/dumps")
    ok = code == 200 and dumps.get("armed") and dumps["retained"]
    if ok:
        name = dumps["retained"][-1]["file"]
        code, blob = await _request(port, "GET", f"/dumps/{name}")
        payload = json.loads(blob)
        ok = code == 200 and payload.get("schema", "").startswith(
            "paddle_tpu.flight_recorder/")
    out["dumps_parse"] = bool(ok)
    return out


def gateway_leg(config=None, flight_dir=None):
    import tempfile

    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.observability import SLOMonitor, tracing
    from paddle_tpu.serving import EngineStepper, ServingGateway
    from tools.serve_bench import _tiny_cpu_engine

    config = config or DEFAULT_CONFIG
    on_tpu = init_platform() == "tpu"
    ecfg = config["engine"]
    rng = np.random.default_rng(ecfg["seed"])
    eng, V = _tiny_cpu_engine(rng, max_seq_len=ecfg["max_seq_len"])
    wl = config["workload"]
    wrng = np.random.default_rng(wl["seed"])

    def _mk(plen):
        return [int(t) for t in wrng.integers(1, V, plen)]

    prompts = {f"s{j}": _mk(p) for j, (p, n) in enumerate(wl["streams"])}
    prompts["cancel"] = _mk(wl["cancel"]["prompt_len"])
    prompts["deadline"] = _mk(wl["deadline"]["prompt_len"])
    prompts["shed"] = _mk(wl["shed"]["prompt_len"])
    news = {f"s{j}": n for j, (p, n) in enumerate(wl["streams"])}
    news["cancel"] = wl["cancel"]["max_new_tokens"]
    news["deadline"] = wl["deadline"]["max_new_tokens"]
    news["shed"] = wl["shed"]["max_new_tokens"]
    refs = {}
    for k, p in prompts.items():
        n = news[k]
        ref = eng.generate(np.asarray(p, np.int32)[None, :],
                           max_new_tokens=n)[0, :n].tolist()
        refs[k] = {"prompt": p, "tokens": ref}

    monitor = BurnFlagMonitor(SLOMonitor.from_config(config["slo"]))
    cb = ContinuousBatchingEngine(
        eng, num_blocks=ecfg["num_blocks"],
        block_size=ecfg["block_size"], max_batch=ecfg["max_batch"],
        prefill_chunk=ecfg["prefill_chunk"], spec_k=ecfg["spec_k"],
        prefix_cache=ecfg["prefix_cache"], monitor=monitor,
        shed_on_pressure=True,
        shed_priority_min=ecfg["shed_priority_min"])
    fr = tracing.get_flight_recorder()
    fr.arm(flight_dir or tempfile.mkdtemp(prefix="serve_gateway_"))

    stepper = EngineStepper(cb).start()
    gw = ServingGateway(stepper, monitor=monitor)

    # direct-engine wall for the overhead table: same four streams,
    # no HTTP in the path (fresh scheduler on the same compiled engine)
    cb_direct = ContinuousBatchingEngine(
        eng, num_blocks=ecfg["num_blocks"],
        block_size=ecfg["block_size"], max_batch=ecfg["max_batch"],
        prefill_chunk=ecfg["prefill_chunk"], spec_k=ecfg["spec_k"])

    async def _main():
        from paddle_tpu.incubate.nn import GenerationRequest

        await gw.start()
        passes = []
        warm_buckets = None
        pass_walls = []
        for k, (tag, faulted) in enumerate(
                (("p1", False), ("p2", True), ("p3", True))):
            if k == 2:
                nonlocal_warm = set(cb._seen_buckets)
                await asyncio.wrap_future(
                    stepper.call(lambda c: c.declare_warm()))
                warm_buckets = nonlocal_warm
            t0 = time.perf_counter()
            passes.append(await _drive_pass(
                gw, stepper, monitor, wl, refs, tag, faulted))
            pass_walls.append(time.perf_counter() - t0)
        # evidence for the /dumps roundtrip, then the control plane
        tracing.write_dump(os.path.join(fr._dir,
                                        "flightrec_manual_gate_0.json"),
                           reason="manual", gate="serve_gateway")
        control = await _check_control_plane(gw, stepper, "p3s0")
        await gw.close()

        # direct-engine comparison (no HTTP): wall for the same
        # 4-stream workload
        t0 = time.perf_counter()
        for j, (p, n) in enumerate(wl["streams"]):
            cb_direct.submit(GenerationRequest(
                np.asarray(refs[f"s{j}"]["prompt"], np.int32), n,
                request_id=f"dir{j}"))
        cb_direct.run()
        direct_wall = time.perf_counter() - t0
        return passes, warm_buckets, control, pass_walls, direct_wall

    try:
        passes, warm_buckets, control, pass_walls, direct_wall = \
            asyncio.run(_main())
    finally:
        stepper.stop()
    p1, p2, p3 = passes

    out = {
        "schema": REPORT_SCHEMA,
        "interpret": not on_tpu,
        "config": config,
        "workload": {k: {"prompt_len": len(refs[k]["prompt"]),
                         "new_tokens": news[k]} for k in sorted(refs)},
        "ref_tokens": {k: refs[k]["tokens"] for k in sorted(refs)},
        "passes": passes,
        "statuses_gated": p3["statuses"],
        "stream_emissions_gated": p3["stream_emissions"],
        "streams_token_exact": all(p["streams_token_exact"]
                                   for p in passes),
        "sse_order_matches_spans": all(p["sse_order_matches_spans"]
                                       for p in passes),
        "cancel_ok": all(p["cancel_ok"] for p in passes),
        "deadline_ok": all(p["deadline_ok"] for p in passes),
        "shed_ok": all(p["shed_ok"] for p in passes),
        "reject_ok": all(p["reject_ok"] for p in passes),
        "healthz_flips": bool(p2.get("healthz_flips")
                              and p3.get("healthz_flips")),
        "gauges_return_to_baseline": all(p["gauges_baseline"]
                                         for p in passes),
        "new_buckets_after_warmup": len(set(cb._seen_buckets)
                                        - warm_buckets),
        "deterministic_replay": (
            p3["statuses"] == p2["statuses"]
            and p3["stream_emissions"] == p2["stream_emissions"]),
        "control_plane": control,
        "overhead": {
            "gateway_pass3_wall_s": round(pass_walls[2], 3),
            "direct_engine_wall_s": round(direct_wall, 3),
        },
        "steps": int(cb._step_count),
    }
    print(f"gateway leg: {len(wl['streams'])} concurrent streams x3 "
          f"passes token-exact={out['streams_token_exact']}, "
          f"sse-order={out['sse_order_matches_spans']}, statuses "
          f"{out['statuses_gated']}, new buckets after warmup "
          f"{out['new_buckets_after_warmup']}, gateway wall "
          f"{out['overhead']['gateway_pass3_wall_s']}s vs direct "
          f"{out['overhead']['direct_engine_wall_s']}s"
          + (" [interpret]" if not on_tpu else ""))
    return out


# deterministic keys gated against the committed baseline
GATEWAY_KEYS = ("workload", "ref_tokens", "statuses_gated",
                "stream_emissions_gated")

# invariants that must hold regardless of the baseline
GATEWAY_INVARIANTS = (
    "streams_token_exact", "sse_order_matches_spans", "cancel_ok",
    "deadline_ok", "shed_ok", "reject_ok", "healthz_flips",
    "gauges_return_to_baseline", "deterministic_replay",
)


def check_gateway(base):
    cur = gateway_leg(config=base.get("config") or DEFAULT_CONFIG)
    bad = [k for k in GATEWAY_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    for k in GATEWAY_INVARIANTS:
        if cur[k] is not True:
            print(f"REGRESSION: {k} is {cur[k]!r}")
            bad.append(k)
    if cur["new_buckets_after_warmup"] != 0:
        print(f"REGRESSION: pass 3 compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    for k, ok in cur["control_plane"].items():
        if ok is not True and not k.endswith(("_missing", "_error")):
            print(f"REGRESSION: control plane {k} failed "
                  f"({cur['control_plane']})")
            bad.append(k)
    if bad:
        return 1
    print("gateway leg OK: streamed tokens byte-identical to "
          "engine.generate(), SSE order matches the span ring, "
          "cancel/deadline/shed/reject typed + coded, KV gauges at "
          "baseline, 0 new buckets, control plane parses")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="HTTP/SSE serving-gateway gate")
    ap.add_argument("--json", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate against a committed baseline "
                         "(tools/serve_gateway.json)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight-recorder dir for the run "
                         "(default: a fresh tmpdir)")
    args = ap.parse_args()

    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        if "gateway" not in base:
            print(f"{args.check}: no 'gateway' section to gate")
            return 1
        return check_gateway(base["gateway"])

    out = gateway_leg(flight_dir=args.flight_dir)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    from paddle_tpu.observability import tracing as _tr
    sys.exit(_tr.run_with_abort_evidence(main))
