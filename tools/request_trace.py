#!/usr/bin/env python
"""Replay a flight-recorder dump as per-request timelines.

The serving stack's flight recorder (paddle_tpu/observability/
tracing.py) dumps the last N seconds of lifecycle spans + a metrics
snapshot when an anomaly fires (KV alloc failure, post-warmup
recompile, TPOT SLO breach, comm-watchdog stall) — or on demand via
``serve_llama.py --trace`` / ``tracing.write_dump()``. This CLI answers
"why was THIS request slow" from such a dump:

    python tools/request_trace.py DUMP.json              # all requests
    python tools/request_trace.py DUMP.json --request 3  # one lane
    python tools/request_trace.py DUMP.json --json       # digests only

Per request it prints the ``explain()`` digest (queue wait, TTFT,
chunk grants vs requests, stalls, spec accept rate) and the span
timeline (relative ms, duration, args). stdlib-only by the same
contract as tools/metrics_snapshot.py: the dump must be readable in a
bare container, before jax — the observability package is loaded
standalone by path when paddle_tpu isn't importable.
"""
import argparse
import json
import sys

try:
    from tools.metrics_snapshot import _load_observability
except ImportError:          # executed as a script from tools/
    from metrics_snapshot import _load_observability


def _fmt_args(args):
    return " ".join(f"{k}={v}" for k, v in sorted(args.items()))


_HOST_PHASES = ("host_sched_us", "host_build_us", "host_dispatch_us",
                "host_fetch_us")


def _render_host_phases(engine_spans, out):
    """Host-side phase split of the serve_step lane: where each step's
    wall time went around the device dispatch (scheduler admit/preempt,
    work-list build, dispatch, token fetch) —
    one rollup line answering "is the host the bottleneck" without
    grepping span args. Dumps predating the args render nothing."""
    steps = [s for s in engine_spans if s["name"] == "serve_step"
             and all(k in s["args"] for k in _HOST_PHASES)]
    if not steps:
        return
    parts = " ".join(
        f"{k[len('host_'):-len('_us')]}="
        f"{sum(s['args'][k] for s in steps) / 1e3:.3f}ms"
        for k in _HOST_PHASES)
    print(f"host phases over {len(steps)} steps: {parts}", file=out)


def render_request(dump, request, out=sys.stdout):
    """One request's digest + span timeline from a loaded dump."""
    tracing = _load_observability().tracing
    spans = [s for s in dump["spans"] if s["request"] == request]
    digest = tracing.request_summary(request, spans=dump["spans"])
    print(f"request {request}: {len(spans)} spans", file=out)
    for key in ("prompt_tokens", "generated_tokens", "queue_wait_s",
                "ttft_s", "tpot_s", "retired"):
        print(f"  {key}: {digest[key]}", file=out)
    # router lane: which replica served this request (and any crash
    # resubmission hops), from the EngineRouter's route/resubmit events
    hops = []
    for s in sorted((x for x in spans
                     if x["name"] in ("route", "resubmit")),
                    key=lambda x: x["ts_us"]):
        a = s["args"]
        if s["name"] == "route":
            hops.append(f"replica {a.get('replica')} "
                        f"[{a.get('policy')}]")
        else:
            hops.append(f"resubmit -> replica {a.get('replica')} "
                        f"({a.get('reason')})")
    if hops:
        print(f"  routing: {' ; '.join(hops)}", file=out)
    chunks = digest["prefill_chunks"]
    if chunks:
        granted = sum(c["granted"] or 0 for c in chunks)
        requested = sum(c["requested"] or 0 for c in chunks)
        print(f"  prefill_chunks: {len(chunks)} "
              f"(granted {granted}/{requested} requested)", file=out)
    stalls = digest["stalls"]
    if any(stalls.values()):
        print(f"  stalls: {_fmt_args(stalls)}", file=out)
    spec = digest["spec"]
    if spec["drafted"]:
        print(f"  spec: accepted {spec['accepted']}/{spec['drafted']} "
              f"({spec['accept_rate']:.0%}), {spec['rewinds']} rewinds, "
              f"{spec['blocks_freed']} blocks freed", file=out)
    if not spans:
        return digest
    t0 = min(s["ts_us"] for s in spans)
    print("  timeline (ms rel):", file=out)
    for s in sorted(spans, key=lambda s: s["ts_us"]):
        rel = (s["ts_us"] - t0) / 1e3
        dur = s["dur_us"] / 1e3
        extra = _fmt_args(s["args"]) if s["args"] else ""
        print(f"    {rel:10.3f} +{dur:8.3f}  {s['name']:<15} {extra}",
              file=out)
    return digest


def render_dump(dump, request=None, as_json=False, out=sys.stdout):
    tracing = _load_observability().tracing
    requests = dump["requests"] if request is None else [request]
    if as_json:
        digests = {str(r): tracing.request_summary(r, spans=dump["spans"])
                   for r in requests}
        json.dump({"reason": dump["reason"], "time": dump["time"],
                   "requests": digests}, out, indent=1)
        print(file=out)
        return
    print(f"flight dump: reason={dump['reason']} "
          f"window={dump['window_s']}s spans={len(dump['spans'])} "
          f"requests={dump['requests']}", file=out)
    if dump.get("context"):
        print(f"context: {_fmt_args(dump['context'])}", file=out)
    for r in requests:
        print(file=out)
        render_request(dump, r, out=out)
    engine = [s for s in dump["spans"] if s["request"] is None]
    if engine and request is None:
        names = {}
        for s in engine:
            names[s["name"]] = names.get(s["name"], 0) + 1
        print(f"\nengine lane: {_fmt_args(names)}", file=out)
        _render_host_phases(engine, out)


def main():
    ap = argparse.ArgumentParser(
        description="per-request timelines from a flight-recorder dump")
    ap.add_argument("dump", help="flight-recorder json "
                                 "(tracing.DUMP_SCHEMA)")
    ap.add_argument("--request", default=None,
                    help="only this request id (int ids are coerced)")
    ap.add_argument("--json", action="store_true",
                    help="emit the explain() digests as json")
    args = ap.parse_args()
    tracing = _load_observability().tracing
    try:
        dump = tracing.load_dump(args.dump)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"request_trace: cannot load {args.dump}: {e}",
              file=sys.stderr)
        return 1
    request = args.request
    if request is not None:
        try:
            request = int(request)
        except ValueError:
            pass                      # string request ids are legal
        if request not in dump["requests"]:
            print(f"request_trace: request {request!r} not in dump "
                  f"(has {dump['requests']})", file=sys.stderr)
            return 1
    render_dump(dump, request=request, as_json=args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
