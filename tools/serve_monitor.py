#!/usr/bin/env python
"""Heavy-tail serving load monitor + SLO gate (ISSUE 8).

The production-front-door question the step-count benches cannot
answer: under a heavy-tail workload (Pareto prompt lengths, Poisson
arrivals — the shape real traffic has, not the fixed ragged batch),
with the SAMPLER running, do the WINDOWED p99s hold and does the SLO
engine stay quiet? This tool drives `ContinuousBatchingEngine` with an
attached `SLOMonitor` (observability/slo.py), renders a periodic text
dashboard from the windowed time series, writes a JSON report, and —
via ``--check tools/serve_slo.json`` — gates:

* **windowed p99 TTFT / TPOT** (delta-histogram quantiles over the
  monitored run, not process lifetime) under the declared objectives,
* **zero burn-rate breaches** across both evaluation windows,
* **zero new compile buckets** after the warmup run,
* **monitor neutrality**: the monitored and unmonitored runs must be
  token-exact with identical step counts (the PR 6 trace-leg contract,
  extended to the SLO engine),
* the host-deterministic workload accounting (steps, tokens, arrival
  schedule) against the committed baseline.

Workload generation is config-seeded (one `np.random.default_rng` per
leg) and arrivals live on the STEP clock, so every count gated here is
host-deterministic; wall-clock latencies are evaluated only against
the generous declared objectives (off-TPU they time the Pallas
interpreter, not the chip — same caveat as every serve_bench leg).

``--scrape URL`` flips the tool into a CROSS-PROCESS dashboard: it
polls a live serving gateway's ``/metrics`` (Prometheus text, parsed
with ``observability.parse_prometheus``) and ``/healthz`` instead of
the in-process registry, and renders the same one-line dashboard —
stdlib-only (the standalone observability load), so the sidecar runs
in a bare container next to any ``examples/serve_gateway.py``.

Repeat ``--scrape`` for a FLEET dashboard over N replicas: each
target's scrape converts through
``observability.snapshot_from_prometheus`` and the round merges with
``merge_snapshots`` (fleet_obs), so the rendered tokens/s is the
exact-summed fleet counter and the latency line shows REAL fleet
p50/p95/p99 (merged fixed-bucket histograms — never averages of
per-replica quantiles), plus a quorum ``/healthz`` rollup (majority of
targets healthy = fleet healthy) and a per-rank inflight/queue strip.

Usage:
  python tools/serve_monitor.py [--dashboard-every N] [--json OUT]
  python tools/serve_monitor.py --check tools/serve_slo.json
  python tools/serve_monitor.py --scrape http://127.0.0.1:8000 \
      [--scrape-interval S] [--scrape-count N]
  python tools/serve_monitor.py --scrape http://host-a:8000 \
      --scrape http://host-b:8000 --scrape http://host-c:8000
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.serve_monitor/1"

DEFAULT_CONFIG = {
    "workload": {
        # Pareto lengths: min + scale * pareto(alpha), clamped — alpha
        # near 1 is the heavy tail (most prompts short, a few near max)
        "seed": 0, "requests": 12, "pareto_alpha": 1.1,
        "prompt_min": 4, "prompt_scale": 6, "prompt_max": 40,
        "new_tokens_mean": 5, "new_tokens_min": 2, "new_tokens_max": 8,
        # Poisson arrivals on the step clock: exponential gaps, floored
        "arrival_mean_steps": 2.0,
    },
    "engine": {
        "seed": 0, "max_seq_len": 64, "num_blocks": 40, "block_size": 8,
        "max_batch": 4, "prefill_chunk": 8, "token_budget": 16,
        # the SAMPLER runs: temperature > 0 exercises the fused
        # sampling path (step counts stay host-deterministic — the
        # schedule never depends on token VALUES)
        "temperature": 0.8, "top_p": 0.95,
    },
    "slo": {
        "cadence_s": 0.05,
        "windows": [
            {"name": "fast", "window_s": 2.0, "burn_threshold": 10.0},
            {"name": "slow", "window_s": 15.0, "burn_threshold": 2.0},
        ],
        # generous off-TPU bounds: the MECHANISM gates (breach counting,
        # window math, neutrality); the absolute numbers are interpret-
        # mode ceilings, not speed claims
        "objectives": [
            {"name": "ttft_p99", "kind": "quantile",
             "metric": "serve_ttft_seconds", "q": 0.99, "max": 60.0},
            {"name": "tpot_p99", "kind": "quantile",
             "metric": "serve_time_per_output_token_seconds",
             "q": 0.99, "max": 20.0},
            {"name": "queue_wait_p95", "kind": "quantile",
             "metric": "serve_queue_wait_seconds", "q": 0.95,
             "max": 120.0},
            {"name": "kv_alloc_failure_ratio", "kind": "ratio",
             "num": "kv_alloc_failures_total",
             "den": "serve_tokens_total", "max": 0.001},
        ],
    },
}


def build_workload(cfg, vocab):
    """Config-seeded heavy-tail request set: (prompt ids, new_tokens,
    arrival step) per request — every number a pure function of the
    seed, so the committed baseline can gate the schedule."""
    import numpy as np

    rng = np.random.default_rng(cfg["seed"])
    n = cfg["requests"]
    lens = np.clip(
        (cfg["prompt_min"]
         + cfg["prompt_scale"] * rng.pareto(cfg["pareto_alpha"], n))
        .astype(np.int64), cfg["prompt_min"], cfg["prompt_max"])
    new = np.clip(rng.poisson(cfg["new_tokens_mean"], n),
                  cfg["new_tokens_min"], cfg["new_tokens_max"])
    gaps = rng.exponential(cfg["arrival_mean_steps"], n)
    arrivals = np.floor(np.cumsum(gaps) - gaps[0]).astype(np.int64)
    prompts = [rng.integers(1, vocab, int(p)).astype(np.int32)
               for p in lens]
    return {"prompts": prompts, "new_tokens": [int(x) for x in new],
            "arrival_steps": [int(a) for a in arrivals],
            "prompt_lens": [int(x) for x in lens]}


def _drive(cb, workload, tag, max_ticks=10000):
    """Submit per the arrival schedule (step clock) and step to
    completion; returns outputs in request order + engine accounting."""
    from paddle_tpu.incubate.nn import GenerationRequest

    reqs = [GenerationRequest(p.copy(), n, request_id=f"{tag}{j}")
            for j, (p, n) in enumerate(zip(workload["prompts"],
                                           workload["new_tokens"]))]
    arrivals = workload["arrival_steps"]
    i, tick = 0, 0
    while i < len(reqs) or cb.queue or cb.num_active:
        while i < len(reqs) and arrivals[i] <= tick:
            cb.submit(reqs[i])
            i += 1
        cb.step()
        tick += 1
        if tick > max_ticks:
            raise RuntimeError(f"serve_monitor: {tag} run did not "
                               f"converge within {max_ticks} ticks")
    cb._retire()                    # flush the last step's finishers
    return {"outputs": [cb.finished[r.request_id] for r in reqs],
            "steps": cb._step_count, "ticks": tick,
            "buckets": set(cb._seen_buckets)}


def _pcts(ts, metric, window_s, now):
    out = {}
    for q in (0.5, 0.95, 0.99):
        v = ts.quantile(metric, q, window_s, now=now)
        out[f"p{int(q * 100)}"] = None if v is None else round(v * 1e3, 3)
    return out


def _ahead_share(by_mode):
    """Share of the compiled steps dispatched while the step before them
    was still in flight (`serve_steps_dispatched_total{mode}`): how often
    the scheduler's look-ahead engaged."""
    total = sum(by_mode.values())
    return "-" if not total \
        else f"{100 * by_mode.get('ahead', 0) / total:.0f}%"


def render_dashboard(monitor, registry, tick, out=sys.stdout):
    """One text-dashboard line + per-objective burn rates from the
    monitor's windowed rings (what a production loop would push to a
    terminal or a status page)."""
    import time as _time

    ts = monitor.timeseries
    now = _time.monotonic()
    fast = monitor.engine.windows[0]["window_s"]

    def g(name):
        s = ts.gauge_stats(name, fast, now=now)
        return "-" if s is None else f"{s['last']:g}"

    ttft = ts.quantile("serve_ttft_seconds", 0.99, fast, now=now)
    tpot = ts.quantile("serve_time_per_output_token_seconds", 0.99,
                       fast, now=now)
    rate = ts.rate("serve_tokens_total", fast, now=now)
    drops = registry.timeline_stats()["dropped"]
    modes = registry.snapshot().get(
        "serve_steps_dispatched_total", {}).get("children", {})
    print(f"[monitor step {tick:4d}] inflight {g('serve_inflight_requests')}"
          f" queue {g('serve_queue_depth')}"
          f" | kv free {g('kv_blocks_free')}"
          f" | ttft p99 {'-' if ttft is None else f'{ttft * 1e3:.0f}ms'}"
          f" tpot p99 {'-' if tpot is None else f'{tpot * 1e3:.0f}ms'}"
          f" | tok/s {'-' if rate is None else f'{rate:.1f}'}"
          f" | ahead {_ahead_share({m: c['value'] for m, c in modes.items()})}"
          f" | breaches {monitor.breaches_total}"
          + (f" | timeline drops {drops}" if drops else ""), file=out)
    rep = monitor.last_report
    if rep and rep["breaches"]:
        for o in rep["objectives"]:
            if not o["breached"]:
                continue
            for wname, ev in o["windows"].items():
                if ev and ev["breached"]:
                    print(f"  BREACH {o['name']} [{wname}]: burn "
                          f"{ev['burn_rate']:.1f}x "
                          f"(bad {ev['bad_fraction']:.2%} of "
                          f"{ev['count']})", file=out)


def _fam_sum(fams, name):
    """Sum of a family's non-histogram samples, or None when absent."""
    fam = fams.get(name)
    if not fam:
        return None
    vals = [v for n, _, v in fam["samples"] if n == name]
    return sum(vals) if vals else None


def _fam_last(fams, name):
    fam = fams.get(name)
    if not fam:
        return None
    for n, _, v in fam["samples"]:
        if n == name:
            return v
    return None


def _fam_per_label(fams, name, label):
    """{label value: sample value} for a labeled gauge family — the
    per-device view the mesh dashboard renders (empty when the scraped
    engine never exported the family, i.e. single-chip)."""
    fam = fams.get(name)
    if not fam:
        return {}
    out = {}
    for n, labels, v in fam["samples"]:
        if n == name and label in (labels or {}):
            out[labels[label]] = v
    return out


def replica_strip(fams):
    """' | replicas N [0:a 1:b]' from the router's per-replica
    inflight gauge — empty for a single-engine gateway (the family
    only exists when an EngineRouter fronts a pool)."""
    repl = _fam_per_label(fams, "router_replica_inflight", "replica")
    if not repl:
        return ""
    live = _fam_last(fams, "router_replicas_live")
    cells = " ".join(
        f"{r}:{v:g}" for r, v in sorted(repl.items(),
                                        key=lambda kv: int(kv[0])))
    n = int(live) if live is not None else len(repl)
    return f" | replicas {n}/{len(repl)} [{cells}]"


def scrape_leg(url, interval_s=2.0, count=0, out=sys.stdout):
    """Poll a live gateway's /metrics + /healthz and render the
    dashboard cross-process. `count` 0 = forever. Returns 0 once the
    poll budget is spent, 1 if every poll failed."""
    import time
    import urllib.error
    import urllib.request

    from tools.metrics_snapshot import _load_observability

    obs = _load_observability()
    base = url.rstrip("/")
    if base.endswith("/metrics"):
        base = base[: -len("/metrics")]
    prev_tokens = prev_t = None
    polls = ok_polls = 0
    while count == 0 or polls < count:
        if polls:
            time.sleep(interval_s)
        polls += 1
        try:
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=5) as r:
                fams = obs.parse_prometheus(r.read().decode())
        except (OSError, ValueError) as e:
            print(f"[scrape {polls}] {base}/metrics unreachable: {e}",
                  file=out)
            continue
        ok_polls += 1
        try:
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=5) as r:
                health = f"ok({r.status})"
        except urllib.error.HTTPError as e:
            health = f"degraded({e.code})"
        except OSError:
            health = "unreachable"
        now = time.monotonic()
        tokens = _fam_sum(fams, "serve_tokens_total")
        rate = None
        if tokens is not None and prev_tokens is not None \
                and now > prev_t:
            rate = (tokens - prev_tokens) / (now - prev_t)
        prev_tokens, prev_t = tokens, now

        def g(name):
            v = _fam_last(fams, name)
            return "-" if v is None else f"{v:g}"

        breaches = _fam_sum(fams, "slo_breaches_total")
        ahead = _ahead_share(_fam_per_label(
            fams, "serve_steps_dispatched_total", "mode"))
        # mesh-aware view: a TP engine exports per-device KV/HBM
        # gauges — render every device's shard, not a silent device-0
        # aggregate (single-chip gateways simply lack the family)
        dev_kv = _fam_per_label(fams, "kv_device_bytes_used", "device")
        tp_w = _fam_last(fams, "serve_tp_degree")
        mesh = ""
        if dev_kv:
            cells = " ".join(
                f"{d}:{int(v) // 1024}K"
                for d, v in sorted(dev_kv.items(),
                                   key=lambda kv: int(kv[0])))
            mesh = (f" | tp {int(tp_w) if tp_w else len(dev_kv)}"
                    f" kv/dev [{cells}]")
        print(f"[scrape {polls:3d}] health {health}"
              f" | inflight {g('serve_inflight_requests')}"
              f" queue {g('serve_queue_depth')}"
              f" | kv free {g('kv_blocks_free')}{mesh}"
              f"{replica_strip(fams)}"
              f" | conns {g('gateway_live_connections')}"
              f" streams {g('gateway_live_streams')}"
              f" sse-pending {g('gateway_sse_pending_events')}"
              f" | tokens {int(tokens) if tokens is not None else '-'}"
              f" ({'-' if rate is None else f'{rate:.1f}/s'})"
              f" | ahead {ahead}"
              f" | breaches {int(breaches) if breaches is not None else 0}",
              file=out)
    return 0 if ok_polls else 1


def _merged_counter(view, name):
    fam = view["metrics"].get(name)
    if not fam or fam.get("kind") != "counter":
        return None
    vals = [c["value"] for c in fam["children"].values()]
    return sum(vals) if vals else None


def _rank_gauge_strip(view, name):
    """'r0:3 r1:5 ...' from a merged gauge's appended rank label."""
    fam = view["metrics"].get(name)
    if not fam or fam.get("kind") != "gauge":
        return ""
    cells = {}
    for ckey, child in fam["children"].items():
        rank = ckey.rsplit(",", 1)[-1] if ckey else ckey
        cells[rank] = cells.get(rank, 0.0) + child["value"]
    return " ".join(f"r{r}:{v:g}" for r, v in
                    sorted(cells.items(), key=lambda kv: kv[0]))


def scrape_fleet(urls, interval_s=2.0, count=0, out=sys.stdout):
    """Poll N live gateways and render the AGGREGATED dashboard: each
    round's scrapes convert through snapshot_from_prometheus and merge
    with merge_snapshots, so tokens/s is the exact fleet counter sum,
    the latency cells are real merged-histogram quantiles, and health
    is a quorum rollup over the targets' /healthz answers. A partially
    reachable fleet still renders (the view covers the ranks that
    answered); a round where NO target answers counts as failed."""
    import time
    import urllib.error
    import urllib.request

    from tools.metrics_snapshot import _load_observability

    obs = _load_observability()
    bases = []
    for u in urls:
        base = u.rstrip("/")
        if base.endswith("/metrics"):
            base = base[: -len("/metrics")]
        bases.append(base)
    world = len(bases)
    quorum = world // 2 + 1
    prev_tokens = prev_t = None
    polls = ok_polls = 0
    while count == 0 or polls < count:
        if polls:
            time.sleep(interval_s)
        polls += 1
        snaps, health = {}, {}
        for rank, base in enumerate(bases):
            try:
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=5) as r:
                    snaps[rank] = {
                        "rank": rank, "world_size": world,
                        "metrics": obs.snapshot_from_prometheus(
                            r.read().decode())}
            except (OSError, ValueError) as e:
                health[rank] = "unreachable"
                print(f"[fleet {polls}] r{rank} {base}/metrics "
                      f"unreachable: {e}", file=out)
                continue
            try:
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=5) as r:
                    health[rank] = "ok"
            except urllib.error.HTTPError:
                health[rank] = "degraded"
            except OSError:
                health[rank] = "unreachable"
        if not snaps:
            continue
        ok_polls += 1
        view = obs.merge_snapshots(snaps)
        n_ok = sum(1 for h in health.values() if h == "ok")
        rollup = "ok" if n_ok >= quorum else \
            ("degraded" if n_ok else "down")
        now = time.monotonic()
        tokens = _merged_counter(view, "serve_tokens_total")
        rate = None
        if tokens is not None and prev_tokens is not None \
                and now > prev_t:
            rate = (tokens - prev_tokens) / (now - prev_t)
        prev_tokens, prev_t = tokens, now

        def pcts(name):
            cells = []
            for q in (0.5, 0.95, 0.99):
                try:
                    v = obs.merged_quantile(view, name, q)
                except (KeyError, ValueError):
                    v = None
                cells.append("-" if v is None else f"{v * 1e3:.0f}")
            return "/".join(cells)

        breaches = _merged_counter(view, "slo_breaches_total")
        print(f"[fleet {polls:3d}] quorum {rollup} ({n_ok}/{world} ok,"
              f" {len(snaps)} scraped)"
              f" | ttft p50/95/99 {pcts('serve_ttft_seconds')}ms"
              f" tpot {pcts('serve_tpot_seconds')}ms"
              f" | inflight [{_rank_gauge_strip(view, 'serve_inflight_requests')}]"
              f" queue [{_rank_gauge_strip(view, 'serve_queue_depth')}]"
              f" | tokens {int(tokens) if tokens is not None else '-'}"
              f" ({'-' if rate is None else f'{rate:.1f}/s'})"
              f" | breaches {int(breaches) if breaches is not None else 0}",
              file=out)
    return 0 if ok_polls else 1


def monitor_leg(config=None, dashboard_every=0):
    """The full leg: warmup run -> monitored run (SLO engine attached)
    -> unmonitored run; neutrality + bucket accounting + windowed
    percentiles + the final SLO report."""
    import time as _time

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from tools.serve_bench import _tiny_cpu_engine

    import numpy as np

    config = config or DEFAULT_CONFIG
    on_tpu = init_platform() == "tpu"
    ecfg = config["engine"]
    rng = np.random.default_rng(ecfg["seed"])
    eng, V = _tiny_cpu_engine(rng, max_seq_len=ecfg["max_seq_len"])
    workload = build_workload(config["workload"], V)

    def make_cb(monitor=None):
        return ContinuousBatchingEngine(
            eng, num_blocks=ecfg["num_blocks"],
            block_size=ecfg["block_size"], max_batch=ecfg["max_batch"],
            prefill_chunk=ecfg["prefill_chunk"],
            token_budget=ecfg["token_budget"],
            temperature=ecfg["temperature"], top_p=ecfg["top_p"],
            monitor=monitor)

    warm = _drive(make_cb(), workload, "mw")

    monitor = obs.SLOMonitor.from_config(config["slo"])
    reg = obs.get_registry()
    t0 = _time.monotonic()
    if dashboard_every:
        # wrap the monitor's tick to interleave dashboard rendering the
        # way a server's status loop would
        cb_mon = make_cb(monitor)
        orig_step, ticks = cb_mon.step, [0]

        def step_with_dash():
            r = orig_step()
            ticks[0] += 1
            if ticks[0] % dashboard_every == 0:
                render_dashboard(monitor, reg, ticks[0])
            return r

        cb_mon.step = step_with_dash
        monitored = _drive(cb_mon, workload, "mm")
    else:
        monitored = _drive(make_cb(monitor), workload, "mm")
    elapsed = _time.monotonic() - t0
    final = monitor.force()         # end-of-run sample + evaluation

    # windowed percentiles NOW, while `now` still sits at the monitored
    # run's end: the plain leg below takes about as long as the
    # monitored one, and a later `now` would drift the window
    # [now - W, now] past the newest sample — the p99 gate would read
    # an empty window ("no data") instead of the run it claims to gate
    now = _time.monotonic()
    full_window = elapsed + 2 * monitor.cadence_s + 1.0
    ts = monitor.timeseries
    windowed = {
        "window_s": round(full_window, 3),
        "ttft_ms": _pcts(ts, "serve_ttft_seconds", full_window, now),
        "tpot_ms": _pcts(ts, "serve_time_per_output_token_seconds",
                         full_window, now),
        "queue_wait_ms": _pcts(ts, "serve_queue_wait_seconds",
                               full_window, now),
    }

    plain = _drive(make_cb(), workload, "mp")

    out = {
        "schema": REPORT_SCHEMA,
        "interpret": not on_tpu,
        "config": {k: config[k] for k in ("workload", "engine", "slo")},
        "workload": {
            "requests": len(workload["prompts"]),
            "prompt_lens": workload["prompt_lens"],
            "new_tokens": workload["new_tokens"],
            "arrival_steps": workload["arrival_steps"],
            "total_prompt_tokens": sum(workload["prompt_lens"]),
            "total_new_tokens": sum(workload["new_tokens"]),
        },
        "steps_warmup": warm["steps"],
        "steps_monitored": monitored["steps"],
        "steps_plain": plain["steps"],
        "tokens_generated": sum(len(o) for o in monitored["outputs"]),
        "token_exact_monitor_on_off":
            monitored["outputs"] == plain["outputs"],
        "new_buckets_after_warmup": len(
            (monitored["buckets"] | plain["buckets"]) - warm["buckets"]),
        "monitor": {
            "ticks": monitored["ticks"] + 1,    # + the final force()
            "evaluations": monitor.engine.evaluations,
            "samples": ts.samples_taken,
            "cadence_s": monitor.cadence_s,
        },
        "windowed": windowed,
        "breaches": monitor.breaches_total,
        "breach_counts": {f"{o}/{w}": n for (o, w), n
                          in monitor.engine.breach_counts.items()},
        # json_safe: an infinite burn rate (zero-budget ratio breach)
        # must not land as a bare `Infinity` literal in the report file
        "slo_report": obs.json_safe(final),
        "timeline_dropped": reg.timeline_stats()["dropped"],
    }
    fr = obs.get_flight_recorder()
    out["flight"] = {"armed": fr.armed, "retained": len(fr.retained()),
                     "dumps_this_process": len(fr.dumps)}
    print(f"monitor leg: {out['steps_monitored']} steps monitored vs "
          f"{out['steps_plain']} plain ({out['tokens_generated']} tokens,"
          f" {out['monitor']['evaluations']} SLO evaluations), "
          f"{out['breaches']} breaches, "
          f"{out['new_buckets_after_warmup']} new buckets after warmup; "
          f"windowed ttft p99 {out['windowed']['ttft_ms']['p99']} ms, "
          f"tpot p99 {out['windowed']['tpot_ms']['p99']} ms"
          + (" [interpret: latencies time the interpreter, not the "
             "chip]" if not on_tpu else ""))
    return out


# host-deterministic keys: must match the committed baseline exactly
MONITOR_KEYS = ("workload", "steps_warmup", "steps_monitored",
                "steps_plain", "tokens_generated",
                "token_exact_monitor_on_off", "new_buckets_after_warmup",
                "breaches")


def _objective_max(config, metric):
    for o in config["slo"]["objectives"]:
        if o.get("metric") == metric:
            return o["max"]
    return None


def check_monitor(base):
    """CI gate: deterministic accounting against the committed
    baseline, monitor neutrality, zero breaches, zero new buckets, and
    windowed p99 TTFT/TPOT under the declared objectives."""
    cur = monitor_leg(config=base.get("config") or DEFAULT_CONFIG)
    bad = [k for k in MONITOR_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if not cur["token_exact_monitor_on_off"]:
        print("REGRESSION: attaching the SLO monitor changed generated "
              "tokens")
        bad.append("token_exact_monitor_on_off")
    if cur["steps_monitored"] != cur["steps_plain"]:
        print(f"REGRESSION: monitoring changed the step count "
              f"({cur['steps_monitored']} vs {cur['steps_plain']})")
        bad.append("steps_monitored")
    if cur["new_buckets_after_warmup"] != 0:
        print(f"REGRESSION: the monitored run compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    if cur["breaches"] != 0:
        print(f"REGRESSION: {cur['breaches']} SLO burn-rate breaches on "
              f"the healthy heavy-tail workload: {cur['breach_counts']}")
        bad.append("breaches")
    cfg = base.get("config") or DEFAULT_CONFIG
    for label, metric in (("ttft_ms", "serve_ttft_seconds"),
                          ("tpot_ms",
                           "serve_time_per_output_token_seconds")):
        p99 = cur["windowed"][label]["p99"]
        limit = _objective_max(cfg, metric)
        if p99 is None:
            print(f"REGRESSION: windowed {label} p99 has no data")
            bad.append(label)
        elif limit is not None and p99 / 1e3 >= limit:
            print(f"REGRESSION: windowed {label} p99 {p99} ms breaches "
                  f"the declared objective ({limit * 1e3:g} ms)")
            bad.append(label)
    # the report embedded in the run must satisfy its own schema
    from paddle_tpu.observability import validate_report
    try:
        validate_report(cur["slo_report"])
    except ValueError as e:
        print(f"REGRESSION: SLO report schema violation: {e}")
        bad.append("slo_report")
    if bad:
        return 1
    print(f"monitor leg OK: {cur['steps_monitored']} steps (monitor on "
          f"== off), token-exact, 0 breaches / "
          f"{cur['monitor']['evaluations']} evaluations, 0 new buckets, "
          f"windowed p99 under objectives")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="heavy-tail serving load + windowed SLO monitoring")
    ap.add_argument("--json", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate against a committed baseline "
                         "(tools/serve_slo.json)")
    ap.add_argument("--dashboard-every", type=int, default=10,
                    help="render the text dashboard every N engine "
                         "steps (0 disables)")
    ap.add_argument("--no-flight-recorder", action="store_true",
                    help="do not arm the flight recorder (armed by "
                         "default with bounded retention — the "
                         "server-entrypoint policy)")
    ap.add_argument("--scrape", metavar="URL", action="append",
                    default=None,
                    help="poll a live gateway's /metrics + /healthz "
                         "instead of driving an in-process engine "
                         "(cross-process dashboard; stdlib-only). "
                         "Repeat for a FLEET: N targets merge into one "
                         "aggregated dashboard with real fleet "
                         "quantiles and a quorum /healthz rollup")
    ap.add_argument("--scrape-interval", type=float, default=2.0,
                    help="seconds between scrape polls")
    ap.add_argument("--scrape-count", type=int, default=0,
                    help="number of polls (0 = forever)")
    args = ap.parse_args()

    if args.scrape:
        # a sidecar scraper neither serves nor dumps: no engine, no
        # flight recorder, no jax
        if len(args.scrape) > 1:
            return scrape_fleet(args.scrape, args.scrape_interval,
                                args.scrape_count)
        return scrape_leg(args.scrape[0], args.scrape_interval,
                          args.scrape_count)

    from paddle_tpu.observability import tracing
    if not args.no_flight_recorder:
        fr = tracing.arm_default()
        print(f"flight recorder armed: {fr._dir} "
              f"(max_dumps={fr.max_dumps}, max_bytes={fr.max_bytes})")

    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        if "monitor" not in base:
            print(f"{args.check}: no 'monitor' section to gate")
            return 1
        return check_monitor(base["monitor"])

    out = monitor_leg(dashboard_every=args.dashboard_every)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    # operator abort mid-run writes the operator_abort flight dump
    # (span window + full metrics snapshot) before exiting — a monitor
    # killed mid-incident must not take its evidence along
    from paddle_tpu.observability import tracing
    sys.exit(tracing.run_with_abort_evidence(main))
