#!/usr/bin/env python
"""Multi-replica router gate (ISSUE 19): dp serving as CI.

N independent ContinuousBatchingEngine replicas (one EngineStepper
thread each, identical weights) sit behind one EngineRouter, and the
gate drives the pool through every routing policy on a shared-prefix
workload (M prompt families x R nested resumes — the chat-traffic
shape prefix caching exists for):

* **token-exact under every policy** — round_robin, least_loaded and
  prefix_affinity all stream tokens BYTE-IDENTICAL to a single
  reference ``engine.generate()``; routing must never change results,
  only where they compute;
* **prefix_affinity strictly beats round_robin** — the committed
  per-policy routing tables and cache counters prove the perf claim:
  affinity maps strictly MORE cached-prefix tokens and prefills
  strictly FEWER sweep tokens than the rotation baseline (exact
  counts, not a benchmark);
* **crash/drain** — an injected ``step()`` fault on one replica fans
  the stepper's structured ``engine_error`` terminals: the mid-stream
  request forwards the failure (its KV died with the replica), the
  queued never-streamed request is transparently resubmitted to the
  survivor and finishes token-exact, later submits route only to
  survivors, and the pool's ``error`` stays None (/healthz keeps
  answering ok);
* **0 new compile buckets after per-replica warmup** — on the
  affinity pool, a third wave replaying the warm-path second wave
  compiles nothing new on either replica.

Determinism: head-of-family submits land as one held batch (no
terminal can fire between routing decisions), resumes go one at a
time (each sees the summaries its predecessors published from
terminal fanout), and the crash is driven by manual held steps — so
the routing tables, cache counters and the crashed stream's prefix
length are exact committed numbers, not wall-clock accidents.

Usage:
  python tools/serve_replica.py [--json OUT]
  python tools/serve_replica.py --check tools/serve_replica.json
"""
import argparse
import json
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.serve_replica/1"

DEFAULT_CONFIG = {
    "engine": {
        "seed": 0, "max_seq_len": 64, "num_blocks": 40, "block_size": 8,
        "max_batch": 4, "prefill_chunk": 8, "prefix_cache": True,
    },
    "pool": {"replicas": 2},
    "workload": {
        "seed": 0,
        # M families x R nested resumes: resume r of family m is the
        # family sequence's first prefix_len + r*resume_step + tail
        # tokens, so each resume extends the last — the +tail keeps
        # prompts off block alignment (the full-coverage COW edge is
        # chaos-gate territory, not routing's)
        "families": 3, "resumes": 3,
        "prefix_len": 16, "resume_step": 8, "tail": 3,
        "max_new_tokens": 4,
    },
    "crash": {
        # stream: short prompt (one chunk -> first token on the first
        # held step), long budget (cannot finish before the fault)
        "stream": {"prompt_len": 5, "max_new_tokens": 24},
        "bystander": {"prompt_len": 11, "max_new_tokens": 4},
        "victim": {"prompt_len": 19, "max_new_tokens": 4},
        "post": {"prompt_len": 7, "max_new_tokens": 4},
    },
}

POLICY_ORDER = ("round_robin", "least_loaded", "prefix_affinity")


class _Sub:
    """One request's event subscription: collects the fanout, flags
    the first token and the terminal for cross-thread waits."""

    def __init__(self):
        self.events = []
        self.first_token = threading.Event()
        self.done = threading.Event()
        self.end = None

    def __call__(self, ev):
        self.events.append(ev)
        if ev["type"] == "token":
            self.first_token.set()
        elif ev["type"] == "end":
            self.end = ev
            self.done.set()

    @property
    def tokens(self):
        return [t for e in self.events if e["type"] == "token"
                for t in e["tokens"]]


def _mk_request(prompt, n, rid):
    import numpy as np

    from paddle_tpu.incubate.nn import GenerationRequest

    return GenerationRequest(np.asarray(prompt, np.int32), n,
                             request_id=rid)


def _build_pool(config, policy):
    """N fresh replicas (same seed -> identical weights) behind one
    started EngineRouter."""
    import numpy as np

    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.serving import EngineRouter, EngineStepper
    from tools.serve_bench import _tiny_cpu_engine

    ecfg = config["engine"]
    steppers = []
    for slot in range(config["pool"]["replicas"]):
        rng = np.random.default_rng(ecfg["seed"])
        eng, _ = _tiny_cpu_engine(rng, max_seq_len=ecfg["max_seq_len"])
        cb = ContinuousBatchingEngine(
            eng, num_blocks=ecfg["num_blocks"],
            block_size=ecfg["block_size"], max_batch=ecfg["max_batch"],
            prefill_chunk=ecfg["prefill_chunk"],
            prefix_cache=ecfg["prefix_cache"])
        steppers.append(EngineStepper(cb, name=f"replica-{slot}"))
    return EngineRouter(steppers, policy=policy).start()


def _alloc_baseline(cb):
    a = cb.allocator
    return (a.num_used == 0 and not a._ref
            and a.num_free + a.num_pooled == a.num_blocks - a.reserved)


def _wait(sub, what, timeout=300.0):
    if not sub.done.wait(timeout):
        raise RuntimeError(f"timed out waiting for {what}")


def _run_wave(router, wl, prompts, rid_of):
    """One wave over the full workload. Family heads land as ONE held
    batch (no terminal can fire between their routing decisions — the
    in-flight counts the policies see are exactly the submit order);
    resumes go one at a time, each seeing the prefix summaries its
    predecessors published at terminal fanout. Returns {key: _Sub}."""
    subs = {}
    n = wl["max_new_tokens"]
    router.hold()
    futs = []
    for m in range(wl["families"]):
        sub = _Sub()
        subs[(m, 0)] = sub
        futs.append(router.submit(
            _mk_request(prompts[(m, 0)], n, rid_of(m, 0)),
            on_event=sub))
    router.release()
    for f in futs:
        f.result(60)
    for m in range(wl["families"]):
        _wait(subs[(m, 0)], f"head {rid_of(m, 0)}")
    for r in range(1, wl["resumes"]):
        for m in range(wl["families"]):
            sub = _Sub()
            subs[(m, r)] = sub
            router.submit(_mk_request(prompts[(m, r)], n, rid_of(m, r)),
                          on_event=sub).result(60)
            _wait(sub, f"resume {rid_of(m, r)}")
    return subs


def _wave_exact(subs, refs):
    return all(sub.end is not None and sub.end["status"] == "finished"
               and sub.tokens == refs[key]
               for key, sub in subs.items())


def _routes_of(tracer, wl, rid_of):
    out = {}
    for m in range(wl["families"]):
        for r in range(wl["resumes"]):
            rep = None
            for s in tracer.spans(request=rid_of(m, r)):
                if s["name"] == "route":
                    rep = s["args"].get("replica")
            out[f"f{m}r{r}"] = rep
    return out


def _policy_leg(config, policy, prompts, refs, tracer):
    """One policy, one fresh pool: wave 1 cold (the committed routing
    table + cache counters), and — affinity only — wave 2 to cover the
    warm-path shapes, declare_warm, wave 3 as the 0-new-buckets
    replay."""
    wl = config["workload"]
    bs = config["engine"]["block_size"]
    router = _build_pool(config, policy)
    try:
        nrep = router.num_replicas
        tracer.clear()
        subs = _run_wave(router, wl, prompts,
                         lambda m, r: f"{policy}.w1.f{m}r{r}")
        routes = _routes_of(tracer, wl,
                            lambda m, r: f"{policy}.w1.f{m}r{r}")
        exact = _wave_exact(subs, refs)
        stats = [router.steppers[i].call(
            lambda c: dict(c.cache_stats)).result(60)
            for i in range(nrep)]
        cached = sum(s["hit_blocks"] for s in stats) * bs
        total_prompt = sum(len(p) for p in prompts.values())
        leg = {
            "routes": routes,
            "cache_stats": stats,
            "cached_prefix_tokens": cached,
            "prefill_sweep_tokens": total_prompt - cached,
        }
        new_buckets = None
        if policy == "prefix_affinity":
            exact = exact and _wave_exact(
                _run_wave(router, wl, prompts,
                          lambda m, r: f"{policy}.w2.f{m}r{r}"), refs)
            warm = [router.steppers[i].call(
                lambda c: (c.declare_warm(),
                           set(c._seen_buckets))[1]).result(60)
                for i in range(nrep)]
            exact = exact and _wave_exact(
                _run_wave(router, wl, prompts,
                          lambda m, r: f"{policy}.w3.f{m}r{r}"), refs)
            new_buckets = sum(
                len(router.steppers[i].call(
                    lambda c: set(c._seen_buckets)).result(60) - warm[i])
                for i in range(nrep))
        leg["token_exact"] = exact
        leg["gauges_baseline"] = all(
            router.steppers[i].call(_alloc_baseline).result(60)
            for i in range(nrep))
        print(f"  {policy}: routes {routes}, cached "
              f"{leg['cached_prefix_tokens']} tok, sweeps "
              f"{leg['prefill_sweep_tokens']} tok, "
              f"token-exact={exact}")
        return leg, new_buckets
    finally:
        router.stop()


def _inject_fault(cb):
    def _boom():
        raise RuntimeError("injected replica fault")
    cb.step = _boom


def _crash_leg(config, crefs, cprompts, tracer):
    """Round-robin pool; replica 0 is held, fed a streaming request
    (manually stepped to its first token) and a queued victim, then
    its step() is swapped for a fault and released: the streamed
    request must forward the structured failure, the victim must be
    resubmitted to replica 1 and finish token-exact, and the pool must
    keep routing (error masked) on the survivor."""
    ccfg = config["crash"]
    router = _build_pool(config, "round_robin")
    try:
        tracer.clear()
        s0 = router.steppers[0]
        s0.hold()
        sub_a = _Sub()
        router.submit(_mk_request(cprompts["stream"],
                                  ccfg["stream"]["max_new_tokens"],
                                  "crash.stream"),
                      on_event=sub_a).result(60)      # rr -> replica 0
        steps_to_token = 0
        while not sub_a.first_token.is_set():
            s0.call(lambda c: c.step()).result(60)
            steps_to_token += 1
            if steps_to_token > 20:
                raise RuntimeError("stream never produced a token")
        sub_b = _Sub()
        router.submit(_mk_request(cprompts["bystander"],
                                  ccfg["bystander"]["max_new_tokens"],
                                  "crash.bystander"),
                      on_event=sub_b).result(60)      # rr -> replica 1
        _wait(sub_b, "bystander")
        sub_c = _Sub()
        router.submit(_mk_request(cprompts["victim"],
                                  ccfg["victim"]["max_new_tokens"],
                                  "crash.victim"),
                      on_event=sub_c).result(60)      # rr -> replica 0
        s0.call(_inject_fault).result(60)
        s0.release()                   # next step raises -> drain
        _wait(sub_a, "crashed stream terminal")
        _wait(sub_c, "resubmitted victim")
        sub_d = _Sub()
        router.submit(_mk_request(cprompts["post"],
                                  ccfg["post"]["max_new_tokens"],
                                  "crash.post"),
                      on_event=sub_d).result(60)      # survivors only
        _wait(sub_d, "post-crash submit")

        resubmit_target = route_post = None
        for s in tracer.spans(request="crash.victim"):
            if s["name"] == "resubmit":
                resubmit_target = s["args"].get("replica")
        for s in tracer.spans(request="crash.post"):
            if s["name"] == "route":
                route_post = s["args"].get("replica")
        ref_a = crefs["stream"]
        leg = {
            "steps_to_first_token": steps_to_token,
            "streamed_prefix_len": len(sub_a.tokens),
            "statuses": {k: (s.end["status"] if s.end else None)
                         for k, s in (("stream", sub_a),
                                      ("bystander", sub_b),
                                      ("victim", sub_c),
                                      ("post", sub_d))},
            "stream_reason": sub_a.end and sub_a.end["reason"],
            "resubmit_target": resubmit_target,
            "post_route": route_post,
            "live_after": router.live_replicas(),
        }
        inv = {
            "crash_stream_failed_structured": bool(
                sub_a.end and sub_a.end["status"] == "failed"
                and sub_a.end["reason"] == "engine_error"
                and len(sub_a.tokens) >= 1
                and sub_a.tokens == ref_a[:len(sub_a.tokens)]),
            "crash_victim_resubmitted_exact": bool(
                sub_c.end and sub_c.end["status"] == "finished"
                and sub_c.tokens == crefs["victim"]
                and resubmit_target == 1),
            "crash_bystander_exact": bool(
                sub_b.end and sub_b.end["status"] == "finished"
                and sub_b.tokens == crefs["bystander"]),
            "crash_post_routes_survivor": bool(
                sub_d.end and sub_d.end["status"] == "finished"
                and sub_d.tokens == crefs["post"]
                and route_post == 1
                and router.live_replicas() == [1]),
            "pool_error_masked": bool(
                router.error is None and s0.error is not None),
            "crash_survivor_gauges_baseline": bool(
                router.steppers[1].call(_alloc_baseline).result(60)),
        }
        print(f"  crash: stream failed after "
              f"{leg['streamed_prefix_len']} token(s), victim "
              f"resubmitted -> replica {resubmit_target}, post-crash "
              f"route -> replica {route_post}, live {leg['live_after']}")
        return leg, inv
    finally:
        router.stop()


def replica_leg(config=None):
    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.observability import tracing
    from tools.serve_bench import _tiny_cpu_engine

    config = config or DEFAULT_CONFIG
    on_tpu = init_platform() == "tpu"
    ecfg = config["engine"]
    wl = config["workload"]
    rng = np.random.default_rng(ecfg["seed"])
    eng_ref, V = _tiny_cpu_engine(rng, max_seq_len=ecfg["max_seq_len"])

    wrng = np.random.default_rng(wl["seed"])
    full = wl["prefix_len"] + (wl["resumes"] - 1) * wl["resume_step"] \
        + wl["tail"]
    seqs = [[int(t) for t in wrng.integers(1, V, full)]
            for _ in range(wl["families"])]
    prompts = {
        (m, r): seqs[m][:wl["prefix_len"] + r * wl["resume_step"]
                        + wl["tail"]]
        for m in range(wl["families"]) for r in range(wl["resumes"])}
    cprompts = {k: [int(t) for t in
                    wrng.integers(1, V, config["crash"][k]["prompt_len"])]
                for k in ("stream", "bystander", "victim", "post")}

    def _ref(p, n):
        return eng_ref.generate(np.asarray(p, np.int32)[None, :],
                                max_new_tokens=n)[0, :n].tolist()

    refs = {k: _ref(p, wl["max_new_tokens"]) for k, p in prompts.items()}
    crefs = {k: _ref(p, config["crash"][k]["max_new_tokens"])
             for k, p in cprompts.items()}

    tracer = tracing.get_tracer()
    print(f"replica leg: {config['pool']['replicas']} replicas, "
          f"{wl['families']} families x {wl['resumes']} resumes"
          + (" [interpret]" if not on_tpu else ""))
    routing = {}
    new_buckets = None
    for policy in POLICY_ORDER:
        leg, buckets = _policy_leg(config, policy, prompts, refs, tracer)
        routing[policy] = leg
        if buckets is not None:
            new_buckets = buckets
    crash, crash_inv = _crash_leg(config, crefs, cprompts, tracer)

    aff = routing["prefix_affinity"]
    rr = routing["round_robin"]
    out = {
        "schema": REPORT_SCHEMA,
        "interpret": not on_tpu,
        "config": config,
        "workload": {
            "prompt_lens": {f"f{m}r{r}": len(prompts[(m, r)])
                            for m in range(wl["families"])
                            for r in range(wl["resumes"])},
            "crash_prompt_lens": {k: len(p)
                                  for k, p in sorted(cprompts.items())},
            "max_new_tokens": wl["max_new_tokens"],
        },
        "ref_tokens": {f"f{m}r{r}": refs[(m, r)]
                       for m in range(wl["families"])
                       for r in range(wl["resumes"])},
        "routing": routing,
        "crash": crash,
        "new_buckets_after_warmup": new_buckets,
        "token_exact_all_policies": all(
            routing[p]["token_exact"] for p in POLICY_ORDER),
        "affinity_beats_round_robin": bool(
            aff["cached_prefix_tokens"] > rr["cached_prefix_tokens"]
            and aff["prefill_sweep_tokens"] < rr["prefill_sweep_tokens"]),
        "gauges_return_to_baseline": all(
            routing[p]["gauges_baseline"] for p in POLICY_ORDER),
    }
    out.update(crash_inv)
    print(f"replica leg: affinity cached {aff['cached_prefix_tokens']} "
          f"vs round_robin {rr['cached_prefix_tokens']} tok, sweeps "
          f"{aff['prefill_sweep_tokens']} vs "
          f"{rr['prefill_sweep_tokens']} tok, new buckets after warmup "
          f"{new_buckets}")
    return out


# deterministic keys gated against the committed baseline
REPLICA_KEYS = ("workload", "ref_tokens", "routing", "crash")

# invariants that must hold regardless of the baseline
REPLICA_INVARIANTS = (
    "token_exact_all_policies", "affinity_beats_round_robin",
    "crash_stream_failed_structured", "crash_victim_resubmitted_exact",
    "crash_bystander_exact", "crash_post_routes_survivor",
    "pool_error_masked", "crash_survivor_gauges_baseline",
    "gauges_return_to_baseline",
)


def check_replica(base):
    cur = replica_leg(config=base.get("config") or DEFAULT_CONFIG)
    bad = [k for k in REPLICA_KEYS if cur[k] != base.get(k)]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline "
              f"{base.get(k)!r}")
    for k in REPLICA_INVARIANTS:
        if cur[k] is not True:
            print(f"REGRESSION: {k} is {cur[k]!r}")
            bad.append(k)
    if cur["new_buckets_after_warmup"] != 0:
        print(f"REGRESSION: warm replay compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "per-replica warmup")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print("replica leg OK: every policy token-exact vs the single-"
          "engine reference, prefix_affinity strictly beats "
          "round_robin on cached-prefix/sweep tokens, crash drains to "
          "the survivor (queued resubmitted exact, streamed failed "
          "structured), 0 new buckets after per-replica warmup")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="multi-replica routing gate")
    ap.add_argument("--json", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate against a committed baseline "
                         "(tools/serve_replica.json)")
    args = ap.parse_args()

    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        if "replica" not in base:
            print(f"{args.check}: no 'replica' section to gate")
            return 1
        return check_replica(base["replica"])

    out = replica_leg()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"replica": out}, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    from paddle_tpu.observability import tracing as _tr
    sys.exit(_tr.run_with_abort_evidence(main))
