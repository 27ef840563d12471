#!/usr/bin/env python
"""Fault-injection gate for the serving stack (ISSUE 11).

The resilience claim — "the engine degrades instead of crashing" — run
as CI: a mixed-priority workload is driven through a deliberately tight
KV pool while the deterministic fault harness
(paddle_tpu/testing/faults.py) injects alloc outages, dispatch stalls,
dump-write OSErrors, and mid-stream cancellations, and a step-clock
pressure stub flips the admission gate into shedding. The gate then
asserts the whole contract at once:

* **no unhandled exception** — the run completes; `kv_alloc_failure`
  is a per-request terminal status, not a crash;
* **survivors are token-exact** vs an undisturbed ample-pool reference
  run (greedy decoding: a request's tokens depend only on its own KV,
  so no amount of preemption/cancellation around it may change them);
* **preempted-and-resumed requests are token-exact** — a victim that
  lost its KV mid-generation re-prefills (mostly a block-table copy
  with the prefix cache on) and finishes with exactly the tokens it
  would have produced;
* **cancelled/deadlined requests hold an exact PREFIX** of their
  reference generation;
* **KV/refcount gauges return to baseline** after every pass: zero
  physical blocks in use, an empty refcount table, free + pooled
  covering the whole pool;
* **zero new compile buckets after warmup** — two chaos passes (cold +
  prefix-pool-warm) warm the bucket set, `declare_warm()`, and a third
  identical pass must add none AND replay the second pass's statuses
  and outputs exactly (the fault schedule is deterministic, so any
  drift is a real scheduler nondeterminism bug).

Everything gated here is host-deterministic: faults are scheduled on
step/alloc-call indices, deadlines count steps, pressure windows count
steps, and arrivals live on the step clock. Wall-clock only shows up
in latencies, which this gate does not compare.

Usage:
  python tools/serve_chaos.py [--json OUT]
  python tools/serve_chaos.py --check tools/serve_chaos.json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.serve_chaos/1"

DEFAULT_CONFIG = {
    "engine": {
        "seed": 0, "max_seq_len": 64, "num_blocks": 9, "block_size": 8,
        "max_batch": 3, "prefill_chunk": 8, "spec_k": 2,
        "prefix_cache": True, "shed_priority_min": 1,
    },
    # the undisturbed reference: same scheduling config, ample pool, no
    # faults, no pressure — per-request ground truth under greedy
    "truth_num_blocks": 40,
    "workload": {
        "seed": 0, "requests": 8,
        # mixed classes: 0 = front-door traffic, 1 = batch, 2 = best
        # effort; the late priority-0 arrivals land on a full pool and
        # must preempt their way in
        "priorities":     [2, 1, 0, 2, 1, 0, 2, 0],
        "arrival_steps":  [0, 0, 2, 3, 5, 8, 9, 11],
        "prompt_min": 4, "prompt_max": 20,
        "new_tokens_min": 3, "new_tokens_max": 8,
        # request index -> step deadline (counted from submit)
        "deadline_steps": {"3": 4},
    },
    "faults": {
        # sustained outage at step 6 (every alloc that step fails:
        # preemption rescues what it can, the rest degrade
        # per-request), plus one transient blip at step 14
        "alloc_fail_steps": [6],
        "alloc_fail_calls": [],
        "slow_steps": [4, 10], "slow_delay_s": 0.004,
        # cancel request 1 mid-flight (decode phase by then) and
        # request 6 early (prefill phase)
        "cancel": [{"request": 1, "step": 12}, {"request": 6, "step": 11}],
        "dump_failures": 1,
    },
    # step-clock window where the pressure stub reports an SLO breach:
    # the admission gate sheds the lowest queued class
    "pressure_steps": [[9, 12]],
}


class StepPressureMonitor:
    """Deterministic stand-in for the SLO monitor: reports a burn-rate
    breach while the engine's step count sits inside a configured
    window. The admission gate only reads ``last_report['breaches']``
    and calls ``tick()`` — the same surface SLOMonitor exposes — so the
    shed path under test is exactly the production path, with the
    wall-clock replaced by the step clock."""

    def __init__(self, windows):
        self.windows = [(int(a), int(b)) for a, b in windows]
        self.steps = 0

    @property
    def last_report(self):
        s = self.steps
        hot = any(a <= s < b for a, b in self.windows)
        return {"breaches": 1 if hot else 0}

    def tick(self):
        self.steps += 1


def build_workload(cfg, vocab):
    """Config-seeded request set: prompts, new-token counts, arrivals,
    priorities, deadlines — every number a pure function of the seed."""
    import numpy as np

    rng = np.random.default_rng(cfg["seed"])
    n = cfg["requests"]
    lens = rng.integers(cfg["prompt_min"], cfg["prompt_max"] + 1, n)
    new = rng.integers(cfg["new_tokens_min"], cfg["new_tokens_max"] + 1, n)
    prompts = [rng.integers(1, vocab, int(p)).astype(np.int32)
               for p in lens]
    return {"prompts": prompts,
            "prompt_lens": [int(x) for x in lens],
            "new_tokens": [int(x) for x in new],
            "arrival_steps": list(cfg["arrival_steps"]),
            "priorities": list(cfg["priorities"]),
            "deadline_steps": {int(k): int(v) for k, v
                               in cfg.get("deadline_steps", {}).items()}}


def _build_injector(fcfg, workload, tag):
    from paddle_tpu.testing import FaultInjector

    inj = FaultInjector()
    inj.fail_alloc(calls=fcfg.get("alloc_fail_calls", ()),
                   steps=fcfg.get("alloc_fail_steps", ()))
    if fcfg.get("slow_steps"):
        inj.slow_step(fcfg["slow_steps"], fcfg.get("slow_delay_s", 0.005))
    for c in fcfg.get("cancel", ()):
        inj.cancel_request(f"{tag}{c['request']}", c["step"])
    if fcfg.get("dump_failures"):
        inj.fail_dump_writes(fcfg["dump_failures"])
    return inj


def _drive(cb, workload, tag, faults=None, max_ticks=3000):
    """Submit on the arrival schedule and step to completion. Returns
    per-request results (index order) + engine accounting. With
    `faults`, the injector is attached for the whole drive."""
    import contextlib

    from paddle_tpu.incubate.nn import GenerationRequest

    reqs = [GenerationRequest(
        p.copy(), n, request_id=f"{tag}{j}",
        priority=workload["priorities"][j],
        deadline_steps=workload["deadline_steps"].get(j))
        for j, (p, n) in enumerate(zip(workload["prompts"],
                                       workload["new_tokens"]))]
    arrivals = workload["arrival_steps"]
    i, tick = 0, 0
    step0 = cb._step_count     # passes reuse one engine: report deltas
    ctx = faults.attach(cb) if faults is not None \
        else contextlib.nullcontext()
    with ctx:
        while i < len(reqs) or cb.queue or cb.num_active:
            while i < len(reqs) and arrivals[i] <= tick:
                cb.submit(reqs[i])
                i += 1
            cb.step()
            tick += 1
            if tick > max_ticks:
                raise RuntimeError(f"serve_chaos: {tag} run did not "
                                   f"converge within {max_ticks} ticks")
    cb._retire()
    results = [cb.finished[r.request_id] for r in reqs]
    alloc = cb.allocator
    return {
        "results": results,
        "statuses": [r.status for r in results],
        "tokens": [list(r) for r in results],
        "preemptions": [r.preemptions for r in results],
        "steps": cb._step_count - step0, "ticks": tick,
        "buckets": set(cb._seen_buckets),
        "injected": dict(faults.injected) if faults is not None else {},
        # the baseline the gate requires every pass to return to: no
        # physical block held, refcount table empty, free + pooled
        # covering the whole allocatable pool
        "gauges_baseline": (alloc.num_used == 0 and not alloc._ref
                            and alloc.num_free + alloc.num_pooled
                            == alloc.num_blocks - alloc.reserved),
    }


def chaos_leg(config=None, flight_dir=None):
    """truth run -> chaos pass 1 (cold) -> pass 2 (pool-warm) ->
    declare_warm -> pass 3 (the steady-state gate)."""
    import tempfile

    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.observability import tracing
    from tools.serve_bench import _tiny_cpu_engine

    config = config or DEFAULT_CONFIG
    on_tpu = init_platform() == "tpu"
    ecfg = config["engine"]
    rng = np.random.default_rng(ecfg["seed"])
    eng, V = _tiny_cpu_engine(rng, max_seq_len=ecfg["max_seq_len"])
    workload = build_workload(config["workload"], V)
    fr = tracing.get_flight_recorder()
    fr.arm(flight_dir or tempfile.mkdtemp(prefix="serve_chaos_"))

    def make_cb(num_blocks, pressure):
        return ContinuousBatchingEngine(
            eng, num_blocks=num_blocks, block_size=ecfg["block_size"],
            max_batch=ecfg["max_batch"],
            prefill_chunk=ecfg["prefill_chunk"], spec_k=ecfg["spec_k"],
            prefix_cache=ecfg["prefix_cache"],
            monitor=StepPressureMonitor(config["pressure_steps"])
            if pressure else None,
            shed_on_pressure=pressure,
            shed_priority_min=ecfg["shed_priority_min"])

    # the reference runs the same prompts WITHOUT deadlines: ground
    # truth is "what would each request have generated", and a
    # deadline retires by design even in a healthy engine
    truth = _drive(make_cb(config["truth_num_blocks"], pressure=False),
                   dict(workload, deadline_steps={}), "ct")
    assert all(s == "finished" for s in truth["statuses"]), \
        "reference run must complete undisturbed"

    cb = make_cb(ecfg["num_blocks"], pressure=True)
    fcfg = config["faults"]
    passes = []
    for k, tag in enumerate(("c1", "c2", "c3")):
        if k == 2:
            warm_buckets = set(cb._seen_buckets)
            cb.declare_warm()
        passes.append(_drive(cb, workload, tag,
                             faults=_build_injector(fcfg, workload, tag)))
    p1, p2, p3 = passes

    def exact(pass_res):
        """survivor exactness + prefix exactness per category."""
        ok_full, ok_prefix, ok_resumed = True, True, True
        for j, res in enumerate(pass_res["results"]):
            ref = truth["tokens"][j]
            if res.status == "finished":
                if list(res) != ref:
                    ok_full = False
                if res.preemptions and list(res) != ref:
                    ok_resumed = False
            elif res.status in ("cancelled", "deadline_exceeded",
                                "failed"):
                if list(res) != ref[:len(res)]:
                    ok_prefix = False
        return ok_full, ok_prefix, ok_resumed

    ex = [exact(p) for p in passes]
    resumed_finished = sum(
        1 for p in passes for r in p["results"]
        if r.status == "finished" and r.preemptions)
    status_counts = {}
    for p in passes:
        for r in p["results"]:
            status_counts[r.status] = status_counts.get(r.status, 0) + 1
    tokens_by_status = {}
    for p in passes:
        for r in p["results"]:
            tokens_by_status[r.status] = \
                tokens_by_status.get(r.status, 0) + len(r)

    out = {
        "schema": REPORT_SCHEMA,
        "interpret": not on_tpu,
        "config": {k: config[k] for k in
                   ("engine", "truth_num_blocks", "workload", "faults",
                    "pressure_steps")},
        "workload": {k: workload[k] for k in
                     ("prompt_lens", "new_tokens", "arrival_steps",
                      "priorities")},
        "truth_steps": truth["steps"],
        "truth_tokens": sum(len(t) for t in truth["tokens"]),
        "passes": [{
            "steps": p["steps"],
            "statuses": p["statuses"],
            "preemptions": p["preemptions"],
            "tokens_per_request": [len(t) for t in p["tokens"]],
            "injected": p["injected"],
            "gauges_baseline": p["gauges_baseline"],
        } for p in passes],
        "status_counts": status_counts,
        "tokens_by_status": tokens_by_status,
        "resumed_and_finished": resumed_finished,
        "survivors_token_exact": all(e[0] for e in ex),
        "partials_prefix_exact": all(e[1] for e in ex),
        "preempted_resumed_token_exact": all(e[2] for e in ex)
        and resumed_finished > 0,
        "gauges_return_to_baseline": all(p["gauges_baseline"]
                                         for p in passes),
        "new_buckets_after_warmup": len(set(cb._seen_buckets)
                                        - warm_buckets),
        "deterministic_replay": (p3["statuses"] == p2["statuses"]
                                 and p3["tokens"] == p2["tokens"]
                                 and p3["steps"] == p2["steps"]),
        "flight_dumps": len(fr.dumps),
    }
    print(f"chaos leg: truth {out['truth_steps']} steps / "
          f"{out['truth_tokens']} tokens; statuses over 3 passes "
          f"{out['status_counts']}; resumed+finished "
          f"{out['resumed_and_finished']}; injected (last pass) "
          f"{p3['injected']}; new buckets after warmup "
          f"{out['new_buckets_after_warmup']}"
          + (" [interpret]" if not on_tpu else ""))
    return out


# host-deterministic keys gated against the committed baseline
CHAOS_KEYS = ("workload", "truth_steps", "truth_tokens", "passes",
              "status_counts", "tokens_by_status", "resumed_and_finished")

# invariants that must hold REGARDLESS of the baseline
CHAOS_INVARIANTS = ("survivors_token_exact", "partials_prefix_exact",
                    "preempted_resumed_token_exact",
                    "gauges_return_to_baseline", "deterministic_replay")


def check_chaos(base):
    cur = chaos_leg(config=base.get("config") or DEFAULT_CONFIG)
    bad = [k for k in CHAOS_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    for k in CHAOS_INVARIANTS:
        if cur[k] is not True:
            print(f"REGRESSION: {k} is {cur[k]!r}")
            bad.append(k)
    if cur["new_buckets_after_warmup"] != 0:
        print(f"REGRESSION: chaos pass 3 compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print(f"chaos leg OK: no unhandled exception across "
          f"{sum(p['steps'] for p in cur['passes'])} chaotic steps, "
          f"survivors token-exact, "
          f"{cur['resumed_and_finished']} preempted requests resumed "
          f"token-exact, gauges at baseline, 0 new buckets")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="serving fault-injection (chaos) gate")
    ap.add_argument("--json", default=None,
                    help="write the full JSON report here")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate against a committed baseline "
                         "(tools/serve_chaos.json)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight-recorder dump dir for the chaos run "
                         "(default: a fresh tmpdir)")
    args = ap.parse_args()

    if args.check:
        with open(args.check) as f:
            base = json.load(f)
        if "chaos" not in base:
            print(f"{args.check}: no 'chaos' section to gate")
            return 1
        return check_chaos(base["chaos"])

    out = chaos_leg(flight_dir=args.flight_dir)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
