#!/usr/bin/env python
"""Per-program cost & memory report — and the `train_obs` CI gate.

The question PR 3-8 couldn't answer: not "how slow was it" but "how
fast SHOULD it be". This tool drives the two instrumented stacks with
the cost catalog enabled (observability/costs.py) and prints, per warm
compiled program, the numbers XLA itself attributes: FLOPs, bytes
accessed, peak HBM, arithmetic intensity, dispatch p50, and achieved
MFU — plus the live-array census/leak accounting (observability/
memory.py) and the collective telemetry of a sharded step.

Legs:
  * serve — the ragged continuous-batching workload with speculative
    decode AND prefix caching on, so all three serving programs
    dispatch: `paged_step` (the mixed prefill/decode step),
    `paged_rewind` (spec-rejection cache rollback), `paged_copy`
    (copy-on-write block duplication). Token-exactness vs a
    catalog-off run and zero new compile buckets after warmup are
    asserted — the telemetry must be a pure observer. A census
    before/after the replay churn is the serving leak check.
  * pretrain — a small sharded pretrain run on the virtual 8-device
    mesh (dp=2 x fsdp=2 x mp=2, the dryrun_multichip pattern):
    `pretrain_step` cost/MFU (the step blocks on the loss, so dispatch
    wall is real step wall), per-shard byte skew of the placed params,
    and eager-collective bytes/latency through the comm watchdog.

Modes:
  python tools/cost_report.py                  # report (both legs)
  python tools/cost_report.py --json out.json
  python tools/cost_report.py --census         # census table + diff
  python tools/cost_report.py --check tools/train_obs.json
                                               # the train_obs gate

The --check gate is the training-side analogue of the serve_slo gate:
"MFU is a number the CI checks". The committed baseline carries BOUNDS
(per-figure [lo, hi] brackets — interpret-mode CPU numbers are coverage
evidence, not speed claims, so the brackets are wide) plus exact
requirements: every required program attributed, token-exact, 0 new
buckets, 0 census leak groups, 0 KV blocks held after retirement.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.cost_report/1"
BASELINE_SCHEMA = "paddle_tpu.train_obs/1"

SERVE_PROGRAMS = ("paged_step", "paged_rewind", "paged_copy")


def _force_virtual_devices(n=8):
    """Ask the CPU backend (JAX_PLATFORMS=cpu) for n virtual devices:
    must run before jax initializes."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()


def serve_cost_leg(new_tokens=24, spec_k=4, chunk=8, block_size=8):
    """Drive the ragged serving workload with the catalog on; returns
    the per-program attribution plus the neutrality and leak gates."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    from tools.serve_bench import _tiny_cpu_engine

    on_tpu = init_platform() == "tpu"
    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=128)
    # the PR-5 repetitive workload: prompt-lookup drafts hit often but
    # not always, so the step, the rewind (rejections), and the COW
    # copy (shared pattern prompts + prefix cache) all dispatch
    pattern = [7, 23, 41, 11]
    prompts = [np.asarray(pattern * 8, np.int32),
               np.asarray(pattern * 4, np.int32)]

    def make_cb():
        return ContinuousBatchingEngine(
            eng, num_blocks=24, block_size=block_size, max_batch=2,
            prefill_chunk=chunk, spec_k=spec_k, prefix_cache=True)

    def drive(cb, tag):
        reqs = [GenerationRequest(p.copy(), new_tokens,
                                  request_id=f"{tag}{j}")
                for j, p in enumerate(prompts)]
        for r in reqs:
            cb.submit(r)
        out = cb.run()
        return [out[r.request_id] for r in reqs]

    catalog = obs.get_cost_catalog()
    catalog.reset()
    catalog.enabled = True
    cb = make_cb()
    try:
        drive(cb, "cw")             # cold: analyses at the real misses
        drive(cb, "cm")             # resume: the prefix cache serves the
                                    # pattern blocks now, which changes
                                    # the chunk grants — warm THOSE
                                    # buckets too before declaring warm
        cb.declare_warm()
        warm_buckets = set(cb._seen_buckets)
        baseline_census = obs.live_array_census()
        out_on = drive(cb, "cr")    # replay churn: the leak window
        final_census = obs.live_array_census()
        new_buckets = len(set(cb._seen_buckets) - warm_buckets)
    finally:
        catalog.enabled = False
    # catalog off, fresh scheduler at the same resume state (one cold +
    # one resume pass, outputs of the second compared): the reference
    cb_off = make_cb()
    drive(cb_off, "cf")
    out_off = drive(cb_off, "cg")
    leak = obs.census_diff(baseline_census, final_census)
    rows = {r["program"]: r for r in catalog.table()
            if r["program"] in SERVE_PROGRAMS}
    obs.record_census(final_census)
    return {
        "census": final_census,
        "interpret": not on_tpu,
        "workload": {"prompt_lens": [len(p) for p in prompts],
                     "new_tokens": new_tokens, "spec_k": spec_k,
                     "chunk": chunk, "block_size": block_size},
        "token_exact": out_on == out_off,
        "new_buckets_after_warmup": new_buckets,
        "leak": {
            "census_delta_groups": len(leak),
            "census_delta": leak,
            "kv_used_final": cb.allocator.num_used,
            "kv_pooled_final": cb.allocator.num_pooled,
        },
        "programs": rows,
    }


def pretrain_cost_leg(steps=3, dp=2, fsdp=2, mp=2):
    """Sharded pretrain step on the virtual mesh: pretrain_step
    cost/MFU (blocking on the loss makes dispatch wall real), shard
    skew of the placed params, and eager-collective telemetry."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import observability as obs
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, pretrain

    n_dev = dp * fsdp * mp
    devs = jax.devices()
    if len(devs) < n_dev:
        return {"skipped": f"need {n_dev} devices, have {len(devs)}"}
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32, dtype="float32")
    model = LlamaForCausalLM(cfg)
    mesh = pretrain.make_mesh(n_dev, dp=dp, fsdp=fsdp, mp=mp)
    params, opt_state, meta = pretrain.make_train_state(model, mesh)
    skew = obs.shard_skew(params)
    step = pretrain.make_train_step(model, mesh, meta)
    catalog = obs.get_cost_catalog()
    catalog.enabled = True
    rng = np.random.default_rng(0)
    b, s = max(2, dp * fsdp), 32
    try:
        loss = None
        for _ in range(steps):
            batch = pretrain.shard_batch(
                {"input_ids": rng.integers(0, 128, (b, s)).astype(np.int32),
                 "labels": rng.integers(0, 128, (b, s)).astype(np.int32)},
                mesh)
            params, opt_state, loss, gnorm = step(params, opt_state, batch)
            float(loss)     # block: dispatch wall == real step wall
    finally:
        catalog.enabled = False
    # eager-collective telemetry through the watchdog wrappers: one
    # all_reduce + all_gather of stat-sized tensors, the fleet.metrics
    # path — lands collective_seconds{op,axis} + bandwidth + a span
    dist.enable_comm_watchdog(timeout=600, poll_interval=60)
    try:
        t = paddle.to_tensor(np.ones(4096, np.float32))
        dist.all_reduce(t)
        gathered = []
        dist.all_gather(gathered, paddle.to_tensor(np.ones(1024,
                                                           np.float32)))
    finally:
        dist.disable_comm_watchdog()
    reg = obs.get_registry()
    snap = reg.snapshot()
    coll = sorted(snap.get("collective_seconds", {}).get("children", {}))
    bw = {k: v["value"] for k, v in snap.get(
        "collective_bandwidth_bytes_per_s", {}).get("children",
                                                    {}).items()}
    rows = {r["program"]: r for r in catalog.table()
            if r["program"] == "pretrain_step"}
    return {
        "mesh": {"dp": dp, "fsdp": fsdp, "mp": mp},
        "steps": steps,
        "tokens_per_step": b * s,
        "final_loss": float(loss),
        "shard_skew": skew.get("skew"),
        "shard_devices": len(skew.get("devices", {})),
        "collectives": coll,
        "collective_bandwidth": bw,
        "programs": rows,
    }


def build_report(census_mode=False):
    from paddle_tpu import observability as obs

    report = {
        "schema": REPORT_SCHEMA,
        "peaks": {"flops_per_s": obs.peak_flops(),
                  "bytes_per_s": obs.peak_bandwidth()},
        "serve": serve_cost_leg(),
        "pretrain": pretrain_cost_leg(),
    }
    # the serve leg's end-of-churn census is the informative one (its
    # arrays were alive when taken); keep it at top level only in
    # census mode, it is the report's biggest section
    census = report["serve"].pop("census")
    if census_mode:
        report["census"] = census
    return report


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, float):
        if v >= 1e9:
            return f"{v / 1e9:.2f}G{unit}"
        if v >= 1e6:
            return f"{v / 1e6:.2f}M{unit}"
        if v >= 1e3:
            return f"{v / 1e3:.2f}k{unit}"
        return f"{v:.4g}{unit}"
    return str(v)


def print_report(report):
    print(f"peaks: {_fmt(report['peaks']['flops_per_s'])}FLOP/s, "
          f"{_fmt(report['peaks']['bytes_per_s'])}B/s"
          + (" (nominal CPU peaks: MFU is coverage evidence, not a "
             "speed claim)" if report["serve"].get("interpret") else ""))
    cols = ("program", "flops", "bytes", "intensity", "peak_hbm",
            "disp_p50", "mfu")
    print(" | ".join(f"{c:>12}" for c in cols))
    programs = dict(report["serve"]["programs"])
    programs.update(report["pretrain"].get("programs", {}))
    for name, r in sorted(programs.items()):
        lat = r.get("dispatch_s")
        print(" | ".join(f"{v:>12}" for v in (
            name, _fmt(r.get("flops")), _fmt(r.get("bytes_accessed")),
            "-" if r.get("intensity") is None
            else f"{r['intensity']:.2f}",
            _fmt(r.get("peak_hbm")),
            "-" if lat is None else f"{lat * 1e3:.2f}ms",
            "-" if r.get("mfu") is None else f"{r['mfu']:.2e}")))
    s = report["serve"]
    print(f"serve: token_exact={s['token_exact']}, "
          f"{s['new_buckets_after_warmup']} new buckets after warmup, "
          f"census leak groups={s['leak']['census_delta_groups']}, "
          f"KV used after retirement={s['leak']['kv_used_final']}")
    p = report["pretrain"]
    if "skipped" in p:
        print(f"pretrain: skipped ({p['skipped']})")
    else:
        print(f"pretrain: mesh dp{p['mesh']['dp']}xfsdp{p['mesh']['fsdp']}"
              f"xmp{p['mesh']['mp']}, shard_skew={p['shard_skew']:.3f} "
              f"over {p['shard_devices']} devices, "
              f"collectives={p['collectives']}")
    if "census" in report:
        print("census (top groups by bytes):")
        top = sorted(report["census"].items(),
                     key=lambda kv: -kv[1]["bytes"])[:12]
        for k, v in top:
            print(f"  {k:>32}  x{v['count']:<4} {_fmt(float(v['bytes']))}B")
        delta = report["serve"]["leak"]["census_delta"]
        print(f"census diff over the replay churn: "
              f"{delta if delta else 'empty (no leak)'}")


def _lookup(report, dotted):
    cur = report
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check(baseline_path):
    """The train_obs gate: schema + required programs + exact fields +
    bracketed bounds, all against the committed baseline."""
    with open(baseline_path) as f:
        base = json.load(f)
    if base.get("schema") != BASELINE_SCHEMA:
        print(f"{baseline_path}: not a {BASELINE_SCHEMA} baseline")
        return 1
    report = build_report()
    print_report(report)
    bad = []
    if report.get("schema") != REPORT_SCHEMA:
        bad.append(f"report schema {report.get('schema')!r}")
    programs = dict(report["serve"]["programs"])
    programs.update(report["pretrain"].get("programs", {}))
    for name in base["require_programs"]:
        r = programs.get(name)
        if r is None:
            bad.append(f"program {name} not attributed")
            continue
        for field in ("flops", "bytes_accessed", "peak_hbm", "mfu"):
            if r.get(field) is None:
                bad.append(f"{name}.{field} missing")
    for dotted, want in base.get("exact", {}).items():
        got = _lookup(report, dotted)
        if got != want:
            bad.append(f"{dotted}: {got!r} != required {want!r}")
    for dotted, (lo, hi) in base.get("bounds", {}).items():
        got = _lookup(report, dotted)
        if got is None:
            bad.append(f"{dotted}: missing (bounds [{lo}, {hi}])")
        elif not (lo <= got <= hi):
            bad.append(f"{dotted}: {got} outside [{lo}, {hi}]")
    if bad:
        print(f"train_obs gate: FAIL ({len(bad)} problems)")
        for b in bad:
            print("  " + b)
        return 1
    print(f"train_obs gate OK: {len(base['require_programs'])} programs "
          f"attributed, {len(base.get('bounds', {}))} bounds, "
          f"{len(base.get('exact', {}))} exact fields")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="per-program cost/memory report + train_obs gate")
    ap.add_argument("--json", default=None, help="write the report here")
    ap.add_argument("--census", action="store_true",
                    help="include the live-array census table + the "
                         "churn diff")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate the report against a committed "
                         "train_obs baseline (bounds + exact fields)")
    args = ap.parse_args()
    _force_virtual_devices(8)
    if args.check:
        return check(args.check)
    report = build_report(census_mode=args.census)
    print_report(report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
