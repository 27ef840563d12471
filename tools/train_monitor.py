#!/usr/bin/env python
"""Training health monitor drive — and the `train_health` CI gate.

Drives a short sharded pretrain (the dp2 x fsdp2 x mp2 virtual-8-device
mesh, the dryrun_multichip pattern) with the per-layer-group telemetry
and the TrainHealthMonitor on, healthy AND under injected faults
(paddle_tpu/testing/faults.py TrainFaultInjector), and proves the
ISSUE-14 contract end to end:

* **neutrality** — telemetry-on is loss-BIT-exact vs telemetry-off
  over the same seeded workload, and compile-count-neutral after
  warmup (the packed in-graph vector is a pure extra output; one bulk
  host fetch per cadence, zero per-tensor syncs).
* **healthy** — a monitored run through the REAL instrumented
  DataLoader (instrument=True: wait histograms, queue-depth gauge,
  `data_wait` chrome spans) raises ZERO breaches, and reports the
  per-group norm snapshot plus the data-wait/host/dispatch step-phase
  split.
* **faults** — each injected production failure fires exactly its
  detector(s), exactly once, with a schema-valid loadable flight dump:
  - a NaN'd batch (out-of-vocab ids -> NaN embeddings) -> `non_finite`
    -> a `non_finite_loss` dump, and training CONTINUES (degrade,
    don't crash — the PR-11 discipline);
  - an lr spike (one update at 64x lr through the step's lr_scale=
    program) -> `grad_spike` + `loss_spike` on the next step ->
    `grad_norm_spike` + `loss_divergence` dumps;
  - a throttled loader (injected sleep upstream of the wait
    measurement) -> `data_stall` -> a `data_stall` dump.

Modes:
  python tools/train_monitor.py                  # report
  python tools/train_monitor.py --json out.json
  python tools/train_monitor.py --check tools/train_health.json

The --check gate (wired into tools/lint.sh next to the serve gates)
compares the report against the committed baseline: exact fired-count
matrices per fault, dump reasons, zero healthy breaches, loss
exactness, zero new compiles after warmup, and the exact bounded group
label set.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPORT_SCHEMA = "paddle_tpu.train_health_report/1"
BASELINE_SCHEMA = "paddle_tpu.train_health/1"

# the gate workload: tiny llama on the virtual 8-device mesh
MESH = {"dp": 2, "fsdp": 2, "mp": 2}
BATCH, SEQ, VOCAB = 8, 16, 128


def _force_virtual_devices(n=8):
    """Ask the CPU backend (JAX_PLATFORMS=cpu) for n virtual devices:
    must run before jax initializes."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()


def _fresh_run(telemetry=False, monitor=None):
    """Freshly seeded model + sharded state + train step — every leg
    starts from IDENTICAL parameters (the step donates its buffers, so
    state can never be shared across legs)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, pretrain

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=SEQ, dtype="float32")
    model = LlamaForCausalLM(cfg)
    n_dev = MESH["dp"] * MESH["fsdp"] * MESH["mp"]
    mesh = pretrain.make_mesh(n_dev, **MESH)
    params, opt_state, meta = pretrain.make_train_state(model, mesh)
    step = pretrain.make_train_step(model, mesh, meta,
                                    telemetry=telemetry, monitor=monitor)
    return mesh, params, opt_state, step


def _batches(n, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, VOCAB, (BATCH, SEQ)).astype(
                 np.int32),
             "labels": rng.integers(0, VOCAB, (BATCH, SEQ)).astype(
                 np.int32)}
            for _ in range(n)]


def _monitor(base_cfg, flight_dir, **overrides):
    from paddle_tpu import observability as obs
    flight = obs.FlightRecorder(min_interval_s=0.0)
    flight.arm(flight_dir)
    return obs.TrainHealthMonitor.from_config(
        base_cfg, flight_recorder=flight, **overrides)


def _collect_dumps(flight):
    """Load + schema-validate every dump the leg's recorder wrote."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import train_health as th
    out, ok = [], True
    for path in flight.dumps:
        try:
            dump = obs.load_dump(path)
            digest = th.breach_summary(dump)
            out.append({"reason": dump["reason"],
                        "check": digest["check"], "valid": True})
        except (OSError, ValueError) as e:
            ok = False
            out.append({"reason": os.path.basename(path),
                        "check": None, "valid": False, "error": str(e)})
    return out, ok


def neutrality_leg(steps=6):
    """Telemetry-on vs telemetry-off: loss bit-exactness + zero
    compiles after warmup. Warmup is the first TWO steps — step 0
    compiles the program, step 1 recompiles once when its inputs
    arrive as step 0's donated-aliased outputs (pre-existing behavior,
    identical with telemetry off; verified both ways here)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.models import pretrain

    obs.install_compile_watch()

    def backend_compiles():
        snap = obs.get_registry().snapshot().get("jax_compiles_total", {})
        return sum(c["value"]
                   for name, c in snap.get("children", {}).items()
                   if name.startswith("backend_compile"))

    def drive(telemetry):
        mesh, params, opt_state, step = _fresh_run(telemetry=telemetry)
        losses = []
        after_warmup = None
        for i, b in enumerate(_batches(steps)):
            if i == 2:
                after_warmup = backend_compiles()
            params, opt_state, loss, gnorm = step(
                params, opt_state, pretrain.shard_batch(b, mesh))
            losses.append(float(loss))
        return losses, backend_compiles() - after_warmup

    losses_off, _ = drive(False)
    losses_on, new_compiles = drive(True)
    return {
        "steps": steps,
        "losses_off": losses_off,
        "losses_on": losses_on,
        "loss_exact": losses_off == losses_on,
        "new_compiles_after_warmup": new_compiles,
    }


def healthy_leg(monitor_cfg, steps=10):
    """Monitored run through the instrumented DataLoader: zero
    breaches, per-group norms, step-phase split."""
    import numpy as np
    from paddle_tpu import observability as obs
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models import pretrain
    from paddle_tpu.observability import train_health as th

    th.pop_data_wait()      # no stale wait from a previous leg
    flight_dir = tempfile.mkdtemp(prefix="train_health_ok_")
    try:
        mon = _monitor(monitor_cfg, flight_dir, data_stall_s=30.0)
        mesh, params, opt_state, step = _fresh_run(monitor=mon)

        samples = []
        for b in _batches(steps):
            for j in range(BATCH):
                samples.append({"input_ids": b["input_ids"][j],
                                "labels": b["labels"][j]})
        loader = DataLoader(
            samples, batch_size=BATCH, num_workers=2, instrument=True,
            collate_fn=lambda rows: {k: np.stack([r[k] for r in rows])
                                     for k in rows[0]})
        loader.health_monitor = mon
        n = 0
        for b in loader:
            params, opt_state, loss, gnorm = step(
                params, opt_state, pretrain.shard_batch(b, mesh))
            n += 1
            if n >= steps:
                break
    finally:
        shutil.rmtree(flight_dir, ignore_errors=True)

    reg = obs.get_registry()
    snap = reg.snapshot()

    def gauge_children(name):
        return {k: v["value"]
                for k, v in snap.get(name, {}).get("children",
                                                   {}).items()}

    def p50(name):
        fam = reg.get(name)
        return None if fam is None or not fam.count else \
            fam.quantile(0.5)

    groups = {}
    for label, g in gauge_children("train_group_grad_norm").items():
        groups[label] = {
            "grad_norm": g,
            "param_norm": gauge_children(
                "train_group_param_norm").get(label),
            "update_ratio": gauge_children(
                "train_group_update_ratio").get(label),
        }
    return {
        "steps": n,
        "breaches": mon.breaches_total,
        "breach_counts": dict(mon.breach_counts),
        "data_batches": int(
            snap.get("train_data_batches_total", {}).get(
                "children", {}).get("", {}).get("value", 0)),
        "group_norms": groups,
        "phase_p50_s": {"data_wait": p50("train_data_wait_seconds"),
                        "host": p50("train_host_seconds"),
                        "dispatch": p50("train_step_seconds")},
    }


def nan_batch_leg(monitor_cfg, steps=8, fault_at=5):
    """Out-of-vocab ids at one step -> non-finite loss/grads -> the
    non_finite detector fires ONCE (transition), a non_finite_loss
    dump lands, and the loop runs to completion."""
    from paddle_tpu.models import pretrain
    from paddle_tpu.observability import train_health as th
    from paddle_tpu.testing.faults import TrainFaultInjector

    th.pop_data_wait()
    flight_dir = tempfile.mkdtemp(prefix="train_health_nan_")
    try:
        mon = _monitor(monitor_cfg, flight_dir)
        mesh, params, opt_state, step = _fresh_run(monitor=mon)
        inj = TrainFaultInjector().nan_batch(fault_at)
        completed = 0
        for i, b in enumerate(_batches(steps)):
            b = inj.adjust_batch(i, b)
            params, opt_state, loss, gnorm = step(
                params, opt_state, pretrain.shard_batch(b, mesh))
            completed += 1
        dumps, dumps_valid = _collect_dumps(mon.flight_recorder)
    finally:
        shutil.rmtree(flight_dir, ignore_errors=True)
    return {
        "steps": completed,
        "fault_at": fault_at,
        "injected": dict(inj.injected),
        "fired": dict(mon.breach_counts),
        "dump_reasons": sorted(d["reason"] for d in dumps),
        "dumps_valid": dumps_valid,
        "continued_after_fault": completed == steps,
    }


def lr_spike_leg(monitor_cfg, steps=10, fault_at=6, factor=4096.0):
    """One update at factor x lr (the lr_scale= program). Fires THREE
    detectors deterministically: update_ratio at the faulted step
    itself (the update/param ratio jumps ~60x over the explosion
    bound — the canonical lr-spike signature), then loss_spike +
    grad_spike at the NEXT step when the blown-up parameters send
    loss/grad-norm out of the rolling median+MAD baseline (4096x is
    tuned for margin: loss 4.87 -> 9.7 vs threshold ~6.8, gnorm
    1.26 -> 10.3 vs ~1.8 — large and seeded-deterministic, yet
    finite, so non_finite stays quiet)."""
    from paddle_tpu.models import pretrain
    from paddle_tpu.observability import train_health as th
    from paddle_tpu.testing.faults import TrainFaultInjector

    th.pop_data_wait()
    flight_dir = tempfile.mkdtemp(prefix="train_health_lr_")
    try:
        mon = _monitor(monitor_cfg, flight_dir)
        mesh, params, opt_state, step = _fresh_run(monitor=mon)
        inj = TrainFaultInjector().lr_spike(fault_at, factor=factor)
        for i, b in enumerate(_batches(steps)):
            params, opt_state, loss, gnorm = step(
                params, opt_state, pretrain.shard_batch(b, mesh),
                lr_scale=inj.lr_scale_for(i))
        dumps, dumps_valid = _collect_dumps(mon.flight_recorder)
    finally:
        shutil.rmtree(flight_dir, ignore_errors=True)
    return {
        "steps": steps,
        "fault_at": fault_at,
        "factor": factor,
        "injected": dict(inj.injected),
        "fired": dict(mon.breach_counts),
        "dump_reasons": sorted(d["reason"] for d in dumps),
        "dumps_valid": dumps_valid,
    }


def data_stall_leg(monitor_cfg, steps=6, stall_at=3, delay_s=1.0):
    """A throttled loader: the injected sleep rides UPSTREAM of the
    instrumented loader's wait measurement, so the stall detector sees
    a real starved pipeline and fires the data_stall dump."""
    from paddle_tpu.models import pretrain
    from paddle_tpu.observability import train_health as th
    from paddle_tpu.testing.faults import TrainFaultInjector

    th.pop_data_wait()
    flight_dir = tempfile.mkdtemp(prefix="train_health_stall_")
    try:
        mon = _monitor(monitor_cfg, flight_dir)
        mesh, params, opt_state, step = _fresh_run(monitor=mon)
        inj = TrainFaultInjector().stall_loader(stall_at,
                                                delay_s=delay_s)
        loader = th.instrument_loader(inj.wrap_loader(_batches(steps)),
                                      monitor=mon)
        for b in loader:
            params, opt_state, loss, gnorm = step(
                params, opt_state, pretrain.shard_batch(b, mesh))
        dumps, dumps_valid = _collect_dumps(mon.flight_recorder)
    finally:
        shutil.rmtree(flight_dir, ignore_errors=True)
    return {
        "steps": steps,
        "stall_at": stall_at,
        "delay_s": delay_s,
        "injected": dict(inj.injected),
        "fired": dict(mon.breach_counts),
        "dump_reasons": sorted(d["reason"] for d in dumps),
        "dumps_valid": dumps_valid,
    }


def build_report(monitor_cfg):
    from paddle_tpu.observability import train_health as th

    mesh, params, opt_state, step = _fresh_run(telemetry=True)
    groups = list(step._telemetry_spec.labels)
    del params, opt_state
    return {
        "schema": REPORT_SCHEMA,
        "workload": {"mesh": dict(MESH), "batch": BATCH, "seq": SEQ,
                     "vocab": VOCAB},
        "monitor": dict(monitor_cfg),
        "groups": groups,
        "checks": list(th.CHECKS),
        "neutrality": neutrality_leg(),
        "healthy": healthy_leg(monitor_cfg),
        "faults": {
            "nan_batch": nan_batch_leg(monitor_cfg),
            "lr_spike": lr_spike_leg(monitor_cfg),
            "data_stall": data_stall_leg(monitor_cfg),
        },
    }


DEFAULT_MONITOR = {
    "window_s": 120.0, "min_count": 4, "loss_spike_mads": 8.0,
    "grad_spike_mads": 8.0, "mad_floor_frac": 0.05,
    "update_ratio_bounds": [1e-9, 1.0], "data_stall_s": 0.3,
    "cooldown_s": 600.0,
}


def print_report(report):
    n = report["neutrality"]
    print(f"neutrality: loss_exact={n['loss_exact']} over {n['steps']} "
          f"steps, {n['new_compiles_after_warmup']} compiles after "
          f"warmup")
    h = report["healthy"]
    ph = h["phase_p50_s"]

    def ms(v):
        return "-" if v is None else f"{v * 1e3:.1f}ms"

    print(f"healthy: {h['breaches']} breaches over {h['steps']} steps "
          f"({h['data_batches']} batches); p50 data-wait "
          f"{ms(ph['data_wait'])} / host {ms(ph['host'])} / dispatch "
          f"{ms(ph['dispatch'])}")
    print(f"{'group':>14} | {'grad_norm':>10} | {'param_norm':>10} | "
          f"{'upd/param':>10}")
    for label in report["groups"]:
        g = h["group_norms"].get(label)
        if g is None:
            continue
        print(f"{label:>14} | {g['grad_norm']:>10.4f} | "
              f"{g['param_norm']:>10.2f} | {g['update_ratio']:>10.2e}")
    for name, leg in report["faults"].items():
        print(f"fault {name}: fired={leg['fired']} "
              f"dumps={leg['dump_reasons']} valid={leg['dumps_valid']}")


def _lookup(report, dotted):
    cur = report
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check(baseline_path):
    """The train_health gate: schema + exact fired matrices + dump
    reasons + neutrality + bounds, against the committed baseline."""
    with open(baseline_path) as f:
        base = json.load(f)
    if base.get("schema") != BASELINE_SCHEMA:
        print(f"{baseline_path}: not a {BASELINE_SCHEMA} baseline")
        return 1
    report = build_report(base.get("monitor", DEFAULT_MONITOR))
    print_report(report)
    bad = []
    if report.get("schema") != REPORT_SCHEMA:
        bad.append(f"report schema {report.get('schema')!r}")
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
        print(f"train_health gate: FAIL ({len(bad)} problems)")
        for b in bad:
            print("  " + b)
        return 1
    print(f"train_health gate OK: {len(base.get('exact', {}))} exact "
          f"fields, {len(base.get('bounds', {}))} bounds")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="training health monitor drive + train_health gate")
    ap.add_argument("--json", default=None, help="write the report here")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate the report against a committed "
                         "train_health baseline")
    args = ap.parse_args()
    _force_virtual_devices(8)
    if args.check:
        return check(args.check)
    report = build_report(DEFAULT_MONITOR)
    print_report(report)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=str)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
