"""Sharded Llama pretraining over a device mesh (the headline path).

Run (the chips jax finds):     python examples/llama_pretrain_sharded.py
Run (8 virtual CPU devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/llama_pretrain_sharded.py --dp 2 --fsdp 2 --mp 2

The mesh axes are the parallelism plan: dp shards the batch, fsdp shards
params + optimizer moments (ZeRO-3 at rest), mp is tensor parallelism,
sp sequence/context parallelism. GSPMD inserts every collective."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import argparse

import numpy as np

from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, pretrain


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--metrics", action="store_true",
                    help="count XLA compiles + step time/tokens-per-s "
                         "and print the metrics snapshot at the end")
    ap.add_argument("--health", action="store_true",
                    help="training health monitoring: per-layer-group "
                         "gradient telemetry + divergence detection "
                         "(TrainHealthMonitor), step-phase breakdown, "
                         "and the arm-by-default flight recorder — a "
                         "NaN'd loss or a starved pipeline leaves a "
                         "dump instead of a ruined run")
    args = ap.parse_args()

    monitor = None
    if args.health:
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import tracing
        # serve entrypoints arm by default (PR 8); with --health the
        # pretrain example does too: breach dumps land in
        # $PADDLE_TPU_FLIGHT_DIR (or the tmp default) under retention
        tracing.arm_default()
        monitor = obs.TrainHealthMonitor()

    if args.metrics:
        from paddle_tpu import observability as obs
        obs.install_compile_watch()

    cfg = LlamaConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=688,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=args.seq, dtype="float32")
    model = LlamaForCausalLM(cfg)

    n_dev = args.dp * args.fsdp * args.mp * args.sp
    mesh = pretrain.make_mesh(n_dev, dp=args.dp, fsdp=args.fsdp,
                              mp=args.mp, sp=args.sp)
    params, opt_state, meta = pretrain.make_train_state(model, mesh)
    step = pretrain.make_train_step(model, mesh, meta, monitor=monitor)

    rng = np.random.default_rng(0)

    def gen_batches():
        for _ in range(args.steps):
            yield {"input_ids": rng.integers(
                       0, cfg.vocab_size,
                       (args.batch, args.seq)).astype(np.int32),
                   "labels": rng.integers(
                       0, cfg.vocab_size,
                       (args.batch, args.seq)).astype(np.int32)}

    batches = gen_batches()
    if monitor is not None:
        # data-pipeline telemetry: per-batch wait + stall detection on
        # the same monitor (a real run would set instrument=True on
        # its DataLoader instead)
        from paddle_tpu.observability import train_health
        batches = train_health.instrument_loader(batches,
                                                 monitor=monitor)
    for i, host_batch in enumerate(batches):
        batch = pretrain.shard_batch(host_batch, mesh)
        params, opt_state, loss, gnorm = step(params, opt_state, batch)
        print(f"step {i}: loss {float(loss):.4f} gnorm {float(gnorm):.3f}")

    if monitor is not None:
        rep = monitor.report()
        print(f"train health: {rep['breaches_total']} breaches over "
              f"{rep['steps_observed']} monitored steps "
              f"({rep['breach_counts'] or 'all checks quiet'})")
        from paddle_tpu import observability as obs
        snap = obs.get_registry().snapshot()
        groups = snap.get("train_group_grad_norm", {}).get("children",
                                                           {})
        ratios = snap.get("train_group_update_ratio",
                          {}).get("children", {})
        for label in groups:
            print(f"  {label:>14}: grad_norm "
                  f"{groups[label]['value']:.4f}  upd/param "
                  f"{ratios.get(label, {}).get('value', 0):.2e}")

    if args.metrics:
        reg = obs.get_registry()
        snap = reg.snapshot().get("jax_compiles_total", {})
        backend = sum(
            c["value"] for name, c in snap.get("children", {}).items()
            if name.startswith("backend_compile"))
        print(f"backend compiles: {backend:.0f}")
        steps_h = reg.get("train_step_seconds")
        if steps_h is not None and steps_h.count:
            print(f"step p50 {steps_h.quantile(0.5)*1e3:.1f} ms, "
                  f"p95 {steps_h.quantile(0.95)*1e3:.1f} ms over "
                  f"{steps_h.count} steps")
        print(obs.to_json(indent=1))


if __name__ == "__main__":
    main()
