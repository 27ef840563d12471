#!/usr/bin/env python
"""Serve a llama-style model over the HTTP/SSE gateway (ISSUE 12).

The production-front-door entrypoint: the continuous-batching engine
(paged ragged attention, chunked prefill, speculative decode, prefix
caching, priority/deadline resilience) on a dedicated stepper thread,
fronted by the asyncio gateway — per-token SSE streaming, mid-stream
cancellation, and the live observability control plane (/metrics,
/slo, /requests, /dumps, /healthz).

Same operational posture as serve_llama/serve_bench/serve_monitor:
the flight recorder is armed by default with bounded retention, and
Ctrl-C (or a mid-run sys.exit) leaves an `operator_abort` flight dump
carrying the span window + a final metrics snapshot.

Try it:
  python examples/serve_gateway.py --port 8000 &
  curl -N -X POST localhost:8000/v1/generate \
    -d '{"prompt": [11, 7, 19], "max_new_tokens": 8}'
  curl localhost:8000/metrics | head
  curl localhost:8000/healthz
  python tools/serve_monitor.py --scrape http://localhost:8000
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from paddle_tpu.inference import FusedMultiTransformerEngine  # noqa: E402


def main():
    ap = argparse.ArgumentParser(
        description="HTTP/SSE serving gateway over the "
                    "continuous-batching engine")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--num-blocks", type=int, default=64,
                    help="paged-KV pool size (blocks)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--token-budget", type=int, default=None)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (greedy only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed COW prefix sharing")
    ap.add_argument("--shed-on-pressure", action="store_true",
                    help="shed low-priority queued work on SLO burn / "
                         "HBM pressure")
    ap.add_argument("--no-flight-recorder", action="store_true",
                    help="do not arm the anomaly flight recorder "
                         "(armed by default with bounded retention)")
    ap.add_argument("--flight-dir", default=None)
    args = ap.parse_args()

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.observability import SLOMonitor, tracing
    from paddle_tpu.serving import run_gateway

    print(f"platform: {init_platform()}")   # raises with no TPU unless
    #                                         JAX_PLATFORMS=cpu asks for it

    if not args.no_flight_recorder:
        fr = tracing.arm_default(args.flight_dir)
        print(f"flight recorder armed: {fr._dir} "
              f"(max_dumps={fr.max_dumps}, max_bytes={fr.max_bytes})")

    # the serve_llama demo model: random weights, llama-shaped config
    rng = np.random.default_rng(0)
    V, E, H, G, D, L, F = 512, 128, 8, 4, 16, 4, 344
    SMAX = 128

    def mk(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    weights = dict(
        ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E), lm_head=mk(E, V))
    engine = FusedMultiTransformerEngine(
        weights, num_heads=H, head_dim=D, max_seq_len=SMAX,
        dtype="float32", norm_type="rmsnorm", activation="swiglu",
        gqa_group_size=G)

    monitor = SLOMonitor.from_config({
        "cadence_s": 1.0,
        "objectives": [
            {"name": "ttft_p99", "kind": "quantile",
             "metric": "serve_ttft_seconds", "q": 0.99, "max": 60.0},
            {"name": "kv_alloc_failure_ratio", "kind": "ratio",
             "num": "kv_alloc_failures_total",
             "den": "serve_tokens_total", "max": 0.001},
        ]})
    cb = ContinuousBatchingEngine(
        engine, num_blocks=args.num_blocks, block_size=args.block_size,
        max_batch=args.max_batch, prefill_chunk=args.prefill_chunk,
        token_budget=args.token_budget, spec_k=args.spec_k,
        prefix_cache=args.prefix_cache, monitor=monitor,
        shed_on_pressure=args.shed_on_pressure)
    print(f"engine up: vocab {V}, {L} layers, {args.num_blocks} KV "
          f"blocks x {args.block_size}, max_batch {args.max_batch}")
    return run_gateway(cb, host=args.host, port=args.port,
                       monitor=monitor)


if __name__ == "__main__":
    # operator abort (Ctrl-C / sys.exit mid-serve) leaves evidence: the
    # shared wrapper writes an operator_abort flight dump (span window
    # + full metrics snapshot) before exiting 130
    from paddle_tpu.observability import tracing
    sys.exit(tracing.run_with_abort_evidence(main))
