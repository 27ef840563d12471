"""Serving: fused-transformer decode engine with the whole generation loop
compiled as ONE program (prefill + lax.scan decode, donated caches).

Run: python examples/serve_llama.py [--quant int8|int4] [--continuous]
Weight-only quantization halves (int8) or quarters (int4) the decoder
weight HBM — the dequant fuses into the MXU matmul.

--continuous switches to the continuous-batching path: requests of
unequal prompt/output lengths share one paged KV cache through a
host-side block allocator, and every step runs the whole mixed-progress
batch as one compiled program over the ragged paged-attention kernel."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import argparse
import time

import numpy as np

from paddle_tpu.inference import FusedMultiTransformerEngine


def run_continuous(engine, rng, V, args):
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    if not args.no_flight_recorder:
        # server-style entrypoints arm by default with bounded
        # retention: an anomaly mid-serve leaves evidence without a
        # human having opted in first (disable with --no-flight-recorder)
        from paddle_tpu.observability import tracing
        fr = tracing.arm_default(args.flight_dir)
        print(f"flight recorder armed: {fr._dir} "
              f"(max_dumps={fr.max_dumps}, replay dumps with "
              "tools/request_trace.py)")
    cb = ContinuousBatchingEngine(engine, num_blocks=33, block_size=16,
                                  max_batch=args.batch,
                                  prefill_chunk=args.prefill_chunk,
                                  token_budget=args.token_budget,
                                  spec_k=args.spec_k,
                                  prefix_cache=args.prefix_cache)
    free0 = cb.allocator.num_free
    lengths = [(5, 12), (23, 8), (3, 30), (17, 17), (9, 5), (40, 11)]
    if args.prefix_cache:
        # shared system preamble: every request repeats the same
        # 48-token prefix — only the FIRST prefills it; the rest map
        # the cached blocks straight into their block tables
        preamble = rng.integers(1, V, 48).astype(np.int32)
        prompts = [np.concatenate([preamble,
                                   rng.integers(1, V, p).astype(np.int32)])
                   for p, _ in lengths]
        lengths = [(len(pr), n) for pr, (_, n) in zip(prompts, lengths)]
    else:
        prompts = [rng.integers(1, V, p).astype(np.int32)
                   for p, _ in lengths]
    reqs = [GenerationRequest(pr, n) for pr, (_, n) in zip(prompts, lengths)]
    for r in reqs:
        cb.submit(r)
    t0 = time.perf_counter()
    out = cb.run()
    dt = time.perf_counter() - t0
    tok = sum(len(v) for v in out.values())
    print(f"continuous batching: {len(reqs)} ragged requests "
          f"(prompts {[p for p, _ in lengths]}) -> {tok} tokens in "
          f"{cb._step_count} steps, {dt * 1000:.1f} ms; "
          f"free blocks {cb.allocator.num_free}"
          + (f" + {cb.allocator.num_pooled} pooled" if args.prefix_cache
             else "")
          + f"/{free0}")
    drafted = sum(r.spec_drafted for r in reqs)
    if drafted:
        print(f"  speculative: {sum(r.spec_accepted for r in reqs)}"
              f"/{drafted} drafts accepted")
    if cb.tp > 1:
        from paddle_tpu import observability as obs
        rows = cb.device_kv_report()
        comm = obs.get_registry().get("collective_bytes_total")
        total = sum(c.value for c in comm._children.values()) \
            if comm is not None else 0
        print(f"  tensor parallel: tp={cb.tp}, per-device KV high-water "
              f"{rows[0]['kv_bytes_high_water']} B (1/{cb.tp} of "
              f"single-chip), collective payload {int(total)} B "
              f"(psum over 'tp')")
    if args.prefix_cache:
        cached = {r.request_id: cb.explain(r.request_id)
                  ["cached_prefix_tokens"] for r in reqs}
        print(f"  prefix cache: reused tokens per request {cached} "
              f"(shared blocks skip their prefill chunks entirely)")
    for r, (p, n) in zip(reqs, lengths):
        print(f"  req {r.request_id} (prompt {p:2d}, max_new {n:2d}): "
              f"{out[r.request_id][:8]}")
    if args.trace:
        from paddle_tpu.observability import tracing
        path = tracing.write_dump(args.trace, reason="serve_llama",
                                  requests=len(reqs))
        print(f"  trace dump -> {path} "
              "(replay: python tools/request_trace.py " + args.trace + ")")
        for r in reqs:
            ex = cb.explain(r.request_id)
            print(f"  req {r.request_id}: queue_wait "
                  f"{ex['queue_wait_s'] * 1e3:.2f} ms, ttft "
                  f"{ex['ttft_s'] * 1e3:.1f} ms, "
                  f"{len(ex['prefill_chunks'])} prefill chunks, "
                  f"{ex['decode_steps']} decode steps, "
                  f"stalls {sum(ex['stalls'].values())}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quant", choices=["none", "int8", "int4"],
                    default="none")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching serving over the paged "
                         "cache (ragged Pallas kernel)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt tokens consumed per slot per step "
                         "(1 = the old one-token-per-step prefill)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget shared by decode slots "
                         "(1 token each, mandatory) and prompt chunks")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: up to K prompt-lookup "
                         "draft tokens per decode slot per step "
                         "(greedy only; 0 disables)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="(--continuous only) content-addressed sharing "
                         "of full paged-KV blocks across requests: "
                         "repeated prompt prefixes map cached blocks "
                         "instead of re-prefilling (copy-on-write on "
                         "divergence)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="(--continuous only) dump per-request lifecycle "
                         "spans + metrics after the run; replay with "
                         "tools/request_trace.py")
    ap.add_argument("--flight-dir", default=None,
                    help="(--continuous only) flight-recorder dump dir "
                         "(default: $PADDLE_TPU_FLIGHT_DIR or the "
                         "system tmpdir; retention keeps it bounded)")
    ap.add_argument("--no-flight-recorder", action="store_true",
                    help="(--continuous only) do not arm the anomaly "
                         "flight recorder (armed by default with "
                         "bounded retention)")
    ap.add_argument("--tp", type=int, default=1,
                    help="(--continuous only) tensor-parallel width: "
                         "shard the paged serving path over a tp-device "
                         "mesh (kv-head-sharded cache + work-list "
                         "kernel, Megatron column/row weight split, one "
                         "scheduler brain on the host). Off-TPU the "
                         "mesh is virtual CPU devices. Requires heads/"
                         "kv-heads/FFN divisible by tp (here: tp in "
                         "{1, 2, 4})")
    args = ap.parse_args()
    if args.tp > 1:
        if not args.continuous:
            ap.error("--tp needs --continuous (the paged serving path "
                     "is the sharded one; dense generate() is "
                     "single-chip)")
        # must land before the first jax backend init: under
        # JAX_PLATFORMS=cpu the tp mesh runs on virtual CPU devices
        import os
        flag = f"--xla_force_host_platform_device_count={args.tp}"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    from paddle_tpu.framework.platform import init_platform
    print(f"platform: {init_platform()}")   # raises with no TPU unless
    #                                         JAX_PLATFORMS=cpu asks for it
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
        gqa_group_size=G,
        weight_quant=None if args.quant == "none" else args.quant,
        tp=args.tp)

    if args.continuous:
        return run_continuous(engine, rng, V, args)

    prompts = rng.integers(0, V, (args.batch, 16)).astype(np.int32)
    engine.generate(prompts, max_new_tokens=args.new_tokens)  # compile
    t0 = time.perf_counter()
    out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                          temperature=0.8, top_p=0.95, seed=7)
    dt = time.perf_counter() - t0
    print(f"quant={args.quant}: generated {out.shape} in {dt * 1000:.1f} ms "
          f"({args.batch * args.new_tokens / dt:.0f} tok/s)")
    print("sampled ids[0]:", out[0][:16].tolist())


if __name__ == "__main__":
    # operator abort (Ctrl-C / sys.exit mid-serve) leaves evidence
    # instead of dying mid-step with none: the shared wrapper writes an
    # operator_abort flight dump (span window + full metrics snapshot)
    from paddle_tpu.observability import tracing
    sys.exit(tracing.run_with_abort_evidence(main))
