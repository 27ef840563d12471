"""Device-time serving benchmark.

This tool measures DEVICE time: it captures an XLA device trace around
`generate()` and reads the per-program device durations from the "XLA
Modules" lane — `jit_steps` (the whole decode loop as ONE lax.scan
program) and `jit_prefill` appear as separate entries, so decode
tokens/s excludes the host and the prefill.

Legs:
  - bf16 / weight-only int8 / weight-only int4 decode at the flagship
    GQA shape (24L/1024E, 16 q-heads / 8 kv-heads, B=8) via
    FusedMultiTransformerEngine
  - the ragged paged kernel's grid accounting over a ragged batch
    (ops/pallas/paged_attention.py)

Usage: python tools/serve_bench.py [--json out.json]
Reference bar: the fused_multi_transformer int8 inference tier,
paddle/phi/kernels/fusion/gpu/fused_multi_transformer_int8_kernel.cu.
"""
import argparse
import collections
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _module_device_ms(trace_dir):
    """{module_name_prefix: total device ms} from the XLA Modules lane."""
    f = sorted(glob.glob(trace_dir + "/**/*.trace.json.gz",
                         recursive=True))[-1]
    with gzip.open(f) as fh:
        tr = json.load(fh)
    ev = tr.get("traceEvents")
    if not isinstance(ev, list):
        raise SystemExit(
            f"serve_bench: {f} has no traceEvents list — "
            "profiler schema drift or truncated capture")
    tids = {e["tid"]: e["args"]["name"] for e in ev
            if e.get("ph") == "M" and e.get("name") == "thread_name"
            and e.get("pid") == 3}
    out = collections.Counter()
    for e in ev:
        if e.get("ph") == "X" and e.get("pid") == 3 \
                and tids.get(e.get("tid")) == "XLA Modules":
            name = e["name"].split("(")[0]
            out[name] += e.get("dur", 0) / 1e3  # us -> ms
    return dict(out)


def _capture(fn):
    import jax
    d = tempfile.mkdtemp(prefix="serve_bench_")
    fn()  # warm/compile outside the trace
    jax.profiler.start_trace(d)
    fn()
    jax.profiler.stop_trace()
    mods = _module_device_ms(d)
    shutil.rmtree(d, ignore_errors=True)
    return mods


def decode_leg(weight_quant, B=8, NEW=64):
    import numpy as np

    from paddle_tpu.inference import FusedMultiTransformerEngine

    rng = np.random.default_rng(0)
    V, E, H, G, D, L, F = 32000, 1024, 16, 8, 64, 24, 2816
    SMAX = 512

    def mk(*shape, scale=0.02):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    w = dict(
        ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E), lm_head=mk(E, V))
    eng = FusedMultiTransformerEngine(
        w, num_heads=H, head_dim=D, max_seq_len=SMAX, dtype="bfloat16",
        norm_type="rmsnorm", activation="swiglu", gqa_group_size=G,
        weight_quant=weight_quant)
    ids = rng.integers(0, V, (B, 16)).astype(np.int32)

    mods = _capture(lambda: eng.generate(ids, max_new_tokens=NEW))
    # the scanned decode program; bucketing may name it jit_steps
    decode_ms = sum(v for k, v in mods.items() if "steps" in k)
    if decode_ms == 0:
        raise RuntimeError(f"no decode module in trace: {mods}")
    # NEW is bucketed inside generate() to the smallest power of two
    # >= NEW-1 (the prefill already emitted token 1), clamped to the cache
    n_run = 1 << max(0, NEW - 2).bit_length() if NEW > 1 else 0
    n_run = min(n_run, 512 - 16)
    return {
        "decode_device_ms": decode_ms,
        "decode_tokens": B * n_run,
        "decode_tok_per_s": B * n_run / (decode_ms / 1e3),
        "prefill_device_ms": sum(v for k, v in mods.items()
                                 if "prefill" in k),
    }


def ragged_leg(iters=4):
    """The ragged work-list grid over a RAGGED batch at the round-5
    decode-attention shape, beside what a B x KVH x max_blocks grid
    (`legacy_grid_steps`, arithmetic) would walk. Grid-step counts are
    exact host math (they gate in --check); the call's timing is
    whole-call wall-clock on EVERY platform (dispatch included), recorded
    for context only — under CPU interpret it measures the interpreter,
    not the chip."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.ops.pallas import paged_attention as pa

    on_tpu = init_platform() == "tpu"
    B, H, KVH, D, BS = 8, 16, 8, 64, 64
    max_nb = 7                      # 448-token capacity (round-5 ctx)
    lens = np.array([448, 64, 192, 27, 448, 1, 320, 100], np.int32)
    nb = B * max_nb + 1
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, H, D)), dt)
    kc = jnp.asarray(rng.standard_normal((KVH, nb, BS, D)), dt)
    vc = jnp.asarray(rng.standard_normal((KVH, nb, BS, D)), dt)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, nb))[:B * max_nb].reshape(B, max_nb),
        jnp.int32)
    lens_j = jnp.asarray(lens)
    pack = pa.default_pack(B, H // KVH)
    work, t_real, t_total, pack = pa.build_ragged_work(
        np.asarray(tables), lens, BS, pack)
    total_blocks = int(sum(-(-int(x) // BS) for x in lens))
    out = {
        "shape": {"B": B, "H": H, "KVH": KVH, "D": D, "block_size": BS,
                  "max_blocks": max_nb},
        "context_lens": lens.tolist(),
        "pack": pack,
        "total_kv_blocks": total_blocks,
        "work_items": t_real,
        "legacy_grid_steps": B * KVH * max_nb,
        "ragged_grid_steps": KVH * t_total,
        "interpret": not on_tpu,
    }

    kv = jnp.stack([kc, vc])

    def call():
        return pa.ragged_paged_attention(
            q, kv, tables, lens_j, work=(work, t_real, t_total, pack))

    jax.block_until_ready(call())
    t0 = time.perf_counter()
    for _ in range(iters):
        o = call()
    jax.block_until_ready(o)
    out["ragged_call_us"] = (time.perf_counter() - t0) / iters * 1e6
    return out


_TINY_DIMS = (128, 64, 4, 2, 16, 2, 96)     # V, E, H, G, D, L, F


def _tiny_cpu_weights(rng):
    """Raw fp32 weights for the CPU-sized serving engine (V=128/E=64/
    L=2, GQA 4q/2kv) — split out so the --quant leg can build dense AND
    weight-quant engines over the SAME draws."""
    import numpy as np

    V, E, H, G, D, L, F = _TINY_DIMS

    def mk(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    return dict(
        ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E), lm_head=mk(E, V))


def _tiny_cpu_engine(rng, max_seq_len, **engine_kw):
    """The CPU-sized serving engine both the --metrics and --prefill legs
    drive. Takes the caller's rng so the weight draws stay at the head
    of its stream — prompt draws follow from the same generator, keeping
    committed baselines reproducible. Extra kwargs (weight_quant,
    autotune_cache, ...) pass through to the engine constructor."""
    from paddle_tpu.inference import FusedMultiTransformerEngine

    V, E, H, G, D, L, F = _TINY_DIMS
    eng = FusedMultiTransformerEngine(
        _tiny_cpu_weights(rng), num_heads=H, head_dim=D,
        max_seq_len=max_seq_len, dtype="float32", norm_type="rmsnorm",
        activation="swiglu", gqa_group_size=G, **engine_kw)
    return eng, V


def serving_metrics_leg():
    """Continuous-batching serving with the observability layer on: drive
    `ContinuousBatchingEngine.run()` over a ragged request mix (CPU-sized
    engine, interpret mode off-TPU) and read the registry back as
    p50/p95/p99 TTFT / per-output-token latency, KV-pool gauges, the
    bucket-recompile counter, and the jax compile watch — the metrics
    snapshot BASELINE.md commits and the acceptance gate asserts on.

    Latency numbers off-TPU measure the Pallas interpreter, not the
    chip (same caveat as the ragged leg's call timings): the committed
    percentiles are shape/coverage evidence, not speed claims."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    obs.install_compile_watch()

    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=32)
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=4)
    # ragged mix (prompt len, new tokens): same spread-of-lengths spirit
    # as the ragged leg's context_lens, scaled to the tiny capacity;
    # 6 requests > 4 slots forces queueing + mid-flight retirement
    workload = [(5, 4), (11, 3), (3, 6), (8, 2), (6, 5), (12, 3)]
    reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n)
            for p, n in workload]
    for r in reqs:
        cb.submit(r)
    done = cb.run()
    assert sorted(len(v) for v in done.values()) == \
        sorted(n for _, n in workload)

    reg = obs.get_registry()

    def pcts(hist_name):
        h = reg.get(hist_name)
        if h is None or h.count == 0:
            return None
        return {f"p{int(q * 100)}": round(h.quantile(q) * 1e3, 3)
                for q in (0.5, 0.95, 0.99)}

    snap = reg.snapshot()

    def children(name):
        return {k: v["value"]
                for k, v in snap.get(name, {}).get("children", {}).items()}

    backend_compiles = sum(
        v for k, v in children("jax_compiles_total").items()
        if k.startswith("backend_compile"))
    out = {
        "interpret": not on_tpu,
        "workload": workload,
        "requests": len(workload),
        "tokens_generated": reg.get("serve_tokens_total").value,
        "steps": cb._step_count,
        "percentiles": {
            "ttft_ms": pcts("serve_ttft_seconds"),
            "tpot_ms": pcts("serve_time_per_output_token_seconds"),
            "queue_wait_ms": pcts("serve_queue_wait_seconds"),
        },
        "kv_pool": {
            "blocks_free_final": reg.get("kv_blocks_free").value,
            "blocks_high_water": reg.get("kv_blocks_high_water").value,
            "alloc_failures": (reg.get("kv_alloc_failures_total").value
                               if reg.get("kv_alloc_failures_total")
                               else 0.0),
        },
        "bucket_recompiles": children("serve_bucket_recompiles_total"),
        "jax_backend_compiles": backend_compiles,
        "exporters": {
            "prometheus_lines": len(obs.to_prometheus().splitlines()),
            "json_metrics": len(snap),
            "chrome_counter_events": len(obs.chrome_counter_events()),
        },
    }
    return out


def prefill_leg(chunk=64, prompt_lens=(64, 256, 512), block_size=64):
    """Chunked vs unchunked prefill TTFT: drive the continuous-batching
    engine with a single P-token prompt and count the steps (and host
    wall) until its FIRST token lands. Unchunked (prefill_chunk=1, the
    PR-1 behaviour) pays P compiled steps; chunked pays ceil(P/chunk).
    Steps-to-first-token is host-deterministic and is the gated claim;
    wall TTFT is context (off-TPU it times the Pallas interpreter, not
    the chip). Both variants share one FusedMultiTransformerEngine so
    the measured pass runs against warm compile caches."""
    import time

    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=max(prompt_lens) * 2)
    num_blocks = max(prompt_lens) // block_size + 3

    def first_token(prompt, prefill_chunk):
        cb = ContinuousBatchingEngine(
            eng, num_blocks=num_blocks, block_size=block_size,
            max_batch=1, prefill_chunk=prefill_chunk)
        req = GenerationRequest(prompt, 2)
        cb.submit(req)
        # the compiled step that sampled the first token, by the label
        # `on_token` carries (steps count from 0 as dispatched); the
        # token reaches the host one `step()` call after that step's
        # dispatch, the scheduler looks one step ahead
        first = []
        cb.on_token = lambda rid, toks, step: first.append(step)
        t0 = time.monotonic()
        calls = 0
        while not first:
            cb.step()
            calls += 1
            if calls > len(prompt) + 4:
                raise RuntimeError("first token never arrived")
        return (first[0] + 1, (time.monotonic() - t0) * 1e3,
                len(cb._seen_buckets))

    out = {"chunk": chunk, "block_size": block_size,
           "interpret": not on_tpu, "prompts": {}}
    for p_len in prompt_lens:
        prompt = rng.integers(1, V, p_len).astype(np.int32)
        row = {"expected_chunked_steps": -(-p_len // chunk)}
        for label, pc in (("unchunked", 1), ("chunked", chunk)):
            first_token(prompt, pc)      # warm the compile caches
            steps, ttft_ms, buckets = first_token(prompt, pc)
            row[f"{label}_steps_to_first_token"] = steps
            row[f"{label}_ttft_ms"] = round(ttft_ms, 1)
            row[f"{label}_buckets"] = buckets
        assert row["chunked_steps_to_first_token"] == \
            row["expected_chunked_steps"], row
        out["prompts"][str(p_len)] = row
        print(f"prefill[P={p_len}]: steps-to-first-token "
              f"{row['unchunked_steps_to_first_token']} unchunked vs "
              f"{row['chunked_steps_to_first_token']} chunked "
              f"(chunk={chunk}); TTFT {row['unchunked_ttft_ms']:.0f} ms "
              f"vs {row['chunked_ttft_ms']:.0f} ms"
              + (" [interpret: times the interpreter, not the chip]"
                 if not on_tpu else ""))
    return out


def spec_leg(spec_k=4, new_tokens=24, include_spec=True):
    """Speculative vs decode-1 continuous batching on a REPETITIVE
    workload (the prompt-lookup sweet spot: repeated n-grams + the
    self-repeating loops greedy decoding falls into). Both runs must be
    token-exact; the speculative one must finish in FEWER compiled
    steps. Steps, draft/accept counts, and the after-warmup bucket
    delta are host-deterministic (greedy fp32) and gate in --check;
    wall time is not measured at all — off-TPU it would time the Pallas
    interpreter."""
    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=128)
    pattern = [7, 23, 41, 11]
    prompts = [np.asarray(pattern * 8, np.int32),      # 32 tokens
               np.asarray(pattern * 4, np.int32)]      # 16 tokens

    def run(k):
        cb = ContinuousBatchingEngine(eng, num_blocks=24, block_size=8,
                                      max_batch=2, prefill_chunk=8,
                                      spec_k=k)
        def submit():
            reqs = [GenerationRequest(p.copy(), new_tokens)
                    for p in prompts]
            for r in reqs:
                cb.submit(r)
            return reqs
        reqs = submit()
        out = cb.run()
        steps = cb._step_count
        warm = set(cb._seen_buckets)
        reqs2 = submit()                # same workload again: warm replay
        out2 = cb.run()
        return {
            "steps": steps,
            "tokens": sum(len(out[r.request_id]) for r in reqs),
            "drafted": sum(r.spec_drafted for r in reqs),
            "accepted": sum(r.spec_accepted for r in reqs),
            "new_buckets_after_warmup": len(set(cb._seen_buckets) - warm),
            "outputs": [out[r.request_id] for r in reqs],
        }

    s_off = run(0)
    if not include_spec:
        # --no-spec: just the decode-1 reference side
        out = {
            "interpret": not on_tpu,
            "prompt_lens": [len(p) for p in prompts],
            "new_tokens": new_tokens,
            "tokens_per_run": s_off["tokens"],
            "steps_nospec": s_off["steps"],
            "steps_per_token_nospec": round(
                s_off["steps"] / s_off["tokens"], 4),
        }
        print(f"no-spec: {out['steps_nospec']} decode-1 steps for "
              f"{out['tokens_per_run']} tokens "
              f"({out['steps_per_token_nospec']} steps/token)")
        return out
    s_on = run(spec_k)
    assert s_on["outputs"] == s_off["outputs"], \
        "speculative decoding is not token-exact vs decode-1"
    out = {
        "interpret": not on_tpu,
        "spec_k": spec_k,
        "prompt_lens": [len(p) for p in prompts],
        "new_tokens": new_tokens,
        "tokens_per_run": s_on["tokens"],
        "steps_spec": s_on["steps"],
        "steps_nospec": s_off["steps"],
        "steps_per_token_spec": round(s_on["steps"] / s_on["tokens"], 4),
        "steps_per_token_nospec": round(s_off["steps"] / s_off["tokens"],
                                        4),
        "drafted": s_on["drafted"],
        "accepted": s_on["accepted"],
        "accept_rate": round(s_on["accepted"] / s_on["drafted"], 4)
        if s_on["drafted"] else 0.0,
        "new_buckets_after_warmup": s_on["new_buckets_after_warmup"],
    }
    print(f"spec[k={spec_k}]: {out['steps_spec']} steps vs "
          f"{out['steps_nospec']} decode-1 for {out['tokens_per_run']} "
          f"tokens ({out['steps_per_token_spec']} vs "
          f"{out['steps_per_token_nospec']} steps/token); acceptance "
          f"{out['accepted']}/{out['drafted']} = "
          f"{out['accept_rate']:.0%}; "
          f"{out['new_buckets_after_warmup']} new buckets after warmup")
    return out


def trace_leg(chunk=4, new_tokens=5):
    """Per-request lifecycle tracing on the fixed ragged workload:
    tracing must be TOKEN-EXACT-NEUTRAL (same outputs, same step count,
    zero new compile buckets with the span ring on) and span counts per
    request are pure host math — ceil(P/chunk) prefill_chunk spans, one
    queue_wait, new_tokens-1 decode spans — so they gate in --check
    exactly like the grid-step counts. Wall times (on vs off) are
    recorded for the BASELINE.md overhead table but NOT gated: off-TPU
    they time the Pallas interpreter, not the tracer."""
    import time

    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=32)
    workload = [(5, new_tokens), (11, new_tokens), (3, new_tokens)]
    prompts = [rng.integers(1, V, p).astype(np.int32) for p, _ in workload]
    tracer = obs.get_tracer()

    def run(traced):
        cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                      max_batch=2, prefill_chunk=chunk)
        # string request ids: the auto counter is process-global, so
        # committed span-count keys must not depend on how many
        # requests OTHER legs created first
        reqs = [GenerationRequest(p.copy(), n, request_id=f"tr{j}")
                for j, (p, (_, n)) in enumerate(zip(prompts, workload))]
        tracer.clear()
        prev, tracer.enabled = tracer.enabled, traced
        t0 = time.perf_counter()
        try:
            for r in reqs:
                cb.submit(r)
            out = cb.run()
        finally:
            tracer.enabled = prev
        wall_ms = (time.perf_counter() - t0) * 1e3
        counts = {}
        for r in reqs:
            per = {}
            for s in tracer.spans(request=r.request_id):
                per[s["name"]] = per.get(s["name"], 0) + 1
            counts[str(r.request_id)] = per
        return (cb, [out[r.request_id] for r in reqs], cb._step_count,
                wall_ms, counts)

    cb_w, out_w, steps_w, _, _ = run(traced=True)       # warm compiles
    warm_buckets = set(cb_w._seen_buckets)
    cb_on, out_on, steps_on, wall_on, counts = run(traced=True)
    _, out_off, steps_off, wall_off, counts_off = run(traced=False)
    assert out_on == out_off, "tracing changed generated tokens"
    assert counts_off == {str(r): {} for r in counts}, \
        f"disabled tracer still recorded: {counts_off}"
    expected = {}
    for (p_len, n), rid in zip(workload, counts):
        expected[rid] = {"submit": 1, "queue_wait": 1,
                         "prefill_chunk": -(-p_len // chunk),
                         "first_token": 1, "decode": n - 1, "retire": 1}
    out = {
        "interpret": not on_tpu,
        "chunk": chunk,
        "workload": [list(w) for w in workload],   # json-stable
        "steps_traced": steps_on,
        "steps_untraced": steps_off,
        "new_buckets_after_warmup": len(set(cb_on._seen_buckets)
                                        - warm_buckets),
        "span_counts": counts,
        "expected_span_counts": expected,
        "wall_ms_traced": round(wall_on, 1),
        "wall_ms_untraced": round(wall_off, 1),
        "spans_recorded": sum(sum(c.values()) for c in counts.values()),
    }
    # flight-recorder roundtrip on the SAME workload: a forced
    # post-warmup recompile (wider prompt -> fresh work-list bucket)
    # must dump, and the dump must load through the schema validator
    import shutil
    import tempfile

    d = tempfile.mkdtemp(prefix="serve_trace_")
    try:
        cb_on.declare_warm()
        obs.get_flight_recorder().arm(d, window_s=120.0)
        # two concurrent longer prompts push the work list past every
        # bucket the fixed workload warmed — a guaranteed fresh
        # (work, chunk) pair, i.e. a post-warmup recompile
        big = GenerationRequest(rng.integers(1, V, 23).astype(np.int32),
                                2, request_id="trbig")
        big2 = GenerationRequest(rng.integers(1, V, 21).astype(np.int32),
                                 2, request_id="trbig2")
        cb_on.submit(big)
        cb_on.submit(big2)
        cb_on.run()
        dumps = [f for f in os.listdir(d)
                 if f.startswith("flightrec_post_warmup_recompile")]
        # both keys ALWAYS present: a regression that stops the dump
        # must gate as a MISMATCH, not crash check_trace on a KeyError
        out["flight_dump_written"] = len(dumps) >= 1
        out["flight_dump_loads"] = False
        if dumps:
            dump = obs.load_dump(os.path.join(d, dumps[0]))
            out["flight_dump_loads"] = (
                dump["reason"] == "post_warmup_recompile"
                and big.request_id in dump["requests"])
    finally:
        obs.get_flight_recorder().disarm()
        shutil.rmtree(d, ignore_errors=True)
    print(f"trace leg: {steps_on} steps traced vs {steps_off} untraced, "
          f"{out['spans_recorded']} spans, "
          f"{out['new_buckets_after_warmup']} new buckets after warmup; "
          f"wall {wall_on:.0f} vs {wall_off:.0f} ms"
          + (" [interpret: wall times the interpreter, not the tracer]"
             if not on_tpu else ""))
    return out


def prefix_leg(n_requests=8, prefix_len=448, suffix_len=8, chunk=64,
               block_size=64, new_tokens=4):
    """Automatic prefix caching: N requests sharing a long prompt prefix
    (the system-prompt / few-shot-preamble shape). Three shared runs on
    ONE engine — cold (leader computes, followers wavefront-map), resume
    (every block served from the LRU reuse pool after the first wave
    retired), and a warm replay of resume (the zero-new-buckets gate) —
    against an unshared reference. The gated claims are host math:
    prefill chunk sweeps over the SHARED portion drop to 1/N (one sweep
    per unique prefix), KV-pool high-water drops from N*blocks to
    ~blocks + N*tail, and outputs are token-exact in every mode. Wall
    time is not measured (off-TPU it times the Pallas interpreter)."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    rng = np.random.default_rng(0)
    eng, V = _tiny_cpu_engine(rng, max_seq_len=512)
    prefix = rng.integers(1, V, prefix_len).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, V, suffix_len)
                               .astype(np.int32)])
               for _ in range(n_requests)]
    blocks_per_req = -(-(prefix_len + suffix_len + new_tokens)
                       // block_size)
    num_blocks = n_requests * blocks_per_req + 4
    tracer = obs.get_tracer()

    def submit_and_run(cb, tag):
        reqs = [GenerationRequest(p.copy(), new_tokens,
                                  request_id=f"{tag}{j}")
                for j, p in enumerate(prompts)]
        tracer.clear()
        step0 = cb._step_count
        for r in reqs:
            cb.submit(r)
        out = cb.run()
        # prefill chunk sweeps, split at the shared-prefix boundary:
        # a chunk whose span starts before prefix_len swept shared
        # prompt; the rest is each request's unique tail
        total = on_prefix = 0
        for s in tracer.spans():
            if s["name"] != "prefill_chunk":
                continue
            total += 1
            a = s["args"]
            if a["granted"] and a["progress"] - a["granted"] < prefix_len:
                on_prefix += 1
        return {
            "steps": cb._step_count - step0,
            "prefill_chunks": total,
            "prefill_chunks_on_prefix": on_prefix,
            "cached_prefix_tokens": sum(r.cached_prefix for r in reqs),
            "outputs": [out[r.request_id] for r in reqs],
        }

    cb_off = ContinuousBatchingEngine(
        eng, num_blocks=num_blocks, block_size=block_size,
        max_batch=n_requests, prefill_chunk=chunk, prefix_cache=False)
    unshared = submit_and_run(cb_off, "pu")
    unshared["high_water"] = cb_off.allocator.high_water

    cb = ContinuousBatchingEngine(
        eng, num_blocks=num_blocks, block_size=block_size,
        max_batch=n_requests, prefill_chunk=chunk, prefix_cache=True)
    cold = submit_and_run(cb, "pc")
    cold["high_water"] = cb.allocator.high_water
    resume = submit_and_run(cb, "pr")       # conversation-resume: every
    warm = set(cb._seen_buckets)            # prefix block is pooled now
    replay = submit_and_run(cb, "pw")
    new_buckets = len(set(cb._seen_buckets) - warm)

    exact = (cold["outputs"] == unshared["outputs"]
             and resume["outputs"] == unshared["outputs"]
             and replay["outputs"] == unshared["outputs"])
    out = {
        "interpret": not on_tpu,
        "n_requests": n_requests,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "chunk": chunk,
        "block_size": block_size,
        "new_tokens": new_tokens,
        "token_exact_all_modes": exact,
        "new_buckets_after_warmup": new_buckets,
        "cache": {"hits": cb.cache_stats["hit_blocks"],
                  "misses": cb.cache_stats["miss_blocks"],
                  "cow_copies": cb.cache_stats["cow_copies"],
                  "pooled_final": cb.allocator.num_pooled,
                  "evictions": cb.allocator.evictions},
        "unshared": {k: unshared[k] for k in
                     ("steps", "prefill_chunks",
                      "prefill_chunks_on_prefix", "high_water")},
        "shared_cold": {k: cold[k] for k in
                        ("steps", "prefill_chunks",
                         "prefill_chunks_on_prefix",
                         "cached_prefix_tokens", "high_water")},
        "shared_resume": {k: resume[k] for k in
                          ("steps", "prefill_chunks",
                           "prefill_chunks_on_prefix",
                           "cached_prefix_tokens")},
    }
    print(f"prefix[{n_requests}x{prefix_len}+{suffix_len} chunk={chunk}]: "
          f"prefix-portion chunk sweeps "
          f"{unshared['prefill_chunks_on_prefix']} unshared -> "
          f"{cold['prefill_chunks_on_prefix']} shared -> "
          f"{resume['prefill_chunks_on_prefix']} resume; "
          f"high-water {unshared['high_water']} -> {cold['high_water']}; "
          f"token-exact={exact}, {new_buckets} new buckets after warmup")
    return out


def _tiny_tp_engine(weights, tp):
    """One engine per mesh width over SHARED weights: 8 q heads / 8 kv
    heads (GQA packing) so the kv-head axis splits at tp = 1/2/4/8 on
    the virtual 8-device mesh."""
    from paddle_tpu.inference import FusedMultiTransformerEngine

    return FusedMultiTransformerEngine(
        dict(weights), num_heads=8, head_dim=8, max_seq_len=64,
        dtype="float32", norm_type="rmsnorm", activation="swiglu",
        gqa_group_size=8, tp=tp)


def _tp_weights(rng):
    V, E, H, G, D, L, F = 128, 64, 8, 8, 8, 2, 96

    def mk(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype("float32")

    import numpy as np
    w = dict(
        ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E), lm_head=mk(E, V))
    return w, V, L, E


def tp_leg(tps=(1, 2, 4, 8)):
    """Tensor-parallel serving on the virtual 8-device mesh
    (`__graft_entry__.dryrun_multichip` pattern: force the CPU platform,
    fake the device count). For each mesh width the SAME host-side
    scheduler drives the kv-head-sharded engine through plain / chunked
    / spec / prefix workloads; the gated claims are host-deterministic:

      * token-exact vs the tp=1 engine in every mode,
      * per-device KV high-water BYTES exactly 1/tp of single-chip
        (same block count — each device holds KVH/tp heads of every
        block),
      * per-step collective payload (2 psums/layer over the [B, C, E]
        slab) matches the aval math and lands in
        collective_bytes_total{op="psum",axis="tp"},
      * zero new compile buckets after warmup, per mesh shape.

    Wall time is not measured: off-TPU it times the Pallas interpreter
    (the per-device grid is 1/tp of the single-chip one, so the
    interpret-mode total is ~constant in tp — a real mesh splits it)."""
    import jax
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    need = max(tps)
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"tp leg needs {need} devices (run with "
            f"--xla_force_host_platform_device_count={need}; the --tp "
            "flag sets it when it runs before jax initializes)")
    rng = np.random.default_rng(0)
    weights, V, L, E = _tp_weights(rng)
    block_size = 8
    workload = [(5, 4), (11, 3), (3, 6), (8, 2)]
    pattern = [7, 23, 41, 11]
    prefix_toks = rng.integers(1, V, 24).astype(np.int32)
    uid = [0]

    def tag(p):
        uid[0] += 1
        return f"{p}{uid[0]}"

    def modes(engine):
        out = {}
        runs = {}

        def drive(cb, reqs):
            for r in reqs:
                cb.submit(r)
            res = cb.run()
            return [list(res[r.request_id]) for r in reqs]

        # plain FIFO over the ragged mix
        cb = ContinuousBatchingEngine(engine, num_blocks=24,
                                      block_size=block_size, max_batch=4)
        prng = np.random.default_rng(7)
        toks = drive(cb, [GenerationRequest(
            prng.integers(1, V, p).astype(np.int32), n,
            request_id=tag("tp_pl")) for p, n in workload])
        runs["plain"] = {"outputs": toks, "steps": cb._step_count,
                         "high_water_blocks": cb.allocator.high_water}
        # chunked prefill under a token budget (+ the warm-replay
        # bucket gate rides this config)
        cb = ContinuousBatchingEngine(engine, num_blocks=24,
                                      block_size=block_size, max_batch=4,
                                      prefill_chunk=4, token_budget=6)
        prng = np.random.default_rng(7)
        toks = drive(cb, [GenerationRequest(
            prng.integers(1, V, p).astype(np.int32), n,
            request_id=tag("tp_ch")) for p, n in workload])
        cb.declare_warm()
        warm = set(cb._seen_buckets)
        prng = np.random.default_rng(5)
        drive(cb, [GenerationRequest(
            prng.integers(1, V, p).astype(np.int32), n,
            request_id=tag("tp_cw")) for p, n in workload])
        runs["chunked"] = {
            "outputs": toks, "steps": cb._step_count,
            "new_buckets_after_warmup":
                len(set(cb._seen_buckets) - warm)}
        # speculative decode on the repetitive workload
        cb = ContinuousBatchingEngine(engine, num_blocks=24,
                                      block_size=block_size, max_batch=2,
                                      prefill_chunk=8, spec_k=4)
        reqs = [GenerationRequest(np.asarray(pattern * 6, np.int32), 10,
                                  request_id=tag("tp_sp")),
                GenerationRequest(np.asarray(pattern * 3, np.int32), 10,
                                  request_id=tag("tp_sp"))]
        toks = drive(cb, reqs)
        runs["spec"] = {"outputs": toks, "steps": cb._step_count,
                        "drafted": sum(r.spec_drafted for r in reqs),
                        "accepted": sum(r.spec_accepted for r in reqs)}
        # prefix cache over a shared preamble
        cb = ContinuousBatchingEngine(engine, num_blocks=24,
                                      block_size=block_size, max_batch=4,
                                      prefill_chunk=8, prefix_cache=True)
        prng = np.random.default_rng(3)
        toks = drive(cb, [GenerationRequest(
            np.concatenate([prefix_toks,
                            prng.integers(1, V, 3).astype(np.int32)]),
            4, request_id=tag("tp_pf")) for _ in range(4)])
        runs["prefix"] = {"outputs": toks, "steps": cb._step_count,
                          "cache_hits": cb.cache_stats["hit_blocks"],
                          "cow_copies": cb.cache_stats["cow_copies"]}
        out["runs"] = runs
        out["tokens"] = sum(
            len(t) for t in runs["plain"]["outputs"])
        out["kv_device_high_water_bytes"] = (
            runs["plain"]["high_water_blocks"]
            * engine.kv_device_block_bytes(block_size))
        return out

    reg = obs.get_registry()

    def coll_bytes():
        fam = reg.get("collective_bytes_total")
        return sum(c.value for c in fam._children.values()) \
            if fam is not None else 0.0

    per_tp = {}
    for tp in tps:
        b0 = coll_bytes()
        engine = _tiny_tp_engine(weights, tp)
        r = modes(engine)
        r["collective_bytes"] = int(coll_bytes() - b0)
        per_tp[str(tp)] = r
        print(f"tp[{tp}]: plain {r['runs']['plain']['steps']} steps / "
              f"{r['tokens']} tokens, spec "
              f"{r['runs']['spec']['accepted']}/"
              f"{r['runs']['spec']['drafted']} accepted, per-device KV "
              f"high-water {r['kv_device_high_water_bytes']} B, "
              f"collective {r['collective_bytes']} B, "
              f"{r['runs']['chunked']['new_buckets_after_warmup']} new "
              "buckets after warmup")

    base = per_tp[str(tps[0])]
    exact = {}
    for tp in tps[1:]:
        exact[str(tp)] = all(
            per_tp[str(tp)]["runs"][m]["outputs"]
            == base["runs"][m]["outputs"]
            for m in ("plain", "chunked", "spec", "prefix"))
    out = {
        "interpret": not on_tpu,
        "shape": {"V": V, "E": E, "H": 8, "KVH": 8, "D": 8, "L": L,
                  "block_size": block_size},
        "tps": list(tps),
        "workload": [list(w) for w in workload],
        "token_exact": exact,
        "steps": {m: base["runs"][m]["steps"]
                  for m in ("plain", "chunked", "spec", "prefix")},
        "spec": {"drafted": base["runs"]["spec"]["drafted"],
                 "accepted": base["runs"]["spec"]["accepted"]},
        "prefix": {"cache_hits": base["runs"]["prefix"]["cache_hits"],
                   "cow_copies": base["runs"]["prefix"]["cow_copies"]},
        "effective_tokens_per_step": round(
            base["tokens"] / base["runs"]["plain"]["steps"], 4),
        "kv_high_water_blocks": base["runs"]["plain"]
        ["high_water_blocks"],
        "kv_device_high_water_bytes": {
            str(tp): per_tp[str(tp)]["kv_device_high_water_bytes"]
            for tp in tps},
        "collective_bytes": {
            str(tp): per_tp[str(tp)]["collective_bytes"] for tp in tps},
        "new_buckets_after_warmup": {
            str(tp): per_tp[str(tp)]["runs"]["chunked"]
            ["new_buckets_after_warmup"] for tp in tps},
    }
    print(f"tp leg: token-exact {exact}, per-device KV high-water "
          f"{out['kv_device_high_water_bytes']} (1/tp scaling), "
          f"eff tokens/step {out['effective_tokens_per_step']}")
    return out


TP_KEYS = ("shape", "tps", "workload", "token_exact", "steps", "spec",
           "prefix", "effective_tokens_per_step", "kv_high_water_blocks",
           "kv_device_high_water_bytes", "collective_bytes",
           "new_buckets_after_warmup")


def check_tp(base):
    """CI gate for tensor-parallel serving: every mode token-exact vs
    single-chip at TP=2/4/8, per-device KV high-water bytes exactly
    1/tp of the single-chip figure, deterministic collective payload,
    and zero new compile buckets after warmup on every mesh shape —
    all against the committed baseline."""
    cur = tp_leg()
    bad = [k for k in TP_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if not all(cur["token_exact"].values()):
        print("REGRESSION: tensor-parallel serving is not token-exact "
              f"vs single-chip: {cur['token_exact']}")
        bad.append("token_exact")
    hw = cur["kv_device_high_water_bytes"]
    for tp, v in hw.items():
        if int(tp) > 1 and v * int(tp) != hw["1"]:
            print(f"REGRESSION: per-device KV high-water at tp={tp} is "
                  f"{v}, not 1/{tp} of single-chip {hw['1']}")
            bad.append("kv_device_high_water_bytes")
    if any(cur["new_buckets_after_warmup"].values()):
        print("REGRESSION: a mesh shape compiled fresh buckets after "
              f"warmup: {cur['new_buckets_after_warmup']}")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print(f"tp leg OK: TP={cur['tps']} token-exact, per-device KV "
          f"high-water {hw} (1/tp), collective "
          f"{cur['collective_bytes']} B, 0 new buckets")
    return 0


PREFIX_KEYS = ("n_requests", "prefix_len", "suffix_len", "chunk",
               "block_size", "new_tokens", "token_exact_all_modes",
               "new_buckets_after_warmup", "cache", "unshared",
               "shared_cold", "shared_resume")


def check_prefix(base):
    """CI gate for the prefix-caching leg: the chunk-sweep / high-water
    accounting is host-deterministic and must match the committed
    baseline; the shared run must sweep the shared portion exactly once
    (1/N of the unshared run), every mode must stay token-exact, and
    warmup must cover every compile bucket."""
    cur = prefix_leg()
    bad = [k for k in PREFIX_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if not cur["token_exact_all_modes"]:
        print("REGRESSION: prefix caching changed generated tokens")
        bad.append("token_exact_all_modes")
    n = cur["n_requests"]
    if cur["shared_cold"]["prefill_chunks_on_prefix"] * n != \
            cur["unshared"]["prefill_chunks_on_prefix"]:
        print("REGRESSION: shared run did not sweep the shared prefix "
              f"exactly once per unique prefix "
              f"({cur['shared_cold']['prefill_chunks_on_prefix']} * {n} "
              f"!= {cur['unshared']['prefill_chunks_on_prefix']})")
        bad.append("prefill_chunks_on_prefix")
    if cur["shared_cold"]["high_water"] >= cur["unshared"]["high_water"]:
        print("REGRESSION: sharing did not reduce KV-pool high-water "
              f"({cur['shared_cold']['high_water']} vs "
              f"{cur['unshared']['high_water']})")
        bad.append("high_water")
    if cur["new_buckets_after_warmup"] != 0:
        print("REGRESSION: prefix caching compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print(f"prefix leg OK: {cur['unshared']['prefill_chunks_on_prefix']} "
          f"-> {cur['shared_cold']['prefill_chunks_on_prefix']} "
          f"prefix-portion chunk sweeps (1/{n}), high-water "
          f"{cur['unshared']['high_water']} -> "
          f"{cur['shared_cold']['high_water']}, token-exact, 0 new "
          "buckets")
    return 0


TRACE_KEYS = ("chunk", "workload", "steps_traced", "steps_untraced",
              "new_buckets_after_warmup", "span_counts",
              "expected_span_counts", "spans_recorded",
              "flight_dump_written", "flight_dump_loads")


def check_trace(base):
    """CI gate for the tracing leg: span counts per request are host
    math (ceil(P/chunk) prefill spans, N-1 decodes), tracing must not
    change the step count, and the flight-recorder roundtrip must
    hold — all against the committed baseline."""
    cur = trace_leg()
    bad = [k for k in TRACE_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if cur["steps_traced"] != cur["steps_untraced"]:
        print(f"REGRESSION: tracing changed the step count "
              f"({cur['steps_traced']} vs {cur['steps_untraced']})")
        bad.append("steps_traced")
    if cur["span_counts"] != cur["expected_span_counts"]:
        print("REGRESSION: span counts drifted from the host-math "
              f"expectation: {cur['span_counts']} vs "
              f"{cur['expected_span_counts']}")
        bad.append("span_counts")
    if cur["new_buckets_after_warmup"] != 0:
        print("REGRESSION: tracing compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print(f"trace leg OK: {cur['steps_traced']} steps (tracing on == "
          f"off), {cur['spans_recorded']} spans, span counts exact, "
          "flight dump loads")
    return 0


GRID_KEYS = ("total_kv_blocks", "work_items", "legacy_grid_steps",
             "ragged_grid_steps", "pack", "context_lens")

SPEC_KEYS = ("spec_k", "prompt_lens", "new_tokens", "tokens_per_run",
             "steps_spec", "steps_nospec", "drafted", "accepted",
             "new_buckets_after_warmup")


def check_spec(base):
    """CI gate for the speculative leg: the host-deterministic counts
    must match the committed baseline, speculation must pay (strictly
    fewer steps than decode-1), and warmup must cover every compile
    bucket (zero recompiles on replay with speculation ON)."""
    cur = spec_leg()
    bad = [k for k in SPEC_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if cur["steps_spec"] >= cur["steps_nospec"]:
        print(f"REGRESSION: speculative steps ({cur['steps_spec']}) not "
              f"below decode-1 ({cur['steps_nospec']})")
        bad.append("steps_spec")
    if cur["new_buckets_after_warmup"] != 0:
        print("REGRESSION: speculation compiled "
              f"{cur['new_buckets_after_warmup']} fresh buckets after "
              "warmup")
        bad.append("new_buckets_after_warmup")
    if bad:
        return 1
    print(f"spec leg OK: {cur['steps_spec']} steps vs decode-1's "
          f"{cur['steps_nospec']} for {cur['tokens_per_run']} tokens, "
          f"acceptance {cur['accepted']}/{cur['drafted']}")
    return 0


def check_ragged(base):
    """CI gate: the ragged leg's grid-step accounting must match the
    committed baseline exactly (these are host-deterministic), and the
    ragged grid must stay strictly below the legacy B x max_blocks one."""
    cur = ragged_leg(iters=1)
    bad = [k for k in GRID_KEYS if cur[k] != base[k]]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline {base[k]!r}")
    if cur["ragged_grid_steps"] >= cur["legacy_grid_steps"]:
        print(f"REGRESSION: ragged grid ({cur['ragged_grid_steps']}) not "
              f"below legacy grid ({cur['legacy_grid_steps']})")
        bad.append("ragged_grid_steps")
    if bad:
        return 1
    print(f"ragged leg OK: {cur['ragged_grid_steps']} grid steps vs "
          f"legacy {cur['legacy_grid_steps']} "
          f"({cur['total_kv_blocks']} actual KV blocks)")
    return 0


AUTOTUNE_WORKLOAD = [(5, 3), (11, 4), (3, 5), (8, 2)]


def _autotune_sweep(at, measure):
    """The committed sweep: the tiny engine's shape class (kvh=2, g=2,
    block=8, d=16, f32) over its two occupancy buckets — the decode
    bucket at the workload's post-prefill length spread, and the
    chunk-8 prefill bucket."""
    lens = [p + n for p, n in AUTOTUNE_WORKLOAD]
    cache = None
    for chunk in (None, 8):
        cache = at.sweep_ragged_serve(
            2, 2, 16, 8, lens, chunk=chunk, measure=measure, cache=cache)
    return cache


def autotune_leg():
    """Serving-kernel autotune end to end: sweep the ragged kernel's
    (pack, prefill_chunk, buffer_depth) per occupancy bucket, rank by
    the deterministic analytic model (this leg is the CI gate — on a
    real TPU, run sweep_ragged_serve with measure=True to re-tune), and
    drive the SAME continuous-batching workload untuned vs tuned-from-
    cache: token ids must match exactly, the tuned engine must mint
    zero new compile buckets after warmup, and a second sweep must
    reproduce the winner table bit-for-bit."""
    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    from paddle_tpu.ops.pallas import autotune as at

    on_tpu = init_platform() == "tpu"
    cache = _autotune_sweep(at, measure=False)
    deterministic = _autotune_sweep(at, measure=False) == cache
    shape_cls = at.serve_shape_class(2, 2, 8, 16, "float32")

    def drive(tune):
        rng = np.random.default_rng(0)
        eng, V = _tiny_cpu_engine(rng, max_seq_len=64,
                                  autotune_cache=tune)
        cb = ContinuousBatchingEngine(eng, num_blocks=24, block_size=8,
                                      max_batch=4, autotune_cache=tune)

        def submit():
            prng = np.random.default_rng(7)
            reqs = [GenerationRequest(
                prng.integers(1, V, p).astype(np.int32), n)
                for p, n in AUTOTUNE_WORKLOAD]
            for r in reqs:
                cb.submit(r)
            return reqs
        reqs = submit()
        out = cb.run()
        toks = [list(map(int, out[r.request_id])) for r in reqs]
        steps = cb._step_count
        warm = set(cb._seen_buckets)
        submit()                     # same workload again: warm replay
        cb.run()
        return {
            "tokens": toks, "steps": steps,
            "new_buckets": len(set(cb._seen_buckets) - warm),
            "config": {"pack": cb._pack,
                       "prefill_chunk": cb.prefill_chunk,
                       "kv_buffer_depth": eng.kv_buffer_depth},
        }

    default = drive(None)
    tuned = drive(cache)
    ntok = sum(n for _, n in AUTOTUNE_WORKLOAD)
    out = {
        "interpret": not on_tpu,
        "shape_class": shape_cls,
        "winner": dict(cache["shapes"][shape_cls]["winner"]),
        "buckets": {
            k: {p: b[p] for p in ("pack", "prefill_chunk",
                                  "buffer_depth")}
            for k, b in cache["shapes"][shape_cls]["buckets"].items()},
        "deterministic": deterministic,
        # lists, not tuples: the committed baseline round-trips JSON
        "workload": [list(t) for t in AUTOTUNE_WORKLOAD],
        "tokens": ntok,
        "steps_default": default["steps"],
        "steps_tuned": tuned["steps"],
        "steps_per_token_default": round(default["steps"] / ntok, 4),
        "steps_per_token_tuned": round(tuned["steps"] / ntok, 4),
        "token_exact_tuned_vs_default":
            tuned["tokens"] == default["tokens"],
        "default_config": default["config"],
        "tuned_config": tuned["config"],
        "new_buckets_after_warmup_tuned": tuned["new_buckets"],
        "cache": cache,
    }
    print(f"autotune[{shape_cls}]: winner {out['winner']}, "
          f"{out['steps_tuned']} tuned vs {out['steps_default']} default "
          f"steps for {ntok} tokens; "
          f"{out['new_buckets_after_warmup_tuned']} new buckets after "
          "warmup; deterministic="
          f"{out['deterministic']}")
    return out


AUTOTUNE_KEYS = ("shape_class", "winner", "buckets", "deterministic",
                 "workload", "tokens", "steps_default", "steps_tuned",
                 "token_exact_tuned_vs_default", "default_config",
                 "tuned_config", "new_buckets_after_warmup_tuned")


def check_autotune(base):
    """CI gate for the committed serve-autotune cache: a fresh
    model-ranked sweep must reproduce the committed winner table
    bit-for-bit, the tuned engine must stay token-exact vs the default
    one with zero new compile buckets after warmup, and the gate
    metadata must match the committed figures exactly."""
    cur = autotune_leg()
    bad = []
    if cur["cache"]["shapes"] != base.get("shapes"):
        print("MISMATCH winner table: re-sweep disagrees with the "
              "committed shapes section — regenerate with "
              "`serve_bench --autotune --quant --json "
              "tools/serve_autotune.json` if the model changed")
        bad.append("shapes")
    gate = base.get("gate", {}).get("autotune", {})
    for k in AUTOTUNE_KEYS:
        if cur[k] != gate.get(k):
            print(f"MISMATCH {k}: current {cur[k]!r} != baseline "
                  f"{gate.get(k)!r}")
            bad.append(k)
    for k, want in (("deterministic", True),
                    ("token_exact_tuned_vs_default", True)):
        if cur[k] is not want:
            print(f"REGRESSION: {k} is {cur[k]}")
            bad.append(k)
    if cur["new_buckets_after_warmup_tuned"] != 0:
        print("REGRESSION: tuned engine compiled "
              f"{cur['new_buckets_after_warmup_tuned']} fresh buckets "
              "after warmup")
        bad.append("new_buckets_after_warmup_tuned")
    if bad:
        return 1
    print(f"autotune leg OK: winner {cur['winner']}, tuned engine "
          f"token-exact in {cur['steps_tuned']} steps, 0 new buckets")
    return 0


def quant_leg(kinds=("int8", "int4")):
    """int4/int8 weight-only serving on the PAGED path: for each quant
    kind, the continuous-batching engine built over the SAME quantized
    weights must emit greedy token ids EXACTLY matching the dense
    weight_quant engine's generate() in every scheduler mode
    (plain / chunked / budgeted / speculative / prefix-cached), with
    zero new compile buckets after warmup."""
    import numpy as np

    from paddle_tpu.framework.platform import init_platform
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    on_tpu = init_platform() == "tpu"
    V, E, H, G, D, L, F = _TINY_DIMS
    workload = [(5, 4), (11, 3), (3, 6), (8, 2)]
    prng = np.random.default_rng(7)
    prompts = [prng.integers(1, V, p).astype(np.int32)
               for p, _ in workload]
    pattern = [7, 23, 41, 11]
    spec_prompts = [np.asarray(pattern * 6, np.int32),
                    np.asarray(pattern * 3, np.int32)]
    pfx_rng = np.random.default_rng(3)
    prefix = pfx_rng.integers(1, V, 24).astype(np.int32)
    pfx_prompts = [np.concatenate(
        [prefix, pfx_rng.integers(1, V, 3).astype(np.int32)])
        for _ in range(4)]

    modes = {
        "plain": ({}, prompts, [n for _, n in workload]),
        "chunked": ({"prefill_chunk": 4}, prompts,
                    [n for _, n in workload]),
        "budgeted": ({"prefill_chunk": 4, "token_budget": 6}, prompts,
                     [n for _, n in workload]),
        "spec": ({"max_batch": 2, "prefill_chunk": 8, "spec_k": 4},
                 spec_prompts, [10, 10]),
        "prefix": ({"prefill_chunk": 8, "prefix_cache": True},
                   pfx_prompts, [4, 4, 4, 4]),
    }

    token_exact, steps, new_buckets = {}, {}, {}
    for kind in kinds:
        eng, _ = _tiny_cpu_engine(np.random.default_rng(0),
                                  max_seq_len=64, weight_quant=kind)
        refs = {m: [list(map(int, eng.generate(
            p[None], max_new_tokens=n)[0]))
            for p, n in zip(ps, ns)]
            for m, (_, ps, ns) in modes.items()}
        token_exact[kind], steps[kind] = {}, {}
        for m, (kw, ps, ns) in modes.items():
            ckw = dict(num_blocks=24, block_size=8, max_batch=4)
            ckw.update(kw)
            cb = ContinuousBatchingEngine(eng, **ckw)
            reqs = [GenerationRequest(p.copy(), n)
                    for p, n in zip(ps, ns)]
            for r in reqs:
                cb.submit(r)
            out = cb.run()
            got = [list(map(int, out[r.request_id])) for r in reqs]
            token_exact[kind][m] = got == refs[m]
            steps[kind][m] = cb._step_count
            if m == "chunked":
                warm = set(cb._seen_buckets)
                for r in [GenerationRequest(p.copy(), n)
                          for p, n in zip(ps, ns)]:
                    cb.submit(r)
                cb.run()
                new_buckets[kind] = len(set(cb._seen_buckets) - warm)
    out = {
        "interpret": not on_tpu,
        "kinds": list(kinds),
        "modes": sorted(modes),
        "workload": [list(t) for t in workload],
        "token_exact": token_exact,
        "steps": steps,
        "new_buckets_after_warmup": new_buckets,
    }
    for kind in kinds:
        ok = all(token_exact[kind].values())
        print(f"quant[{kind}]: paged-vs-dense token ids "
              f"{'EXACT' if ok else 'MISMATCH'} across "
              f"{len(modes)} modes; {new_buckets[kind]} new buckets "
              "after warmup")
    return out


QUANT_KEYS = ("kinds", "modes", "workload", "token_exact", "steps",
              "new_buckets_after_warmup")


def check_quant(base):
    """CI gate for quantized paged serving: token ids must match the
    dense weight_quant generate() in EVERY mode for EVERY kind, the
    deterministic step counts must match the committed baseline, and
    the warm replay must mint zero fresh compile buckets."""
    cur = quant_leg()
    bad = [k for k in QUANT_KEYS if cur[k] != base.get(k)]
    for k in bad:
        print(f"MISMATCH {k}: current {cur[k]!r} != baseline "
              f"{base.get(k)!r}")
    for kind, per_mode in cur["token_exact"].items():
        for m, ok in per_mode.items():
            if not ok:
                print(f"REGRESSION: {kind} paged serving diverged from "
                      f"dense weight_quant generate() in mode {m}")
                bad.append(f"token_exact.{kind}.{m}")
    for kind, n in cur["new_buckets_after_warmup"].items():
        if n != 0:
            print(f"REGRESSION: {kind} engine compiled {n} fresh "
                  "buckets after warmup")
            bad.append(f"new_buckets.{kind}")
    if bad:
        return 1
    print(f"quant leg OK: {'/'.join(cur['kinds'])} token-exact across "
          f"{len(cur['modes'])} modes, 0 new buckets")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--batches", default="1,8",
                    help="comma-separated decode batch sizes")
    ap.add_argument("--skip-paged", action="store_true")
    ap.add_argument("--ragged", action="store_true",
                    help="run only the ragged paged leg "
                         "(works on CPU via interpret mode)")
    ap.add_argument("--check", metavar="BASELINE_JSON", default=None,
                    help="gate against a committed baseline — runs the "
                         "legs the file carries: 'ragged' (grid-step "
                         "accounting) and/or 'spec' (speculative steps/"
                         "token + acceptance + zero-recompile)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative vs decode-1 steps-per-token + "
                         "acceptance rate on a repetitive workload "
                         "(works on CPU via interpret mode)")
    ap.add_argument("--no-spec", action="store_true",
                    help="run only the decode-1 reference side of the "
                         "--spec workload (steps-per-token without "
                         "speculation)")
    ap.add_argument("--metrics", action="store_true",
                    help="drive the continuous-batching engine with the "
                         "observability layer on and report p50/p95/p99 "
                         "TTFT / per-token latency from the histograms "
                         "(works on CPU via interpret mode)")
    ap.add_argument("--prefill", action="store_true",
                    help="chunked vs unchunked prefill TTFT + steps-to-"
                         "first-token at prompt lengths 64/256/512 "
                         "(works on CPU via interpret mode; minutes, "
                         "the unchunked leg really pays P steps)")
    ap.add_argument("--trace", action="store_true",
                    help="per-request lifecycle tracing: span counts "
                         "per request (ceil(P/chunk) prefill spans), "
                         "tracing-on vs -off step parity, overhead wall "
                         "times, and a flight-recorder dump roundtrip "
                         "(works on CPU via interpret mode)")
    ap.add_argument("--prefix", action="store_true",
                    help="automatic prefix caching: N requests sharing "
                         "a long prompt prefix — chunk sweeps over the "
                         "shared portion must drop to 1/N and KV-pool "
                         "high-water accordingly, token-exact in every "
                         "mode (works on CPU via interpret mode)")
    ap.add_argument("--tp", action="store_true",
                    help="tensor-parallel serving on the virtual "
                         "8-device mesh: token-exactness vs single-chip "
                         "at TP=1/2/4/8 across plain/chunked/spec/"
                         "prefix, per-device KV high-water = 1/tp, "
                         "collective payload accounting, 0 new buckets "
                         "after warmup (works on CPU)")
    ap.add_argument("--autotune", action="store_true",
                    help="serving-kernel autotune leg: sweep the ragged "
                         "kernel's (pack, prefill_chunk, buffer_depth) "
                         "per occupancy bucket, model-ranked "
                         "deterministically, and drive tuned-vs-default "
                         "engines token-exact (works on CPU; with "
                         "--json the serve cache + gate metadata land "
                         "in ONE engine-loadable file)")
    ap.add_argument("--quant", action="store_true",
                    help="int4/int8 weight-only serving on the paged "
                         "path: continuous-batching token ids vs the "
                         "dense weight_quant engine's generate() in "
                         "every scheduler mode (works on CPU via "
                         "interpret mode)")
    ap.add_argument("--chunk", type=int, default=64,
                    help="prefill chunk size for the --prefill leg")
    ap.add_argument("--no-flight-recorder", action="store_true",
                    help="do not arm the anomaly flight recorder "
                         "(server-style entrypoints arm by default with "
                         "bounded retention; legs that manage their own "
                         "arming still override it)")
    args = ap.parse_args()
    base = None
    if args.check:
        with open(args.check) as f:
            base = json.load(f)
    if args.tp or (base is not None and "tp" in base):
        # the tp leg needs 8 devices: on the CPU (JAX_PLATFORMS=cpu)
        # that is the virtual mesh, and XLA reads this flag at BACKEND
        # INIT — set it before anything touches jax.devices()
        flag = "--xla_force_host_platform_device_count=8"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    if not args.no_flight_recorder:
        from paddle_tpu.observability import tracing
        tracing.arm_default()
    if args.check:
        if base.get("schema", "").startswith("paddle_tpu.serve_autotune"):
            # the committed serve-autotune cache doubles as the gate
            # baseline: shapes = the winner table engines load, gate =
            # the leg metadata (extra top-level keys are ignored by
            # load_serve_cache by design)
            rc = check_autotune(base)
            rc |= check_quant(base.get("gate", {}).get("quant", {}))
            return rc
        rc = 0
        ran = False
        if "ragged" in base:
            ran = True
            rc |= check_ragged(base["ragged"])
        if "spec" in base:
            ran = True
            rc |= check_spec(base["spec"])
        if "trace" in base:
            ran = True
            rc |= check_trace(base["trace"])
        if "prefix" in base:
            ran = True
            rc |= check_prefix(base["prefix"])
        if "tp" in base:
            ran = True
            rc |= check_tp(base["tp"])
        if not ran:
            print(f"{args.check}: no 'ragged'/'spec'/'trace'/'prefix'/"
                  "'tp' section to gate")
            return 1
        return rc
    if args.autotune or args.quant:
        # these two produce the ONE committed file tools/serve_autotune
        # .json: the serve cache engines load (schema/kernel/shapes)
        # with the gate metadata alongside under "gate"
        at_out = autotune_leg() if args.autotune else None
        q_out = quant_leg() if args.quant else None
        if args.json:
            doc = dict(at_out.pop("cache")) if at_out else {}
            doc["gate"] = {}
            if at_out:
                doc["gate"]["autotune"] = at_out
            if q_out:
                doc["gate"]["quant"] = q_out
            from paddle_tpu.ops.pallas.autotune import save_serve_cache
            if "schema" in doc:
                save_serve_cache(doc, args.json)
            else:
                with open(args.json, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                    f.write("\n")
            print(f"wrote {args.json}")
        return 0
    if args.ragged or args.metrics or args.prefill or args.spec \
            or args.no_spec or args.trace or args.prefix or args.tp:
        out = {}
        if args.ragged:
            out["ragged"] = ragged_leg()
            print(json.dumps(out["ragged"], indent=1))
        if args.spec:
            out["spec"] = spec_leg()
        elif args.no_spec:
            out["no_spec"] = spec_leg(include_spec=False)
        if args.metrics:
            sm = serving_metrics_leg()
            # percentiles live at top level (the committed baseline's
            # `percentiles` block) — not duplicated inside the leg dict
            out["percentiles"] = sm.pop("percentiles")
            out["serving_metrics"] = sm
            print(json.dumps(out["percentiles"], indent=1))
            print(json.dumps(sm, indent=1))
            p = out["percentiles"]["tpot_ms"]
            if p:
                print(f"per-output-token latency: p50 {p['p50']} ms, "
                      f"p95 {p['p95']} ms, p99 {p['p99']} ms"
                      + (" (interpret mode: measures the interpreter, "
                         "not the chip)" if sm["interpret"] else ""))
        if args.prefill:
            # AFTER the metrics leg: the prefill leg drives the serving
            # engine too, and the process-wide registry must not count
            # its steps into the committed metrics snapshot
            out["prefill"] = prefill_leg(chunk=args.chunk)
        if args.trace:
            # after --metrics for the same reason as --prefill
            out["trace"] = trace_leg()
        if args.prefix:
            # after --metrics too: it drives the serving engine
            out["prefix"] = prefix_leg()
        if args.tp:
            # last for the same registry-isolation reason
            out["tp"] = tp_leg()
        if args.json:
            with open(args.json, "w") as f:
                json.dump(out, f, indent=1)
            print(f"wrote {args.json}")
        return 0
    from paddle_tpu.framework.platform import init_platform
    if init_platform() != "tpu":
        print("# the device-time legs need the TPU; --ragged / --check "
              "are the CPU-runnable legs", file=sys.stderr)
        return 1
    out = {}
    # B=1 is the weight-bound regime where weight-only quant pays (every
    # step streams the full weights for one token); B=8 amortizes weight
    # reads 8x, so the weight fraction — and the quant ceiling — shrinks
    for B in [int(b) for b in args.batches.split(",")]:
        for quant in [None, "int8", "int4"]:
            leg = decode_leg(quant, B=B)
            out[f"decode_b{B}_{quant or 'bf16'}"] = leg
            print(f"decode[B={B} {quant or 'bf16'}]: "
                  f"{leg['decode_tok_per_s']:.0f} tok/s device-time "
                  f"({leg['decode_device_ms']/leg['decode_tokens']*B:.2f} "
                  f"ms/step; prefill {leg['prefill_device_ms']:.1f} ms)")
        base = out[f"decode_b{B}_bf16"]["decode_tok_per_s"]
        for q in ["int8", "int4"]:
            out[f"b{B}_{q}_speedup_vs_bf16"] = out[
                f"decode_b{B}_{q}"]["decode_tok_per_s"] / base
            print(f"  B={B} {q} speedup vs bf16: "
                  f"{out[f'b{B}_{q}_speedup_vs_bf16']:.2f}x")
    if not args.skip_paged:
        rg = ragged_leg()
        out["ragged"] = rg
        print(f"ragged paged leg: {rg['ragged_grid_steps']} grid steps "
              f"({rg['ragged_call_us']:.0f} us/call) where a B x KVH x "
              f"max_blocks grid walks {rg['legacy_grid_steps']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    # operator abort mid-leg writes the operator_abort flight dump
    # (span window + full metrics snapshot) before exiting, so an
    # interrupted bench still ships the evidence it gathered
    from paddle_tpu.observability import tracing
    sys.exit(tracing.run_with_abort_evidence(main))
