"""One-chip timing of the flagship Llama train step and the dense decode
path, on the TPU only. ROADMAP S1 replaces this file with the benchmark
(cells, end-to-end metrics, the ledger's inputs); until then it prints
ONE JSON line and fails — no number, non-zero exit — when jax finds no
TPU, when the chip's `device_kind` has no peak entry, or when either leg
fails.

  {"metric": "...", "value": N, "unit": "...", "platform": "tpu",
   "device_kind": "...", "device_count": N, "mfu": N, ...}
"""
import json
import sys
import time

import numpy as np


def main():
    from paddle_tpu.framework.platform import init_platform
    platform = init_platform()
    if platform != "tpu":
        print(f"bench.py times the chip; jax found platform {platform!r}",
              file=sys.stderr)
        return 1
    import jax

    from paddle_tpu.models import LlamaForCausalLM, pretrain
    from paddle_tpu.observability import peak_flops

    peak = peak_flops()     # raises on a device_kind the table lacks
    cfg, batch, seq = pretrain.flagship_config()
    iters, warmup = 20, 3

    model = LlamaForCausalLM(cfg)
    mesh = pretrain.make_mesh(1, dp=1, fsdp=1, mp=1, sp=1)
    params, opt_state, meta = pretrain.make_train_state(model, mesh)
    step = pretrain.make_train_step(model, mesh, meta)
    rng = np.random.default_rng(0)

    def fresh_batch():
        # a DIFFERENT random batch every step: the printed loss is then a
        # true random-data loss (~ln V), not single-batch memorization
        return pretrain.shard_batch(
            {"input_ids": rng.integers(0, cfg.vocab_size,
                                       (batch, seq)).astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size,
                                    (batch, seq)).astype(np.int32)}, mesh)

    for _ in range(warmup):
        params, opt_state, loss, gnorm = step(params, opt_state,
                                              fresh_batch())
    jax.block_until_ready(loss)

    batches = [fresh_batch() for _ in range(iters)]  # pre-staged
    t0 = time.perf_counter()
    for bd in batches:
        params, opt_state, loss, gnorm = step(params, opt_state, bd)
    jax.block_until_ready(loss)
    tokens_per_sec = batch * seq * iters / (time.perf_counter() - t0)

    # MFU: 6*N per token (fwd+bwd) + attention term, vs chip peak
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    flops_per_token = 6 * n_params + \
        12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = flops_per_token * tokens_per_sec / peak
    loss = float(loss)
    del params, opt_state, batches

    decode_tps = _serving_decode_tps()
    decode_tps_int8 = _serving_decode_tps(weight_quant="int8")

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": f"tokens/s ({n_params / 1e6:.0f}M params, "
                f"bs{batch}x{seq})",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mfu": round(mfu, 4),
        "loss": round(loss, 3),
        "dense_decode_tokens_per_sec": round(decode_tps, 1),
        "dense_decode_int8_tokens_per_sec": round(decode_tps_int8, 1),
    }))
    return 0


def _serving_decode_tps(weight_quant=None):
    """Greedy-decode throughput of the flagship serving widths (GQA: q
    heads > kv heads) through FusedMultiTransformerEngine.generate() —
    the dense-cache path, NOT the paged path the gateway serves (ROADMAP
    S2); with weight_quant='int8'/'int4' the weight-only quantized tier."""
    from paddle_tpu.inference import FusedMultiTransformerEngine

    rng = np.random.default_rng(0)
    V, E, H, G, D, L, F = 32000, 1024, 16, 8, 64, 24, 2816
    B, SMAX, NEW = 8, 512, 64

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
    # warm with the SAME n: the scanned decode specializes on step count
    eng.generate(ids, max_new_tokens=NEW)
    t0 = time.perf_counter()
    out = eng.generate(ids, max_new_tokens=NEW)   # returns host tokens
    dt = time.perf_counter() - t0
    if out.shape != (B, NEW):
        raise RuntimeError(f"generate returned {out.shape}, "
                           f"expected {(B, NEW)}")
    return B * NEW / dt


if __name__ == "__main__":
    sys.exit(main())
