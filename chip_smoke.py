"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU

One process, the only one that touches jax. It drives both hot paths
once through the entry points a user calls, at the full width of the
Llama-shaped flagship (random weights from a seed), and checks what comes
out with the repo's own references:

  kernels   ragged paged attention (decode rows, a chunked-prefill slab,
            buffer_depth 1 and 2, a zero-length row) against
            `ragged_paged_attention_reference`; flash forward and the
            fused backward at S=2048/D=64 against `_sdpa_ref`; the paged
            cache writers against numpy, the writer of new rows also at
            each served cache's shape, timed beside the scatter it
            replaced
  trainer   `make_mesh(1)` -> `make_train_state` -> `make_train_step`,
            three steps on one seeded batch
  server    `FusedMultiTransformerEngine` -> `ContinuousBatchingEngine`
            (default options) -> `EngineStepper` -> `ServingGateway` on
            loopback port 0; concurrent `POST /v1/generate` SSE requests
  four chips, when jax finds four: the trainer on fsdp=2 x mp=2 and the
            server at tp=4, against the one-chip results

It exits non-zero — and prints no result line — when the platform is not
`tpu`, and on the first leg that fails; nothing on its path turns a
lowering or runtime error into a reference path, an interpreter run or a
skipped leg. On success the last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Served tokens vs the dense path. bf16 near-ties make exact token
equality with `engine.generate()` unstable: with random weights the two
largest of 32000 logits are often closer than the bf16 noise between two
correct but differently ordered computations (paged kernel vs dense
einsum, tp=4 psum vs one chip), and one flipped argmax changes every
later token. What is stable, and is checked for EVERY served token: the
dense reference run over the served prefix (teacher forcing) must rank
the served token within `2 * TOL[dtype] * max|logit|` of its own best — so
wherever the reference's top-2 gap exceeds that, the served token must
BE the reference argmax. A wrong kernel puts tokens at a random rank
(logits several sigma below the best) and fails. `generate()` itself
runs too: at its first disagreement with a served stream, its token must
pass the same test against the same reference row.
"""
import asyncio
import contextlib
import gc
import json
import math
import sys
import time

import numpy as np

# max |got - ref| allowed, as a fraction of max |ref|, by compute dtype:
# a few units of bf16's 2**-8 roundoff / of f32 accumulation-order noise.
TOL = {"bfloat16": 2.0 ** -5, "float32": 2.0 ** -16}
GRAD_TOL_FACTOR = 4     # two more roundings (p, ds casts) on the way back
LOGIT_TOL_FACTOR = 2    # rounding of the residual stream over the depth

# Serving widths of the flagship (the widths bench.py times): 16 q / 8 kv
# heads x 64, bf16. Prompts: unequal lengths, one past `prefill_chunk`
# (64) so it takes several chunks, one that retires early while the
# others continue.
FULL_SERVE = dict(V=32000, E=1024, H=16, G=8, D=64, L=24, F=2816,
                  dtype="bfloat16", max_seq_len=512, block_size=16,
                  num_blocks=257, scale=0.02,
                  requests=[(9, 24), (150, 16), (33, 6), (70, 24),
                            (17, 12)])
FULL_KERNELS = dict(dtype="bfloat16", KVH=8, G=2, D=64, BS=16, max_nb=16,
                    decode_lens=[40, 16, 0, 129, 200, 1, 77, 256],
                    chunk=64,
                    chunk_qlens=[64, 1, 0, 20, 64, 1, 7, 33],
                    # a chunk step as the scheduler sends it: one slot
                    # prefills a full 128-wide chunk beside decoding
                    # slots, a parked one and a 1 + 3 speculative span
                    mixed_chunk=128,
                    mixed_qlens=[1, 128, 0, 1, 1, 1, 4, 1],
                    flash=dict(B=1, S=2048, H=16, D=64))

# One layer's cache as each served configuration holds it (KVH, NB, BS,
# key width, value width; perfbench/configs/*-serve-1chip.json): the
# writer of new rows is checked and timed there, a 256-row tile as a
# wide step packs it.
WRITER_SHAPES = {
    "mistral7b": (8, 321, 128, 128, 128),
    "mimo full layer": (4, 289, 256, 192, 128),
    "mimo window layer": (8, 33, 256, 192, 128),
}


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# -- accounting: compile seconds, cache events, device memory ---------------

def _registry_sums():
    from paddle_tpu import observability as obs
    snap = obs.get_registry().snapshot()
    comp = sum(c["sum"] for c in snap.get(
        "jax_compile_seconds", {}).get("children", {}).values())
    compiles = sum(
        c["value"] for k, c in snap.get(
            "jax_compiles_total", {}).get("children", {}).items()
        if k.startswith("backend_compile"))
    events = {k: int(c["value"]) for k, c in snap.get(
        "jax_cache_events_total", {}).get("children", {}).items()}
    return comp, int(compiles), events


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def leg(name, report):
    """Time one leg: compile seconds (the sum of jax's own trace / lower /
    backend-compile event durations — an upper bound, a nested jit's
    trace is counted in its caller's too) apart from the rest of its
    wall time."""
    c0, n0, _ = _registry_sums()
    t0 = time.perf_counter()
    print(f"[{name}] start", flush=True)
    yield
    wall = time.perf_counter() - t0
    c1, n1, _ = _registry_sums()
    peak = _peak_bytes()
    row = {"compile_s": round(c1 - c0, 2),
           "run_s": round(max(wall - (c1 - c0), 0.0), 2),
           "backend_compiles": n1 - n0, "peak_bytes_in_use": peak}
    report[name] = row
    print(f"[{name}] ok: compile {row['compile_s']} s "
          f"({row['backend_compiles']} programs), run {row['run_s']} s, "
          f"peak_bytes_in_use "
          f"{'not reported' if peak is None else peak}", flush=True)


def _release():
    import jax
    gc.collect()
    jax.clear_caches()


# -- kernels -----------------------------------------------------------------

def _close(name, got, ref, tol):
    """max |got - ref| <= tol * max |ref|; NaN and all-zero outputs fail."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite values")
    scale = float(np.abs(ref).max())
    check(scale > 0, f"{name}: reference is all zero")
    check(float(np.abs(got).max()) > 0.1 * scale,
          f"{name}: output is (nearly) all zero")
    err = float(np.abs(got - ref).max())
    print(f"  {name}: max_err {err:.3e} (allowed {tol * scale:.3e})",
          flush=True)
    check(err <= tol * scale,
          f"{name}: max_err {err:.4e} > {tol * scale:.4e}")


def kernels_leg(k):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _sdpa_ref
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(0)
    dt = jnp.dtype(k["dtype"])
    tol = TOL[k["dtype"]]
    KVH, G, D, BS, max_nb = k["KVH"], k["G"], k["D"], k["BS"], k["max_nb"]
    H, B = KVH * G, len(k["decode_lens"])
    Dc = pa.paged_head_dim(D)
    NB = B * max_nb + 1

    def cache():        # rows as the engine stores them: zero pad lanes
        c = np.zeros((KVH, NB, BS, Dc), np.float32)
        c[..., :D] = rng.standard_normal((KVH, NB, BS, D))
        return jnp.asarray(c, dt)

    kc, vc = cache(), cache()
    kv = jnp.stack([kc, vc])    # one layer's cache as the engine holds it
    tables = (1 + np.arange(B * max_nb, dtype=np.int32)).reshape(B, max_nb)

    # ragged paged attention: decode rows, then a chunked-prefill slab
    lens = np.asarray(k["decode_lens"], np.int32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dt)
    ref = pa.ragged_paged_attention_reference(q, kc, vc, tables, lens)
    for depth in (1, 2):
        out = pa.ragged_paged_attention(q, kv, tables, lens,
                                        buffer_depth=depth)
        _close(f"ragged decode depth={depth}", out, ref, tol)
        check(not np.asarray(out, np.float32)[lens == 0].any(),
              "ragged decode: a zero-length row is not zero")
    C = k["chunk"]
    qlens = np.asarray(k["chunk_qlens"], np.int32)
    ctx = np.minimum(lens + qlens, max_nb * BS).astype(np.int32)
    qc = jnp.asarray(rng.standard_normal((B, C, H, D)), dt)
    ref = pa.ragged_paged_attention_reference(qc, kc, vc, tables, ctx,
                                              q_lens=qlens)
    for depth in (1, 2):
        out = pa.ragged_paged_attention(qc, kv, tables, ctx,
                                        q_lens=qlens, buffer_depth=depth)
        _close(f"ragged chunk C={C} depth={depth}", out, ref, tol)
    # the mixed slab over a BUCKETED work list (its second half padding
    # entries, which visit no query rows): each entry's grid step walks
    # the live sub-tiles of its own slot only
    C = k["mixed_chunk"]
    qlens = np.asarray(k["mixed_qlens"], np.int32)
    ctx = np.minimum(lens + qlens, max_nb * BS).astype(np.int32)
    qc = jnp.asarray(rng.standard_normal((B, C, H, D)), dt)
    work = pa.build_ragged_work(
        tables, ctx, BS, pa.default_pack(B, G),
        bucket_to=lambda n: 2 * pa.next_pow2(n), q_lens=qlens)
    check(work[2] >= 2 * work[1], "mixed slab: the list is not padded")
    live, visited = pa.attn_rows(work[0], work[3], C, G, BS)
    print(f"  ragged mixed C={C}: {work[1]} entries of {work[2]}, "
          f"{live} live query rows of {visited} visited "
          f"({work[2] * work[3] * C * G} in whole tiles)", flush=True)
    ref = pa.ragged_paged_attention_reference(
        qc, kc, vc, tables, ctx, pack=work[3], q_lens=qlens)
    for depth in (1, 2):
        out = pa.ragged_paged_attention(qc, kv, tables, ctx, q_lens=qlens,
                                        work=work, buffer_depth=depth)
        _close(f"ragged mixed C={C} depth={depth}", out, ref, tol)
        dead = np.arange(C)[None, :] >= qlens[:, None]
        check(not np.asarray(out, np.float32)[dead].any(),
              "ragged mixed: a dead row is not zero")

    # the paged cache writers on the stacked cache, as the engine's
    # programs call them, against numpy (pure data movement: exact)
    cap = max_nb * BS
    kn, vn = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
    lens_w = np.asarray(k["decode_lens"], np.int32)
    lens_w[-1] = cap                    # a full row: its write must drop
    k1 = rng.standard_normal((B, KVH, D)).astype(np.float32)
    v1 = rng.standard_normal((B, KVH, D)).astype(np.float32)
    k1, v1 = (np.asarray(jnp.asarray(a, dt), np.float32) for a in (k1, v1))
    got_k, got_v = pa.append_paged_kv(
        kv, jnp.asarray(k1, dt), jnp.asarray(v1, dt),
        jnp.asarray(tables), jnp.asarray(lens_w))
    want_k, want_v = kn.copy(), vn.copy()
    for b in range(B):
        p = int(lens_w[b])
        if p < cap:
            want_k[:, tables[b, p // BS], p % BS, :D] = k1[b]
            want_v[:, tables[b, p // BS], p % BS, :D] = v1[b]
    check(np.array_equal(np.asarray(got_k, np.float32), want_k)
          and np.array_equal(np.asarray(got_v, np.float32), want_v),
          "append_paged_kv differs from numpy")
    kchunk = rng.standard_normal((B, C, KVH, D)).astype(np.float32)
    kchunk = np.asarray(jnp.asarray(kchunk, dt), np.float32)
    got_k, got_v = pa.append_paged_kv_chunk(
        kv, jnp.asarray(kchunk, dt), jnp.asarray(kchunk, dt),
        jnp.asarray(tables), jnp.asarray(lens_w), jnp.asarray(qlens))
    want_k = kn.copy()
    for b in range(B):
        for j in range(int(qlens[b])):
            p = int(lens_w[b]) + j
            if p < cap:
                want_k[:, tables[b, p // BS], p % BS, :D] = kchunk[b, j]
    check(np.array_equal(np.asarray(got_k, np.float32), want_k),
          "append_paged_kv_chunk differs from numpy")
    span = 8
    new_l = np.maximum(lens_w - rng.integers(0, span + 1, B), 0) \
        .astype(np.int32)
    got_k, _ = pa.truncate_paged_kv(
        kv, jnp.asarray(tables), jnp.asarray(new_l),
        jnp.asarray(lens_w), span)
    want_k = kn.copy()
    for b in range(B):
        for p in range(int(new_l[b]), min(int(lens_w[b]), cap)):
            want_k[:, tables[b, p // BS], p % BS] = 0
    check(np.array_equal(np.asarray(got_k, np.float32), want_k),
          "truncate_paged_kv differs from numpy")
    got_k, got_v = pa.copy_paged_kv(kv, jnp.int32(3), jnp.int32(NB - 1))
    want_k, want_v = kn.copy(), vn.copy()
    want_k[:, NB - 1], want_v[:, NB - 1] = kn[:, 3], vn[:, 3]
    check(np.array_equal(np.asarray(got_k, np.float32), want_k)
          and np.array_equal(np.asarray(got_v, np.float32), want_v),
          "copy_paged_kv differs from numpy")
    print("  paged cache writers: equal to numpy", flush=True)
    for name, shape in WRITER_SHAPES.items():
        writer_at(name, shape, dt)
        writer_at(name + " decode", shape, dt, rows=16)

    # flash forward + fused backward, against _sdpa_ref
    f = k["flash"]
    shape = (f["B"], f["S"], f["H"], f["D"])
    fq, fk, fv, cot = (jnp.asarray(rng.standard_normal(shape), dt)
                       for _ in range(4))

    def loss(fn):
        return lambda a, b, c: (fn(a, b, c).astype(jnp.float32)
                                * cot.astype(jnp.float32)).sum()

    flash = lambda a, b, c: fa.flash_attention_bshd(a, b, c, causal=True)
    sdpa = lambda a, b, c: _sdpa_ref(a, b, c, causal=True)
    _close(f"flash fwd S={f['S']} D={f['D']}", jax.jit(flash)(fq, fk, fv),
           jax.jit(sdpa)(fq, fk, fv), tol)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(fq, fk, fv)
    want = jax.jit(jax.grad(loss(sdpa), argnums=(0, 1, 2)))(fq, fk, fv)
    for name, g, w in zip("qkv", got, want):
        _close(f"flash bwd d{name}", g, w, GRAD_TOL_FACTOR * tol)


def _scatter_rows(cache, k_rows, v_rows, blk, off):
    """The writer of new rows until PR 43, kept here as the yardstick:
    one scatter index row per (half, kv head, token)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    d = cache.shape[-1]
    new = jnp.stack([pa._lane_pad(k_rows, d), pa._lane_pad(v_rows, d)])
    idx = (jnp.arange(2)[:, None, None], jnp.arange(cache.shape[1]),
           blk[:, None], off[:, None])
    return cache.at[idx].set(new, mode="drop")


def writer_at(name, shape, dt, rows=256, timed=50):
    """`append_paged_kv_rows` on one layer's cache of `shape`, a tile of
    `rows` packed rows: a slot prefilling across a block boundary, then
    decode rows, one of them at its table's capacity (dropped), the rest
    of the tile dead. Equal to numpy everywhere; then the time of one
    call, beside the scatter's (a loop of `timed` calls in one program,
    the cache its carry, an empty loop's time taken off)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa

    KVH, NB, BS, DK, DV = shape
    Dc = pa.paged_head_dim(max(DK, DV))
    rng = np.random.default_rng(1)
    cache = jax.random.normal(
        jax.random.PRNGKey(0), (2, KVH, NB, BS, Dc), jnp.float32).astype(dt)
    B, max_nb = 16, 2
    tables = (1 + np.arange(B * max_nb, dtype=np.int32)).reshape(B, max_nb)
    cap = max_nb * BS
    span = rows * 135 // 256 if rows > B else 0     # crosses into block 2
    slot = np.concatenate([np.zeros(span, np.int32),
                           np.arange(1, B, dtype=np.int32),
                           np.zeros(rows - span - B + 1, np.int32)])
    pos = np.concatenate([BS - span // 2 + np.arange(span, dtype=np.int32),
                          rng.integers(0, cap, B - 1).astype(np.int32),
                          np.zeros(rows - span - B + 1, np.int32)])
    pos[span + B - 2] = cap             # a full sequence: dropped
    live = np.arange(rows) < span + B - 1
    k = jnp.asarray(rng.standard_normal((rows, KVH, DK)), dt)
    v = jnp.asarray(rng.standard_normal((rows, KVH, DV)), dt)
    args = (k, v, jnp.asarray(tables), jnp.asarray(slot), jnp.asarray(pos),
            jnp.asarray(live))
    want = np.asarray(cache).copy()
    kn, vn = np.asarray(k), np.asarray(v)
    for r in range(rows):
        if live[r] and pos[r] < cap:
            cell = (slice(None), tables[slot[r], pos[r] // BS], pos[r] % BS)
            want[(0,) + cell] = 0
            want[(1,) + cell] = 0
            want[(0,) + cell + (slice(0, DK),)] = kn[r]
            want[(1,) + cell + (slice(0, DV),)] = vn[r]
    got = jax.jit(pa.append_paged_kv_rows)(cache, *args)
    check(np.array_equal(np.asarray(got), want),
          f"{name}: append_paged_kv_rows differs from numpy")
    del got, want

    blk, off = pa._span_cells(
        jnp.asarray(tables)[slot], jnp.asarray(pos),
        jnp.asarray(pos + live), 1, NB, BS)
    forms = {
        "kernel": lambda c: pa.append_paged_kv_rows(c, *args),
        "scatter": lambda c: _scatter_rows(c, k, v, blk[:, 0], off[:, 0]),
        "empty loop": lambda c: c,
    }
    took = {}
    for form, fn in forms.items():
        loop = jax.jit(lambda c, fn=fn: jax.lax.fori_loop(
            0, timed, lambda i, c: fn(c), c), donate_argnums=0)
        cache = jax.block_until_ready(loop(cache))
        t0 = time.perf_counter()
        cache = jax.block_until_ready(loop(cache))
        took[form] = (time.perf_counter() - t0) / timed * 1e3
    print(f"  writer {name} {tuple(cache.shape)}: {int(live.sum())} live of "
          f"{rows} rows: kernel {took['kernel'] - took['empty loop']:.4f} ms"
          f" a call, the scatter {took['scatter'] - took['empty loop']:.4f}"
          f" (empty loop {took['empty loop']:.4f})", flush=True)


# -- trainer -----------------------------------------------------------------

def trainer_leg(cfg, batch, seq, mesh_kw, steps=3):
    """`make_mesh` -> `make_train_state` -> `make_train_step`, `steps`
    steps on one seeded batch. On the TPU the compiled step must hold the
    flash kernels. Returns (losses, cache_hit): cache_hit says whether
    compiling the identical step program a second time was served by the
    persistent compilation cache."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import ProcessMesh, get_mesh, set_mesh
    from paddle_tpu.models import LlamaForCausalLM, pretrain

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    mesh = pretrain.make_mesh(**mesh_kw)
    params, opt_state, meta = pretrain.make_train_state(model, mesh)
    n_dev = mesh.devices.size
    if n_dev > 1:
        for name, p in params.items():
            devs = {s.device.id for s in p.addressable_shards}
            check(len(devs) == n_dev,
                  f"param {name} sits on {len(devs)} of {n_dev} devices")
        sharded = sum(1 for p in params.values()
                      if p.addressable_shards[0].data.size < p.size)
        print(f"  {len(params)} params on {n_dev} devices, {sharded} of "
              f"them sharded ({dict(mesh.shape)})", flush=True)
    step = pretrain.make_train_step(model, mesh, meta)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    data = pretrain.shard_batch(
        {"input_ids": ids, "labels": np.roll(ids, -1, axis=1)}, mesh)
    losses, gnorms = [], []
    for _ in range(steps):
        params, opt_state, loss, gnorm = step(params, opt_state, data)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    print(f"  losses {[round(x, 4) for x in losses]} grad norms "
          f"{[round(x, 4) for x in gnorms]} (ln V = "
          f"{math.log(cfg.vocab_size):.4f})", flush=True)
    check(all(map(math.isfinite, losses + gnorms)),
          f"non-finite loss or grad norm: {losses} {gnorms}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) <= 0.5,
          f"step-0 loss {losses[0]:.4f} not within 0.5 of ln V")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")

    # the same program compiled a second time, with jax's in-memory
    # caches dropped so the request reaches the persistent one: is the
    # Pallas kernel in it, and was it a cache hit? (context as run()
    # traces it)
    jax.clear_caches()
    _, _, ev0 = _registry_sums()
    prev, was_training = get_mesh(), model.training
    model.train()
    set_mesh(ProcessMesh(mesh))
    try:
        with mesh:
            text = step._jitted.lower(params, opt_state, data) \
                .compile().as_text()
    finally:
        set_mesh(prev)
        if not was_training:
            model.eval()
    _, _, ev1 = _registry_sums()
    hit = ev1.get("cache_hits", 0) > ev0.get("cache_hits", 0)
    n_kernels = text.count("tpu_custom_call")
    print(f"  compiled step: {n_kernels} Pallas custom calls; second "
          f"identical program was a cache hit: {hit}", flush=True)
    if jax.devices()[0].platform == "tpu":
        check(n_kernels >= 2 * cfg.num_hidden_layers,
              f"compiled step holds {n_kernels} Pallas custom calls, "
              f"expected flash fwd+bwd in each of "
              f"{cfg.num_hidden_layers} layers: the flash kernel did "
              "not run")
    return losses, hit


# -- server ------------------------------------------------------------------

def _serve_weights(s):
    rng = np.random.default_rng(0)
    V, E, H, G, D, L, F = (s[k] for k in "VEHGDLF")

    def mk(*shape):
        return (rng.standard_normal(shape) * s["scale"]).astype(np.float32)

    # neox rotary tables [2, 1, 1, S, D]: positions matter to the check
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    ang = np.arange(s["max_seq_len"])[:, None] * inv[None]
    rot = np.stack([np.concatenate([np.cos(ang)] * 2, -1),
                    np.concatenate([np.sin(ang)] * 2, -1)])
    return dict(
        ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E), lm_head=mk(E, V),
        rotary_embs=rot[:, None, None].astype(np.float32))


_ENGINE_KW = dict(norm_type="rmsnorm", activation="swiglu",
                  use_neox_rotary_style=True)


def _engine(s, weights, tp=1):
    from paddle_tpu.inference import FusedMultiTransformerEngine
    return FusedMultiTransformerEngine(
        weights, num_heads=s["H"], head_dim=s["D"],
        max_seq_len=s["max_seq_len"], dtype=s["dtype"],
        gqa_group_size=s["G"], tp=tp, **_ENGINE_KW)


def _dense_logits_fn(engine, s):
    """[B, S] token ids -> [B, S, V] f32 logits through the dense
    `fused_multi_transformer` op — the computation `generate()`'s prefill
    runs — with no cache: the teacher-forcing reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn.functional import fused_multi_transformer

    def logits(w, ids):
        out = fused_multi_transformer(
            Tensor(w["embedding"][ids]), w["ln_scales"], None,
            w["qkv_weights"], None, w["linear_weights"], None,
            w["ffn_ln_scales"], None, w["ffn1_weights"], None,
            w["ffn2_weights"], None, rotary_embs=w["rotary_embs"],
            gqa_group_size=s["G"], **_ENGINE_KW)
        return (out.data @ w["lm_head"]).astype(jnp.float32)

    fn = jax.jit(logits)
    return lambda ids: np.asarray(fn(engine._w, ids))


async def _wave(gw, stepper, prompts, new_tokens, tag):
    """One wave of concurrent SSE requests (the gateway gate's asyncio
    client). The stepper is held while they arrive, so all land on one
    admission pass and every wave walks the same compile buckets.
    Returns the streamed tokens per request."""
    from tools.serve_gateway import (_end_event, _next_sse, _open_stream,
                                     _stream_tokens)

    stepper.hold()
    conns = []
    for j, (p, n) in enumerate(zip(prompts, new_tokens)):
        status, reader, writer = await _open_stream(gw.port, {
            "prompt": [int(t) for t in p], "max_new_tokens": int(n),
            "request_id": f"{tag}-{j}"})
        check(status == 200, f"POST /v1/generate answered {status}")
        first = await _next_sse(reader)
        check(first and first[0] == "accepted", f"first SSE frame {first}")
        conns.append((f"{tag}-{j}", reader, writer))
    stepper.release()

    async def drain(reader, writer):
        events = []
        while not events or events[-1][0] != "end":
            ev = await _next_sse(reader)
            check(ev is not None, "SSE stream closed before its end event")
            events.append(ev)
        writer.close()
        return events

    streams = await asyncio.wait_for(asyncio.gather(
        *[drain(r, w) for _, r, w in conns]), timeout=900)
    out = []
    for (rid, _, _), events, n in zip(conns, streams, new_tokens):
        end = _end_event(events)
        # a crashed step() fans `failed` terminals while the gateway keeps
        # answering: HTTP 200 proves nothing, the terminal status does
        check(end["status"] == "finished",
              f"{rid}: ended {end['status']} ({end.get('reason')}, "
              f"{end.get('error')})")
        toks = _stream_tokens(events)
        check(toks == end["tokens"],
              f"{rid}: streamed tokens differ from the terminal record")
        check(len(toks) == n, f"{rid}: {len(toks)} tokens, asked for {n}")
        out.append(toks)
    return out


def _near_argmax(rows, toks, tol, what):
    """Every token must sit within tol * max|logit| of its reference
    row's best logit. Returns (worst margin / allowance, exact count)."""
    worst, exact = 0.0, 0
    for j, (row, t) in enumerate(zip(rows, toks)):
        best = float(row.max())
        allow = tol * float(np.abs(row).max())
        gap = best - float(row[t])
        exact += int(int(row.argmax()) == t)
        worst = max(worst, gap / allow)
        check(gap <= allow,
              f"{what}: token {j} (id {t}) is {gap:.4f} below the "
              f"reference's best logit; allowed {allow:.4f}")
    return worst, exact


def _check_tokens(name, served, prompts, ref_logits, tol):
    """Teacher forcing: the dense reference over each served stream."""
    width = max(len(p) + len(t) for p, t in zip(prompts, served))
    ids = np.zeros((len(prompts), width), np.int32)
    for b, (p, t) in enumerate(zip(prompts, served)):
        ids[b, :len(p) + len(t)] = np.concatenate([p, t])
    logits = ref_logits(ids)
    check(np.isfinite(logits).all(), f"{name}: non-finite reference logits")
    rows, total, exact, worst = [], 0, 0, 0.0
    for b, (p, t) in enumerate(zip(prompts, served)):
        r = logits[b, len(p) - 1:len(p) - 1 + len(t)]
        w, e = _near_argmax(r, t, tol, f"{name} request {b}")
        rows.append(r)
        total, exact, worst = total + len(t), exact + e, max(worst, w)
    print(f"  {name}: {exact}/{total} served tokens are the dense "
          f"reference's argmax, the rest near-ties (worst margin "
          f"{worst:.2f} of the allowance)", flush=True)
    return rows


def _serve_once(s, engine, prompts, new_tokens, ref_logits, name):
    """Gateway up, two identical waves, /healthz, gateway down. Returns
    (served tokens, reference rows, the engine's paged caches)."""
    import jax

    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.serving import (EngineStepper, ServingGateway,
                                    validate_healthz)
    from tools.serve_gateway import _get_json

    cb = ContinuousBatchingEngine(engine, num_blocks=s["num_blocks"],
                                  block_size=s["block_size"])
    stepper = EngineStepper(cb).start()

    async def drive():
        gw = await ServingGateway(stepper, port=0).start()
        try:
            first = await _wave(gw, stepper, prompts, new_tokens, "w1")
            await asyncio.wrap_future(
                stepper.call(lambda e: e.declare_warm()))
            buckets = set(cb._seen_buckets)
            compiles = _registry_sums()[1]
            second = await _wave(gw, stepper, prompts, new_tokens, "w2")
            code, health = await _get_json(gw.port, "/healthz")
            return first, second, buckets, compiles, code, health
        finally:
            await gw.close()

    try:
        first, second, buckets, compiles, code, health = asyncio.run(drive())
    finally:
        stepper.stop()
    check(stepper.error is None, f"stepper died: {stepper.error!r}")
    check(set(cb._seen_buckets) == buckets
          and _registry_sums()[1] == compiles,
          f"{name}: the second identical wave compiled again "
          f"(buckets {sorted(set(cb._seen_buckets) - buckets)}, "
          f"{_registry_sums()[1] - compiles} backend compiles)")
    check(second == first,
          f"{name}: the second identical wave served different tokens")
    validate_healthz(health)
    check(code == 200 and health["status"] == "ok",
          f"{name}: /healthz {code} {health}")
    check(health["finished"] == 2 * len(prompts)
          and health["inflight"] == 0, f"{name}: /healthz {health}")
    print(f"  {name}: {len(prompts)} requests x 2 waves finished in "
          f"{cb._step_count} steps over buckets {sorted(buckets)}; 0 "
          f"recompiles in wave 2; /healthz ok "
          f"(mesh tp={health['mesh']['tp']})", flush=True)
    rows = _check_tokens(name, first, prompts, ref_logits,
                         LOGIT_TOL_FACTOR * TOL[s["dtype"]])
    jax.block_until_ready(cb.caches)
    return first, rows, cb.caches


def server_leg(s, four_chips=False):
    weights = _serve_weights(s)
    engine = _engine(s, weights)
    ref_logits = _dense_logits_fn(engine, s)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, s["V"], n).astype(np.int32)
               for n, _ in s["requests"]]
    new_tokens = [n for _, n in s["requests"]]
    served, rows, _ = _serve_once(s, engine, prompts, new_tokens,
                                  ref_logits, "gateway tp=1")

    # generate(): the dense-cache path, ragged batch mode
    width = max(map(len, prompts))
    ids = np.zeros((len(prompts), width), np.int32)
    for b, p in enumerate(prompts):
        ids[b, :len(p)] = p
    gen = engine.generate(ids, max_new_tokens=max(new_tokens),
                          prompt_lens=[len(p) for p in prompts])
    agree = 0
    for b, (t, r) in enumerate(zip(served, rows)):
        g = [int(x) for x in gen[b, :len(t)]]
        first_diff = next((j for j in range(len(t)) if g[j] != t[j]), None)
        agree += first_diff is None
        if first_diff is not None:
            # same prefix up to here, so the reference row applies to both
            _near_argmax(r[first_diff:first_diff + 1], g[first_diff:],
                         LOGIT_TOL_FACTOR * TOL[s["dtype"]],
                         f"generate() request {b}")
    print(f"  generate(): {agree}/{len(served)} streams token-equal to the "
          "served ones, the others part at a near-tie", flush=True)

    if four_chips:
        engine4 = _engine(s, weights, tp=4)
        served4, _, caches = _serve_once(
            s, engine4, prompts, new_tokens, ref_logits, "gateway tp=4")
        from paddle_tpu.ops.pallas.paged_attention import paged_head_dim
        want = (2, s["G"] // 4, s["num_blocks"], s["block_size"],
                paged_head_dim(s["D"]))
        shards = caches[0].addressable_shards
        check({sh.data.shape for sh in shards} == {want}
              and len({sh.device.id for sh in shards}) == 4,
              f"tp=4 cache shards {[sh.data.shape for sh in shards]} on "
              f"{[sh.device.id for sh in shards]}, expected {want} on "
              "four devices")
        same = sum(a == b for a, b in zip(served4, served))
        print(f"  tp=4: cache shards {want} on 4 devices; {same}/"
              f"{len(served)} streams token-equal to tp=1", flush=True)


# -- main ---------------------------------------------------------------------

def main():
    from paddle_tpu.framework.platform import compile_cache_dir, init_platform
    platform = init_platform()      # raises with no TPU unless CPU is named
    import jax

    import paddle_tpu
    from paddle_tpu import observability as obs
    from paddle_tpu.models import pretrain

    dev = jax.devices()[0]
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    print(f"chip_smoke: platform={platform} device_kind={dev.device_kind!r} "
          f"device_count={len(jax.devices())} jax={jax.__version__} "
          f"libtpu={libtpu_version} python={sys.version.split()[0]} "
          f"native={paddle_tpu.native.AVAILABLE} "
          f"compile_cache={compile_cache_dir()}", flush=True)
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {platform!r}",
              file=sys.stderr)
        return 2
    if not paddle_tpu.native.AVAILABLE:
        print("chip_smoke: note: the native library did not build; its "
              "consumers run their Python fallbacks", flush=True)
    obs.install_compile_watch()
    four = len(jax.devices()) >= 4
    report = {}

    with leg("kernels", report):
        kernels_leg(FULL_KERNELS)
    _release()
    cfg, batch, seq = pretrain.flagship_config()
    with leg("trainer", report):
        losses, hit = trainer_leg(cfg, batch, seq, dict(n_devices=1))
    _release()
    if four:
        with leg("trainer fsdp=2 x mp=2", report):
            losses4, _ = trainer_leg(
                cfg, batch, seq, dict(n_devices=4, fsdp=2, mp=2))
            check(abs(losses4[0] - losses[0]) <= 2.0 ** -8 * losses[0],
                  f"step-0 loss on four chips {losses4[0]:.4f} vs one "
                  f"chip {losses[0]:.4f}")
            print(f"  step-0 loss {losses4[0]:.4f} vs one chip "
                  f"{losses[0]:.4f}", flush=True)
        _release()
    with leg("server", report):
        server_leg(FULL_SERVE, four_chips=four)
    _release()

    _, _, events = _registry_sums()
    print(f"compile cache {compile_cache_dir()}: "
          f"{events.get('cache_hits', 0)} hits, "
          f"{events.get('cache_misses', 0)} misses this run; second "
          f"identical program was a cache hit: {hit}", flush=True)
    print(json.dumps({"legs": report}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
