"""Kernel autotune layer (reference: paddle/phi/kernels/autotune/ —
cache + gpu_timer: time candidate algorithms once per key, remember the
winner across the process AND across runs).

TPU-native shape: XLA autotunes its own fusions; what's left to tune are
the PALLAS grid parameters. Two tiers live here:

  * the generic ``autotune(key, candidates, run)`` harness — time
    candidate configs on the live inputs the first time a
    (kernel, shape-class) key is seen in EAGER mode, then serve the
    winner from an in-memory + on-disk JSON cache (write-through,
    atomic replace). Under a trace, timing is impossible — the cached
    winner (or the measured default) is used. Enable with
    FLAGS_use_autotune (reference flag of the same name); the cache
    path follows FLAGS_autotune_cache_file or
    ~/.cache/paddle_tpu/autotune.json.
  * the SERVING sweep (``sweep_ragged_serve``) — the ragged
    paged-attention kernel's tunables (work-list ``pack`` factor,
    prefill chunk width, KV DMA buffer depth) swept per
    (shape-class, occupancy-bucket), ranked by measured wall time
    cross-checked against the cost catalog's bytes/flops (a "winner"
    that regresses arithmetic intensity is suspect), winners persisted
    to a committed, schema-validated JSON
    (``tools/serve_autotune.json``) keyed exactly like the serving
    compile buckets, and picked up by
    ``FusedMultiTransformerEngine`` / ``ContinuousBatchingEngine`` at
    construction — zero per-step host cost, zero new compile buckets
    after warmup. Off-TPU the sweep ranks by the deterministic analytic
    model (the interpreter's wall clock measures the interpreter), so a
    CPU re-run reproduces the committed winners bit-for-bit.

This module also carries the shared Mosaic compiler tuning the kernel
tier imports (``cparams``/``VMEM_LIMIT``, absorbed from the retired
``tuning.py`` shim).
"""
import json
import math
import os
import sys
import time

__all__ = ["autotune", "cache_stats", "clear_cache",
           "cparams", "VMEM_LIMIT",
           "SERVE_SCHEMA", "serve_shape_class", "serve_bucket_key",
           "ragged_cost_model", "ragged_candidates", "sweep_ragged_serve",
           "load_serve_cache", "save_serve_cache", "serve_winner",
           "serve_winner_for_engine"]

# -- Mosaic compiler params (absorbed from the retired tuning.py) --------
#
# One scoped-VMEM budget for every kernel: v5e/v5p carry 128 MiB of
# physical VMEM, but Mosaic's default scoped limit is 16 MiB, which
# forces undersized tiles (measured round 5: the flash backward at
# 512/1024 tiles was the single largest consumer of the pretrain step).
# A per-chip knob — retune HERE, not per kernel, when targeting a part
# with less VMEM.
VMEM_LIMIT = 100 * 1024 * 1024


def cparams():
    # function-level import: compat pulls core/, and this module is
    # reachable from the package __init__ — resolving at call time keeps
    # the import graph acyclic
    from ...framework.compat import resolve_compiler_params
    return resolve_compiler_params()(vmem_limit_bytes=VMEM_LIMIT)


def _metrics():
    # lazy: the observability registry must stay optional from the
    # kernel tier (stdlib-only consumers import this module's cache
    # helpers without jax on the path)
    from ...observability import instrument
    return instrument


_mem = None
_stats = {"hits": 0, "misses": 0, "tuned": 0}


def _cache_path():
    return os.environ.get(
        "PADDLE_TPU_AUTOTUNE_CACHE",
        os.path.expanduser("~/.cache/paddle_tpu/autotune.json"))


def _load():
    global _mem
    if _mem is None:
        try:
            with open(_cache_path()) as f:
                _mem = json.load(f)
        except Exception:
            _mem = {}
    return _mem


def _save():
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(_mem, f)
        os.replace(tmp, path)
    except Exception:
        pass  # cache is an optimization; never fail the op


def cache_stats():
    return dict(_stats, entries=len(_load()))


def clear_cache():
    global _mem
    _mem = {}
    try:
        os.unlink(_cache_path())
    except FileNotFoundError:
        pass


def _kernel_label(key):
    # bounded metric label: the kernel family prefix, never the full
    # shape-bearing key (graftlint GL112: label sets must be small)
    return str(key).split(":", 1)[0].split("/", 1)[0] or "unknown"


def autotune(key, candidates, run, reps=3):
    """Return the best candidate for `key`.

    `run(candidate)` executes the kernel with that config and returns a
    value to block on (jax array). Timing: one warmup (compile) + `reps`
    timed calls per candidate. The winner persists in the JSON cache keyed
    by `key` (a string). A candidate that raises (e.g. a block shape
    Mosaic rejects) is skipped, counted and printed; every candidate
    failing is an error."""
    import jax

    sync = jax.block_until_ready
    cache = _load()
    key = str(key)
    hit = cache.get(key)
    if hit is not None:
        _stats["hits"] += 1
        _metrics().autotune_cache_hits().inc()
        # stored as a list (JSON); candidates are tuples
        hit = tuple(hit) if isinstance(hit, list) else hit
        return hit
    _stats["misses"] += 1
    _metrics().autotune_cache_misses().inc()
    trials = _metrics().autotune_trials().labels(kernel=_kernel_label(key))
    best, best_t = None, None
    failed = []
    for cand in candidates:
        try:
            sync(run(cand))  # warmup/compile
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run(cand)
            sync(out)
            dt = (time.perf_counter() - t0) / reps
        except Exception as e:
            failed.append((cand, e))
            continue
        trials.inc()
        if best_t is None or dt < best_t:
            best, best_t = cand, dt
    for cand, e in failed:
        print(f"autotune {key}: candidate {cand} failed: "
              f"{type(e).__name__}: {str(e).splitlines()[0][:200]}",
              file=sys.stderr)
    if best is None:
        last = f" (last: {failed[-1][1]!r})" if failed else ""
        raise RuntimeError(
            f"autotune: every candidate failed for {key}{last}")
    _stats["tuned"] += 1
    cache[key] = list(best) if isinstance(best, tuple) else best
    _save()
    return best


# -- serving-kernel sweep (ragged paged attention) -----------------------

SERVE_SCHEMA = "paddle_tpu.serve_autotune/1"
_SERVE_KERNEL = "ragged_paged_attention"

# nominal single-core throughput the analytic model prices candidates
# with (v5e-class f32 MXU / HBM figures). Only RATIOS matter: the model
# ranks candidates against each other (and supplies the arithmetic-
# intensity cross-check for measured winners); it never claims
# wall-clock accuracy.
_PEAK_FLOPS = 180e12
_PEAK_BW = 820e9
_SWAP_S = 2e-6       # q/out block revisit bubble per output-block change
_DMA_LAT_S = 5e-7    # HBM DMA start->first-byte latency (hidden by any
                     # depth >= 2; fully exposed per step at depth 1)
_SUBLANE = 8         # f32 MXU sublane granularity (pallas guide)


def _dtype_name(dtype):
    # np.dtype chokes on "bfloat16" unless ml_dtypes registered it; the
    # key only needs a stable spelling, not a real dtype object
    try:
        import numpy as np
        return np.dtype(dtype).name
    except Exception:
        return str(dtype)


def serve_shape_class(kv_heads, group_q, block_size, head_dim, dtype):
    """Shape-class key: everything that keys the kernel's compiled
    geometry EXCEPT the per-step occupancy (which the bucket key
    carries)."""
    return (f"kvh{int(kv_heads)}_g{int(group_q)}_bs{int(block_size)}"
            f"_d{int(head_dim)}_{_dtype_name(dtype)}")


def serve_bucket_key(t_total, chunk):
    """Occupancy-bucket key — the EXACT (padded work-list length,
    chunk-width) pair `ContinuousBatchingEngine._seen_buckets` tracks
    as its compile bucket, stringified for JSON."""
    return f"t{int(t_total)}_c{int(chunk)}"


def ragged_cost_model(pack, chunk, group_q, block_size, head_dim,
                      t_total, kv_heads, batch, itemsize=4,
                      buffer_depth=2):
    """Analytic per-bucket cost of one ragged-kernel invocation under a
    candidate config. Returns a dict with `flops` (useful work: valid
    query rows only), `bytes` (KV DMA + q/out block traffic),
    `intensity` (flops/bytes), and `model_wall_s`.

    The model prices the three effects the tunables actually move:
      * pack — a bigger packed tile costs MXU rows in SUBLANE-granule
        steps (rows below 8 are padding the hardware burns anyway, so
        pack*chunk*G up to 8 is free) but cuts output-block revisits
        (fewer q/out swaps = fewer pipeline bubbles);
      * chunk — a wider prefill slab amortizes per-call overhead over
        more tokens (scores are per-token downstream);
      * buffer_depth — depth 1 serializes DMA against compute; depth>=2
        overlaps them; each extra slot adds one pipeline-fill DMA.
    """
    pg = int(pack) * int(chunk) * int(group_q)
    rows_eff = -(-pg // _SUBLANE) * _SUBLANE
    steps = int(kv_heads) * int(t_total)
    flops_step = 4.0 * rows_eff * block_size * head_dim
    kv_bytes_step = 2.0 * block_size * head_dim * itemsize
    compute_s = flops_step / _PEAK_FLOPS
    dma_s = kv_bytes_step / _PEAK_BW
    # depth 1 waits out every copy start-to-finish (latency + transfer
    # serialized against compute); depth >= 2 overlaps the transfer and
    # hides the issue latency behind the previous step's compute
    per_step = (compute_s + dma_s + _DMA_LAT_S) if buffer_depth == 1 \
        else max(compute_s, dma_s)
    ngroups = -(-int(batch) // int(pack))
    swaps = ngroups * int(kv_heads)
    wall = (steps * per_step + swaps * _SWAP_S
            + (int(buffer_depth) - 1) * dma_s)
    useful_flops = 4.0 * chunk * group_q * block_size * head_dim * steps
    total_bytes = (steps * kv_bytes_step
                   + swaps * 2.0 * pg * head_dim * itemsize)
    return {
        "flops": useful_flops,
        "bytes": total_bytes,
        "intensity": useful_flops / max(total_bytes, 1.0),
        "model_wall_s": wall,
    }


def ragged_candidates(batch, group_q, chunk=None, max_chunk=256,
                      depths=(1, 2, 4)):
    """The candidate grid for one bucket: pow2 packs up to the batch,
    pow2 chunk widths up to `max_chunk` (decode buckets — chunk=None —
    pin chunk to 1), and the DMA depths. Chunk candidates stay in the
    pow2 family by construction, so a tuned width never mints a compile
    bucket the default pow2 treadmill wouldn't."""
    packs, p = [], 1
    while p <= max(1, int(batch)):
        packs.append(p)
        p *= 2
    if chunk is None:
        chunks = [1]
    else:
        chunks, c = [], 1
        while c <= max(int(chunk), 1):
            if c <= max_chunk:
                chunks.append(c)
            c *= 2
    return [{"pack": pk, "prefill_chunk": ch, "buffer_depth": int(d)}
            for pk in packs for ch in chunks for d in depths]


def _model_score(cand, model):
    """Deterministic ranking tuple for interpret-mode sweeps: per-token
    model wall first, then prefer the tile that fills (not spills) the
    sublane granule, smaller pack, shallower buffer — every tie broken
    by a static preference, so `sweep twice, same winner` holds."""
    pg = cand["pack"] * cand["prefill_chunk"] * cand["_group_q"]
    tokens = max(1, cand["_batch"] * cand["prefill_chunk"])
    return (model["model_wall_s"] / tokens,
            -min(pg, _SUBLANE), pg,
            abs(cand["buffer_depth"] - 2), cand["buffer_depth"])


def sweep_ragged_serve(kv_heads, group_q, head_dim, block_size,
                       context_lens, *, chunk=None, dtype="float32",
                       candidates=None, depths=(1, 2, 4), reps=3,
                       measure=None, cache=None, seed=0):
    """Sweep the ragged kernel's tunables for ONE
    (shape-class, occupancy) bucket and record the winner.

    `context_lens` describes the bucket's occupancy (one entry per
    active sequence, post-step KV length); `chunk=None` sweeps a decode
    bucket (one query per sequence), an int sweeps a prefill bucket of
    that slab width. When `measure` is true (default: only on a real
    TPU backend) every candidate is timed on synthetic live inputs and
    ranked by wall clock, cross-checked against the analytic
    bytes/flops — a measured winner whose arithmetic intensity
    regresses >10% below the default config's is SUSPECT (it won on
    noise or on wasted traffic) and is excluded from the podium.
    Otherwise (CPU interpret mode: the wall clock times the
    interpreter, not the kernel) candidates rank by the deterministic
    analytic model, so committed winners reproduce bit-for-bit.

    Mutates + returns `cache` (a serve-autotune cache dict, fresh one
    created when None); every trial lands in the cost catalog (when
    enabled) and on the `tuning` tracer span."""
    import numpy as np

    from ...observability import tracing as _tracing
    from ...observability.costs import get_cost_catalog
    from .paged_attention import (build_ragged_work, default_pack,
                                  next_pow2)

    lens = np.asarray(context_lens, np.int64).reshape(-1)
    batch = int(lens.shape[0])
    try:
        itemsize = int(np.dtype(dtype).itemsize)
    except Exception:
        itemsize = 2                       # bfloat16-family strings
    c_width = 1 if chunk is None else int(chunk)
    shape_cls = serve_shape_class(kv_heads, group_q, block_size,
                                  head_dim, dtype)

    # the bucket is keyed by the DEFAULT config's padded work length —
    # the same (t_total, c) pair the scheduler's _seen_buckets tracks
    max_nb = max(1, int(-(-int(lens.max(initial=1)) // block_size)))
    tables = np.arange(batch * max_nb, dtype=np.int32) \
        .reshape(batch, max_nb)
    dflt_pack = default_pack(batch, group_q)
    q_lens = None if chunk is None \
        else np.minimum(np.maximum(lens, 1), c_width).astype(np.int64)
    _, _, t_total, _ = build_ragged_work(
        tables, lens, block_size, dflt_pack, bucket_to=next_pow2,
        q_lens=q_lens)
    bucket = serve_bucket_key(t_total, next_pow2(c_width))

    if candidates is None:
        candidates = ragged_candidates(batch, group_q, chunk=chunk,
                                       depths=depths)
    if measure is None:
        import jax
        measure = jax.devices()[0].platform == "tpu"

    catalog = get_cost_catalog()
    trials = _metrics().autotune_trials().labels(kernel=_SERVE_KERNEL)
    runner = _make_bucket_runner(
        kv_heads, group_q, head_dim, block_size, lens, chunk, dtype,
        tables, seed) if measure else None

    records = []
    failed = []
    with _tracing.get_tracer().span(
            "tuning", kernel=_SERVE_KERNEL, shape_class=shape_cls,
            bucket=bucket, candidates=len(candidates)):
        for cand in candidates:
            model = ragged_cost_model(
                cand["pack"], cand["prefill_chunk"], group_q, block_size,
                head_dim, t_total, kv_heads, batch, itemsize=itemsize,
                buffer_depth=cand["buffer_depth"])
            rec = dict(cand, **model, measured=bool(measure))
            rec["_group_q"] = group_q
            rec["_batch"] = batch
            if measure:
                try:
                    wall = runner(cand, reps)
                except Exception as e:  # graftlint: disable=GL113 - a sweep, not a serve loop: a candidate the kernel rejects is skipped, counted and printed, and all failing raises below
                    failed.append(e)
                    print(f"sweep_ragged_serve {shape_cls}/{bucket}: "
                          f"candidate {cand} failed: {type(e).__name__}: "
                          f"{str(e).splitlines()[0][:200]}",
                          file=sys.stderr)
                    continue
                rec["wall_s"] = wall
            else:
                rec["wall_s"] = model["model_wall_s"]
            trials.inc()
            if catalog is not None and getattr(catalog, "enabled", False):
                catalog.record(
                    f"autotune/{_SERVE_KERNEL}",
                    flops=model["flops"],
                    bytes_accessed=model["bytes"],
                    signature=f"{shape_cls}/{bucket}/pack{cand['pack']}"
                              f"_c{cand['prefill_chunk']}"
                              f"_depth{cand['buffer_depth']}")
            records.append(rec)
    if not records:
        raise RuntimeError(
            f"sweep_ragged_serve: every candidate failed for "
            f"{shape_cls}/{bucket}"
            + (f" (last: {failed[-1]!r})" if failed else ""))

    base_intensity = min(
        (r["intensity"] for r in records
         if r["pack"] == dflt_pack and r["buffer_depth"] == 2),
        default=max(r["intensity"] for r in records))
    if measure:
        ranked = sorted(
            records,
            key=lambda r: (r["wall_s"]
                           / max(1, batch * r["prefill_chunk"])))
        # intensity cross-check: a wall-clock winner doing >10% more
        # byte traffic per useful flop than the default config is
        # suspect — keep honest candidates unless ALL are suspect
        honest = [r for r in ranked
                  if r["intensity"] >= 0.9 * base_intensity]
        win = (honest or ranked)[0]
        win = dict(win, suspect=win["intensity"] < 0.9 * base_intensity)
    else:
        win = dict(min(records, key=lambda r: _model_score(r, r)),
                   suspect=False)

    entry = {k: win[k] for k in ("pack", "prefill_chunk", "buffer_depth")}
    entry.update(
        wall_us=round(win["wall_s"] * 1e6, 3),
        intensity=round(win["intensity"], 4),
        measured=win["measured"], suspect=win["suspect"],
        trials=len(records))
    g = _metrics().autotune_winner()
    for param in ("pack", "prefill_chunk", "buffer_depth"):
        # bounded by construction: the literal 3-tuple above IS the
        # label set
        g.labels(kernel=_SERVE_KERNEL, param=param).set(entry[param])  # graftlint: disable=GL112 - fixed 3-element literal label set

    if cache is None:
        cache = {"schema": SERVE_SCHEMA, "kernel": _SERVE_KERNEL,
                 "shapes": {}}
    sec = cache.setdefault("shapes", {}).setdefault(shape_cls, {})
    sec.setdefault("buckets", {})[bucket] = entry
    # the per-shape "winner" the engines pick up at construction:
    # pack/buffer_depth vote across ALL buckets (wall-weighted toward
    # the bucket that costs the most); prefill_chunk votes among the
    # PREFILL buckets only — a decode bucket's pinned chunk=1 must
    # never talk the scheduler into one-token-at-a-time prefill
    buckets = sec["buckets"]

    def vote(field, rows):
        tally = {}
        for b in rows:
            tally[b[field]] = tally.get(b[field], 0.0) \
                + float(b.get("wall_us", 1.0))
        return max(sorted(tally), key=lambda k: tally[k])

    prefill_rows = [b for b in buckets.values()
                    if b["prefill_chunk"] > 1] or list(buckets.values())
    sec["winner"] = {
        "pack": vote("pack", buckets.values()),
        "prefill_chunk": vote("prefill_chunk", prefill_rows),
        "buffer_depth": vote("buffer_depth", buckets.values()),
    }
    return cache


def _make_bucket_runner(kv_heads, group_q, head_dim, block_size, lens,
                        chunk, dtype, tables, seed):
    """Device-measurement closure: synthetic cache/query tensors for the
    bucket, one compiled call per candidate, mean wall over `reps`
    ending in `block_until_ready`. A candidate the kernel rejects
    raises."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .paged_attention import (_lane_pad, build_ragged_work, next_pow2,
                                  paged_head_dim, ragged_paged_attention)

    rng = np.random.default_rng(seed)
    batch = lens.shape[0]
    num_blocks = int(tables.max()) + 1
    h = kv_heads * group_q
    # cache rows as the engine allocates them: lane-padded with zeros
    dc = paged_head_dim(head_dim)
    kc = _lane_pad(jnp.asarray(rng.standard_normal(
        (kv_heads, num_blocks, block_size, head_dim)) * 0.1, dtype), dc)
    vc = _lane_pad(jnp.asarray(rng.standard_normal(
        (kv_heads, num_blocks, block_size, head_dim)) * 0.1, dtype), dc)
    kv = jnp.stack([kc, vc])    # one layer's cache as the engine holds it
    if chunk is None:
        q = jnp.asarray(rng.standard_normal(
            (batch, h, head_dim)) * 0.1, dtype)
        q_lens = None
    else:
        q = jnp.asarray(rng.standard_normal(
            (batch, int(chunk), h, head_dim)) * 0.1, dtype)
        q_lens = np.minimum(np.maximum(lens, 1), int(chunk))

    def run(cand, reps):
        work = build_ragged_work(
            tables, lens, block_size, cand["pack"],
            bucket_to=next_pow2, q_lens=q_lens)

        def call():
            return ragged_paged_attention(
                q, kv, tables, jnp.asarray(lens, jnp.int32),
                work=work, q_lens=q_lens,
                buffer_depth=cand["buffer_depth"])

        jax.block_until_ready(call())      # warmup + compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = call()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    return run


# -- committed serve-cache file ------------------------------------------

def _valid_winner(w):
    return (isinstance(w, dict)
            and all(isinstance(w.get(k), int) and w[k] >= 1
                    for k in ("pack", "prefill_chunk", "buffer_depth")))


def load_serve_cache(path):
    """Read + schema-validate a committed serve-autotune JSON. Returns
    the cache dict, or None when the file is missing, unparsable, from
    a FOREIGN/STALE schema, or structurally broken — a bad cache must
    degrade to untuned defaults, never crash an engine constructor."""
    if isinstance(path, dict):
        cache = path              # already-loaded dict passes through
    else:
        try:
            with open(path) as f:
                cache = json.load(f)
        except Exception:
            return None
    if not isinstance(cache, dict) or cache.get("schema") != SERVE_SCHEMA:
        return None
    shapes = cache.get("shapes")
    if not isinstance(shapes, dict):
        return None
    for sec in shapes.values():
        if not isinstance(sec, dict) or not _valid_winner(sec.get("winner")):
            return None
        if not isinstance(sec.get("buckets"), dict):
            return None
        if not all(_valid_winner(b) for b in sec["buckets"].values()):
            return None
    return cache


def save_serve_cache(cache, path):
    """Atomic, diff-stable (sorted keys, indented) write of the serve
    cache — the file is COMMITTED and gated, so byte-stability across
    re-runs matters as much as atomicity."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def serve_winner(cache, shape_class, bucket=None):
    """Winner lookup: the exact occupancy bucket when asked (and
    present), else the shape-class's aggregate winner. Counts cache
    hits/misses — the zero-per-step-cost contract means these move at
    ENGINE CONSTRUCTION only."""
    inst = _metrics()
    sec = (cache or {}).get("shapes", {}).get(shape_class)
    if sec is None:
        inst.autotune_cache_misses().inc()
        return None
    inst.autotune_cache_hits().inc()
    if bucket is not None:
        b = sec.get("buckets", {}).get(bucket)
        if b is not None:
            return dict(b)
    return dict(sec["winner"])


def serve_winner_for_engine(cache, kv_heads, group_q, head_dim, dtype):
    """Engine-constructor lookup when the paged block_size is not known
    yet (it belongs to the scheduler): match every shape-class section
    on (kvh, group, head_dim, dtype) ignoring block size; first match
    in sorted key order wins (deterministic across runs)."""
    if not cache:
        _metrics().autotune_cache_misses().inc()
        return None
    want_pre = f"kvh{int(kv_heads)}_g{int(group_q)}_bs"
    want_suf = f"_d{int(head_dim)}_{_dtype_name(dtype)}"
    for key in sorted(cache.get("shapes", {})):
        if key.startswith(want_pre) and key.endswith(want_suf):
            return serve_winner(cache, key)
    _metrics().autotune_cache_misses().inc()
    return None
