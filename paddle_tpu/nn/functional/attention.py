"""Attention functionals.

Reference surface: python/paddle/nn/functional/flash_attention.py:358
(flash_attention), :756 (flash_attn_unpadded), :1299 (flashmask_attention),
scaled_dot_product_attention. On TPU the fused kernel is a Pallas flash
kernel (paddle_tpu/ops/pallas/flash_attention.py, M7 tier); this module holds
the API and the XLA reference path used on CPU / for small shapes.
"""
import math
import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op
from ...core import random as _random

_USE_PALLAS = True


@functools.lru_cache(maxsize=1)
def _flash_available():
    """The Pallas flash kernel runs on the TPU; every other backend takes
    the XLA reference path. A backend that fails to initialise raises."""
    return _USE_PALLAS and jax.devices()[0].platform == "tpu"


def _per_shard(fn, q, k, v, *rope):
    """Run the flash kernel under the global mesh. GSPMD cannot partition
    a Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"
    at lowering), so on a multi-device mesh the call goes through a
    fully-manual shard_map: batch over (dp, fsdp), heads over mp, every
    other dim whole — an sp-sharded sequence gathers, since the kernel
    needs all of it (context parallelism has its own ring path). An axis
    that does not divide its dim is left out, as `spec_for_param` does
    for parameters. cos/sin tables replicate."""
    from ...distributed.mesh import get_mesh
    pm = get_mesh()
    if pm is None or pm.size == 1:
        return fn(q, k, v, *rope)
    from jax.sharding import PartitionSpec as P
    from ...framework.compat import shard_map
    mesh = pm.jax_mesh
    batch_axes, shards = [], 1
    for ax in ("dp", "fsdp"):
        n = mesh.shape.get(ax, 1)
        if n > 1 and q.shape[0] % (shards * n) == 0:
            batch_axes.append(ax)
            shards *= n
    mp = mesh.shape.get("mp", 1)
    heads = "mp" if mp > 1 and q.shape[2] % mp == 0 \
        and k.shape[2] % mp == 0 else None
    spec = P(tuple(batch_axes) or None, None, heads, None)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec) + (P(),) * len(rope),
        out_specs=spec, check_vma=False)(q, k, v, *rope)


def _sdpa_ref(q, k, v, mask=None, dropout=0.0, causal=False, scale=None,
              rng_key=None):
    """Reference attention in pure XLA ops, [B, S, H, D] layout (paddle's
    flash_attention layout).

    Dropout requires `rng_key` (a PRNG key array passed in as an *input*,
    never drawn inside this function). Keeping the impl RNG-free is the
    philox-offset discipline (reference paddle/phi/core/generator.h:32):
    the eager vjp cache rematerialises the forward inside its jitted
    backward, and a key passed as an input replays identically there, while
    an internal draw would leak a tracer into the global key chain."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if k.shape[2] != q.shape[2]:  # GQA: broadcast KV head groups
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B,S,H,D] -> [B,H,S,D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # QK logits and the prob·V reduction accumulate in f32 (MXU-native
    # bf16-in/f32-accumulate); only the final output is cast back.
    logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((s, t), dtype=bool), k=t - s)
        logits = jnp.where(cm, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout > 0.0 and rng_key is not None:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhst,bhtd->bhsd", probs,
                     vt.astype(jnp.float32)).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    training=True, rope_cos=None, rope_sin=None):
    """paddle.nn.functional.flash_attention.flash_attention parity:
    inputs [batch, seqlen, num_heads, head_dim]; returns (out, softmax|None).

    On TPU dispatches to the Pallas flash kernel (M7) — a kernel that
    fails to lower or run raises, there is no second path behind it;
    other backends use the XLA reference path. rope_cos/rope_sin
    [S, D/2] (neox): applied to q/k INSIDE the Pallas kernels on TPU,
    otherwise rotated before the reference path — either way rotated
    q/k are an implementation detail."""
    if _flash_available() and dropout == 0.0 and not return_softmax:
        from ...ops.pallas import flash_attention as pallas_flash
        bq, bk = pallas_flash.tuned_blocks(query, key, value, causal)

        def kernel(q, k, v, rc=None, rs=None):
            return pallas_flash.flash_attention_bshd(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                rope_cos=rc, rope_sin=rs)

        def impl(*arrs):
            return _per_shard(kernel, *arrs)

        if rope_cos is None:
            args = (query, key, value)
        else:
            args = (query, key, value, rope_cos, rope_sin)
        return apply_op("flash_attention", impl, args, {}), None
    if rope_cos is not None:
        # non-kernel path: rotate explicitly (same math, materialized)
        from .rope import apply_rotary_pos_emb
        query = apply_rotary_pos_emb(query, rope_cos, rope_sin, True)
        key = apply_rotary_pos_emb(key, rope_cos, rope_sin, True)

    if dropout > 0.0 and training:
        def impl(q, k, v, rk):
            return _sdpa_ref(q, k, v, dropout=dropout, causal=causal,
                             rng_key=rk)
        out = apply_op("flash_attention_ref", impl,
                       (query, key, value, _random.fresh_key_tensor()), {})
        return out, None

    def impl(q, k, v):
        return _sdpa_ref(q, k, v, causal=causal)
    out = apply_op("flash_attention_ref", impl, (query, key, value), {})
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True):
    """paddle.nn.functional.scaled_dot_product_attention parity
    ([B, S, H, D] layout, additive or bool mask)."""
    if attn_mask is None:
        out, _ = flash_attention(query, key, value, dropout=dropout_p,
                                 causal=is_causal, training=training)
        return out

    if dropout_p > 0.0 and training:
        def impl(q, k, v, m, rk):
            return _sdpa_ref(q, k, v, mask=m, dropout=dropout_p,
                             causal=is_causal, rng_key=rk)
        return apply_op("sdpa", impl, (query, key, value, attn_mask,
                                       _random.fresh_key_tensor()), {})

    def impl(q, k, v, m):
        return _sdpa_ref(q, k, v, mask=m, causal=is_causal)
    return apply_op("sdpa", impl, (query, key, value, attn_mask), {})


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=True):
    """FlashMask (reference python/paddle/nn/functional/flash_attention.py:1299):
    column-sparse mask attention for long context. The mask is given as
    start/end row indices per column: position (r, c) is masked out when
    r >= start[c] (LTS) etc. Reference path materializes the mask; the Pallas
    kernel (M7+) consumes indices directly."""
    if startend_row_indices is None:
        out, _ = flash_attention(query, key, value, dropout=dropout, causal=causal)
        return out

    def impl(q, k, v, idx, *rk):
        s = q.shape[1]
        rows = jnp.arange(s)[:, None]  # query row index
        # LTS convention: column c masks query rows r >= start[c]
        start = idx[..., 0]  # [B, nh, S_k]
        keep = rows[None, None] < start[:, :, None, :]
        if causal:
            cm = jnp.tril(jnp.ones((s, s), dtype=bool))
            keep = jnp.logical_and(keep, cm)
        return _sdpa_ref(q, k, v, mask=keep, dropout=dropout, causal=False,
                         rng_key=rk[0] if rk else None)
    args = (query, key, value, startend_row_indices)
    if dropout > 0.0:
        args = args + (_random.fresh_key_tensor(),)
    return apply_op("flashmask_attention", impl, args, {})


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, training=True):
    """Var-len attention (reference flash_attn_unpadded, :756): packed
    [total_tokens, H, D] with cumulative sequence offsets. XLA wants static
    shapes, so this builds a segment mask over the packed layout — the
    idiomatic TPU equivalent of varlen flash (segment-ids pattern)."""
    def impl(q, k, v, cu_q, cu_k, *rk):
        total_q = q.shape[0]
        total_k = k.shape[0]
        pos_q = jnp.arange(total_q)
        pos_k = jnp.arange(total_k)
        seg_q = jnp.searchsorted(cu_q[1:], pos_q, side="right")
        seg_k = jnp.searchsorted(cu_k[1:], pos_k, side="right")
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            off_q = pos_q - jnp.take(cu_q, seg_q)
            off_k = pos_k - jnp.take(cu_k, seg_k)
            mask = jnp.logical_and(mask, off_q[:, None] >= off_k[None, :])
        d = q.shape[-1]
        sc = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("shd,thd->hst", q, k) * sc
        logits = jnp.where(mask[None], logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        if rk:
            keep = jax.random.bernoulli(rk[0], 1.0 - dropout, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
        return jnp.einsum("hst,thd->shd", probs, v)
    args = (query, key, value, cu_seqlens_q, cu_seqlens_k)
    if dropout > 0.0 and training:
        args = args + (_random.fresh_key_tensor(),)
    out = apply_op("flash_attn_unpadded", impl, args, {})
    return out, None


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False, return_softmax=False,
                         fixed_seed_offset=None, training=True):
    """Packed-QKV flash attention (reference flash_attn_qkvpacked):
    qkv [B, S, 3 + 2*(G-1)... ] — paddle layout [B, S, 3, H, D] for MHA;
    unpacks and dispatches to flash_attention."""
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                                max_seqlen_k, scale=None, dropout=0.0,
                                causal=False, return_softmax=False,
                                training=True):
    """Packed var-len form (reference flash_attn_varlen_qkvpacked):
    qkv [total_tokens, 3, H, D]."""
    q = qkv[:, 0]
    k = qkv[:, 1]
    v = qkv[:, 2]
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale=scale,
                               dropout=dropout, causal=causal,
                               return_softmax=return_softmax,
                               training=training)


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None):
    """Block/CSR-sparse attention (reference sparse_attention op): per-row
    allowed key columns given in CSR form. TPU-native path: scatter the CSR
    pattern into a dense boolean mask and run the fused softmax path — XLA
    handles the [S, S] mask well below ~16k; beyond that use
    flashmask_attention (interval masks) which the Pallas tier consumes
    directly."""
    def impl(q, k, v, off, cols, *masks):
        b, h, s, d = q.shape
        # build mask by scattering: for each row r, cols[off[r]:off[r+1]]
        dense = jnp.zeros((b, h, s, s), bool)
        offs = off.reshape(b, h, s + 1)
        colv = cols.reshape(b, h, -1)
        pos = jnp.arange(colv.shape[-1])
        # row of entry i = #rows whose end-offset is <= i
        rows = (pos[None, None, :, None]
                >= offs[:, :, None, 1:]).sum(-1)      # [B,H,nnz]
        valid = pos[None, None] < offs[..., -1:]
        bidx = jnp.arange(b)[:, None, None]
        hidx = jnp.arange(h)[None, :, None]
        # padding entries are pointed out of bounds and dropped — writing
        # False at a clamped (0,0) could clobber a real allowed pair
        dense = dense.at[bidx, hidx,
                         jnp.where(valid, rows, s),
                         jnp.where(valid, colv, s)].set(True, mode="drop")
        import math as _m
        logits = jnp.einsum("bhsd,bhtd->bhst", q, k) / _m.sqrt(d)
        if masks and masks[0] is not None:
            logits = logits + masks[0]
        logits = jnp.where(dense, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v)
    args = (query, key, value, sparse_csr_offset, sparse_csr_columns)
    if attn_mask is not None:
        args = args + (attn_mask,)
    return apply_op("sparse_attention", impl, args, {})
