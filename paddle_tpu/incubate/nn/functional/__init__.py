"""Fused-op python bindings (reference: python/paddle/incubate/nn/
functional/ — fused_multi_head_attention, fused_feedforward,
fused_rotary_position_embedding, masked_multihead_attention,
block_multihead_attention; kernels in paddle/phi/kernels/fusion/gpu/,
SURVEY.md §2.9).

On TPU the "fusion" is either a Pallas kernel (attention family) or a
jnp composition XLA fuses on its own (rope/bias_act/dropout_add — the MXU
epilogue fusions the reference hand-writes in CUDA)."""
import functools
import math
import typing

import jax
import jax.numpy as jnp

from ....core.dispatch import apply_op
from ....core import random as _random
from ....observability import tracing
from ....nn.functional.rope import fused_rotary_position_embedding  # noqa: F401

NEG_INF_F = -1e30

__all__ = [
    "fused_multi_head_attention", "fused_feedforward", "fused_bias_act",
    "fused_dropout_add", "fused_bias_dropout_residual_layer_norm",
    "fused_rotary_position_embedding", "masked_multihead_attention",
    "block_multihead_attention", "fused_linear_param_grad_add",
    "flashmask_attention", "fused_multi_transformer",
    "fused_multi_transformer_int8", "fused_multi_transformer_int4",
    "quantize_int4",
    "fused_matmul_bias", "fused_linear", "fused_linear_activation",
    "fused_moe", "variable_length_memory_efficient_attention",
    "fused_rms_norm", "fused_layer_norm", "blha_get_max_len", "swiglu",
    "block_kv_cache_rewind",
]


def _ln(h, eps, scale=None, bias=None):
    mu = h.mean(-1, keepdims=True)
    var = ((h - mu) ** 2).mean(-1, keepdims=True)
    out = (h - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.0,
                               attn_dropout_rate=0.0, ln_epsilon=1e-5,
                               training=True, num_heads=None):
    """Reference fused_attention_kernel.cu semantics: [pre-LN] -> QKV proj
    -> MHA -> out proj -> residual add [-> post-LN]. One traced graph —
    XLA fuses what the CUDA megakernel fuses by hand."""
    def impl(xa, qkvw, lw, *rest):
        it = iter(rest)
        cache = next(it) if cache_kv is not None else None
        mask_arr = next(it) if attn_mask is not None else None
        plns = next(it) if pre_ln_scale is not None else None
        plnb = next(it) if pre_ln_bias is not None else None
        qb = next(it) if qkv_bias is not None else None
        lb = next(it) if linear_bias is not None else None
        lns = next(it) if ln_scale is not None else None
        lnb = next(it) if ln_bias is not None else None
        kit = it  # trailing args are the dropout keys

        h = _ln(xa, pre_ln_epsilon, plns, plnb) if pre_layer_norm else xa
        b, s, dm = h.shape
        # qkv_weight: [3, num_heads, head_dim, dim] (reference layout)
        nh, hd = qkvw.shape[1], qkvw.shape[2]
        qkv = jnp.einsum("bsd,tnhd->tbsnh", h, qkvw,
                         preferred_element_type=jnp.float32).astype(h.dtype)
        if qb is not None:
            qkv = qkv + qb.reshape(3, 1, 1, nh, hd)
        q, k, v = qkv[0], qkv[1], qkv[2]          # [B, S, H, hd]
        new_cache = None
        if cache is not None:
            # decode: attend over cached K/V ++ current chunk and return
            # the extended cache (reference CacheKV branch)
            k = jnp.concatenate([cache[0], k], axis=1)
            v = jnp.concatenate([cache[1], v], axis=1)
            new_cache = jnp.stack([k, v])
        scale = 1.0 / math.sqrt(hd)
        logits = jnp.einsum("bsnh,btnh->bnst", q, k,
                            preferred_element_type=jnp.float32) * scale
        if mask_arr is not None:
            logits = logits + mask_arr.astype(logits.dtype)
        p = jax.nn.softmax(logits, axis=-1)
        if training and attn_dropout_rate > 0.0:
            keep = jax.random.bernoulli(next(kit),
                                        1.0 - attn_dropout_rate, p.shape)
            p = jnp.where(keep, p / (1.0 - attn_dropout_rate), 0.0)
        ctx = jnp.einsum("bnst,btnh->bsnh", p,
                         v.astype(jnp.float32)).astype(h.dtype)
        out = jnp.einsum("bse,ed->bsd", ctx.reshape(b, s, nh * hd), lw)
        if lb is not None:
            out = out + lb
        if training and dropout_rate > 0.0:
            keep = jax.random.bernoulli(next(kit),
                                        1.0 - dropout_rate, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout_rate), 0.0)
        out = xa + out                             # residual
        if not pre_layer_norm:
            out = _ln(out, ln_epsilon, lns, lnb)
        return out if new_cache is None else (out, new_cache)

    # dropout keys ride as INPUT leaves (philox-as-data discipline,
    # core/random.py): the op stays vjp-cacheable and every capture tier
    # re-draws per call
    n_keys = int(training and attn_dropout_rate > 0.0) + \
        int(training and dropout_rate > 0.0)
    args = [x, qkv_weight, linear_weight]
    for t in (cache_kv, attn_mask, pre_ln_scale, pre_ln_bias, qkv_bias,
              linear_bias, ln_scale, ln_bias):
        if t is not None:
            args.append(t)
    args += [_random.fresh_key_tensor() for _ in range(n_keys)]
    return apply_op("fused_multi_head_attention", impl, tuple(args), {})


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True):
    """Reference fused_feedforward_kernel.cu: [pre-LN] -> FC1 -> act ->
    FC2 -> residual [-> post-LN]."""
    def impl(xa, w1, w2, *rest):
        it = iter(rest)
        b1 = next(it) if linear1_bias is not None else None
        b2 = next(it) if linear2_bias is not None else None
        s1 = next(it) if ln1_scale is not None else None
        sb1 = next(it) if ln1_bias is not None else None
        s2 = next(it) if ln2_scale is not None else None
        sb2 = next(it) if ln2_bias is not None else None

        kit = it  # trailing args are the dropout keys

        def _drop(t, rate):
            if not training or rate <= 0.0:
                return t
            keep = jax.random.bernoulli(next(kit), 1.0 - rate, t.shape)
            return jnp.where(keep, t / (1.0 - rate), 0.0)

        h = _ln(xa, ln1_epsilon, s1, sb1) if pre_layer_norm else xa
        h = jnp.einsum("...d,de->...e", h, w1)
        if b1 is not None:
            h = h + b1
        act = {"relu": jax.nn.relu,
               "gelu": lambda t: jax.nn.gelu(t, approximate=False),
               "silu": jax.nn.silu}[activation]
        h = _drop(act(h), dropout1_rate)
        h = jnp.einsum("...e,ed->...d", h, w2)
        if b2 is not None:
            h = h + b2
        out = xa + _drop(h, dropout2_rate)
        if not pre_layer_norm:
            out = _ln(out, ln2_epsilon, s2, sb2)
        return out

    args = [x, linear1_weight, linear2_weight]
    for t in (linear1_bias, linear2_bias, ln1_scale, ln1_bias, ln2_scale,
              ln2_bias):
        if t is not None:
            args.append(t)
    n_keys = int(training and dropout1_rate > 0.0) + \
        int(training and dropout2_rate > 0.0)
    args += [_random.fresh_key_tensor() for _ in range(n_keys)]
    return apply_op("fused_feedforward", impl, tuple(args), {})


def fused_bias_act(x, bias=None, act_method="gelu"):
    """Reference fused_bias_act_kernel.cu (plain and gated activations)."""
    def impl(xa, *rest):
        h = xa + rest[0] if rest else xa
        if act_method in ("geglu", "swiglu"):
            a, b = jnp.split(h, 2, axis=-1)
            base = jax.nn.gelu if act_method == "geglu" else jax.nn.silu
            return base(a) * b
        act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
               "silu": jax.nn.silu}[act_method]
        return act(h)

    args = (x,) if bias is None else (x, bias)
    return apply_op("fused_bias_act", impl, args, {})


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train"):
    """Reference fused_dropout_add_kernel.cu: dropout(x) + y."""
    def impl(xa, ya, *rk):
        if mode == "downscale_in_infer":
            # train: drop without rescale; infer: scale by (1-p)
            if not training:
                return xa * (1.0 - p) + ya
            if p == 0.0:
                return xa + ya
            keep = jax.random.bernoulli(rk[0], 1.0 - p, xa.shape)
            return jnp.where(keep, xa, 0.0) + ya
        if not training or p == 0.0:
            return xa + ya
        keep = jax.random.bernoulli(rk[0], 1.0 - p, xa.shape)
        return jnp.where(keep, xa / (1.0 - p), 0.0) + ya

    args = (x, y)
    if training and p > 0.0:
        args = args + (_random.fresh_key_tensor(),)
    return apply_op("fused_dropout_add", impl, args, {})


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.0, ln_epsilon=1e-5,
                                           training=True):
    """Reference fused_bias_dropout_residual_layer_norm_kernel.cu."""
    def impl(xa, res, *rest):
        it = iter(rest)
        b = next(it) if bias is not None else None
        s = next(it) if ln_scale is not None else None
        lb = next(it) if ln_bias is not None else None
        h = xa if b is None else xa + b
        if training and dropout_rate > 0.0:
            keep = jax.random.bernoulli(next(it),
                                        1.0 - dropout_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout_rate), 0.0)
        return _ln(h + res, ln_epsilon, s, lb)

    args = [x, residual]
    for t in (bias, ln_scale, ln_bias):
        if t is not None:
            args.append(t)
    if training and dropout_rate > 0.0:
        args.append(_random.fresh_key_tensor())
    return apply_op("fused_bias_dropout_residual_layer_norm", impl,
                    tuple(args), {})


def fused_linear_param_grad_add(x, dout, dweight=None, dbias=None,
                                multi_precision=True, has_bias=True):
    """Reference fused_linear_param_grad_add_kernel.cu: dW += x^T·dout
    (and db += sum(dout)) fused into gradient accumulation — the building
    block sharding/auto-parallel use for param-grad accumulation."""
    def impl(xa, doa, *rest):
        it = iter(rest)
        dw = next(it) if dweight is not None else None
        db = next(it) if dbias is not None else None
        # accumulate in f32 always (MXU-native); emit f32 master grads
        # under multi_precision, else the incoming grad dtype
        out_t = jnp.float32 if multi_precision else doa.dtype
        dW = jnp.einsum("...i,...o->io", xa.astype(jnp.float32),
                        doa.astype(jnp.float32))
        if dw is not None:
            dW = dw.astype(jnp.float32) + dW
        outs = [dW.astype(out_t)]
        if has_bias:
            red = tuple(range(doa.ndim - 1))
            dB = doa.astype(jnp.float32).sum(axis=red)
            if db is not None:
                dB = db.astype(jnp.float32) + dB
            outs.append(dB.astype(out_t))
        return tuple(outs) if len(outs) > 1 else outs[0]

    args = [x, dout]
    for t in (dweight, dbias):
        if t is not None:
            args.append(t)
    return apply_op("fused_linear_param_grad_add", impl, tuple(args), {},
                    differentiable=False)


def masked_multihead_attention(x, cache_kv, seq_lens, src_mask=None,
                               **kwargs):
    """Decode-step MHA over a contiguous KV cache (reference
    masked_multihead_attention_kernel.cu). x: [B, 3*H*D] fused qkv of the
    new token; cache_kv: [2, B, H, S_max, D]; seq_lens: [B] current
    lengths; src_mask (optional): additive logits bias broadcastable to
    [B, H, S_max] (e.g. -inf at excluded slots, or ALiBi biases).
    Returns (out [B, H*D], updated cache_kv)."""
    def impl(xa, cache, lens, *rest):
        two, b, h, smax, d = cache.shape
        qkv = xa.reshape(b, 3, h, d)
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        bidx = jnp.arange(b)
        kc = cache[0].at[bidx, :, lens].set(k_new)
        vc = cache[1].at[bidx, :, lens].set(v_new)
        scale = 1.0 / math.sqrt(d)
        s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        if rest:
            s = s + rest[0].reshape(b, -1, smax).astype(s.dtype)
        pos = jnp.arange(smax)[None, None, :]
        s = jnp.where(pos <= lens[:, None, None], s, NEG_INF_F)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhs,bhsd->bhd", p,
                         vc.astype(jnp.float32)).astype(xa.dtype)
        return out.reshape(b, h * d), jnp.stack([kc, vc])

    args = (x, cache_kv, seq_lens)
    if src_mask is not None:
        args = args + (src_mask,)
    return apply_op("masked_multihead_attention", impl, args, {},
                    differentiable=False)


def block_multihead_attention(qkv, k_cache, v_cache, block_tables,
                              context_lens, scale=None):
    """Paged-cache decode attention (reference
    block_multi_head_attention_kernel.cu). qkv: [B, 3, H, D] for the new
    token; caches [KVH, num_blocks, block_size, D] (KVH == H or a divisor
    for GQA — the kv slice of qkv uses heads [0:KVH]). Appends the token,
    then attends via the ragged Pallas kernel. Returns
    (out [B, H, D], k_cache, v_cache).

    The reference API's separate halves are stacked into the serving
    path's one [2, KVH, NB, BS, D] buffer for the call and sliced back
    out of it: a copy of the cache each way, which no serving step pays
    (the engine keeps the stacked buffer). The work list is built from
    concrete `context_lens`, so this is an eager call; a jitted caller
    builds `build_ragged_work` on the host and calls
    `ragged_paged_attention` itself."""
    from ....ops.pallas.paged_attention import (append_paged_kv,
                                               ragged_paged_attention)

    def impl(qkv_a, kc, vc, tables, lens):
        kvh = kc.shape[0]
        q, k_new, v_new = qkv_a[:, 0], qkv_a[:, 1], qkv_a[:, 2]
        if q.shape[1] != kvh:
            k_new = k_new[:, :kvh]
            v_new = v_new[:, :kvh]
        cache = append_paged_kv(jnp.stack([kc, vc]), k_new, v_new, tables,
                                lens)
        out = ragged_paged_attention(q, cache, tables, lens + 1,
                                     scale=scale)
        return out, cache[0], cache[1]

    return apply_op("block_multihead_attention", impl,
                    (qkv, k_cache, v_cache, block_tables, context_lens),
                    {}, differentiable=False)


def block_kv_cache_rewind(k_cache, v_cache, block_tables, new_lens,
                          old_lens, max_span):
    """Speculative-decode rewind over the paged KV cache: zero positions
    new_lens[b] .. old_lens[b]-1 (the KV a rejected draft span appended)
    so the cache is bit-identical to one that never speculated. Caches
    [KVH, num_blocks, block_size, D]; new_lens/old_lens [B] int32;
    `max_span` a static python int bounding the widest rewind. Returns
    (k_cache, v_cache). The serving engine batches all slots' rewinds
    into one call of this per step (FusedMultiTransformerEngine's
    `_paged_rewind` applies it to every layer in one jitted program)."""
    from ....ops.pallas.paged_attention import truncate_paged_kv
    span = int(max_span)

    def impl(kc, vc, tables, nl, ol):
        cache = truncate_paged_kv(jnp.stack([kc, vc]), tables, nl, ol, span)
        return cache[0], cache[1]

    return apply_op("block_kv_cache_rewind", impl,
                    (k_cache, v_cache, block_tables, new_lens, old_lens),
                    {}, differentiable=False)


def flashmask_attention(query, key, value, startend_row_indices,
                        causal=True):
    """FlashMask sparse-interval attention (reference
    flash_attention.py:1299) — Pallas kernel on TPU (or interpret mode),
    dense-mask XLA fallback elsewhere. Layout [B, S, H, D]."""
    from ....ops.pallas import flash_attention as _fa
    from ....ops.pallas.flashmask import flashmask_attention_bshd

    on_tpu = jax.devices()[0].platform == "tpu" or _fa._INTERPRET

    def impl(q, k, v, idx):
        if on_tpu:
            return flashmask_attention_bshd(q, k, v, idx, causal=causal)
        # dense fallback: materialize the interval mask
        b, s, hq, d = q.shape
        if k.shape[2] != hq:  # GQA: broadcast kv heads like the kernel path
            rep = hq // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        sr = idx[..., 0]
        er = idx[..., 1] if idx.shape[-1] > 1 else jnp.full_like(sr, s)
        if sr.shape[1] != hq:
            sr = jnp.repeat(sr, hq // sr.shape[1], axis=1)
            er = jnp.repeat(er, hq // er.shape[1], axis=1)
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        allowed = jnp.ones((s, s), bool) if not causal else rows >= cols
        allowed = allowed[None, None] & ~(
            (rows[None, None] >= sr[:, :, None, :])
            & (rows[None, None] < er[:, :, None, :]))
        logits = jnp.einsum("bshd,bthd->bhst", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(d)
        logits = jnp.where(allowed, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(allowed.any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhst,bthd->bshd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    return apply_op("flashmask_attention", impl,
                    (query, key, value, startend_row_indices), {})


def _rms(h, eps, scale=None):
    out = h * jax.lax.rsqrt((h * h).mean(-1, keepdims=True) + eps)
    return out * scale if scale is not None else out


def _apply_rope_pair(q, k, cos, sin, neox):
    """q/k: [B, S, H, D]; cos/sin broadcastable [B, S, 1, D], or
    narrower: a table of R < D columns rotates the first R dimensions of
    each head and passes the rest through."""
    rd = cos.shape[-1]
    if rd < q.shape[-1]:
        qr, kr = _apply_rope_pair(q[..., :rd], k[..., :rd], cos, sin, neox)
        return (jnp.concatenate([qr, q[..., rd:]], -1),
                jnp.concatenate([kr, k[..., rd:]], -1))
    if neox:
        half = q.shape[-1] // 2

        def rot(t):
            return jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)
    else:
        def rot(t):
            t2 = t.reshape(*t.shape[:-1], -1, 2)
            r = jnp.stack([-t2[..., 1], t2[..., 0]], axis=-1)
            return r.reshape(t.shape)
    return q * cos + rot(q) * sin, k * cos + rot(k) * sin


class _LayerWeights(typing.NamedTuple):
    """One decoder layer's slice of `fused_multi_transformer`'s twelve
    weight lists (an absent bias is None)."""
    ln: object
    ln_b: object
    qkv: object
    qkv_b: object
    lin: object
    lin_b: object
    fln: object
    fln_b: object
    f1: object
    f1_b: object
    f2: object
    f2_b: object
    router: object = None       # [E, n_routed] where the layer has experts
    router_b: object = None     # [n_routed]: selects, does not weigh
    sink: object = None         # [H] where the layer's softmax has a sink


class ExpertSpec(typing.NamedTuple):
    """A layer's routed experts as one device sees them: the router
    scores all `n_routed` and picks `top_k` a token; this device holds
    experts lo .. lo + held - 1 and computes their share of the sum."""
    n_routed: int
    top_k: int
    lo: int
    held: int


class LayerSpec(typing.NamedTuple):
    """One decoder layer's block description: attention kind x
    feed-forward kind x which cache. `fused_multi_transformer`'s loop
    reads every per-layer choice off it; the op's own global arguments
    (`gqa_group_size`, `activation`, one `rotary_embs`) are the
    description with one kind of layer. Which block table a layer's
    paged cache lives by follows from its attention: 0 for the layers
    that keep every block, 1 for window layers (`table`)."""
    kv_heads: int = 0           # 0: as many as there are query heads
    head_dim: int = 0           # q/k width; needed where v_head_dim is set
    v_head_dim: int = 0         # 0: the q/k width
    window: typing.Optional[int] = None     # None: full causal attention
    sink: bool = False          # a learned logit per head in the softmax
    rope: typing.Optional[int] = 0  # which rotary table; None: no rope
    value_scale: float = 1.0
    activation: str = "gelu"    # of the dense feed-forward, or the experts'
    experts: typing.Optional[ExpertSpec] = None     # None: a dense FFN

    @property
    def table(self):
        return 1 if self.window else 0


# The most sorted assignment rows one grouped product of `expert_ffn` is
# handed (the routed experts' counterpart of `paged_attention.ROW_TILE`,
# kept here because that file's line numbers key every Mosaic program).
# XLA tiles the product by up to 512 rows of its STATIC row count and a
# group that holds any row pays a whole tile, so the static count is
# what a touched expert costs beyond its weights' stream: 0.083 / 0.101
# / 0.159 ms over both products at 128 / 256 / 512 rows on a v5e
# (PERF.md section 6, PR 41, where the cell was measured at this value;
# 128 is faster for the function alone and not yet measured in a cell).
# A decode step's 16 x 8 assignment rows are one narrower slab.
MOE_SLAB = 256


def expert_ffn(z, router, router_b, w13, w2, ex, live, act):
    """Routed experts over the rows z [R, E], this device's share: the
    router scores all ex.n_routed experts in float32 (sigmoid), the
    ex.top_k largest of score + bias are a token's experts, weighed by
    their scores normalised over ALL of them; the assignments that fall
    on a held expert are grouped by expert and multiplied with a grouped
    matrix product over the held experts' stacked weights (w13
    [held, E, 2F] gate|up, w2 [held, F, E]); the weighted partial sum
    comes back per row. Rows that are not `live` route nowhere. Also
    returns how many assignments each held expert got, [held] int32, and
    how many rows the grouped products were handed, a scalar int32.

    The live assignments on held experts are sorted to the front of the
    R x top_k there are, and the products run over slabs of MOE_SLAB
    sorted rows (of all R x top_k where those are fewer), as many slabs
    as the held assignments fill (a trip count read on the device, none
    where nothing fell here): every gather, product and scatter is at
    most MOE_SLAB rows whatever R is, and every held assignment is
    multiplied whatever their number."""
    r = z.shape[0]
    with tracing.device_scope("moe_route"):
        sigma = jax.nn.sigmoid(jnp.dot(
            z.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(sigma + router_b.astype(jnp.float32),
                               ex.top_k)
        wt = jnp.take_along_axis(sigma, sel, axis=1)
        wt = wt / jnp.sum(wt, axis=1, keepdims=True)
        local = sel - ex.lo
        here = (local >= 0) & (local < ex.held) & live[:, None]
        eid = jnp.where(here, local, ex.held).reshape(-1)
    with tracing.device_scope("moe_experts"):
        order = jnp.argsort(eid, stable=True)
        counts = jnp.sum(eid[:, None] == jnp.arange(ex.held)[None, :],
                         axis=0, dtype=jnp.int32)
        # once, not once a slab (dequantized weights come wider than z)
        w13, w2 = w13.astype(z.dtype), w2.astype(z.dtype)

        def multiply(rows, sizes):
            """The sorted assignments `rows` [S], of which the leading
            sum(sizes) are grouped by held expert as `sizes` [held]:
            each one's token row, and its expert's output for that token
            times its routing weight [S, E] (0 past the groups)."""
            tok = rows // ex.top_k
            gu = jax.lax.ragged_dot(z[tok], w13, sizes)
            f = gu.shape[-1] // 2
            y = jax.lax.ragged_dot(
                (act(gu[:, :f]) * gu[:, f:]).astype(z.dtype), w2, sizes,
                preferred_element_type=jnp.float32)
            # rows past the groups are not the product's to write
            return tok, jnp.where(
                (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None],
                y * wt.reshape(-1)[rows][:, None], 0.0)

        n = eid.shape[0]
        width = min(n, MOE_SLAB)
        ends = jnp.cumsum(counts)
        order = jnp.pad(order, (0, -n % width))     # whole slabs

        def slab(j, out):
            # this slab's share of each group: the assignments of sorted
            # rows j x width .. + width - 1, a group that straddles an
            # edge cut there
            upto = jnp.clip(ends - j * width, 0, width)
            tok, y = multiply(
                jax.lax.dynamic_slice_in_dim(order, j * width, width),
                jnp.diff(upto, prepend=0))
            return out.at[tok].add(y)

        trips = -(-ends[-1] // width)
        with tracing.device_scope("moe_slabs"):
            out = jax.lax.fori_loop(
                0, trips, slab, jnp.zeros((r, w2.shape[-1]), jnp.float32))
    return out.astype(z.dtype), counts, trips * width


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, residual_alpha=1.0, cache_kvs=None, beam_offset=None,
        pre_caches=None, seq_lens=None, rotary_embs=None, time_step=None,
        attn_mask=None, dropout_rate=0.0, rotary_emb_dims=0,
        activation="gelu", training=False, mode="upscale_in_train",
        trans_qkvw=True, ring_id=-1, norm_type="layernorm",
        use_neox_rotary_style=False, gqa_group_size=-1, name=None,
        block_tables=None, ragged_work=None, ragged_pack=None,
        chunk_lens=None, kv_buffer_depth=2, layers=None,
        router_weights=None, router_biases=None, attn_sinks=None,
        _dequant=None, _mm=None, _tp_reduce=None, _live_rows=None):
    """Whole-decoder-stack fused transformer (reference
    fused_multi_transformer op: python/paddle/incubate/nn/functional/
    fused_transformer.py:1053 over
    paddle/phi/kernels/fusion/gpu/fused_multi_transformer_kernel.cu).

    One call runs EVERY decoder layer: [LN → QKV proj (+rope) → cached
    attention → out proj + residual → LN → FFN → residual] × n_layers.
    On TPU the per-layer chain is a jnp composition XLA fuses into the
    matmuls (the epilogue fusions the CUDA kernel hand-writes); decode
    attention over the contiguous [2, B, H, S_max, D] cache is a masked
    einsum the TPU executes from VMEM.

    Shapes (trans_qkvw=True, the reference default):
    x [B, S, E]; qkv_weight [3, H, D, E]; linear_weight [H*D, E];
    ffn1_weight [E, F] (or [E, 2F] for *glu activations); ffn2 [F, E];
    cache_kvs: list of [2, B, H, S_max, D] per layer, updated in place;
    rotary_embs [2, B, 1, S_rope, D] (cos, sin); time_step: scalar int
    tensor = current decode position (decode mode when given).

    Paged-cache decode (the continuous-batching serving path): pass
    `block_tables` [B, max_blocks] plus per-layer caches shaped
    [2, KVH, num_blocks, block_size, D] and per-sequence `seq_lens`; the
    attention runs the ragged Pallas kernel
    (ops/pallas/paged_attention.ragged_paged_attention) after appending
    the new token at slot seq_lens. `ragged_work` is the host-built
    flattened work list, `build_ragged_work(tables, seq_lens +
    chunk_lens, block_size, pack, q_lens=chunk_lens)`: attention covers
    the tokens just appended. x is [B, C, E] with time_step set and
    `chunk_lens` [B] giving how many of each row's C token columns are
    valid this step: sequence b's chunk_lens[b] tokens append at
    positions seq_lens[b].. and each attends causally to its own prefix.
    chunk_lens[b] == 0 parks the row: nothing written, nothing attended,
    output rows zero. Without `chunk_lens` every row holds one token
    (classic decode, x [B, 1, E]).

    A WIDE paged step (B x C > ROW_TILE rows) computes its live rows
    only: the slab's chunk_lens.sum() live tokens are packed to the front
    of a [B x C]-row buffer and every row-wise layer, the cache append
    among them, walks ceil(live / ROW_TILE) row tiles of it
    (ops/pallas/paged_attention.py `live_rows`). A caller that has the
    packing already (`_live_rows`, the engine's paged step) passes x and
    takes the result as the packed [1, R, E] buffer itself.

    `layers` (a `LayerSpec` per layer) is the per-layer block
    description a model whose layers differ brings: kv-head count, value
    width, window, sink, which rotary table (`rotary_embs` is then a
    tuple of tables), value scale, activation, routed experts. Where
    some layers have a window, `block_tables` is two tables side by
    side, [B, 2 x max_blocks]: the full layers', then the window
    layers', whose work list is built on the device from their own
    (`ops/pallas/paged_attention.window_work`).
    Without it the global arguments describe every layer. A layer with
    experts takes `router_weights[l]` [E, n_routed], `router_biases[l]`
    and stacked expert weights in ffn1_weights[l] [held, E, 2F] /
    ffn2_weights[l] [held, F, E]; a layer with a sink `attn_sinks[l]`
    [H]; a qkv weight of two dimensions is q, k and v rows of unequal
    widths, [(H + G) x head_dim + G x v_head_dim, E]. Window layers,
    experts and unequal widths run on the paged path only.

    Returns the output hidden states [B, S, E]; caches are updated
    in place (dygraph reference semantics). Where some layers have
    routed experts it returns (hidden states, counts, handed): the
    assignments each held expert got in each expert layer, [expert
    layers, held] int32, and the rows each expert layer's grouped
    products were handed, [expert layers] int32 (`expert_ffn`).
    """
    from ....core.tensor import Tensor

    if beam_offset is not None:
        raise NotImplementedError(
            "fused_multi_transformer: beam_offset unsupported")
    if chunk_lens is not None and block_tables is None:
        raise ValueError(
            "fused_multi_transformer: chunk_lens (chunked prefill) is a "
            "paged-cache feature — pass block_tables too")
    if pre_caches is not None and time_step is not None:
        raise NotImplementedError(
            "fused_multi_transformer: pre_caches apply to the context/"
            "prefill phase; at decode time the prefix already lives in "
            "cache_kvs (run prefill with pre_caches first)")
    if block_tables is not None:
        if time_step is None or seq_lens is None:
            raise ValueError(
                "fused_multi_transformer: the paged-cache path is decode-"
                "only — pass time_step and per-sequence seq_lens with "
                "block_tables")
        if not cache_kvs:
            raise ValueError(
                "fused_multi_transformer: block_tables without cache_kvs "
                "— the paged path needs the per-layer paged caches")
        if attn_mask is not None:
            raise NotImplementedError(
                "fused_multi_transformer: attn_mask unsupported on the "
                "paged decode path")
        if ragged_work is None:
            raise ValueError(
                "fused_multi_transformer: the paged path takes the host-"
                "built work list — pass ragged_work=build_ragged_work("
                "tables, seq_lens + chunk_lens, block_size, pack, "
                "q_lens=chunk_lens)")
        if chunk_lens is None:
            chunk_lens = jnp.ones(
                (x.data if hasattr(x, "data") else x).shape[0], jnp.int32)
        if len(ragged_work) == 4 and isinstance(ragged_work[0],
                                                (tuple, list)):
            # the full build_ragged_work result: the carried pack is
            # authoritative (the work list's group encoding depends on it)
            if ragged_pack is not None and ragged_pack != ragged_work[3]:
                raise ValueError(
                    f"ragged_pack={ragged_pack} conflicts with the work "
                    f"list (built with pack={ragged_work[3]})")
            ragged_pack = ragged_work[3]
            ragged_work = ragged_work[0]
    n_layers = len(qkv_weights)
    if layers is None:
        layers = (LayerSpec(
            kv_heads=gqa_group_size
            if gqa_group_size and gqa_group_size > 0 else 0,
            activation=activation),) * n_layers
    layers = tuple(layers)
    if len(layers) != n_layers:
        raise ValueError(
            f"fused_multi_transformer: {len(layers)} layer descriptions "
            f"for {n_layers} layers")
    if block_tables is None and any(
            sp.window or sp.experts or sp.v_head_dim or sp.sink
            for sp in layers):
        raise NotImplementedError(
            "fused_multi_transformer: window layers, sinks, routed "
            "experts and values narrower than keys run on the paged "
            "path only (ROADMAP M1): pass block_tables")
    n_tables = 1 + max(sp.table for sp in layers)
    if len({sp.window for sp in layers if sp.window}) > 1:
        raise ValueError(
            "fused_multi_transformer: window layers of ONE window size "
            "share the second block table (ROADMAP M3)")
    caches_in = cache_kvs if cache_kvs is not None else []
    pre_in = pre_caches if pre_caches is not None else []
    dq = _dequant or (lambda w, kind, li: w)
    # _mm(z2d, kind, li) -> z2d @ W[kind][li]: when provided (the Pallas
    # weight-only-quant serving path, ops/pallas/quant_matmul.py), the
    # four projection matmuls run the in-kernel-dequant GEMM instead of
    # dequantize-then-einsum — quantized bytes are all that leave HBM
    # _tp_reduce: the tensor-parallel serving hook (inference/tp_layout
    # Megatron split). Applied to the ROW-parallel matmul outputs —
    # attention out-projection and ffn2 — BEFORE their bias adds, where
    # each device holds a partial sum over its weight-row shard; inside
    # the engine's shard_map'd step it is a psum over the 'tp' axis
    # (two per layer), identity when serving single-chip
    tp_red = _tp_reduce or (lambda x: x)

    def impl(xa, lns, lnb, qkvw, qkvb, linw, linb, flns, flnb, f1w, f1b,
             f2w, f2b, caches, pres, rotary, tstep, mask, slens, qlens,
             tables_a, rwork, dkeys, rows, extras):
        b, s, e = xa.shape
        norm = (lambda h, sc, bi: _rms(h, epsilon, sc)) \
            if norm_type == "rmsnorm" else \
            (lambda h, sc, bi: _ln(h, epsilon, sc, bi))

        def layer(li):
            """Layer li's weights, in the op's argument order."""
            return _LayerWeights(*(
                xs[li] if xs else None
                for xs in (lns, lnb, qkvw, qkvb, linw, linb, flns, flnb,
                           f1w, f1b, f2w, f2b, extras["router"],
                           extras["router_b"], extras["sink"])))

        def table_of(sp):
            """The layer's rotary table: `rotary_embs` itself, or the
            one of several its description names."""
            if rotary is None or sp.rope is None:
                return None
            return rotary[sp.rope] if isinstance(rotary, (list, tuple)) \
                else rotary

        # The layer's row-wise halves, over any [b, s] of rows: the whole
        # slab, or one tile of a wide paged step's packed rows ([1,
        # ROW_TILE]). Attention, between them, is what knows sequences.
        # They take the layer's weights as `lw` and its index only for
        # the quantized engines' hooks.
        def project(h, lw, li, sp):
            """norm + qkv projection + bias: h [b, s, E] -> q [b, s, H, D]
            and k, v [b, s, KVH, D] (v [b, s, KVH, Dv] where the layer's
            values are narrower)."""
            G = sp.kv_heads
            z = norm(h, lw.ln, lw.ln_b) if pre_layer_norm else h
            if lw.qkv.ndim == 2:
                # rows of unequal widths: H q heads and G k heads of
                # head_dim, then G v heads of v_head_dim
                w = dq(lw.qkv, "qkv", li)
                dk, dv = sp.head_dim, sp.v_head_dim or sp.head_dim
                nh = (w.shape[0] - G * dv) // dk - G
                qkv = jnp.einsum("bse,ne->bsn", z.astype(w.dtype), w)
                q, k, v = jnp.split(qkv, [nh * dk, (nh + G) * dk], axis=-1)
                v = v.reshape(z.shape[:2] + (G, dv))
                if sp.value_scale != 1.0:
                    v = v * jnp.asarray(sp.value_scale, v.dtype)
                return (q.reshape(z.shape[:2] + (nh, dk)),
                        k.reshape(z.shape[:2] + (G, dk)), v)
            if sp.value_scale != 1.0 or sp.v_head_dim:
                raise NotImplementedError(
                    "a value scale or a value width of its own needs the "
                    "two-dimensional qkv layout")
            if _mm is not None and trans_qkvw:
                qkv = _mm(z.reshape(-1, e), lw.qkv, "qkv",
                          li).reshape(z.shape[:2] + _mm.qkv_out)
                if lw.qkv_b is not None:
                    qkv = qkv + lw.qkv_b[None, None]
                if G:
                    nh = _mm.qkv_out[0] - 2 * G
                    return (qkv[:, :, :nh], qkv[:, :, nh:nh + G],
                            qkv[:, :, nh + G:])
                return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            w = dq(lw.qkv, "qkv", li)
            if G:
                # GQA packing (reference fused_transformer.py:1009 /
                # infermeta/fusion.cc gqa branch): weight [H + 2G, D, E]
                # — H query heads, then G key heads, then G value heads
                if not trans_qkvw:
                    w = jnp.transpose(w, (1, 2, 0))      # [E,H+2G,D] packed
                nh = w.shape[0] - 2 * G
                qkv = jnp.einsum("bse,hde->bshd", z.astype(w.dtype), w)
                if lw.qkv_b is not None:
                    qkv = qkv + lw.qkv_b[None, None]
                return (qkv[:, :, :nh],                  # [B,S,H,D]
                        qkv[:, :, nh:nh + G],            # [B,S,G,D]
                        qkv[:, :, nh + G:])
            if not trans_qkvw:
                # [E, 3, H, D] layout -> [3, H, D, E]
                w = jnp.transpose(w, (1, 2, 3, 0))
            qkv = jnp.einsum("bse,thde->bsthd", z.astype(w.dtype), w)
            if lw.qkv_b is not None:
                qkv = qkv + lw.qkv_b[None, None]
            return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        def rope(q, k, table, packed=None):
            """Rotate q and k by `table` (`rotary_embs`), each row at its
            position. `packed` = (slot, pos) [R] names them for one tile
            of a wide paged step's packed rows."""
            if table is None:
                return q, k
            cos = table[0][:, 0][:, :, None, :]     # [B, S_rope, 1, D]
            sin = table[1][:, 0][:, :, None, :]
            if packed is not None:
                slot, pos = packed
                bidx = slot if cos.shape[0] > 1 else jnp.zeros_like(slot)
                pos = jnp.minimum(pos, cos.shape[1] - 1)
                cos, sin = cos[bidx, pos][None], sin[bidx, pos][None]
            elif tstep is not None and slens is not None:
                # ragged decode: each sequence sits at its OWN position
                # (its current length), not a shared time step
                ln = jnp.asarray(slens).reshape(-1)
                bidx = jnp.arange(cos.shape[0]) \
                    if cos.shape[0] > 1 else jnp.zeros_like(ln)
                if s == 1:
                    cos = cos[bidx, ln][:, None]    # [B, 1, 1, D]
                    sin = sin[bidx, ln][:, None]
                else:
                    # chunked prefill: token column j of sequence b
                    # rotates at position lens[b] + j (clamped into
                    # the table for the padding columns past qlens)
                    posr = jnp.minimum(
                        ln[:, None] + jnp.arange(s)[None, :],
                        cos.shape[1] - 1)           # [B, C]
                    cos = cos[bidx[:, None], posr]  # [B, C, 1, D]
                    sin = sin[bidx[:, None], posr]
            elif tstep is not None:
                pos = jnp.asarray(tstep).reshape(())
                cos = jax.lax.dynamic_slice_in_dim(cos, pos, 1, 1)
                sin = jax.lax.dynamic_slice_in_dim(sin, pos, 1, 1)
            else:
                cos, sin = cos[:, :s], sin[:, :s]
            return _apply_rope_pair(q, k, cos, sin, use_neox_rotary_style)

        def finish(resid, ctx, lw, li, dkey, sp, live=None):
            """Output projection, residual and feed-forward: the layer's
            input `resid` [b, s, E] and its attention output ctx
            [b, s, H, D] -> the layer's output [b, s, E], and for a layer
            of routed experts `expert_ffn`'s two counts: the assignments
            each held expert got and the rows its products were handed
            (None otherwise). `live` [b, s] marks the rows that hold a
            token (None: all)."""
            b, s = ctx.shape[:2]    # this call's rows, not the slab's
            got = None
            with tracing.device_scope("out_proj"):
                if _mm is not None:
                    attn = _mm(ctx.reshape(b * s, -1), lw.lin,
                               "lin", li).reshape(b, s, -1)
                else:
                    attn = ctx.reshape(b, s, -1) @ dq(lw.lin, "lin", li)
                attn = tp_red(attn)
                if lw.lin_b is not None:
                    attn = attn + lw.lin_b
                if dkey is not None:
                    keep = jax.random.bernoulli(
                        dkey, 1.0 - dropout_rate, attn.shape)
                    attn = jnp.where(
                        keep, attn / (1.0 - dropout_rate), 0.0) \
                        if mode == "upscale_in_train" else \
                        jnp.where(keep, attn, 0.0)
                h = resid * residual_alpha + attn
                if not pre_layer_norm:
                    h = norm(h, lw.ln, lw.ln_b)
            with tracing.device_scope("ffn"):
                resid2 = h
                z2 = norm(h, lw.fln, lw.fln_b) if pre_layer_norm else h
                act = jax.nn.silu if sp.activation == "swiglu" \
                    else jax.nn.relu if sp.activation == "relu" \
                    else jax.nn.gelu
                if sp.experts is not None:
                    f2, *got = expert_ffn(
                        z2.reshape(b * s, -1), lw.router, lw.router_b,
                        dq(lw.f1, "f1", li), dq(lw.f2, "f2", li),
                        sp.experts, jnp.ones(b * s, bool) if live is None
                        else live.reshape(-1), act)
                    f2 = f2.reshape(b, s, -1)
                else:
                    if _mm is not None:
                        f1 = _mm(z2.reshape(b * s, -1), lw.f1, "f1",
                                 li).reshape(b, s, -1)
                    else:
                        f1 = z2 @ dq(lw.f1, "f1", li)
                    if lw.f1_b is not None:
                        f1 = f1 + lw.f1_b
                    if sp.activation.endswith("glu"):
                        a, g = jnp.split(f1, 2, axis=-1)
                        f1 = act(a) * g
                    else:
                        f1 = act(f1)
                    if _mm is not None:
                        f2 = _mm(f1.reshape(b * s, -1), lw.f2, "f2",
                                 li).reshape(b, s, -1)
                    else:
                        f2 = f1 @ dq(lw.f2, "f2", li)
                f2 = tp_red(f2)
                if lw.f2_b is not None:
                    f2 = f2 + lw.f2_b
                h = resid2 * residual_alpha + f2
                if not pre_layer_norm:
                    h = norm(h, lw.fln, lw.fln_b)
            return h, got

        def attend_kw(sp, lw):
            """What the ragged kernel is told of the layer's attention."""
            return dict(buffer_depth=kv_buffer_depth, window=sp.window,
                        sink=lw.sink if sp.sink else None,
                        v_dim=sp.v_head_dim or None)

        @functools.partial(jax.jit, static_argnums=0)
        def packed_paged_layer(key, lw, table, tables, ln, ql, rows, work,
                               dkey, hp, qp, cache):
            """One layer of a WIDE paged step, over its live rows only:
            hp [R, E] holds the slab's live tokens packed at its front
            (`rows`), and each row-wise half runs ROW_TILE rows at a
            time, rows.n_tiles times (a trip count read on the device).
            The layer's cache rides the first loop as a carry: a tile's
            K/V rows are appended where the buffer lies. The kernel keeps
            the slab's [B, C] geometry on its query side: one gather
            lays the packed q rows into it (a dead cell reads some other
            row, which no grid step visits). Its output is never laid
            out as a slab: the second loop reads each tile's ctx rows
            out of the kernel's own tiles (`tile_rows`). qp [R, H, D] is
            the packed q rows' buffer, any layer's.

            Jitted, with everything traced among its arguments: layers
            of one description differ in `lw` only, so one trace and one
            lowered function serve them all. The static `key` is (layer
            index, description); the index is 0 for every layer except
            under the hooks of a quantized engine, which key their
            scales on it (there each layer is its own). `tables` and
            `work` are the layer's own kind's. A layer of routed experts
            groups its assignments per row tile, inside the second loop,
            and carries `expert_ffn`'s two counts beside the rows; it
            returns them last (None otherwise)."""
            li, sp = key

            def before(r0, carry):
                qp, cache = carry
                slot, at = row_tile(rows.slot, r0), row_tile(pos, r0)
                with tracing.device_scope("qkv_proj"):
                    q, k, v = project(row_tile(hp, r0)[None], lw, li, sp)
                with tracing.device_scope("rope"):
                    q, k = rope(q, k, table, (slot, at))
                with tracing.device_scope("kv_write"):
                    cache = append_paged_kv_rows(
                        cache, k[0], v[0], tables, slot, at,
                        row_tile(rows.live, r0))
                with tracing.device_scope("q_pack"):
                    return put_row_tile(qp, q[0], r0), cache

            # each loop under a name of its own: its `%while` and
            # whatever op of its body a fusion left without metadata
            # still say which loop they are
            with tracing.device_scope("rows_before"):
                pos = ln[rows.slot] + rows.col                 # [R]
                qp, cache = over_row_tiles(
                    rows.n_tiles, before, (qp, cache))
            with tracing.device_scope("attention"):
                tiles = ragged_attention_tiles(
                    qp[rows.back], cache,
                    (work, None, work[0].shape[0], ragged_pack),
                    **attend_kw(sp, lw))

            def after(r0, carry):
                hp, *sums = carry if sp.experts else (carry,)
                with tracing.device_scope("attention"):
                    slot, col = row_tile(rows.slot, r0), row_tile(rows.col, r0)
                    live = row_tile(rows.live, r0)
                    ctx = tile_rows(
                        tiles, slot, col, live, ragged_pack,
                        rows.back.shape[1], qp.shape[1],
                        sp.v_head_dim or qp.shape[2]).astype(hp.dtype)
                with tracing.device_scope("out_proj"):
                    resid = row_tile(hp, r0)[None]
                out, got = finish(
                    resid, ctx[None], lw, li, None if dkey is None
                    else jax.random.fold_in(dkey, r0), sp,
                    live[None] if sp.experts else None)
                with tracing.device_scope("ffn"):
                    hp = put_row_tile(hp, out[0], r0)
                if sp.experts:
                    return (hp, *(a + b for a, b in zip(sums, got)))
                return hp

            with tracing.device_scope("rows_after"):
                if sp.experts:
                    hp, *sums = over_row_tiles(
                        rows.n_tiles, after,
                        (hp, jnp.zeros(sp.experts.held, jnp.int32),
                         jnp.int32(0)))
                    return hp, qp, cache, sums
                return (over_row_tiles(rows.n_tiles, after, hp), qp, cache,
                        None)

        padded = False
        if tables_a is not None:
            from ....ops.pallas.paged_attention import (
                ROW_TILE, append_paged_kv_chunk, append_paged_kv_rows,
                live_rows, over_row_tiles, put_row_tile,
                ragged_attention_tiles, ragged_paged_attention, row_tile,
                tile_rows)
            from ....ops.pallas.paged_attention import window_work
            padded = rows is None and b * s > ROW_TILE
            # a block table per cache kind, side by side in one array
            tabs = jnp.split(tables_a, n_tables, axis=1) \
                if n_tables > 1 else [tables_a]
            ln = jnp.asarray(slens).reshape(-1)
            ql = jnp.asarray(qlens).reshape(-1)
            width = s if rows is None else rows.back.shape[1]
            works = {}

            def work_of(sp, cache):
                """The layer's work list: the host's, or for a window
                layer one built here from its own table, once a step for
                every layer of its kind."""
                if sp.window is None:
                    return tuple(rwork)
                k = (sp.window, sp.table)
                if k not in works:
                    with tracing.device_scope("attention"):
                        works[k] = window_work(
                            tabs[sp.table], ln, ql, window=sp.window,
                            block_size=cache.shape[3], chunk=width,
                            pack=ragged_pack)
                return works[k]

        gots = []       # each expert layer's two counts (`expert_ffn`)

        def count(got):
            if got is not None:
                gots.append(got)

        def counted():
            """[[expert layers, held], [expert layers]] where some layer
            has experts."""
            with tracing.device_scope("moe_experts"):
                return [jnp.stack(c) for c in zip(*gots)]

        if padded:
            # a wide slab handed over as [B, C, E]: packed here, and
            # handed back in the slab's geometry
            with tracing.device_scope("embed"):
                rows = live_rows(qlens, s)
                xa = xa[rows.slot, rows.col][None]
        new_caches = []
        if rows is not None:
            # a wide paged step: xa is [1, R, E], the packed live rows
            q_rows = jax.eval_shape(
                lambda z: project(z, layer(0), 0, layers[0])[0], xa)
            with tracing.device_scope("q_pack"):
                hp, qp = xa[0], jnp.zeros(q_rows.shape[1:], q_rows.dtype)
            for li, sp in enumerate(layers):
                hp, qp, cache, got = packed_paged_layer(
                    (li if _dequant or _mm else 0, sp), layer(li),
                    table_of(sp), tabs[sp.table], ln, ql, rows,
                    work_of(sp, caches[li]),
                    dkeys[li] if dkeys else None, hp, qp, caches[li])
                new_caches.append(cache)
                count(got)
            with tracing.device_scope("embed"):
                out = hp[rows.back] if padded else hp[None]
            return tuple([out] + new_caches + counted())
        h = xa
        live = None
        for li, sp in enumerate(layers):
            lw = layer(li)
            resid = h
            with tracing.device_scope("qkv_proj"):
                q, k, v = project(h, lw, li, sp)
            with tracing.device_scope("rope"):
                q, k = rope(q, k, table_of(sp))
            nh, hd = q.shape[2:]
            scale = 1.0 / math.sqrt(hd)
            # grouped-attention geometry: kv heads g, queries-per-group r
            # (r == 1 and g == nh for MHA; the einsums below serve both —
            # no jnp.repeat materialisation of KV on the decode hot path)
            g_eff = sp.kv_heads or nh
            r = nh // g_eff
            if tstep is not None and caches and tables_a is not None:
                # paged step (continuous batching): append this step's
                # token (or prompt CHUNK) into the blocks owned by each
                # sequence starting at slot seq_lens, then run the ragged
                # Pallas kernel over the flattened work list — grid cost
                # scales with the sum of ACTUAL per-sequence KV blocks,
                # not B x max_blocks, and a whole prompt chunk rides one
                # kernel invocation next to the decode rows
                # named for the device trace: `kv_write` is everything
                # the append costs — the new rows stacked, and the
                # writer's kernel that lays them into the layer's
                # [2, KVH, NB, BS, D] cache where it lies, a move per
                # 8-row group that holds a live token — and `attention`
                # the ragged kernel, which reads its blocks out of that
                # same buffer. The buffer is the layer's result: nothing
                # here slices a half out of it or stacks two back
                # (either is a copy of the cache). The kernel's custom
                # call keeps its op metadata, so a trace's readers find
                # it under `kv_write` (perfbench/lib/step_regions.py).
                cache = caches[li]             # [2, KVH, NB, BS, D]
                work = work_of(sp, cache)
                work = (work, None, work[0].shape[0], ragged_pack)
                if sp.experts:  # the rows its router may send anywhere
                    with tracing.device_scope("moe_route"):
                        live = jnp.arange(s)[None, :] < ql[:, None]  # [B, C]
                with tracing.device_scope("kv_write"):
                    cache = append_paged_kv_chunk(
                        cache, k, v, tabs[sp.table], ln, ql)
                with tracing.device_scope("attention"):
                    ctx = ragged_paged_attention(
                        q, cache, tabs[sp.table], ln + ql, scale=scale,
                        work=work, q_lens=ql, **attend_kw(sp, lw)
                        ).astype(xa.dtype)                # [B, C, H, D]
                new_caches.append(cache)
            elif tstep is not None and caches:
                # decode: append the new token, attend over the valid cache
                cache = caches[li]                 # [2, B, g, S_max, D]
                t = jnp.asarray(tstep).reshape(())
                smax = cache.shape[3]
                if slens is not None:
                    # ragged batch: per-sequence append at slot lens[b]
                    # (reference seq_lens contract, as in
                    # masked_multihead_attention); caller advances seq_lens
                    ln = jnp.asarray(slens).reshape(-1)
                    bidx = jnp.arange(b)
                    kc = cache[0].at[bidx, :, ln].set(k[:, 0])
                    vc = cache[1].at[bidx, :, ln].set(v[:, 0])
                    posm = (jnp.arange(smax)[None, None, None, None, :]
                            <= ln[:, None, None, None, None])
                else:
                    kc = jax.lax.dynamic_update_slice_in_dim(
                        cache[0], k.transpose(0, 2, 1, 3), t, axis=2)
                    vc = jax.lax.dynamic_update_slice_in_dim(
                        cache[1], v.transpose(0, 2, 1, 3), t, axis=2)
                    posm = jnp.arange(smax)[None, None, None, None, :] <= t
                qg = q.reshape(b, s, g_eff, r, hd)
                logits = jnp.einsum(
                    "bsgrd,bgtd->bgrst", qg.astype(jnp.float32),
                    kc.astype(jnp.float32)) * scale   # [B,g,r,1,S_max]
                if mask is not None:
                    logits = logits + mask[:, :, None].astype(logits.dtype)
                logits = jnp.where(posm, logits, NEG_INF_F)
                p = jax.nn.softmax(logits, axis=-1)
                ctx = jnp.einsum("bgrst,bgtd->bsgrd", p,
                                 vc.astype(jnp.float32)
                                 ).reshape(b, s, nh, hd).astype(xa.dtype)
                new_caches.append(jnp.stack([kc, vc]))
            else:
                # context/prefill: causal attention, fill cache [0:S];
                # pre_caches (prompt-prefix KV, reference pre_caches arg)
                # prepend their keys — every new row attends to the whole
                # prefix plus the causal part of the new tokens
                kk, vv = k, v
                s_pre = 0
                if pres:
                    pk, pv = pres[li][0], pres[li][1]  # [B, g, S_pre, D]
                    s_pre = pk.shape[2]
                    kk = jnp.concatenate(
                        [pk.transpose(0, 2, 1, 3), k], axis=1)
                    vv = jnp.concatenate(
                        [pv.transpose(0, 2, 1, 3), v], axis=1)
                qg = q.reshape(b, s, g_eff, r, hd)
                logits = jnp.einsum(
                    "bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                    kk.astype(jnp.float32)) * scale   # [B,g,r,S,S_pre+S]
                causal = jnp.tril(jnp.ones((s, s), bool))
                if s_pre:
                    causal = jnp.concatenate(
                        [jnp.ones((s, s_pre), bool), causal], axis=1)
                causal = causal[None, None, None]
                if slens is not None:
                    # padded batch: keys at/after each row's true length
                    # must not contribute (reference seq_lens semantics)
                    valid = (jnp.arange(s)[None, :]
                             < jnp.asarray(slens).reshape(-1, 1))
                    if s_pre:
                        valid = jnp.concatenate(
                            [jnp.ones((b, s_pre), bool), valid], axis=1)
                    causal = causal & valid[:, None, None, None, :]
                if mask is not None:
                    logits = logits + mask[:, :, None].astype(logits.dtype)
                logits = jnp.where(causal, logits, NEG_INF_F)
                p = jax.nn.softmax(logits, axis=-1)
                ctx = jnp.einsum("bgrst,btgd->bsgrd", p,
                                 vv.astype(jnp.float32)
                                 ).reshape(b, s, nh, hd).astype(xa.dtype)
                if caches:
                    cache = caches[li]
                    kc = jax.lax.dynamic_update_slice_in_dim(
                        cache[0], kk.transpose(0, 2, 1, 3), 0, axis=2)
                    vc = jax.lax.dynamic_update_slice_in_dim(
                        cache[1], vv.transpose(0, 2, 1, 3), 0, axis=2)
                    new_caches.append(jnp.stack([kc, vc]))
            h, got = finish(resid, ctx, lw, li,
                            dkeys[li] if dkeys else None, sp, live)
            count(got)
        return tuple([h] + new_caches + counted())

    out = apply_op(
        "fused_multi_transformer", impl,
        (x, list(ln_scales), list(ln_biases or []), list(qkv_weights),
         list(qkv_biases or []), list(linear_weights),
         list(linear_biases or []), list(ffn_ln_scales),
         list(ffn_ln_biases or []), list(ffn1_weights),
         list(ffn1_biases or []), list(ffn2_weights), list(ffn2_biases or []),
         list(caches_in), list(pre_in), rotary_embs, time_step, attn_mask,
         seq_lens, chunk_lens, block_tables,
         list(ragged_work) if ragged_work is not None else [],
         # per-layer dropout keys as input leaves (vjp-cacheable +
         # trace-safe, like the other fused ops)
         [_random.fresh_key_tensor() for _ in range(n_layers)]
         if training and dropout_rate else [], _live_rows,
         dict(router=list(router_weights or []),
              router_b=list(router_biases or []),
              sink=list(attn_sinks or []))),
        {}, differentiable=bool(training) and not caches_in)
    outs = out if isinstance(out, tuple) else (out,)
    h = outs[0]
    # dygraph reference semantics: caches mutate in place
    for cache_t, new_t in zip(caches_in, outs[1:]):
        if isinstance(cache_t, Tensor):
            cache_t._data = new_t._data
    if any(sp.experts for sp in layers):
        return h, outs[-2], outs[-1]
    return h


def fused_multi_transformer_int8(
        x, ln_scales, ln_biases, qkv_weights, qkv_scales, qkv_biases,
        linear_weights, linear_scales, linear_biases, ffn_ln_scales,
        ffn_ln_biases, ffn1_weights, ffn1_scales, ffn1_biases, ffn2_weights,
        ffn2_scales, ffn2_biases, **kwargs):
    """Weight-only-int8 variant (role of the reference's
    fused_multi_transformer_int8_kernel.cu): weights are int8 with
    per-output-channel scales; dequantisation happens inside the op, where
    XLA fuses the int8→bf16 convert+scale into the matmul's operand load —
    the TPU analogue of the CUDA kernel's dequant epilogue.

    Weight lists hold int8 tensors shaped as in fused_multi_transformer;
    each *_scales list holds the matching per-channel scale (last dim of
    the weight's output axis)."""
    from ....core.tensor import Tensor as _T
    scales = {"qkv": list(qkv_scales), "lin": list(linear_scales),
              "f1": list(ffn1_scales), "f2": list(ffn2_scales)}

    def dq(w, kind, li):
        sc = scales[kind][li]
        sc = sc.data if isinstance(sc, _T) else jnp.asarray(sc)
        if kind == "qkv":
            # [3, H, D, E] int8, scale per (3, H, D) output channel
            s3 = sc.reshape(w.shape[0], w.shape[1], w.shape[2], 1)
            return w.astype(jnp.float32) * s3
        return w.astype(jnp.float32) * sc[None, :]

    return fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, _dequant=dq, **kwargs)


def quantize_int4(w, axis=-1, group_size=None):
    """Pack a float weight into (packed int8 nibbles, scales) for
    fused_multi_transformer_int4. Symmetric per-channel (or per-group)
    absmax quantization along the INPUT axis `axis`; two consecutive
    int4 values pack into one int8 byte (low nibble first) along that
    axis, halving weight HBM vs int8.

    Returns (packed, scales): packed has `axis` halved; scales broadcast
    over `axis` (shape keeps other dims, axis -> n_groups or 1)."""
    import numpy as np
    a = np.asarray(w.data if hasattr(w, "data") else w, np.float32)
    a = np.moveaxis(a, axis, -1)
    n = a.shape[-1]
    if n % 2:
        raise ValueError("int4 packing needs an even axis length")
    g = group_size or n
    if n % g:
        raise ValueError("group_size must divide the quantized axis")
    grp = a.reshape(*a.shape[:-1], n // g, g)
    sc = np.abs(grp).max(-1, keepdims=True) / 7.0 + 1e-9
    q = np.clip(np.round(grp / sc), -8, 7).astype(np.int8)
    q = q.reshape(*a.shape[:-1], n)
    lo, hi = q[..., 0::2], q[..., 1::2]
    packed = ((hi.astype(np.uint8) << 4) |
              (lo.astype(np.uint8) & 0x0F)).astype(np.int8)
    packed = np.moveaxis(packed, -1, axis % a.ndim if axis >= 0 else axis)
    scales = np.moveaxis(sc[..., 0], -1, axis % a.ndim if axis >= 0
                         else axis)
    return packed, scales.astype(np.float32)


def _unpack_int4(p, axis=-1):
    """int8-packed nibbles -> int4 values (sign-extended), axis doubled."""
    u = p.astype(jnp.uint8)
    lo = (u & 0x0F).astype(jnp.int8)
    hi = (u >> 4).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.int8)
    hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.int8)
    stacked = jnp.stack([lo, hi], axis=-1)         # [..., n/2, 2]
    out = stacked.reshape(*p.shape[:-1], p.shape[-1] * 2) \
        if axis in (-1, p.ndim - 1) else None
    if out is None:
        m = jnp.moveaxis(p, axis, -1)
        u = m.astype(jnp.uint8)
        lo = (u & 0x0F).astype(jnp.int8)
        hi = (u >> 4).astype(jnp.int8)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        out = jnp.stack([lo, hi], -1).reshape(*m.shape[:-1],
                                              m.shape[-1] * 2)
        out = jnp.moveaxis(out, -1, axis)
    return out


def fused_multi_transformer_int4(
        x, ln_scales, ln_biases, qkv_weights, qkv_scales, qkv_biases,
        linear_weights, linear_scales, linear_biases, ffn_ln_scales,
        ffn_ln_biases, ffn1_weights, ffn1_scales, ffn1_biases, ffn2_weights,
        ffn2_scales, ffn2_biases, **kwargs):
    """Weight-only-int4 variant — HALF the weight HBM of the reference's
    int8 tier (capability upgrade; the reference stops at int8). Weights
    are int8 bytes holding two packed nibbles along the INPUT (embed)
    axis with per-output-channel symmetric scales from `quantize_int4`;
    the unpack + dequant lowers into the matmul's operand load like the
    int8 path.

    Shapes: qkv [3, H, D, E/2] (+scale [3, H, D]); linear [H*D/2, E]
    packed on axis 0 (+scale [E]); ffn1 [E/2, F] (+scale [F]);
    ffn2 [F/2, E] (+scale [E])."""
    from ....core.tensor import Tensor as _T
    scales = {"qkv": list(qkv_scales), "lin": list(linear_scales),
              "f1": list(ffn1_scales), "f2": list(ffn2_scales)}

    def dq(w, kind, li):
        sc = scales[kind][li]
        sc = sc.data if isinstance(sc, _T) else jnp.asarray(sc)
        # quantize_int4's scales already broadcast against the unpacked
        # weight (qkv: [3,H,D,1] vs [3,H,D,E]; lin/f1/f2: [1,out] vs
        # [in,out])
        full = _unpack_int4(w, axis=-1 if kind == "qkv" else 0)
        return full.astype(jnp.float32) * sc

    return fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, _dequant=dq, **kwargs)


# -- cublasLt-epilogue tier (reference fused_matmul_bias.py:31,95,136 — on
# TPU the epilogue IS XLA fusion: bias-add and gelu/relu fuse into the
# matmul's result tiles, so these express intent and let the compiler do
# what cublasLt does by hand) --------------------------------------------

def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """matmul + bias-add in one compiled region (reference
    fused_gemm_epilogue_kernel.cu role)."""
    def impl(xv, yv, *rest):
        a = jnp.swapaxes(xv, -1, -2) if transpose_x else xv
        b = jnp.swapaxes(yv, -1, -2) if transpose_y else yv
        out = a @ b
        if rest:
            out = out + rest[0]
        return out

    args = (x, y) if bias is None else (x, y, bias)
    return apply_op("fused_matmul_bias", impl, args, {})


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """Reference fused_matmul_bias.py:95 — linear via the epilogue path."""
    return fused_matmul_bias(x, weight, bias, False, transpose_weight, name)


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation=None):
    """matmul + bias + gelu/relu epilogue (reference
    fused_matmul_bias.py:136)."""
    if activation not in (None, "none", "gelu", "relu"):
        raise ValueError(f"unsupported epilogue activation {activation}")

    def impl(xv, yv, bv):
        a = jnp.swapaxes(xv, -1, -2) if trans_x else xv
        b = jnp.swapaxes(yv, -1, -2) if trans_y else yv
        out = a @ b + bv
        if activation == "gelu":
            out = jax.nn.gelu(out, approximate=True)
        elif activation == "relu":
            out = jax.nn.relu(out)
        return out

    return apply_op("fused_linear_activation", impl, (x, y, bias), {})


def swiglu(x, y=None, name=None):
    """SwiGLU (reference swiglu.py:26): silu(x) * y; with y=None, x is
    chunked in half on the last axis. The pattern XLA fuses into the
    surrounding GEMMs (the reference has a dedicated CUDA kernel)."""
    if y is None:
        def impl(xv):
            a, b = jnp.split(xv, 2, axis=-1)
            return jax.nn.silu(a) * b
        return apply_op("swiglu", impl, (x,), {})

    def impl(xv, yv):
        return jax.nn.silu(xv) * yv
    return apply_op("swiglu", impl, (x, y), {})


# -- fused norm tier (reference fused_rms_norm.py:59, fused_layer_norm.py:61
# — norm(bias + residual + x) patterns with optional int8 quant of the
# normalized output) ------------------------------------------------------

def _maybe_quant(out, quant_scale, quant_round_type, quant_max_bound,
                 quant_min_bound):
    if quant_scale <= 0:
        return out
    q = out.astype(jnp.float32) * quant_max_bound * quant_scale
    if quant_round_type == 0:
        q = jnp.rint(q)  # round half to even
    else:
        q = jnp.where(q >= 0, jnp.floor(q + 0.5), jnp.ceil(q - 0.5))
    return jnp.clip(q, quant_min_bound, quant_max_bound).astype(jnp.int8)


def fused_rms_norm(x, norm_weight, norm_bias, epsilon, begin_norm_axis,
                   bias=None, residual=None, quant_scale=-1,
                   quant_round_type=0, quant_max_bound=0, quant_min_bound=0):
    """RMSNorm(bias + residual + x) fused (reference fused_rms_norm.py:59).
    Returns (out, residual_out): residual_out is the pre-norm sum the next
    layer's residual branch consumes."""
    def impl(xv, w, *rest):
        it = iter(rest)
        b = next(it) if norm_bias is not None else None
        pb = next(it) if bias is not None else None
        res = next(it) if residual is not None else None
        h = xv
        if pb is not None:
            h = h + pb
        if res is not None:
            h = h + res
        red = tuple(range(begin_norm_axis, h.ndim))
        hf = h.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(hf * hf, axis=red, keepdims=True)
                            + epsilon)
        out = (hf * inv).astype(h.dtype) * w
        if b is not None:
            out = out + b
        out = _maybe_quant(out, quant_scale, quant_round_type,
                           quant_max_bound, quant_min_bound)
        return out, h

    args = [x, norm_weight]
    for t in (norm_bias, bias, residual):
        if t is not None:
            args.append(t)
    return apply_op("fused_rms_norm", impl, tuple(args), {})


def fused_layer_norm(x, norm_weight, norm_bias, epsilon, residual_alpha=1.0,
                     begin_norm_axis=1, bias=None, residual=None,
                     quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                     quant_min_bound=0):
    """LayerNorm(bias + residual_alpha*residual + x) fused (reference
    fused_layer_norm.py:61). With norm_weight=None and norm_bias=None the
    result is just the fused sum. Returns (out, residual_out)."""
    def impl(xv, *rest):
        it = iter(rest)
        w = next(it) if norm_weight is not None else None
        b = next(it) if norm_bias is not None else None
        pb = next(it) if bias is not None else None
        res = next(it) if residual is not None else None
        h = xv
        if pb is not None:
            h = h + pb
        if res is not None:
            h = h + residual_alpha * res
        if w is None and b is None:
            return h, h
        red = tuple(range(begin_norm_axis, h.ndim))
        hf = h.astype(jnp.float32)
        mu = jnp.mean(hf, axis=red, keepdims=True)
        var = jnp.mean((hf - mu) ** 2, axis=red, keepdims=True)
        out = ((hf - mu) * jax.lax.rsqrt(var + epsilon)).astype(h.dtype)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        out = _maybe_quant(out, quant_scale, quant_round_type,
                           quant_max_bound, quant_min_bound)
        return out, h

    args = [x]
    for t in (norm_weight, norm_bias, bias, residual):
        if t is not None:
            args.append(t)
    return apply_op("fused_layer_norm", impl, tuple(args), {})


# -- MoE + var-len attention tier ----------------------------------------

def fused_moe(x, gate_weight, ffn1_weight, ffn2_weight, ffn1_bias=None,
              ffn1_scale=None, ffn2_bias=None, ffn2_scale=None,
              quant_method="None", moe_topk=2, norm_topk_prob=True):
    """Fused MoE FFN (reference fused_moe.py:20): gate -> top-k -> expert
    GLU-FFN -> weighted combine, one compiled region.

    TPU-native: instead of the reference's scatter-to-expert-buffers CUDA
    choreography, every expert's GEMM runs as one batched einsum over a
    dense one-hot combine weight — MXU-friendly static shapes, zero
    dynamic gathers; token routing resolves to the [tokens, experts]
    combine matrix (the same design as incubate/distributed/models/moe)."""
    if quant_method not in (None, "None", "none", "weight_only_int8"):
        raise NotImplementedError(
            f"fused_moe: quant_method={quant_method!r} unsupported "
            "(weight-only int8 via ffn*_scale, or float weights)")

    def impl(xv, gw, w1, w2, *rest):
        it = iter(rest)
        b1 = next(it) if ffn1_bias is not None else None
        b2 = next(it) if ffn2_bias is not None else None
        s1 = next(it) if ffn1_scale is not None else None
        s2 = next(it) if ffn2_scale is not None else None
        # weight-only dequant (reference ffn*_scale contract: one scale per
        # expert per out-channel); the cast+scale fuses into the einsum's
        # operand load like nn/quant.weight_only_linear
        if s1 is not None:
            w1 = w1.astype(jnp.float32) * s1.reshape(
                s1.shape[0], 1, -1).astype(jnp.float32)
        if s2 is not None:
            w2 = w2.astype(jnp.float32) * s2.reshape(
                s2.shape[0], 1, -1).astype(jnp.float32)
        B, S, D = xv.shape
        E = w1.shape[0]
        tokens = xv.reshape(B * S, D)
        # gate_weight per reference: [B, S, E] logits, or a [D, E] weight
        if gw.ndim == 3:
            logits = gw.reshape(B * S, E)
        else:
            logits = tokens.astype(jnp.float32) @ gw.astype(jnp.float32)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(probs, moe_topk)
        if norm_topk_prob:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        combine = jnp.zeros((B * S, E), dtype=jnp.float32)
        combine = combine.at[jnp.arange(B * S)[:, None], topi].add(topv)
        # dense expert batch: [E, T, D] views weighted after the fact — the
        # GEMMs stay large and static; GSPMD shards E over the ep axis
        h = jnp.einsum("td,edf->etf", tokens, w1.astype(tokens.dtype))
        if b1 is not None:
            h = h + b1
        half = h.shape[-1] // 2
        h = jax.nn.silu(h[..., :half]) * h[..., half:] \
            if w2.shape[1] * 2 == h.shape[-1] else jax.nn.gelu(h)
        y = jnp.einsum("etf,efd->etd", h, w2.astype(h.dtype))
        if b2 is not None:
            y = y + b2
        out = jnp.einsum("etd,te->td", y.astype(jnp.float32), combine)
        return out.reshape(B, S, D).astype(xv.dtype)

    args = [x, gate_weight, ffn1_weight, ffn2_weight]
    for t in (ffn1_bias, ffn2_bias, ffn1_scale, ffn2_scale):
        if t is not None:
            args.append(t)
    return apply_op("fused_moe", impl, tuple(args), {})


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """Var-len attention over padded [B, H, S, D] tensors (reference
    variable_length_memory_efficient_attention.py:33, cutlass kernel).
    Per-sequence lengths become masks over the static padded shapes — the
    TPU answer to ragged batches (no dynamic shapes under jit)."""
    def impl(q, k, v, sl, kvl, *rest):
        m = rest[0] if mask is not None else None
        B, H, S, D = q.shape
        Skv = k.shape[2]
        sc = scale if scale is not None else 1.0 / math.sqrt(D)
        logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * sc
        q_pos = jnp.arange(S)[None, :]            # [1, S]
        kv_pos = jnp.arange(Skv)[None, :]         # [1, Skv]
        q_valid = q_pos < sl.reshape(B, 1)        # [B, S]
        kv_valid = kv_pos < kvl.reshape(B, 1)     # [B, Skv]
        neg = jnp.finfo(jnp.float32).min
        logits = jnp.where(kv_valid[:, None, None, :], logits, neg)
        if causal:
            cm = (jnp.arange(Skv)[None, :] - pre_cache_length
                  <= jnp.arange(S)[:, None])
            logits = jnp.where(cm[None, None], logits, neg)
        if m is not None:
            logits = logits + m.astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v)
        return jnp.where(q_valid[:, None, :, None], out, 0)

    args = [query, key, value, seq_lens, kv_seq_lens]
    if mask is not None:
        args.append(mask)
    return apply_op("variable_length_memory_efficient_attention", impl,
                    tuple(args), {})


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    """Max encoder/decoder lengths for block_multihead_attention
    (reference blha_get_max_len.py:26)."""
    def impl(enc, dec, _bsz):
        return (jnp.max(enc).astype(jnp.int32).reshape(1),
                jnp.max(dec).astype(jnp.int32).reshape(1))

    return apply_op("blha_get_max_len", impl,
                    (seq_lens_encoder, seq_lens_decoder, batch_size), {},
                    differentiable=False)
