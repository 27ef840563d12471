"""Flash attention as a Pallas TPU kernel.

Reference analogue: paddle/phi/kernels/gpu/flash_attn_kernel.cu (binds the
vendored third_party/flashattn CUDA library) exposed through
python/paddle/nn/functional/flash_attention.py:358. Here the kernel is
written for the TPU memory hierarchy: queries stream through VMEM in
(BLOCK_Q x head_dim) tiles, keys/values in (BLOCK_K x head_dim) tiles, with
the online-softmax running max/denominator kept in f32 VMEM scratch. The
backward is one fused pass gridded over (key block, query block) pairs
(`_bwd_fused_kernel`) using the saved logsumexp; the softmax-grad
correction term delta = rowsum(do*o) is recomputed in-kernel.

The saved logsumexp is materialized as [BH, 8, S] f32 — the sequence dim
rides the 128-lane axis, so the (8,128) tiling pads nothing. (The earlier
[BH, S, 8] layout tiled 8 lanes up to 128: a 16x HBM expansion, 256MB/layer
at 2k-seq shapes, visible in XLA's allocation dumps.) In-kernel running
max/denominator scratch stays lane-broadcast [block_q, 128] for VPU-friendly
shapes.

Layout contract: [B, S, H, D] at the API boundary (paddle's flash_attention
layout); kernels run on [B*H, S, D].
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import cparams as _cparams

DEFAULT_BLOCK_Q = 2048  # round-5 on v5e (bf16 dot operands): fwd device
DEFAULT_BLOCK_K = 2048  # time 1.63 ms vs 2.2 ms at (1024, 2048); bwd tiles
                        # are clamped separately in _flash_bwd
LANES = 128
LSE_LANES = 8  # one f32 sublane tile: smallest legal trailing dim
NEG_INF = -1e30

_INTERPRET = False  # set True in tests to run kernels on CPU


def _interpret_mode():
    return _INTERPRET


def _pick_block(seq_len, default):
    if seq_len >= default:
        return default
    # small sequences: one block (pallas pads the trailing tile)
    return max(8, seq_len)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rope_fwd(x, cos, sin):
    """Neox rotation on a [N, D] tile: [x1 c - x2 s, x2 c + x1 s] with
    cos/sin [N, D/2] (same math/dtype as nn/functional/rope._rotate,
    computed in the tile's dtype)."""
    half = x.shape[1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=1)


def _rope_bwd(g, cos, sin):
    """Transpose of _rope_fwd: dx = [g1 c + g2 s, g2 c - g1 s]."""
    half = g.shape[1] // 2
    g1, g2 = g[:, :half], g[:, half:]
    c = cos.astype(g.dtype)
    s = sin.astype(g.dtype)
    return jnp.concatenate([g1 * c + g2 * s, g2 * c - g1 * s], axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale, causal, block_q, block_k, seq_len, rope):
    if rope:
        cq_ref, sq_ref, ck_ref, sk_ref = rest[:4]
        o_ref, lse_ref, acc, m_scr, l_scr, qrot_scr = rest[4:]
    else:
        o_ref, lse_ref, acc, m_scr, l_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        if rope:
            # the q tile is loop-invariant across the k sweep: rotate
            # ONCE into scratch (the k tile changes per step and must
            # rotate in-loop)
            qrot_scr[...] = _rope_fwd(q_ref[0], cq_ref[...], sq_ref[...])

    q_start = qi * block_q
    k_start = ki * block_k

    def _update():
        # dots take the NATIVE (bf16) operands with f32 accumulation: an
        # f32 x f32 MXU pass runs at ~1/4 the bf16 rate on v5e, and this
        # kernel is matmul-bound. Softmax math stays f32.
        q = qrot_scr[...] if rope else q_ref[0]   # [BQ, D]
        k = k_ref[0]                       # [BK, D]
        v = v_ref[0]                       # [BK, D]
        if rope:
            # rope folded into the kernel: rotated q/k never reach HBM
            k = _rope_fwd(k, ck_ref[...], sk_ref[...])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if seq_len % block_k:
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(cols < seq_len, s, NEG_INF)

        m_prev = m_scr[:, :1]                        # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [BQ, BK]
        corr = jnp.exp(m_prev - m_new)               # [BQ, 1]
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip key blocks strictly above the causal diagonal
        pl.when(k_start <= q_start + block_q - 1)(_update)
    else:
        _update()

    @pl.when(ki == nk - 1)
    def _final():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(jnp.where(l_scr[:, :1] == 0.0, 1.0,
                                               l_scr[:, :1]))
        # lse_ref block is [LSE_SUBLANES, block_q]: broadcast across sublanes
        lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :],
                                      lse_ref.shape[1:])


def _rope_specs(block_q, block_k, d, q_index, k_index):
    """cos/sin [S, D/2] operand specs: q-row slices then k-row slices;
    q_index/k_index map the grid coords to the row-block index (the fwd
    grid is (b, qi, ki), the fused bwd grid (b, ki, qi))."""
    return [
        pl.BlockSpec((block_q, d // 2), q_index),
        pl.BlockSpec((block_q, d // 2), q_index),
        pl.BlockSpec((block_k, d // 2), k_index),
        pl.BlockSpec((block_k, d // 2), k_index),
    ]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, rope_cos=None,
               rope_sin=None):
    """q,k,v: [BH, S, D] -> (o [BH, S, D], lse [BH, LSE_LANES, S]).
    rope_cos/rope_sin [S, D/2]: neox rotation applied in-kernel."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    rope = rope_cos is not None
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=sk, rope=rope)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    operands = [q, k, v]
    if rope:
        in_specs += _rope_specs(block_q, block_k, d,
                                lambda b, i, j: (i, 0),
                                lambda b, i, j: (j, 0))
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, LSE_LANES, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, LSE_LANES, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ] + ([pltpu.VMEM((block_q, d), q.dtype)] if rope else []),
        name="flash_attn_fwd",
        interpret=_interpret_mode(),
        compiler_params=_cparams(),
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                      scale, causal, block_q, block_k, seq_len, rope):
    """Single-pass backward (round 5): s, p and dp are computed ONCE per
    (k, q) tile and contracted into all three gradients — the two-pass
    form recomputed s and dp in each pass (7 tile-matmuls + 2 exp sweeps
    per tile pair; this kernel does 5 + 1). dk/dv accumulate in VMEM
    scratch across the inner q loop; dq contributions land in a
    per-k-slice partial buffer [nk, BH, S, D] summed by XLA outside (a
    cheap reduction beats cross-iteration read-modify-write aliasing)."""
    if rope:
        cq_ref, sq_ref, ck_ref, sk_ref = rest[:4]
        dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc, krot_scr = rest[4:]
    else:
        dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if rope:
            # the k tile is loop-invariant across the q sweep here
            krot_scr[...] = _rope_fwd(k_ref[0], ck_ref[...], sk_ref[...])

    q_start = qi * block_q
    k_start = ki * block_k

    def _update():
        # bf16 dot operands / f32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        if rope:
            q = _rope_fwd(q, cq_ref[...], sq_ref[...])
        k = krot_scr[...] if rope else k_ref[0]
        v = v_ref[0]
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]              # [BQ, 1]
        delta = jnp.sum(do * o, axis=1, keepdims=True)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if seq_len % block_k:
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(cols < seq_len, s, NEG_INF)
        p = jnp.exp(s - lse)                         # [BQ, BK]
        dp = jax.lax.dot_general(
            do_ref[0], v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                # [BQ, BK]
        ds16 = ds.astype(q.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(q.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BK, D]
        dk_acc[...] += jax.lax.dot_general(
            ds16, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BK, D]
        dq_rot = jax.lax.dot_general(
            ds16, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [BQ, D]
        if rope:
            # counter-rotate: grads flow to the UNROTATED q
            dq_rot = _rope_bwd(dq_rot, cq_ref[...], sq_ref[...])
        dqp_ref[0, 0] = dq_rot

    def _skip():
        # the block buffer is uninitialized memory: a skipped causal tile
        # must still zero its dq partial slot
        dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_update)
        pl.when(k_start > q_start + block_q - 1)(_skip)
    else:
        _update()

    @pl.when(qi == nq - 1)
    def _final():
        dk_fin = dk_acc[...]
        if rope:
            dk_fin = _rope_bwd(dk_fin, ck_ref[...], sk_ref[...])
        dk_ref[0] = dk_fin.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               bwd_block_q=None, bwd_block_k=None, rope_cos=None,
               rope_sin=None):
    block_q = bwd_block_q or min(block_q, 1024)
    block_k = bwd_block_k or min(block_k, 1024)
    bh, sq, d = q.shape
    sk = k.shape[1]
    rope = rope_cos is not None
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, LSE_LANES, block_q), lambda b, j, i: (b, 0, i)),
    ]
    operands = [q, k, v, o, do, lse]
    if rope:
        in_specs += _rope_specs(block_q, block_k, d,
                                lambda b, j, i: (i, 0),
                                lambda b, j, i: (j, 0))
        operands += [rope_cos, rope_sin, rope_cos, rope_sin]

    dqp, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=sk,
                          rope=rope),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, j, i: (j, b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nk, bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ] + ([pltpu.VMEM((block_k, d), k.dtype)] if rope else []),
        name="flash_attn_bwd_fused",
        interpret=_interpret_mode(),
        compiler_params=_cparams(),
    )(*operands)
    dq = dqp.sum(axis=0).astype(q.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper ([B, S, H, D] native layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, rope_cos, rope_sin, scale, causal, block_q, block_k,
           bwd_block_q=None, bwd_block_k=None):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                      rope_cos, rope_sin)
    return o


def _flash_vjp_fwd(q, k, v, rope_cos, rope_sin, scale, causal, block_q,
                   block_k, bwd_block_q, bwd_block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        rope_cos, rope_sin)
    return o, (q, k, v, rope_cos, rope_sin, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, bwd_block_q,
                   bwd_block_k, res, do):
    q, k, v, rope_cos, rope_sin, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, scale, causal,
                            block_q, block_k, bwd_block_q, bwd_block_k,
                            rope_cos, rope_sin)
    return dq, dk, dv, None, None  # cos/sin: no grads (fixed tables)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_bhsd(q, k, v, causal=True, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         bwd_block_q=None, bwd_block_k=None,
                         rope_cos=None, rope_sin=None):
    """q,k,v: [B, H, S, D] (kv heads already matched to q heads).
    rope_cos/rope_sin [S, D/2]: neox rotary embedding applied to q and k
    INSIDE the kernels (fwd rotate, bwd counter-rotate) — the rotated
    tensors never materialize in HBM.

    (A round-5 experiment moved the kernels to 4-D [B, H, S, D] blocks with
    GQA in the index maps; the isolated kernel was equally fast but the
    surrounding XLA fusions regressed the full pretrain step by ~10%, so
    the collapsed [BH, S, D] contract stays.)"""
    b, h, s, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    o = _flash(qf, kf, vf, rope_cos, rope_sin, float(scale), bool(causal),
               block_q, block_k, bwd_block_q, bwd_block_k)
    return o.reshape(b, h, s, d)


def flash_attention_bshd(q, k, v, causal=True, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         bwd_block_q=None, bwd_block_k=None,
                         rope_cos=None, rope_sin=None):
    """q,k,v: [B, S, H, D] (paddle flash_attention layout). GQA: kv heads
    are broadcast up to the query head count. rope_cos/rope_sin: see
    flash_attention_bhsd."""
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    o = flash_attention_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
        rope_cos=rope_cos, rope_sin=rope_sin)
    return jnp.swapaxes(o, 1, 2)


def tuned_blocks(q, k, v, causal=True):
    """(block_q, block_k) for this shape class: the autotuned winner when
    FLAGS_use_autotune is on and inputs are concrete (eager), the
    persisted winner if one exists, the measured defaults otherwise
    (reference: phi/kernels/autotune cache keyed per shape/dtype)."""
    from ...utils import flags as _flags
    import jax as _jax
    b, s, h, d = q.shape
    defaults = (_pick_block(s, DEFAULT_BLOCK_Q),
                _pick_block(s, DEFAULT_BLOCK_K))
    if not _flags.use_autotune:
        return defaults
    from . import autotune as _at
    key = f"flash_bshd:s{s}:h{h}:d{d}:{q.dtype}:causal={int(bool(causal))}"
    cached = _at._load().get(key)
    if cached is not None:
        return tuple(cached)
    arrs = [getattr(x, "data", x) for x in (q, k, v)]
    if any(isinstance(a, _jax.core.Tracer) for a in arrs):
        return defaults  # cannot time under a trace
    cands = []
    for bq in (256, 512, 1024, 2048):
        for bk in (256, 512, 1024, 2048):
            if bq <= max(s, 256) and bk <= max(s, 256):
                cands.append((_pick_block(s, bq), _pick_block(s, bk)))
    cands = sorted(set(cands))

    def run(c):
        # time the COMPILED kernel: an eager run would mostly time
        # per-op dispatch and crown arbitrary winners
        f = _jax.jit(lambda a, b, cv: flash_attention_bshd(
            a, b, cv, causal=causal, block_q=c[0], block_k=c[1]).sum())
        return f(arrs[0], arrs[1], arrs[2])

    return _at.autotune(key, cands, run, reps=10)
