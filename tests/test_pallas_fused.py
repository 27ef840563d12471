"""Pallas kernel + fused-op tests (interpret mode on CPU — the reference
pattern of testing device kernels without the device, SURVEY.md §4).

Numerics checked against dense numpy/jnp references, including gradients
for the differentiable kernels."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

# Tier-1 window: this file is heavy on the 2-core CPU box and runs
# in the `pytest -m slow` tier (split recorded in BASELINE.md).
pytestmark = pytest.mark.slow

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import flashmask as fm
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.incubate.nn import functional as FI


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _dense_flashmask_ref(q, k, v, sr, er, causal):
    # q,k,v: [B,S,H,D]; sr/er: [B,H,S]
    b, s, h, d = q.shape
    qt = np.swapaxes(q, 1, 2).astype(np.float64)
    kt = np.swapaxes(k, 1, 2).astype(np.float64)
    vt = np.swapaxes(v, 1, 2).astype(np.float64)
    logits = qt @ np.swapaxes(kt, -1, -2) / np.sqrt(d)
    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    for bi in range(b):
        for hi in range(h):
            allowed = np.ones((s, s), bool)
            if causal:
                allowed &= rows >= cols
            interval = (rows >= sr[bi, hi][None, :]) & \
                (rows < er[bi, hi][None, :])
            allowed &= ~interval
            logits[bi, hi] = np.where(allowed, logits[bi, hi], -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = p @ vt
    # fully-masked rows produce zeros (flash kernel contract)
    dead = (logits <= -1e29).all(-1)
    out = np.where(dead[..., None], 0.0, out)
    return np.swapaxes(out, 1, 2).astype(np.float32)


class TestFlashMask:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        rng = np.random.default_rng(0)
        B, S, H, D = 2, 32, 2, 8
        q, k, v = (rng.standard_normal((B, S, H, D)).astype(np.float32)
                   for _ in range(3))
        # document mask: two docs [0,20) and [20,32): key col j of doc 1
        # masks rows >= 20 is wrong way; flashmask LT doc mask: col j in
        # doc A masks rows outside doc A below it -> start = doc end
        starts = np.where(np.arange(S) < 20, 20, S)
        sr = np.tile(starts[None, None, :], (B, H, 1)).astype(np.int32)
        er = np.full_like(sr, S)
        idx = np.stack([sr, er], axis=-1)
        out = fm.flashmask_attention_bshd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(idx), causal=causal, block_q=8, block_k=8)
        ref = _dense_flashmask_ref(q, k, v, sr, er, causal)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    def test_single_index_means_mask_below_start(self):
        rng = np.random.default_rng(1)
        B, S, H, D = 1, 16, 1, 8
        q, k, v = (rng.standard_normal((B, S, H, D)).astype(np.float32)
                   for _ in range(3))
        start = rng.integers(1, S, size=S).astype(np.int32)
        idx = np.tile(start[None, None, :, None], (B, H, 1, 1))
        out = fm.flashmask_attention_bshd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(idx), causal=True, block_q=8, block_k=8)
        sr = np.tile(start[None, None, :], (B, H, 1))
        er = np.full_like(sr, S)
        ref = _dense_flashmask_ref(q, k, v, sr, er, True)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    def test_gradients_match_dense(self):
        rng = np.random.default_rng(2)
        B, S, H, D = 1, 16, 1, 8
        q, k, v = (rng.standard_normal((B, S, H, D)).astype(np.float32)
                   for _ in range(3))
        start = np.where(np.arange(S) < 8, 8, S)
        idx = np.tile(start[None, None, :, None], (B, H, 1, 1)).astype(
            np.int32)

        def loss_kernel(q_, k_, v_):
            o = fm.flashmask_attention_bshd(q_, k_, v_, jnp.asarray(idx),
                                            causal=True, block_q=8,
                                            block_k=8)
            return (o ** 2).sum()

        def loss_dense(q_, k_, v_):
            s = jnp.einsum("bshd,bthd->bhst", q_, k_) / np.sqrt(D)
            rows = jnp.arange(S)[:, None]
            cols = jnp.arange(S)[None, :]
            allowed = (rows >= cols) & ~(
                (rows >= jnp.asarray(start)[None, :]) & (rows < S))
            s = jnp.where(allowed[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhst,bthd->bshd", p, v_)
            return (o ** 2).sum()

        args = tuple(map(jnp.asarray, (q, k, v)))
        g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(*args)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3)


class TestPagedAttention:
    def _setup(self, B=3, H=4, KVH=2, D=8, BS=8, NB=10, max_nb=4, seed=3):
        rng = np.random.default_rng(seed)
        q = rng.standard_normal((B, H, D)).astype(np.float32)
        k_cache = rng.standard_normal((KVH, NB, BS, D)).astype(np.float32)
        v_cache = rng.standard_normal((KVH, NB, BS, D)).astype(np.float32)
        # distinct random blocks per sequence
        tables = np.stack([rng.choice(NB, max_nb, replace=False)
                           for _ in range(B)]).astype(np.int32)
        lens = rng.integers(1, max_nb * BS, size=B).astype(np.int32)
        return q, k_cache, v_cache, tables, lens

    def _dense_ref(self, q, kc, vc, tables, lens):
        B, H, D = q.shape
        KVH, NB, BS, _ = kc.shape
        G = H // KVH
        out = np.zeros_like(q)
        for b in range(B):
            ks = np.concatenate([kc[:, t] for t in tables[b]], axis=1)
            vs = np.concatenate([vc[:, t] for t in tables[b]], axis=1)
            for h in range(H):
                kv_h = h // G
                s = ks[kv_h, :lens[b]] @ q[b, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, h] = p @ vs[kv_h, :lens[b]]
        return out

    def test_matches_dense(self):
        q, kc, vc, tables, lens = self._setup()
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.stack([kc, vc]), tables, lens)
        ref = self._dense_ref(q, kc, vc, tables, lens)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    def test_cache_update_then_attend(self):
        q, kc, vc, tables, lens = self._setup(seed=4)
        B, H, D = q.shape
        KVH = kc.shape[0]
        rng = np.random.default_rng(5)
        k_new = rng.standard_normal((B, KVH, D)).astype(np.float32)
        v_new = rng.standard_normal((B, KVH, D)).astype(np.float32)
        kv2 = pa.append_paged_kv(
            jnp.stack([kc, vc]), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(tables), jnp.asarray(lens))
        kc2, vc2 = np.asarray(kv2)
        for b in range(B):
            blk = tables[b, lens[b] // kc.shape[2]]
            off = lens[b] % kc.shape[2]
            np.testing.assert_allclose(kc2[:, blk, off], k_new[b])
            np.testing.assert_allclose(vc2[:, blk, off], v_new[b])
        out = pa.ragged_paged_attention(jnp.asarray(q), kv2, tables,
                                        lens + 1)
        ref = self._dense_ref(q, kc2, vc2, tables, lens + 1)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)


class TestFusedOps:
    def test_masked_multihead_attention_decode(self):
        rng = np.random.default_rng(6)
        B, H, SMAX, D = 2, 2, 8, 4
        cache = rng.standard_normal((2, B, H, SMAX, D)).astype(np.float32)
        lens = np.array([3, 5], np.int32)
        x = rng.standard_normal((B, 3 * H * D)).astype(np.float32)
        out, new_cache = FI.masked_multihead_attention(
            paddle.to_tensor(x), paddle.to_tensor(cache),
            paddle.to_tensor(lens))
        out = out.numpy()
        nc = new_cache.numpy()
        qkv = x.reshape(B, 3, H, D)
        for b in range(B):
            for h in range(H):
                ks = np.concatenate([cache[0, b, h, :lens[b]],
                                     qkv[b, 1, h][None]], 0)
                vs = np.concatenate([cache[1, b, h, :lens[b]],
                                     qkv[b, 2, h][None]], 0)
                s = ks @ qkv[b, 0, h] / np.sqrt(D)
                p = np.exp(s - s.max())
                p /= p.sum()
                np.testing.assert_allclose(
                    out[b, h * D:(h + 1) * D], p @ vs, rtol=1e-4,
                    atol=1e-4)
                np.testing.assert_allclose(nc[0, b, h, lens[b]],
                                           qkv[b, 1, h], rtol=1e-6)

    def test_fused_feedforward_matches_composition(self):
        rng = np.random.default_rng(7)
        x = paddle.to_tensor(rng.standard_normal((2, 4, 8)).astype(
            np.float32))
        w1 = paddle.to_tensor(rng.standard_normal((8, 16)).astype(
            np.float32))
        w2 = paddle.to_tensor(rng.standard_normal((16, 8)).astype(
            np.float32))
        out = FI.fused_feedforward(x, w1, w2, pre_layer_norm=True,
                                   dropout1_rate=0.0, dropout2_rate=0.0,
                                   activation="gelu").numpy()
        h = x.numpy()
        mu, var = h.mean(-1, keepdims=True), h.var(-1, keepdims=True)
        hn = (h - mu) / np.sqrt(var + 1e-5)
        import scipy.special as sp
        act = hn @ w1.numpy()
        act = 0.5 * act * (1 + sp.erf(act / np.sqrt(2)))
        ref = h + act @ w2.numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)

    def test_fused_bias_act_swiglu(self):
        rng = np.random.default_rng(8)
        x = paddle.to_tensor(rng.standard_normal((4, 16)).astype(
            np.float32))
        out = FI.fused_bias_act(x, act_method="swiglu").numpy()
        a, b = np.split(x.numpy(), 2, axis=-1)
        ref = (a / (1 + np.exp(-a))) * b
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_fused_linear_param_grad_add_accumulates(self):
        rng = np.random.default_rng(9)
        x = paddle.to_tensor(rng.standard_normal((4, 3)).astype(np.float32))
        dout = paddle.to_tensor(rng.standard_normal((4, 2)).astype(
            np.float32))
        dw0 = paddle.to_tensor(np.ones((3, 2), np.float32))
        db0 = paddle.to_tensor(np.ones((2,), np.float32))
        dw, db = FI.fused_linear_param_grad_add(x, dout, dw0, db0)
        np.testing.assert_allclose(
            dw.numpy(), 1.0 + x.numpy().T @ dout.numpy(), rtol=1e-5)
        np.testing.assert_allclose(db.numpy(),
                                   1.0 + dout.numpy().sum(0), rtol=1e-5)

    def test_fused_mha_matches_sdpa(self):
        rng = np.random.default_rng(10)
        B, S, NH, HD = 2, 4, 2, 4
        DM = NH * HD
        x = paddle.to_tensor(rng.standard_normal((B, S, DM)).astype(
            np.float32))
        qkvw = paddle.to_tensor(rng.standard_normal(
            (3, NH, HD, DM)).astype(np.float32) * 0.2)
        lw = paddle.to_tensor(rng.standard_normal((DM, DM)).astype(
            np.float32) * 0.2)
        out = FI.fused_multi_head_attention(
            x, qkvw, lw, pre_layer_norm=True).numpy()
        # reference composition
        h = x.numpy()
        mu, var = h.mean(-1, keepdims=True), h.var(-1, keepdims=True)
        hn = (h - mu) / np.sqrt(var + 1e-5)
        qkv = np.einsum("bsd,tnhd->tbsnh", hn, qkvw.numpy())
        q, k, v = qkv
        logits = np.einsum("bsnh,btnh->bnst", q, k) / np.sqrt(HD)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ctx = np.einsum("bnst,btnh->bsnh", p, v).reshape(B, S, DM)
        ref = h + ctx @ lw.numpy()
        np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


class TestFusedLayers:
    def test_fused_encoder_layer(self):
        from paddle_tpu.incubate.nn import (FusedMultiHeadAttention,
                                            FusedFeedForward,
                                            FusedTransformerEncoderLayer,
                                            FusedBiasDropoutResidualLayerNorm)
        paddle.seed(0)
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.standard_normal((2, 6, 16)).astype(
            np.float32))
        for layer in (FusedMultiHeadAttention(16, 4, dropout_rate=0.0,
                                              attn_dropout_rate=0.0),
                      FusedFeedForward(16, 32, dropout_rate=0.0),
                      FusedTransformerEncoderLayer(16, 4, 32,
                                                   dropout_rate=0.0)):
            layer.eval()
            out = layer(x)
            assert out.shape == [2, 6, 16]
            assert np.isfinite(out.numpy()).all()
        b = FusedBiasDropoutResidualLayerNorm(16, dropout_rate=0.0)
        b.eval()
        assert b(x, x).shape == [2, 6, 16]

    def test_fused_encoder_trains(self):
        from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer
        from paddle_tpu import optimizer
        paddle.seed(1)
        enc = FusedTransformerEncoderLayer(8, 2, 16, dropout_rate=0.0)
        enc.train()
        opt = optimizer.Adam(parameters=enc.parameters(),
                             learning_rate=1e-3)
        x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
            (2, 4, 8)).astype(np.float32))
        l0 = None
        for i in range(5):
            loss = (enc(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            if i == 0:
                l0 = float(loss.numpy())
        assert float(loss.numpy()) < l0


class TestFusedMultiTransformer:
    """Reference fused_multi_transformer (whole-decoder-stack inference op,
    paddle/phi/kernels/fusion/gpu/fused_multi_transformer_kernel.cu)."""

    @staticmethod
    def _weights(rng, L, E, H, D, F):
        T = paddle.to_tensor

        def mk(*shape, scale=0.1):
            return T((rng.standard_normal(shape) * scale).astype(np.float32))

        return dict(
            ln_scales=[mk(E, scale=1.0) for _ in range(L)],
            ln_biases=[T(np.zeros(E, np.float32)) for _ in range(L)],
            qkv_weights=[mk(3, H, D, E) for _ in range(L)],
            qkv_biases=[mk(3, H, D) for _ in range(L)],
            linear_weights=[mk(H * D, E) for _ in range(L)],
            linear_biases=[mk(E) for _ in range(L)],
            ffn_ln_scales=[mk(E, scale=1.0) for _ in range(L)],
            ffn_ln_biases=[T(np.zeros(E, np.float32)) for _ in range(L)],
            ffn1_weights=[mk(E, F) for _ in range(L)],
            ffn1_biases=[mk(F) for _ in range(L)],
            ffn2_weights=[mk(F, E) for _ in range(L)],
            ffn2_biases=[mk(E) for _ in range(L)])

    def test_decode_matches_prefill(self):
        import jax
        from paddle_tpu.incubate.nn.functional import fused_multi_transformer
        with jax.default_matmul_precision("float32"):
            rng = np.random.default_rng(0)
            B, S, E, H, D, F, SMAX, L = 2, 5, 32, 4, 8, 64, 16, 2
            w = self._weights(rng, L, E, H, D, F)
            T = paddle.to_tensor
            x = T(rng.standard_normal((B, S, E)).astype(np.float32))
            xt = T(rng.standard_normal((B, 1, E)).astype(np.float32))
            caches = [T(np.zeros((2, B, H, SMAX, D), np.float32))
                      for _ in range(L)]
            fused_multi_transformer(x, cache_kvs=caches, **w)
            assert not np.allclose(caches[0].numpy()[:, :, :, :S], 0)
            o2 = fused_multi_transformer(
                xt, cache_kvs=caches, time_step=T(np.array(S, np.int32)), **w)
            caches2 = [T(np.zeros((2, B, H, SMAX, D), np.float32))
                       for _ in range(L)]
            xfull = T(np.concatenate([x.numpy(), xt.numpy()], axis=1))
            ofull = fused_multi_transformer(xfull, cache_kvs=caches2, **w)
            np.testing.assert_allclose(ofull.numpy()[:, -1], o2.numpy()[:, 0],
                                       atol=2e-5)

    def test_int8_weight_only_tracks_fp32(self):
        from paddle_tpu.incubate.nn.functional import (
            fused_multi_transformer, fused_multi_transformer_int8)
        rng = np.random.default_rng(1)
        B, S, E, H, D, F, SMAX, L = 2, 4, 32, 4, 8, 64, 8, 1
        w = self._weights(rng, L, E, H, D, F)
        T = paddle.to_tensor
        x = T(rng.standard_normal((B, S, E)).astype(np.float32))
        ref = fused_multi_transformer(x, **w)

        def q_last(ws):  # per-out-channel int8 over the last dim=output
            w8s, scs = [], []
            for t in ws:
                a = t.numpy()
                sc = np.abs(a).max(axis=0) / 127.0 + 1e-9
                w8s.append(T(np.round(a / sc[None]).astype(np.int8)))
                scs.append(T(sc.astype(np.float32)))
            return w8s, scs

        qkv8, qkvsc = [], []
        for t in w["qkv_weights"]:
            a = t.numpy()
            sc = np.abs(a).max(axis=-1) / 127.0 + 1e-9
            qkv8.append(T(np.round(a / sc[..., None]).astype(np.int8)))
            qkvsc.append(T(sc.astype(np.float32)))
        lin8, linsc = q_last(w["linear_weights"])
        f18, f1sc = q_last(w["ffn1_weights"])
        f28, f2sc = q_last(w["ffn2_weights"])
        o8 = fused_multi_transformer_int8(
            x, w["ln_scales"], w["ln_biases"], qkv8, qkvsc,
            w["qkv_biases"], lin8, linsc, w["linear_biases"],
            w["ffn_ln_scales"], w["ffn_ln_biases"], f18, f1sc,
            w["ffn1_biases"], f28, f2sc, w["ffn2_biases"])
        rel = np.abs(o8.numpy() - ref.numpy()).max() / \
            (np.abs(ref.numpy()).max() + 1e-9)
        assert rel < 0.1, rel

    def test_serving_engine_greedy_deterministic(self):
        from paddle_tpu.inference import FusedMultiTransformerEngine
        rng = np.random.default_rng(2)
        E, H, D, F, L, V = 32, 4, 8, 64, 2, 50
        w = {k: [t.numpy() for t in v]
             for k, v in self._weights(rng, L, E, H, D, F).items()}
        w["embedding"] = rng.standard_normal((V, E)).astype(np.float32)
        w["lm_head"] = (rng.standard_normal((E, V)) * 0.1).astype(np.float32)
        eng = FusedMultiTransformerEngine(w, num_heads=H, head_dim=D,
                                          max_seq_len=64, dtype="float32")
        ids = rng.integers(0, V, (2, 7)).astype(np.int32)
        out = eng.generate(ids, max_new_tokens=8)
        assert out.shape == (2, 8)
        np.testing.assert_array_equal(out, eng.generate(ids,
                                                        max_new_tokens=8))


class TestFusedMultiTransformerGQA:
    """Round-4 verdict #3: GQA (+pre_caches) in fused_multi_transformer
    (reference python/paddle/incubate/nn/functional/fused_transformer.py:1009
    — qkv weight packed [H + 2G, D, E], cache at G kv heads)."""

    @staticmethod
    def _gqa_weights(rng, L, E, H, G, D, F):
        T = paddle.to_tensor

        def mk(*shape, scale=0.1):
            return T((rng.standard_normal(shape) * scale).astype(np.float32))

        return dict(
            ln_scales=[mk(E, scale=1.0) for _ in range(L)],
            ln_biases=[T(np.zeros(E, np.float32)) for _ in range(L)],
            qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
            qkv_biases=[mk(H + 2 * G, D) for _ in range(L)],
            linear_weights=[mk(H * D, E) for _ in range(L)],
            linear_biases=[mk(E) for _ in range(L)],
            ffn_ln_scales=[mk(E, scale=1.0) for _ in range(L)],
            ffn_ln_biases=[T(np.zeros(E, np.float32)) for _ in range(L)],
            ffn1_weights=[mk(E, F) for _ in range(L)],
            ffn1_biases=[mk(F) for _ in range(L)],
            ffn2_weights=[mk(F, E) for _ in range(L)],
            ffn2_biases=[mk(E) for _ in range(L)])

    def test_gqa_matches_mha_with_replicated_kv(self):
        """A GQA stack must equal an MHA stack whose KV heads replicate
        each group's head r times (the defining GQA identity)."""
        import jax
        from paddle_tpu.incubate.nn.functional import fused_multi_transformer
        with jax.default_matmul_precision("float32"):
            rng = np.random.default_rng(2)
            B, S, E, H, G, D, F, L = 2, 6, 32, 4, 2, 8, 64, 2
            r = H // G
            gw = self._gqa_weights(rng, L, E, H, G, D, F)
            T = paddle.to_tensor
            # MHA twin: q rows as-is; k/v rows replicated r times per group
            mha = dict(gw)
            mha["qkv_weights"] = []
            mha["qkv_biases"] = []
            for wq, bq in zip(gw["qkv_weights"], gw["qkv_biases"]):
                a = wq.numpy()
                bb = bq.numpy()
                q, k, v = a[:H], a[H:H + G], a[H + G:]
                qb, kb, vb = bb[:H], bb[H:H + G], bb[H + G:]
                mha["qkv_weights"].append(T(np.stack(
                    [q, np.repeat(k, r, 0), np.repeat(v, r, 0)])))
                mha["qkv_biases"].append(T(np.stack(
                    [qb, np.repeat(kb, r, 0), np.repeat(vb, r, 0)])))
            x = T(rng.standard_normal((B, S, E)).astype(np.float32))
            o_gqa = fused_multi_transformer(x, gqa_group_size=G, **gw)
            o_mha = fused_multi_transformer(x, **mha)
            np.testing.assert_allclose(o_gqa.numpy(), o_mha.numpy(),
                                       atol=2e-5)

    def test_gqa_decode_matches_prefill(self):
        import jax
        from paddle_tpu.incubate.nn.functional import fused_multi_transformer
        with jax.default_matmul_precision("float32"):
            rng = np.random.default_rng(3)
            B, S, E, H, G, D, F, SMAX, L = 2, 5, 32, 4, 2, 8, 64, 16, 2
            w = self._gqa_weights(rng, L, E, H, G, D, F)
            T = paddle.to_tensor
            x = T(rng.standard_normal((B, S, E)).astype(np.float32))
            xt = T(rng.standard_normal((B, 1, E)).astype(np.float32))
            caches = [T(np.zeros((2, B, G, SMAX, D), np.float32))
                      for _ in range(L)]
            fused_multi_transformer(x, cache_kvs=caches, gqa_group_size=G,
                                    **w)
            assert not np.allclose(caches[0].numpy()[:, :, :, :S], 0)
            o2 = fused_multi_transformer(
                xt, cache_kvs=caches, time_step=T(np.array(S, np.int32)),
                gqa_group_size=G, **w)
            caches2 = [T(np.zeros((2, B, G, SMAX, D), np.float32))
                       for _ in range(L)]
            xfull = T(np.concatenate([x.numpy(), xt.numpy()], axis=1))
            ofull = fused_multi_transformer(xfull, cache_kvs=caches2,
                                            gqa_group_size=G, **w)
            np.testing.assert_allclose(ofull.numpy()[:, -1], o2.numpy()[:, 0],
                                       atol=2e-5)

    def test_pre_caches_prefix_attention(self):
        """pre_caches = prompt-prefix KV: prefill over them must equal one
        prefill over the concatenated sequence (suffix rows compared)."""
        import jax
        from paddle_tpu.incubate.nn.functional import fused_multi_transformer
        with jax.default_matmul_precision("float32"):
            rng = np.random.default_rng(4)
            B, SP, S, E, H, D, F, L = 2, 3, 4, 32, 4, 8, 64, 1
            wref = TestFusedMultiTransformer._weights(rng, L, E, H, D, F)
            T = paddle.to_tensor
            xp = rng.standard_normal((B, SP, E)).astype(np.float32)
            xs = rng.standard_normal((B, S, E)).astype(np.float32)
            SMAX = SP + S
            # full run to harvest the prefix KV from the cache
            cfull = [T(np.zeros((2, B, H, SMAX, D), np.float32))
                     for _ in range(L)]
            ofull = fused_multi_transformer(
                T(np.concatenate([xp, xs], 1)), cache_kvs=cfull, **wref)
            pre = [T(cfull[li].numpy()[:, :, :, :SP]) for li in range(L)]
            o2 = fused_multi_transformer(T(xs), pre_caches=pre, **wref)
            np.testing.assert_allclose(ofull.numpy()[:, SP:], o2.numpy(),
                                       atol=2e-5)

    def test_serving_engine_gqa(self):
        """The engine serves a GQA config (the flagship Llama shape class:
        q heads > kv heads) deterministically."""
        from paddle_tpu.inference import FusedMultiTransformerEngine
        rng = np.random.default_rng(5)
        V, E, H, G, D, F, L = 64, 32, 4, 2, 8, 64, 2
        w = self._gqa_weights(rng, L, E, H, G, D, F)
        # swiglu takes a doubled ffn1 ([E, 2F] -> split into value/gate)
        T = paddle.to_tensor
        w["ffn1_weights"] = [T((rng.standard_normal((E, 2 * F)) * 0.1)
                               .astype(np.float32)) for _ in range(L)]
        w["ffn1_biases"] = [T((rng.standard_normal(2 * F) * 0.1)
                              .astype(np.float32)) for _ in range(L)]
        w["embedding"] = paddle.to_tensor(
            (rng.standard_normal((V, E)) * 0.1).astype(np.float32))
        w["lm_head"] = paddle.to_tensor(
            (rng.standard_normal((E, V)) * 0.1).astype(np.float32))
        eng = FusedMultiTransformerEngine(
            w, num_heads=H, head_dim=D, max_seq_len=32, dtype="float32",
            norm_type="rmsnorm", activation="swiglu", gqa_group_size=G)
        ids = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
        out1 = eng.generate(ids, max_new_tokens=6)
        out2 = eng.generate(ids, max_new_tokens=6)
        assert out1.shape == (2, 6)
        np.testing.assert_array_equal(out1, out2)
        assert eng.new_caches(2)[0].shape == (2, 2, G, 32, D)


class TestKernelAutotune:
    """Kernel autotune layer (reference paddle/phi/kernels/autotune/ —
    round-4 closure of the §2.9 'autotune partial' row)."""

    def test_autotune_picks_and_caches(self, tmp_path, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import autotune as AT
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        AT.clear_cache()
        calls = []

        def run(c):
            calls.append(c)
            import time
            if c == "slow":
                time.sleep(0.02)
            return jnp.zeros(())

        best = AT.autotune("k1", ["slow", "fast"], run, reps=1)
        assert best == "fast"
        n = len(calls)
        # second lookup: served from cache, run not called again
        assert AT.autotune("k1", ["slow", "fast"], run) == "fast"
        assert len(calls) == n
        # cache survives a fresh in-memory state (disk roundtrip)
        AT._mem = None
        assert AT.autotune("k1", ["slow", "fast"], run) == "fast"
        assert len(calls) == n

    def test_failing_candidates_skipped(self, tmp_path, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import autotune as AT
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at2.json"))
        AT.clear_cache()

        def run(c):
            if c[0] == 0:
                raise ValueError("bad block")
            return jnp.zeros(())

        assert AT.autotune("k2", [(0, 1), (2, 2)], run, reps=1) == (2, 2)
        import pytest as _pt
        with _pt.raises(RuntimeError):
            AT.autotune("k3", [(0, 1)], run, reps=1)

    def test_tuned_blocks_defaults_without_flag(self):
        import paddle_tpu as paddle
        from paddle_tpu.ops.pallas.flash_attention import tuned_blocks
        q = paddle.randn([1, 512, 4, 64])
        bq, bk = tuned_blocks(q, q, q, causal=True)
        assert bq >= 256 and bk >= 256  # defaults clamped to the sequence


class TestFusedMultiTransformerInt4:
    """Weight-only int4 tier (capability upgrade over the reference's
    int8 kernel: half the weight HBM)."""

    def test_pack_roundtrip(self):
        from paddle_tpu.incubate.nn.functional import (quantize_int4,
                                                       _unpack_int4)
        rng = np.random.default_rng(0)
        w = rng.standard_normal((8, 16)).astype(np.float32)
        p, sc = quantize_int4(w, axis=0)
        assert p.shape == (4, 16) and p.dtype == np.int8
        rec = np.asarray(_unpack_int4(jnp.asarray(p), axis=0),
                         np.float32) * np.asarray(sc)
        assert np.abs(rec - w).max() / np.abs(w).max() < 0.15

    def test_int4_tracks_fp32(self):
        from paddle_tpu.incubate.nn.functional import (
            fused_multi_transformer, fused_multi_transformer_int4,
            quantize_int4)
        rng = np.random.default_rng(1)
        B, S, E, H, D, F, L = 2, 4, 32, 4, 8, 64, 1
        w = TestFusedMultiTransformer._weights(rng, L, E, H, D, F)
        T = paddle.to_tensor
        x = T(rng.standard_normal((B, S, E)).astype(np.float32))
        ref = fused_multi_transformer(x, **w)

        def q(ws, axis):
            packed, scs = [], []
            for t in ws:
                p, s = quantize_int4(t.numpy(), axis=axis)
                packed.append(T(p))
                scs.append(T(s))
            return packed, scs

        qkv4, qkvsc = q(w["qkv_weights"], -1)
        lin4, linsc = q(w["linear_weights"], 0)
        f14, f1sc = q(w["ffn1_weights"], 0)
        f24, f2sc = q(w["ffn2_weights"], 0)
        o4 = fused_multi_transformer_int4(
            x, w["ln_scales"], w["ln_biases"], qkv4, qkvsc,
            w["qkv_biases"], lin4, linsc, w["linear_biases"],
            w["ffn_ln_scales"], w["ffn_ln_biases"], f14, f1sc,
            w["ffn1_biases"], f24, f2sc, w["ffn2_biases"])
        rel = np.abs(o4.numpy() - ref.numpy()).max() / \
            (np.abs(ref.numpy()).max() + 1e-9)
        assert rel < 0.25, rel  # int4: coarser than int8's 0.1 bound
        # the packed weights really are half-size
        assert qkv4[0].numpy().nbytes * 2 == \
            w["qkv_weights"][0].numpy().astype(np.int8).nbytes


class TestRopeInFlashKernel:
    """Round-5 opt-in capability: neox rope applied INSIDE the flash
    kernels (fwd rotate, bwd counter-rotate). Default OFF on the flagship
    (measured slower: per-tile re-rotation beats the saved HBM traffic —
    BASELINE.md round-5 notes); correctness is gated here."""

    def test_matches_pre_rotated_reference(self):
        import jax
        import jax.numpy as jnp
        import paddle_tpu.ops.pallas.flash_attention as FA
        from paddle_tpu.nn.functional.rope import (
            _rotate, rotary_embedding_cos_sin)
        old = FA._INTERPRET
        FA._INTERPRET = True
        try:
            rng = np.random.default_rng(0)
            B, S, H, D = 2, 128, 4, 64
            q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
            k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
            v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
            cos, sin = rotary_embedding_cos_sin(S, D)

            def fused(q, k, v):
                return FA.flash_attention_bshd(
                    q, k, v, causal=True, block_q=64, block_k=64,
                    bwd_block_q=64, bwd_block_k=64,
                    rope_cos=cos, rope_sin=sin)

            def ref(q, k, v):
                return FA.flash_attention_bshd(
                    _rotate(q, cos, sin, True), _rotate(k, cos, sin, True),
                    v, causal=True, block_q=64, block_k=64,
                    bwd_block_q=64, bwd_block_k=64)

            np.testing.assert_allclose(
                np.asarray(fused(q, k, v)), np.asarray(ref(q, k, v)),
                rtol=1e-5, atol=1e-5)
            g1 = jax.grad(lambda *a: fused(*a).sum(), argnums=(0, 1, 2))(
                q, k, v)
            g2 = jax.grad(lambda *a: ref(*a).sum(), argnums=(0, 1, 2))(
                q, k, v)
            for a, b in zip(g1, g2):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)
        finally:
            FA._INTERPRET = old

    def test_llama_flag_consistent(self, monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.ops.pallas.flash_attention as FA
        import paddle_tpu.nn.functional.attention as ATT
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        old = FA._INTERPRET
        FA._INTERPRET = True
        # force the PALLAS branch on CPU (interpret mode) so the
        # kernel-path rope-operand plumbing through apply_op is what this
        # test actually compares against the standard rope path
        monkeypatch.setattr(ATT, "_flash_available", lambda: True)
        try:
            rng = np.random.default_rng(0)
            ids = paddle.to_tensor(
                rng.integers(0, 128, (2, 32)).astype(np.int32))
            outs = {}
            for fuse in (True, False):
                paddle.seed(7)
                cfg = LlamaConfig.tiny(dtype="float32",
                                       fuse_rope_in_attention=fuse)
                m = LlamaForCausalLM(cfg)
                m.eval()
                outs[fuse] = np.asarray(m(ids).numpy())
            np.testing.assert_allclose(outs[True], outs[False],
                                       rtol=1e-5, atol=2e-5)
        finally:
            FA._INTERPRET = old
