"""Chunked prefill: multi-token ragged attention + token-budget
scheduling (interpret mode on CPU).

Parity ladder, one rung up from test_attention_ragged_paged.py:
  * the chunked kernel must be BIT-EXACT vs the plain-JAX work-list
    reference on a mixed prefill+decode batch,
  * numerically close to an independent per-token dense causal oracle,
  * the chunked engine's generations must match the unchunked engine AND
    the dense `generate()` token for token — under any token budget,
  * and the bucketed (work-list length, chunk-width) compile keys must
    stay FLAT after warmup (the zero-recompiles serving contract).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _setup(h, kvh, lens, q_lens, seed=0, d=32, bs=8, max_nb=6,
           chunk=None):
    rng = np.random.default_rng(seed)
    b = len(lens)
    c = chunk or max(int(max(q_lens)), 1)
    nblk = b * max_nb + 3
    q = rng.standard_normal((b, c, h, d)).astype(np.float32)
    kc = rng.standard_normal((kvh, nblk, bs, d)).astype(np.float32)
    vc = rng.standard_normal((kvh, nblk, bs, d)).astype(np.float32)
    tables = np.stack([rng.choice(nblk, max_nb, replace=False)
                       for _ in range(b)]).astype(np.int32)
    return (q, kc, vc, tables, np.asarray(lens, np.int32),
            np.asarray(q_lens, np.int32))


def _dense_causal_oracle(q, kc, vc, tables, lens, q_lens):
    """Per-token oracle: query j of sequence b sits at absolute position
    (lens[b] - q_lens[b]) + j and attends over every earlier position;
    softmax in float64 over the sequence's gathered blocks."""
    b, c, h, d = q.shape
    kvh, _, bs, _ = kc.shape
    g = h // kvh
    out = np.zeros((b, c, h, d), np.float32)
    for bb in range(b):
        ql, ctx = int(q_lens[bb]), int(lens[bb])
        if ql == 0:
            continue
        ks = np.concatenate([kc[:, t] for t in tables[bb]], axis=1)
        vs = np.concatenate([vc[:, t] for t in tables[bb]], axis=1)
        for j in range(ql):
            n = min(ctx - ql + j + 1, ks.shape[1])
            for hh in range(h):
                kv = hh // g
                s = ks[kv, :n].astype(np.float64) @ \
                    q[bb, j, hh].astype(np.float64) / np.sqrt(d)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[bb, j, hh] = p @ vs[kv, :n].astype(np.float64)
    return out


# lens INCLUDE the query span; mix: mid-prompt chunk, decode (q=1),
# skipped row (q=0), whole-prompt chunk, chunk crossing a block boundary
MIXED_LENS = [24, 17, 40, 4, 13]
MIXED_QLENS = [4, 1, 0, 4, 6]


class TestChunkedKernel:
    @pytest.mark.parametrize("h,kvh", [
        pytest.param(8, 4, id="gqa2"), pytest.param(8, 2, id="gqa4"),
        pytest.param(4, 4, id="mha"), pytest.param(4, 1, id="mqa")])
    def test_mixed_batch_bit_exact_vs_reference(self, h, kvh):
        q, kc, vc, tables, lens, qls = _setup(h, kvh, MIXED_LENS,
                                              MIXED_QLENS)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), q_lens=qls)
        ref = pa.ragged_paged_attention_reference(
            q, kc, vc, tables, lens, q_lens=qls)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_mixed_batch_close_to_dense_causal_oracle(self):
        q, kc, vc, tables, lens, qls = _setup(8, 4, MIXED_LENS,
                                              MIXED_QLENS, seed=1)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), q_lens=qls)
        ref = _dense_causal_oracle(q, kc, vc, tables, lens, qls)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    def test_intra_chunk_causality(self):
        # moving a LATER chunk token must not change an earlier token's
        # output: causal masking inside the chunk, not just vs the cache
        q, kc, vc, tables, lens, qls = _setup(4, 2, [12], [4], seed=2)
        out1 = np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), q_lens=qls))
        q2 = q.copy()
        q2[0, 3] += 100.0
        out2 = np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q2), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), q_lens=qls))
        np.testing.assert_array_equal(out1[0, :3], out2[0, :3])
        assert not np.array_equal(out1[0, 3], out2[0, 3])

    def test_rows_past_q_len_zeroed(self):
        q, kc, vc, tables, lens, qls = _setup(4, 2, MIXED_LENS,
                                              MIXED_QLENS, seed=3)
        out = np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), q_lens=qls))
        for bb, ql in enumerate(qls):
            np.testing.assert_array_equal(out[bb, ql:], 0.0)
        # the q_len-0 row contributed zero work entries
        _, t_real, _, _ = pa.build_ragged_work(
            tables, lens, kc.shape[2], 2, q_lens=qls)
        bs = kc.shape[2]
        expect = sum(-(-int(l) // bs) for l, ql in zip(lens, qls) if ql > 0)
        assert t_real == expect

    def test_work_list_q_spans(self):
        bs, max_nb = 8, 6
        tables = np.arange(5 * max_nb, dtype=np.int32).reshape(5, max_nb)
        lens = np.asarray(MIXED_LENS, np.int32)
        qls = np.asarray(MIXED_QLENS, np.int32)
        (ws, _, _, _, _, _, _, wqs, wql), t_real, _, _ = \
            pa.build_ragged_work(tables, lens, bs, 2, q_lens=qls)
        for t in range(t_real):
            s = ws[t]
            assert wql[t] == qls[s]
            assert wqs[t] == lens[s] - qls[s]
        # default q_lens (decode): span is exactly the last token
        (ws2, _, _, _, _, _, _, wqs2, wql2), t2, _, _ = \
            pa.build_ragged_work(tables, lens, bs, 2)
        assert (wql2[:t2] == 1).all()
        for t in range(t2):
            assert wqs2[t] == lens[ws2[t]] - 1

    def test_bucketed_chunked_work_same_output(self):
        q, kc, vc, tables, lens, qls = _setup(8, 4, MIXED_LENS,
                                              MIXED_QLENS, seed=4)
        plain = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), pack=2, q_lens=qls)
        work = pa.build_ragged_work(tables, lens, kc.shape[2], 2,
                                    bucket_to=pa.next_pow2, q_lens=qls)
        assert work[2] > work[1]  # really padded
        bucketed = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), work=work, q_lens=qls)
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.asarray(bucketed))

    # blocks of 4 rows move whole; blocks of 16 are two of the writer's
    # 8-row groups, and a 12-wide chunk crosses groups and blocks
    @pytest.mark.parametrize("bs,c", [(4, 4), (16, 12)])
    def test_chunk_cache_update_spans_blocks_and_drops(self, bs, c):
        rng = np.random.default_rng(5)
        kvh, nb, d, max_nb = 2, 13, 8, 3
        kc = rng.standard_normal((kvh, nb, bs, d)).astype(np.float32)
        vc = rng.standard_normal((kvh, nb, bs, d)).astype(np.float32)
        kn = rng.standard_normal((3, c, kvh, d)).astype(np.float32)
        vn = rng.standard_normal((3, c, kvh, d)).astype(np.float32)
        tables = np.arange(3 * max_nb, dtype=np.int32).reshape(3, max_nb)
        # row 0: chunk crosses a block boundary; row 1: parked (0 valid);
        # row 2: runs into the table capacity mid-chunk -> dropped
        lens = np.asarray([bs - 2, bs + 1, max_nb * bs - 2], np.int32)
        valid = np.asarray([c, 0, c], np.int32)
        kc2, vc2 = np.asarray(pa.append_paged_kv_chunk(
            jnp.stack([kc, vc]), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(valid)))
        kc_exp, vc_exp = kc.copy(), vc.copy()
        for bb in range(3):
            for j in range(int(valid[bb])):
                p = int(lens[bb]) + j
                if p >= max_nb * bs:
                    continue
                kc_exp[:, tables[bb, p // bs], p % bs] = kn[bb, j]
                vc_exp[:, tables[bb, p // bs], p % bs] = vn[bb, j]
        np.testing.assert_array_equal(kc2, kc_exp)
        np.testing.assert_array_equal(vc2, vc_exp)


_ENGINE_CACHE = {}


def _tiny_engine(seed=0, max_seq_len=32):
    # cached per (seed, max_seq_len): the engine is read-only for the
    # serving tests (weights fixed, jit caches instance-held), and
    # rebuilding it per test recompiles every step program — the single
    # biggest cost of this file (and of test_speculative_decode, which
    # imports this builder) under CPU interpret mode
    key = (seed, max_seq_len)
    if key in _ENGINE_CACHE:
        return _ENGINE_CACHE[key]
    from paddle_tpu.inference import FusedMultiTransformerEngine
    rng = np.random.default_rng(seed)
    V, E, H, G, D, L, F = 128, 64, 4, 2, 16, 2, 96

    def mk(*shape, scale=0.05):
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
        w, num_heads=H, head_dim=D, max_seq_len=max_seq_len,
        dtype="float32", norm_type="rmsnorm", activation="swiglu",
        gqa_group_size=G)
    _ENGINE_CACHE[key] = (eng, V)
    return eng, V


def _serve(eng, prompts, new_tokens, **kw):
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    kw.setdefault("num_blocks", 9)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 2)
    cb = ContinuousBatchingEngine(eng, **kw)
    reqs = [GenerationRequest(p, n) for p, n in zip(prompts, new_tokens)]
    for r in reqs:
        cb.submit(r)
    out = cb.run()
    return [out[r.request_id] for r in reqs], cb


class TestTokenBudgetScheduler:
    def _workload(self, V, seed=3):
        rng = np.random.default_rng(seed)
        lengths = [(5, 4), (11, 3), (3, 6), (8, 2)]
        prompts = [rng.integers(1, V, p).astype(np.int32)
                   for p, _ in lengths]
        return prompts, [n for _, n in lengths]

    def test_chunked_token_exact_vs_unchunked_and_generate(self):
        eng, V = _tiny_engine()
        prompts, news = self._workload(V)
        chunked, cb_c = _serve(eng, prompts, news, prefill_chunk=8)
        unchunked, cb_u = _serve(eng, prompts, news, prefill_chunk=1)
        assert chunked == unchunked
        for p, n, got in zip(prompts, news, chunked):
            ref = eng.generate(p[None, :], max_new_tokens=n)[0, :n]
            assert got == ref.tolist()
        # the whole point: fewer steps to the same tokens
        assert cb_c._step_count < cb_u._step_count
        # no block leaks either way
        assert cb_c.allocator.num_free == \
            cb_c.allocator.num_blocks - cb_c.allocator.reserved

    def test_budget_smaller_than_chunk(self):
        # budget 2 < chunk 8: prompts advance at most 2 tokens/step but
        # the generations stay token-exact
        eng, V = _tiny_engine()
        prompts, news = self._workload(V)
        got, cb = _serve(eng, prompts, news, prefill_chunk=8,
                         token_budget=2)
        for p, n, g in zip(prompts, news, got):
            ref = eng.generate(p[None, :], max_new_tokens=n)[0, :n]
            assert g == ref.tolist()

    def test_prompt_ends_mid_chunk(self):
        # prompt 11 with chunk 4 -> spans 4, 4, 3: the last (partial)
        # chunk must emit the first token, exactly the dense engine's
        eng, V = _tiny_engine()
        rng = np.random.default_rng(9)
        p = rng.integers(1, V, 11).astype(np.int32)
        got, cb = _serve(eng, [p], [3], prefill_chunk=4, max_batch=1)
        ref = eng.generate(p[None, :], max_new_tokens=3)[0, :3]
        assert got[0] == ref.tolist()
        # ceil(11/4)=3 prefill steps + 2 decode steps (first token rides
        # the last prefill step) + 1 drain tick
        assert cb._step_count <= 6

    def test_all_decode_step_under_tiny_budget(self):
        # 1-token prompts put both slots in decode phase immediately;
        # budget 1 < 2 decode slots: decodes are mandatory, both advance
        # every step and finish
        eng, V = _tiny_engine()
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, V, 1).astype(np.int32)
                   for _ in range(2)]
        got, cb = _serve(eng, prompts, [4, 4], prefill_chunk=8,
                         token_budget=1)
        for p, g in zip(prompts, got):
            ref = eng.generate(p[None, :], max_new_tokens=4)[0, :4]
            assert g == ref.tolist()

    def test_steps_to_first_token_drop(self):
        # a 16-token prompt: unchunked pays 16 steps before the first
        # token, chunk=8 pays ceil(16/8)=2
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(13)
        p = rng.integers(1, V, 16).astype(np.int32)

        def steps_to_first(chunk):
            cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                          max_batch=1,
                                          prefill_chunk=chunk)
            req = GenerationRequest(p, 2)
            cb.submit(req)
            # the compiled step that sampled the first token, by the
            # label `on_token` carries (steps count from 0 as they are
            # dispatched); the token itself is committed one `step()`
            # call later, the engine looks one step ahead
            first, calls = [], 0
            cb.on_token = lambda rid, toks, step: first.append(step)
            while not first:
                cb.step()
                calls += 1
                assert calls < 64
            assert calls == first[0] + 2 and len(req.generated) == 1
            return first[0] + 1

        assert steps_to_first(1) == 16
        assert steps_to_first(8) == 2

    def test_recompile_counter_flat_after_warmup_with_chunking(self):
        # same workload twice through one engine: run 2 must replay run
        # 1's (work-list length, chunk width) pairs exactly — zero new
        # compile keys
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        prompts, news = self._workload(V, seed=17)
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2, prefill_chunk=8)
        for p, n in zip(prompts, news):
            cb.submit(GenerationRequest(p, n))
        out1 = cb.run()
        warm = set(cb._seen_buckets)
        assert len(warm) >= 1
        assert cb._step_count > len(warm)   # buckets were REUSED
        reqs2 = [GenerationRequest(p.copy(), n)
                 for p, n in zip(prompts, news)]
        for r in reqs2:
            cb.submit(r)
        out2 = cb.run()
        assert cb._seen_buckets == warm, \
            "chunked admission compiled a fresh (work, chunk) bucket"
        assert sorted(len(out2[r.request_id]) for r in reqs2) == \
            sorted(news)

    def test_mixed_prefill_decode_step_matches_reference_engine_state(self):
        # drive the engine to a genuinely mixed step (slot 0 deep in
        # decode, slot 1 mid-prompt) and check the scheduler's own work
        # list against the reference kernel on the engine's live cache
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(19)
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2, prefill_chunk=4)
        cb.submit(GenerationRequest(rng.integers(1, V, 2), 6))
        cb.submit(GenerationRequest(rng.integers(1, V, 11), 2))
        cb.step()   # admit both; slot 0 finishes its prompt, slot 1 mid
        assert cb.slots[0].progress == 2 and cb.slots[1].progress == 4
        q_lens, drafts = cb._schedule_tokens([0, 1])
        assert q_lens.tolist() == [1, 4]    # decode + prompt chunk
        assert drafts == {}                 # speculation off by default
        attn = (cb.lens + q_lens).astype(np.int32)
        work = pa.build_ragged_work(cb.tables, attn, cb.block_size,
                                    cb._pack, q_lens=q_lens)
        c = int(max(q_lens))
        rng2 = np.random.default_rng(23)
        q = rng2.standard_normal(
            (2, c, eng.num_heads, eng.head_dim)).astype(np.float32)
        layer_cache = np.asarray(cb.caches[0])
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(layer_cache),
            jnp.asarray(cb.tables),
            jnp.asarray(attn), work=work,
            q_lens=jnp.asarray(q_lens, jnp.int32))
        ref = pa.ragged_paged_attention_reference(
            q, layer_cache[0], layer_cache[1], cb.tables, attn,
            pack=cb._pack, q_lens=q_lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestRequestIds:
    def test_duplicate_id_rejected_constant_time(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2)
        cb.submit(GenerationRequest([1, 2], 2, request_id="dup"))
        with pytest.raises(ValueError, match="duplicate"):
            cb.submit(GenerationRequest([3], 1, request_id="dup"))
        out = cb.run()
        assert list(out) == ["dup"]
        # retired ids stay reserved (finished results would collide)
        with pytest.raises(ValueError, match="duplicate"):
            cb.submit(GenerationRequest([4], 1, request_id="dup"))

    def test_user_int_id_reserves_auto_counter(self):
        from paddle_tpu.incubate.nn import GenerationRequest
        base = GenerationRequest([1], 1).request_id
        user = GenerationRequest([1], 1, request_id=base + 50)
        nxt = GenerationRequest([1], 1)
        assert nxt.request_id == user.request_id + 1   # no silent collision

    def test_run_finished_complete_when_queue_drains(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2, prefill_chunk=4)
        reqs = [GenerationRequest([1 + i, 2 + i], 2) for i in range(3)]
        for r in reqs:
            cb.submit(r)
        out = cb.run()
        assert set(out) == {r.request_id for r in reqs}
        assert all(len(v) == 2 for v in out.values())
        assert cb.num_active == 0 and not cb.queue
        assert cb._ids == set()
