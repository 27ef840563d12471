"""Speculative multi-token decode on the ragged work list (interpret
mode on CPU).

Parity ladder, one rung up from test_chunked_prefill.py:
  * the prompt-lookup proposer is pure host math with pinned semantics,
  * the paged-KV rewind (`truncate_paged_kv`) must leave a
    speculated-then-rewound cache BIT-IDENTICAL to a never-speculated
    one — mid-block, across block boundaries, and through a
    rewind-then-append round trip,
  * the speculative engine's generations must match the non-speculative
    engine AND the dense `generate()` token for token (greedy
    verification is exact by construction; the tests make it exact in
    fact),
  * speculation must pay: fewer compiled steps for the same tokens on a
    repetitive workload, with the bucketed compile keys FLAT after
    warmup (the zero-recompiles serving contract),
  * and the TPOT-SLO chunk controller must actually shrink the chunk.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa

from test_chunked_prefill import _tiny_engine


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


class TestPromptLookup:
    def _p(self, toks, k, ngram=2):
        from paddle_tpu.incubate.nn import propose_draft_tokens
        return propose_draft_tokens(toks, k, ngram)

    def test_bigram_continuation(self):
        # suffix [1, 2] matched at position 0 -> continuation [3, 1, 2]
        assert self._p([1, 2, 3, 1, 2], 4) == [3, 1, 2]

    def test_most_recent_match_wins(self):
        # [1, 2] occurs twice; the later one (followed by 9) wins
        assert self._p([1, 2, 7, 1, 2, 9, 1, 2], 2) == [9, 1]

    def test_unigram_fallback(self):
        # no earlier bigram ends before the suffix; unigram 5 matches at
        # position 0 and the continuation may run into the suffix itself
        assert self._p([5, 6, 5], 4) == [6, 5]

    def test_no_match_empty(self):
        assert self._p([5, 6, 7, 8], 4) == []

    def test_caps_at_max_k(self):
        assert self._p([1, 2, 3, 4, 5, 1, 2], 2) == [3, 4]

    def test_k_zero_empty(self):
        assert self._p([1, 2, 1, 2], 0) == []

    def test_short_context(self):
        assert self._p([3], 4) == []
        assert self._p([3, 3], 4) == [3]


def _mk_cache(seed, kvh=2, nb=13, bs=4, d=8):
    rng = np.random.default_rng(seed)
    kc = np.zeros((kvh, nb, bs, d), np.float32)
    vc = np.zeros((kvh, nb, bs, d), np.float32)
    return kc, vc, rng


class TestKVRewind:
    """`truncate_paged_kv` unit contract: zero exactly the rejected
    span, drop everything out of range. The cache is the stacked
    [2, KVH, NB, BS, D] buffer; the cases read its two halves."""

    def _append(self, kc, vc, tables, lens, rows):
        """Append rows [B, C, KVH, D] at positions lens.. (all valid)."""
        c = rows.shape[1]
        counts = np.full(rows.shape[0], c, np.int32)
        return tuple(np.asarray(pa.append_paged_kv_chunk(
            jnp.stack([kc, vc]), jnp.asarray(rows),
            jnp.asarray(rows + 0.5), jnp.asarray(tables),
            jnp.asarray(lens, np.int32), jnp.asarray(counts))))

    def _truncate(self, kc, vc, tables, new_lens, old_lens, span):
        return tuple(np.asarray(pa.truncate_paged_kv(
            jnp.stack([kc, vc]), jnp.asarray(tables),
            jnp.asarray(new_lens, np.int32),
            jnp.asarray(old_lens, np.int32), span)))

    def test_rejection_mid_block(self):
        kc, vc, rng = _mk_cache(0)
        tables = np.arange(2 * 3, dtype=np.int32).reshape(2, 3)
        lens = np.asarray([1, 5], np.int32)
        rows = rng.standard_normal((2, 3, 2, 8)).astype(np.float32)
        kc1, vc1 = self._append(kc, vc, tables, lens, rows)
        # rewind row 0 from 4 back to 2 (both inside block 0, bs=4)
        kc2, vc2 = self._truncate(kc1, vc1, tables, [2, 8], [4, 8], 4)
        exp_k, exp_v = kc1.copy(), vc1.copy()
        for p in (2, 3):
            exp_k[:, tables[0, p // 4], p % 4] = 0.0
            exp_v[:, tables[0, p // 4], p % 4] = 0.0
        np.testing.assert_array_equal(kc2, exp_k)
        np.testing.assert_array_equal(vc2, exp_v)

    def test_rejection_across_block_boundary(self):
        kc, vc, rng = _mk_cache(1)
        tables = np.arange(3, dtype=np.int32).reshape(1, 3)
        lens = np.asarray([2], np.int32)
        rows = rng.standard_normal((1, 5, 2, 8)).astype(np.float32)
        kc1, vc1 = self._append(kc, vc, tables, lens, rows)  # fills 2..6
        # rewind 7 -> 3: positions 3..6 span blocks 0 and 1
        kc2, _ = self._truncate(kc1, vc1, tables, [3], [7], 4)
        exp = kc1.copy()
        for p in range(3, 7):
            exp[:, tables[0, p // 4], p % 4] = 0.0
        np.testing.assert_array_equal(kc2, exp)
        # block 1 (positions 4..7) is now entirely zero again
        np.testing.assert_array_equal(kc2[:, tables[0, 1]], 0.0)

    def test_noop_rows_and_capacity_drop(self):
        kc, vc, rng = _mk_cache(2)
        tables = np.arange(2 * 3, dtype=np.int32).reshape(2, 3)
        lens = np.asarray([4, 10], np.int32)
        rows = rng.standard_normal((2, 2, 2, 8)).astype(np.float32)
        kc1, vc1 = self._append(kc, vc, tables, lens, rows)
        # row 0: new == old (no-op); row 1: old_lens claims past the
        # 12-token table capacity — the over-capacity positions DROP
        kc2, _ = self._truncate(kc1, vc1, tables, [6, 11], [6, 14], 4)
        exp = kc1.copy()
        exp[:, tables[1, 2], 3] = 0.0          # position 11 zeroed
        np.testing.assert_array_equal(kc2, exp)

    def test_rewind_then_append_round_trip_bit_exact(self):
        """Speculate 4, reject 2, append the true tokens: the cache must
        equal one that NEVER speculated, bit for bit."""
        kc, vc, rng = _mk_cache(3)
        tables = np.arange(3, dtype=np.int32).reshape(1, 3)
        true_rows = rng.standard_normal((1, 6, 2, 8)).astype(np.float32)
        junk = rng.standard_normal((1, 2, 2, 8)).astype(np.float32)

        # speculated path: true rows 0,1 land at 0..1; the speculative
        # step appends [true2, true3, junk, junk] at 2..5; verification
        # accepts 2, rewind 6 -> 4; the next step appends true rows 4,5
        spec = np.concatenate([true_rows[:, 2:4], junk], axis=1)
        kA, vA = self._append(kc, vc, tables, np.asarray([0], np.int32),
                              true_rows[:, :2])
        kA, vA = self._append(kA, vA, tables, np.asarray([2], np.int32),
                              spec)
        kA, vA = self._truncate(kA, vA, tables, [4], [6], 4)
        kA, vA = self._append(kA, vA, tables, np.asarray([4], np.int32),
                              true_rows[:, 4:6])

        # never-speculated path: the same 6 true rows, appended straight
        kB, vB = self._append(kc, vc, tables, np.asarray([0], np.int32),
                              true_rows)
        np.testing.assert_array_equal(kA, kB)
        np.testing.assert_array_equal(vA, vB)


def _serve(eng, prompts, news, **kw):
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    kw.setdefault("num_blocks", 12)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 2)
    cb = ContinuousBatchingEngine(eng, **kw)
    reqs = [GenerationRequest(np.asarray(p, np.int32).copy(), n)
            for p, n in zip(prompts, news)]
    for r in reqs:
        cb.submit(r)
    out = cb.run()
    return [out[r.request_id] for r in reqs], cb, reqs


# a repetitive prompt (the prompt-lookup sweet spot) + an irregular one
# (drafts fire rarely / get rejected — the rewind path)
PATTERN = [7, 23, 41, 11]


def _workload(V, seed=3):
    rng = np.random.default_rng(seed)
    return ([np.asarray(PATTERN * 4, np.int32),
             rng.integers(1, V, 5).astype(np.int32)], [10, 6])


class TestSpeculativeEngine:
    def test_token_exact_vs_plain_and_generate(self):
        eng, V = _tiny_engine()
        prompts, news = _workload(V)
        spec, cb_s, reqs = _serve(eng, prompts, news, prefill_chunk=8,
                                  spec_k=4)
        plain, cb_p, _ = _serve(eng, prompts, news, prefill_chunk=8)
        assert spec == plain
        for p, n, got in zip(prompts, news, spec):
            ref = eng.generate(p[None, :], max_new_tokens=n)[0, :n]
            assert got == ref.tolist()
        # the whole point: fewer compiled steps for the same tokens
        assert cb_s._step_count < cb_p._step_count
        # drafts really flowed, and some were accepted AND some rejected
        # (otherwise the rewind path never ran in this test)
        drafted = sum(r.spec_drafted for r in reqs)
        accepted = sum(r.spec_accepted for r in reqs)
        assert drafted > 0 and 0 < accepted < drafted
        # no block leaks through accept/reject churn
        assert cb_s.allocator.num_free == \
            cb_s.allocator.num_blocks - cb_s.allocator.reserved

    def test_token_exact_under_budget(self):
        # budget 3: drafts are filler AFTER mandatory decode-1 and
        # chunks — sometimes granted 0..2 tokens — and stay token-exact
        eng, V = _tiny_engine()
        prompts, news = _workload(V)
        spec, _, _ = _serve(eng, prompts, news, prefill_chunk=8,
                            spec_k=4, token_budget=3)
        plain, _, _ = _serve(eng, prompts, news, prefill_chunk=8)
        assert spec == plain

    def test_acceptance_never_overshoots_max_new(self):
        eng, V = _tiny_engine()
        # a 2-token repetitive prompt locks greedy into a loop fast;
        # max_new 3 with spec_k 4 forces the rem_gen-1 draft cap
        got, cb, reqs = _serve(eng, [np.asarray(PATTERN * 4, np.int32)],
                               [3], prefill_chunk=8, spec_k=4,
                               max_batch=1)
        assert len(got[0]) == 3
        ref = eng.generate(np.asarray(PATTERN * 4, np.int32)[None, :],
                           max_new_tokens=3)[0, :3]
        assert got[0] == ref.tolist()

    def test_recompile_counter_flat_after_warmup_with_spec(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        prompts, news = _workload(V, seed=17)
        cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                      max_batch=2, prefill_chunk=8,
                                      spec_k=4)
        for p, n in zip(prompts, news):
            cb.submit(GenerationRequest(p.copy(), n))
        cb.run()
        warm = set(cb._seen_buckets)
        assert len(warm) >= 2   # spec really widened some slabs
        reqs2 = [GenerationRequest(p.copy(), n)
                 for p, n in zip(prompts, news)]
        for r in reqs2:
            cb.submit(r)
        out2 = cb.run()
        assert cb._seen_buckets == warm, \
            "speculation compiled a fresh (work, chunk) bucket on replay"
        assert sorted(len(out2[r.request_id]) for r in reqs2) == \
            sorted(news)

    def test_spec_metrics_recorded(self):
        from paddle_tpu import observability as obs
        reg = obs.get_registry()

        def val(name):
            m = reg.get(name)
            return m.value if m is not None else 0.0

        d0, a0 = val("spec_draft_tokens_total"), \
            val("spec_accepted_tokens_total")
        t0 = val("serve_tokens_total")
        eng, V = _tiny_engine()
        prompts, news = _workload(V)
        _, cb, reqs = _serve(eng, prompts, news, prefill_chunk=8,
                             spec_k=4)
        drafted = sum(r.spec_drafted for r in reqs)
        accepted = sum(r.spec_accepted for r in reqs)
        assert drafted > 0
        assert val("spec_draft_tokens_total") - d0 == drafted
        assert val("spec_accepted_tokens_total") - a0 == accepted
        h = reg.get("serve_spec_accept_len")
        assert h is not None and h.count > 0
        assert val("serve_tokens_total") - t0 == sum(
            len(r.generated) for r in reqs)

    def test_spec_requires_greedy(self):
        from paddle_tpu.incubate.nn import ContinuousBatchingEngine
        eng, V = _tiny_engine()
        with pytest.raises(ValueError, match="greedy"):
            ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                     spec_k=4, temperature=0.7)


class TestSLOChunkController:
    def test_chunk_shrinks_under_slo_pressure_and_stays_exact(self):
        # an SLO no interpret-mode step can meet: every window trips the
        # controller, so the chunk walks 8 -> 4 -> 2 and floors there
        eng, V = _tiny_engine()
        prompts, news = _workload(V)
        got, cb, _ = _serve(eng, prompts, [12, 8], prefill_chunk=8,
                            tpot_slo=1e-9, min_prefill_chunk=2)
        assert cb.prefill_chunk == 2
        for p, n, g in zip(prompts, [12, 8], got):
            ref = eng.generate(np.asarray(p)[None, :],
                               max_new_tokens=n)[0, :n]
            assert g == ref.tolist()

    def test_chunk_stable_under_loose_slo(self):
        eng, V = _tiny_engine()
        prompts, news = _workload(V)
        _, cb, _ = _serve(eng, prompts, news, prefill_chunk=8,
                          tpot_slo=3600.0, min_prefill_chunk=2)
        assert cb.prefill_chunk == 8
