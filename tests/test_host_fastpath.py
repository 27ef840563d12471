"""Host-step fast path (ISSUE 20): incremental work lists, in-place
step inputs.

Four claims, all host-deterministic under CPU interpret mode:
  * the incremental RaggedWorkBuilder is BIT-EXACT vs the from-scratch
    `build_ragged_work` under seeded random churn (admits, finishes,
    block growth, bucket switches, empty steps),
  * dirty accounting is EXACT: a steady decode reuses every cached
    segment, one dirtied slot rebuilds exactly that slot's segments,
    and a missed dirty mark is CAUGHT by the debug cross-check,
  * with `host_debug_check=True` the engine serves, in every scheduler
    mode, token for token what `engine.generate()` gives, and the
    per-step cross-check against `build_ragged_work` never fires,
  * nothing leaks: KV blocks return to baseline and the builder's
    buffer pool stays bounded by the bucket set it has seen.
"""
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa

from tests.test_chunked_prefill import _serve, _tiny_engine


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _assert_same_work(got, want):
    g_arrs, g_real, g_total, g_pack = got
    w_arrs, w_real, w_total, w_pack = want
    assert (g_real, g_total, g_pack) == (w_real, w_total, w_pack)
    for ga, wa in zip(g_arrs, w_arrs):
        np.testing.assert_array_equal(ga, wa)


class TestBuilderEquivalence:
    def _rand_state(self, rng, b, max_nb, nblk):
        tables = rng.integers(0, nblk, (b, max_nb)).astype(np.int32)
        lens = rng.integers(0, max_nb * 8 + 4, b).astype(np.int32)
        q = rng.integers(0, 4, b).astype(np.int32)
        return tables, lens, q

    @pytest.mark.parametrize("pack", [1, 2, 4])
    def test_seeded_churn_bit_exact(self, pack):
        """200 random steps: every build — incremental or full, any
        bucket, empty included — matches build_ragged_work exactly."""
        rng = np.random.default_rng(0)
        b, max_nb, bs, nblk = 6, 5, 8, 40
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, pack)
        tables, lens, q = self._rand_state(rng, b, max_nb, nblk)
        for step in range(200):
            ev = rng.integers(0, 5)
            if ev == 0:            # admit/finish: slot reset
                s = int(rng.integers(0, b))
                tables[s] = rng.integers(0, nblk, max_nb)
                lens[s] = rng.integers(0, max_nb * 8)
                wb.mark_dirty(s)
            elif ev == 1:          # block churn (grow/COW/rewind)
                s = int(rng.integers(0, b))
                tables[s, rng.integers(0, max_nb)] = \
                    rng.integers(0, nblk)
                wb.mark_dirty(s)
            elif ev == 2:          # decode advance, seglens may move
                lens = np.minimum(lens + q, max_nb * 8 + 4)
            # new q mix every step (q_lens always change per step)
            q = rng.integers(0, 4, b).astype(np.int32)
            if ev == 3:
                q[:] = 0           # empty step: t_real == 0 path
            attn = (lens + q).astype(np.int32)
            got = wb.build(tables, attn, q)
            want = pa.build_ragged_work(
                tables, attn, bs, pack, bucket_to=pa.next_pow2,
                q_lens=q)
            _assert_same_work(got, want)

    def test_over_capacity_lens_clamped_like_rebuild(self):
        rng = np.random.default_rng(1)
        b, max_nb, bs = 4, 3, 8
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, 2)
        tables = rng.integers(0, 9, (b, max_nb)).astype(np.int32)
        q = np.ones(b, np.int32)
        attn = np.asarray([100, 3, max_nb * bs, 1], np.int32)
        _assert_same_work(
            wb.build(tables, attn, q),
            pa.build_ragged_work(tables, attn, bs, 2,
                                 bucket_to=pa.next_pow2, q_lens=q))


class TestDirtyAccounting:
    def test_steady_decode_reuses_everything(self):
        """After the first build, pure decode (same seglens, clean
        slots) reuses every segment and assembles incrementally."""
        rng = np.random.default_rng(2)
        b, max_nb, bs = 4, 4, 8
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, 2)
        tables = rng.integers(0, 20, (b, max_nb)).astype(np.int32)
        lens = np.asarray([9, 10, 11, 12], np.int32)
        q = np.ones(b, np.int32)
        wb.build(tables, lens + q, q)
        base = (wb.segments_reused, wb.segments_rebuilt,
                wb.assemblies_incremental, wb.assemblies_full)
        for _ in range(3):          # attn stays inside block 2
            lens = lens + 1
            got = wb.build(tables, lens + q, q)
            _assert_same_work(got, pa.build_ragged_work(
                tables, lens + q, bs, 2, bucket_to=pa.next_pow2,
                q_lens=q))
        assert wb.segments_rebuilt == base[1]
        assert wb.assemblies_full == base[3]
        assert wb.assemblies_incremental == base[2] + 3
        assert wb.segments_reused == base[0] + 3 * b  # every slot, every step

    def test_one_dirty_slot_rebuilds_exactly_its_segments(self):
        rng = np.random.default_rng(3)
        b, max_nb, bs = 4, 4, 8
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, 2)
        tables = rng.integers(0, 20, (b, max_nb)).astype(np.int32)
        lens = np.asarray([9, 10, 11, 12], np.int32)
        q = np.ones(b, np.int32)
        wb.build(tables, lens + q, q)
        tables[2, 0] = 19           # COW retarget, same seglen
        wb.mark_dirty(2)
        r0, rb0 = wb.segments_reused, wb.segments_rebuilt
        got = wb.build(tables, lens + q, q)
        _assert_same_work(got, pa.build_ragged_work(
            tables, lens + q, bs, 2, bucket_to=pa.next_pow2,
            q_lens=q))
        assert wb.segments_rebuilt - rb0 == 1      # slot 2, nobody else
        assert wb.segments_reused - r0 == b - 1    # everyone else

    def test_missed_dirty_mark_goes_stale_and_debug_check_catches(self):
        """The hazard the engine's `host_debug_check` exists for: a
        table write without mark_dirty serves a STALE segment on the
        incremental path — build_ragged_work disagrees."""
        rng = np.random.default_rng(4)
        b, max_nb, bs = 4, 4, 8
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, 2)
        tables = rng.integers(0, 18, (b, max_nb)).astype(np.int32)
        lens = np.asarray([9, 10, 11, 12], np.int32)
        q = np.ones(b, np.int32)
        wb.build(tables, lens + q, q)
        tables[1, 0] = 19           # forgot wb.mark_dirty(1)
        got = wb.build(tables, lens + q, q)
        want = pa.build_ragged_work(tables, lens + q, bs, 2,
                                    bucket_to=pa.next_pow2, q_lens=q)
        with pytest.raises(AssertionError):
            _assert_same_work(got, want)


_MODE_KW = {
    "plain": {},
    "chunked": {"prefill_chunk": 4},
    "budgeted": {"prefill_chunk": 4, "token_budget": 6},
    "spec": {"prefill_chunk": 8, "spec_k": 4},
    "prefix": {"prefill_chunk": 8, "prefix_cache": True,
               "num_blocks": 16},
}


def _mode_workload(mode, V):
    rng = np.random.default_rng(5)
    if mode == "spec":
        pat = [7, 23, 41, 11]
        return [np.asarray(pat * 4, np.int32),
                np.asarray(pat * 2, np.int32)], [8, 8]
    if mode == "prefix":
        pre = rng.integers(1, V, 16).astype(np.int32)
        return [np.concatenate([pre,
                                rng.integers(1, V, 2).astype(np.int32)])
                for _ in range(2)], [4, 4]
    return [rng.integers(1, V, p).astype(np.int32)
            for p in (5, 11)], [4, 3]


class TestEngineTokenExactness:
    @pytest.mark.parametrize("mode", sorted(_MODE_KW))
    def test_served_tokens_match_generate_under_debug_check(self, mode):
        """Every step's incremental work list is held, array for array,
        to a from-scratch `build_ragged_work` (`_check_host_state`
        raises on the first difference), and the tokens served are
        `engine.generate()`'s."""
        eng, V = _tiny_engine()
        prompts, new = _mode_workload(mode, V)
        toks, cb = _serve(eng, prompts, new, **_MODE_KW[mode],
                          host_debug_check=True)
        assert cb._step_count > 0
        for p, n, got in zip(prompts, new, toks):
            ref = eng.generate(p[None, :], max_new_tokens=n)[0, :n]
            assert list(got) == ref.tolist()
        hs = cb.host_stats()
        assert hs["segments_reused"] + hs["segments_rebuilt"] > 0
        assert hs["assemblies_full"] + hs["assemblies_incremental"] \
            == cb._step_count
        # KV leak check: every allocatable block back, either free
        # or parked in the (reclaimable) prefix pool
        assert (cb.allocator.num_free
                + getattr(cb.allocator, "num_pooled", 0)
                == cb.allocator.num_blocks - cb.allocator.reserved)

    def test_debug_check_fires_when_a_table_write_is_not_marked(self):
        """The engine-level half of the hazard above. Four requests of
        one shape through two slots: the second pair takes the slots'
        rows over with other blocks at the same segment lengths, which
        only the dirty mark can tell the builder. With `_dirty_slot`
        silenced `host_debug_check` fails that step."""
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(5)
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2, host_debug_check=True)
        cb._dirty_slot = lambda i: None
        for _ in range(4):
            cb.submit(GenerationRequest(
                rng.integers(1, V, 7).astype(np.int32), 2))
        with pytest.raises(AssertionError, match="_dirty_slot"):
            cb.run()

    def test_debug_check_holds_through_preempt_and_cancel(self):
        """The two schedules `_serve` cannot drive: a late priority-0
        arrival evicts its way into a tight pool, and a request is
        cancelled mid-decode. Every table write on those paths is
        marked, or the cross-check would fail the step."""
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(11)
        cb = ContinuousBatchingEngine(eng, num_blocks=7, block_size=8,
                                      max_batch=2, host_debug_check=True)
        low = [GenerationRequest(rng.integers(1, V, 14).astype(np.int32),
                                 8, priority=2) for _ in range(2)]
        high = GenerationRequest(rng.integers(1, V, 12).astype(np.int32),
                                 6, priority=0)
        for r in low:
            cb.submit(r)
        for _ in range(4):
            cb.step()
        cb.submit(high)
        out = cb.run()
        assert sum(r.preemptions for r in low) >= 1
        for r in low + [high]:
            ref = eng.generate(np.asarray(r.prompt)[None, :],
                               max_new_tokens=r.max_new_tokens)
            assert list(out[r.request_id]) == \
                ref[0, :r.max_new_tokens].tolist()

        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2, host_debug_check=True)
        reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32),
                                  8) for p in (6, 9, 4)]
        for r in reqs:
            cb.submit(r)
        for _ in range(4):
            cb.step()
        cb.cancel(reqs[1].request_id)
        cb.run()
        assert [r.status for r in reqs] == ["finished", "cancelled",
                                            "finished"]
        assert cb.allocator.num_free == (cb.allocator.num_blocks
                                         - cb.allocator.reserved)

    def test_engine_steady_decode_reuses_every_segment(self):
        """Three decode-only slots sized so that no block boundary is
        crossed: after the first decode step every build reuses every
        segment and assembles incrementally."""
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(17)
        cb = ContinuousBatchingEngine(eng, num_blocks=24, block_size=8,
                                      max_batch=4, host_debug_check=True)
        for _ in range(3):
            cb.submit(GenerationRequest(
                rng.integers(1, V, 9).astype(np.int32), 6))
        snaps = []
        while cb.queue or cb.num_active:
            cb.step()
            snaps.append(cb.host_stats())
        steady = [
            (b["segments_rebuilt"] - a["segments_rebuilt"],
             b["assemblies_full"] - a["assemblies_full"],
             b["segments_reused"] - a["segments_reused"],
             b["assemblies_incremental"] - a["assemblies_incremental"])
            for a, b in zip(snaps[1:], snaps[2:])]
        # (the run's last step only retires: it builds nothing)
        assert steady[:4] == [(0, 0, 3, 1)] * 4
        assert set(steady[4:]) <= {(0, 0, 0, 0)}

    def test_phases_reported(self):
        eng, V = _tiny_engine()
        prompts, new = _mode_workload("plain", V)
        _, cb = _serve(eng, prompts, new)
        phases = cb.host_stats()["phases"]
        assert set(phases) == {"schedule", "build", "dispatch",
                               "fetch", "commit"}
        rid = next(iter(cb.finished))
        assert cb.explain(rid)["host_phases"] == phases


class TestNoLeaks:
    def test_builder_buffer_pool_bounded_by_bucket_set(self):
        rng = np.random.default_rng(6)
        b, max_nb, bs = 6, 5, 8
        wb = pa.RaggedWorkBuilder(b, max_nb, bs, 2)
        buckets = set()
        tables = rng.integers(0, 40, (b, max_nb)).astype(np.int32)
        for _ in range(300):
            lens = rng.integers(0, max_nb * 8, b).astype(np.int32)
            q = rng.integers(0, 3, b).astype(np.int32)
            wb.mark_all_dirty()
            _, t_real, t_total, _ = wb.build(
                tables, (lens + q).astype(np.int32), q)
            if t_real:
                buckets.add(t_total)
        assert set(wb._bufs) <= buckets
        assert len(wb._bufs) <= len(buckets)

    def test_engine_kv_gauge_returns_to_baseline_under_cancel(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(7)
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2)
        reqs = [GenerationRequest(
            rng.integers(1, V, p).astype(np.int32), 8)
            for p in (6, 9)]
        for r in reqs:
            cb.submit(r)
        for _ in range(4):
            cb.step()
        cb.cancel(reqs[1].request_id)
        cb.run()
        assert cb.allocator.num_free == (cb.allocator.num_blocks
                                         - cb.allocator.reserved)
        assert reqs[1].status == "cancelled"
