"""Automatic prefix caching: content-addressed, refcounted,
copy-on-write sharing of paged-KV blocks across requests (interpret
mode on CPU).

Parity ladder, one rung up from test_speculative_decode.py:
  * `BlockAllocator` invariants hold BEFORE sharing enters the picture
    (freeing an unallocated block raises instead of corrupting the free
    list, `num_used` is structurally non-negative, `high_water` counts
    physical blocks),
  * sharing bookkeeping is exact: refcounts, the hash->block index,
    LRU pool parking / resurrection / eviction, first-writer-wins
    registration,
  * the engine stays TOKEN-EXACT with sharing on — vs sharing off, vs
    `engine.generate()`, with speculative decode layered on top, and
    through conversation resume off the reuse pool,
  * a write into a block other requests still read copies first
    (`copy_paged_kv` + `_cow_block`): the shared original must be
    BIT-IDENTICAL after the writer diverges,
  * and churn leaks nothing: after every request retires the allocator
    holds zero refcounts and the compile buckets stay flat on replay.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.incubate.nn import (BlockAllocator,
                                    ContinuousBatchingEngine,
                                    GenerationRequest)
from paddle_tpu.incubate.nn.continuous_batching import block_key

from test_chunked_prefill import _tiny_engine


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


class TestBlockKey:
    def test_same_tokens_same_parent_equal(self):
        assert block_key(None, [1, 2, 3]) == block_key(None, (1, 2, 3))

    def test_chain_makes_position_implicit(self):
        # identical token window at a different prefix depth: different
        # key (rope positions and attention context differ)
        a = block_key(block_key(None, [9, 9]), [1, 2])
        b = block_key(block_key(None, [8, 8]), [1, 2])
        root = block_key(None, [1, 2])
        assert a != b and a != root and b != root

    def test_numpy_tokens_normalize(self):
        assert block_key(None, np.asarray([1, 2], np.int32)) == \
            block_key(None, [1, 2])


class TestAllocatorInvariants:
    """The hardening satellite: these hold with sharing never used."""

    def test_free_never_allocated_raises(self):
        # every in-pool block starts on the free list, so "unallocated"
        # surfaces as a free-list double-free from a fresh allocator
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="unallocated"):
            a.free([2])

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        b = a.alloc()
        a.free([b])
        with pytest.raises(ValueError, match="free list"):
            a.free([b])

    def test_free_out_of_pool_raises(self):
        a = BlockAllocator(4, reserved=1)
        with pytest.raises(ValueError, match="out-of-pool"):
            a.free([0])        # the reserved parking block
        with pytest.raises(ValueError, match="out-of-pool"):
            a.free([4])

    def test_free_pooled_raises(self):
        a = BlockAllocator(4)
        b = a.alloc()
        a.register(b, block_key(None, [1]))
        a.free([b])            # parks in the reuse pool (registered)
        with pytest.raises(ValueError, match="reuse pool"):
            a.free([b])

    def test_num_used_non_negative_and_physical(self):
        a = BlockAllocator(6)
        assert a.num_used == 0
        b = a.alloc()
        a.share(b)
        a.share(b)
        # one physical block, three holders
        assert a.num_used == 1 and a.refcount(b) == 3
        a.free([b, b, b])
        assert a.num_used == 0 and a.refcount(b) == 0

    def test_high_water_counts_physical_not_logical(self):
        a = BlockAllocator(8)
        b1, b2 = a.alloc(), a.alloc()
        for _ in range(5):
            a.share(b1)
        assert a.high_water == 2       # 7 logical holders, 2 physical

    def test_exhaustion_still_raises(self):
        a = BlockAllocator(3)          # 2 allocatable
        a.alloc(), a.alloc()
        with pytest.raises(RuntimeError, match="out of cache blocks"):
            a.alloc()

    def test_share_unallocated_raises(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="sharing unallocated"):
            a.share(2)

    def test_register_unallocated_raises(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="registering unallocated"):
            a.register(2, block_key(None, [1]))


class TestAllocatorSharing:
    def test_register_lookup_acquire(self):
        a = BlockAllocator(6)
        b = a.alloc()
        k = block_key(None, [1, 2])
        assert a.register(b, k) is True
        assert a.lookup(k) == b
        assert a.acquire(k) == b and a.refcount(b) == 2
        assert a.acquire(block_key(None, [9])) is None

    def test_register_first_writer_wins(self):
        a = BlockAllocator(6)
        b1, b2 = a.alloc(), a.alloc()
        k = block_key(None, [1])
        assert a.register(b1, k) is True
        assert a.register(b2, k) is False          # key taken
        assert a.register(b1, block_key(None, [2])) is False  # block taken
        assert a.lookup(k) == b1

    def test_registered_free_parks_in_pool(self):
        a = BlockAllocator(6)
        b = a.alloc()
        k = block_key(None, [3])
        a.register(b, k)
        free0 = a.num_free
        a.free([b])
        assert a.num_pooled == 1 and a.num_free == free0
        assert a.num_used == 0
        assert a.lookup(k) == b                    # still indexed

    def test_acquire_resurrects_from_pool(self):
        a = BlockAllocator(6)
        b = a.alloc()
        k = block_key(None, [3])
        a.register(b, k)
        a.free([b])
        hw = a.high_water
        assert a.acquire(k) == b
        assert a.refcount(b) == 1 and a.num_pooled == 0
        assert a.high_water >= hw

    def test_lru_eviction_oldest_first(self):
        a = BlockAllocator(4)                      # 3 allocatable
        keys = [block_key(None, [i]) for i in range(3)]
        blocks = [a.alloc() for _ in range(3)]
        for b, k in zip(blocks, keys):
            a.register(b, k)
        a.free([blocks[0]])                        # oldest in the pool
        a.free([blocks[1]])
        a.free([blocks[2]])
        assert a.num_free == 0 and a.num_pooled == 3
        got = a.alloc()                            # reclaims LRU-oldest
        assert got == blocks[0] and a.evictions == 1
        assert a.lookup(keys[0]) is None           # evicted from index
        assert a.lookup(keys[1]) == blocks[1]      # newer survivors stay
        # the reclaimed block is a fresh private block now
        assert a.refcount(got) == 1

    def test_pool_refreshes_on_reuse(self):
        # park A, park B, resurrect+repark A: B is now LRU-oldest
        a = BlockAllocator(4)
        ka, kb = block_key(None, [1]), block_key(None, [2])
        ba, bb = a.alloc(), a.alloc()
        a.register(ba, ka), a.register(bb, kb)
        a.free([ba]), a.free([bb])
        assert a.acquire(ka) == ba
        a.free([ba])
        a.alloc()                                  # uses the free block
        assert a.alloc() == bb and a.lookup(kb) is None
        assert a.lookup(ka) == ba

    def test_num_available_spans_free_and_pool(self):
        a = BlockAllocator(5)
        b = a.alloc()
        a.register(b, block_key(None, [1]))
        a.free([b])
        assert a.num_available == a.num_free + a.num_pooled == 4


class TestIndexDeltaLog:
    """The bounded delta log behind the router's incremental summary
    refresh: epoch bumps track EXACTLY the two index mutation sites
    (register add, LRU-reclaim remove), replay reconstructs
    ``index_keys()`` bit-exact, and an aged-out epoch returns None
    instead of a silently-truncated delta."""

    def _replay(self, base, ops):
        cur = set(base)
        for added, key in ops:
            (cur.add if added else cur.discard)(key)
        return frozenset(cur)

    def test_epoch_bumps_only_on_index_mutation(self):
        a = BlockAllocator(6)
        b = a.alloc()
        assert a.index_epoch == 0                  # alloc: no index op
        k = block_key(None, [1])
        a.register(b, k)
        assert a.index_epoch == 1
        a.register(b, k)                           # no-op repeat
        assert a.index_epoch == 1
        a.free([b])                                # parks, stays indexed
        assert a.index_epoch == 1
        assert a.acquire(k) == b                   # resurrect: no op
        assert a.index_epoch == 1

    def test_delta_replay_matches_index_keys(self):
        a = BlockAllocator(4)                      # 3 allocatable
        e0, base = a.index_epoch, a.index_keys()
        blocks = [a.alloc() for _ in range(3)]
        keys = [block_key(None, [i]) for i in range(3)]
        for b, k in zip(blocks, keys):
            a.register(b, k)
        for b in blocks:
            a.free([b])
        a.alloc()                                  # reclaims, removes keys[0]
        e1, ops = a.index_delta_since(e0)
        assert e1 == a.index_epoch == 4            # 3 adds + 1 remove
        assert self._replay(base, ops) == a.index_keys()
        # empty delta at the current epoch
        assert a.index_delta_since(e1) == (e1, ())

    def test_key_leaving_and_reentering_replays_in_order(self):
        a = BlockAllocator(3)                      # 2 allocatable
        k = block_key(None, [7])
        b1, b2 = a.alloc(), a.alloc()
        a.register(b1, k)
        e0, base = a.index_epoch, a.index_keys()
        a.free([b1])                               # parks b1 under k
        a.free([b2])                               # plain free
        a.alloc()                                  # takes the free block
        got = a.alloc()                            # reclaims b1: k leaves
        assert got == b1 and a.lookup(k) is None
        a.register(b1, k)                          # k re-enters
        e1, ops = a.index_delta_since(e0)
        assert [added for added, _ in ops] == [False, True]
        assert self._replay(base, ops) == a.index_keys() \
            == frozenset({k})

    def test_aged_out_epoch_returns_none(self):
        a = BlockAllocator(4)
        a._index_log = __import__("collections").deque(maxlen=2)
        blocks = [a.alloc() for _ in range(3)]
        for i, b in enumerate(blocks):
            a.register(b, block_key(None, [i]))
        assert a.index_delta_since(0) is None      # 3 ops, log holds 2
        assert a.index_delta_since(1) is not None  # last 2 still covered
        assert a.index_delta_since(a.index_epoch + 1) is None  # future


def _serve(eng, prompts, news, ids=None, cb=None, **kw):
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    if cb is None:
        cb = ContinuousBatchingEngine(eng, **kw)
    reqs = [GenerationRequest(np.asarray(p, np.int32).copy(), n,
                              request_id=None if ids is None
                              else f"{ids}{j}")
            for j, (p, n) in enumerate(zip(prompts, news))]
    for r in reqs:
        cb.submit(r)
    out = cb.run()
    return [out[r.request_id] for r in reqs], cb, reqs


class TestTokenExact:
    def _shared_workload(self, V, n=3, seed=11):
        rng = np.random.default_rng(seed)
        prefix = rng.integers(1, V, 16)            # 2 full blocks of 8
        return [np.concatenate([prefix, rng.integers(1, V, 3 + j)])
                for j in range(n)]

    def test_sharing_on_off_and_generate(self):
        eng, V = _tiny_engine()
        prompts = self._shared_workload(V)
        news = [5] * len(prompts)
        off, _, _ = _serve(eng, prompts, news, ids="tob")
        on, cb, reqs = _serve(eng, prompts, news, ids="ton",
                              prefix_cache=True)
        assert on == off
        for p, o in zip(prompts, on):
            ref = np.asarray(eng.generate(
                np.asarray(p, np.int32)[None], max_new_tokens=5))[0]
            assert list(ref) == o
        # followers mapped the shared prefix instead of prefilling it
        assert cb.cache_stats["hit_blocks"] >= 2 * (len(prompts) - 1)
        assert sum(r.cached_prefix for r in reqs) >= \
            16 * (len(prompts) - 1)

    def test_identical_block_aligned_prompts_trigger_cow(self):
        # whole prompt cached: the last token is handed back to the
        # scheduler and its write lands INSIDE the shared tail block —
        # the copy-on-write trigger
        eng, V = _tiny_engine()
        rng = np.random.default_rng(3)
        p = rng.integers(1, V, 16)                 # exactly 2 blocks
        off, _, _ = _serve(eng, [p, p, p], [4, 4, 4], ids="cob")
        on, cb, _ = _serve(eng, [p, p, p], [4, 4, 4], ids="con",
                           prefix_cache=True)
        assert on == off
        assert cb.cache_stats["cow_copies"] >= 1

    def test_cow_preserves_shared_original(self):
        # two live holders of the tail block: the follower's divergent
        # write must land in a PRIVATE copy — the original physical
        # block stays bit-identical from the moment it was registered
        eng, V = _tiny_engine()
        rng = np.random.default_rng(4)
        p = rng.integers(1, V, 16)                 # exactly 2 blocks
        cb = ContinuousBatchingEngine(
            eng, num_blocks=24, block_size=8, max_batch=4,
            prefill_chunk=8, prefix_cache=True)
        reqs = [GenerationRequest(np.asarray(p, np.int32).copy(), 4,
                                  request_id=f"cp{j}") for j in range(2)]
        for r in reqs:
            cb.submit(r)
        tail_key = block_key(block_key(None, p[:8]), p[8:16])
        for _ in range(8):
            cb.step()
            if cb.allocator.lookup(tail_key) is not None:
                break
        orig = cb.allocator.lookup(tail_key)
        assert orig is not None
        before = [np.asarray(c[:, :, orig]).copy() for c in cb.caches]
        out = cb.run()
        assert cb.cache_stats["cow_copies"] >= 1
        after = [np.asarray(c[:, :, orig]) for c in cb.caches]
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)
        ref = np.asarray(eng.generate(
            np.asarray(p, np.int32)[None], max_new_tokens=4))[0]
        for r in reqs:
            assert list(ref) == out[r.request_id]

    def test_spec_decode_with_sharing_round_trip(self):
        # speculation + sharing together: rewinds fire while blocks are
        # registered/shared, and a resume request off the pool must
        # still be token-exact — the speculated-then-rewound shared
        # state is indistinguishable from never-shared, never-speculated
        eng, V = _tiny_engine()
        pattern = [7, 23, 41, 11]
        p = np.asarray(pattern * 4, np.int32)      # 16 = 2 full blocks
        ref, _, _ = _serve(eng, [p, p], [10, 10], ids="srb")
        out, cb, reqs = _serve(eng, [p, p], [10, 10], ids="sra",
                               prefix_cache=True, spec_k=4)
        assert out == ref
        assert sum(r.spec_drafted for r in reqs) > 0
        resume, cb, r3 = _serve(eng, [p], [10], ids="src", cb=cb,
                                prefix_cache=True, spec_k=4)
        assert resume[0] == ref[0]
        assert r3[0].cached_prefix > 0, "resume paid full prefill"

    def test_wavefront_concurrent_duplicates_dedup(self):
        # submitted in the same wave: the follower defers while the
        # leader computes, then maps each block the step after it
        # registers — the shared prefix is computed ONCE
        eng, V = _tiny_engine()
        rng = np.random.default_rng(9)
        p = rng.integers(1, V, 19)                 # 2 full blocks + tail
        off, _, _ = _serve(eng, [p, p.copy()], [4, 4], ids="wvb")
        on, cb, reqs = _serve(eng, [p, p.copy()], [4, 4], ids="wva",
                              prefix_cache=True)
        assert on == off
        assert reqs[1].cached_prefix == 16
        # one miss per block position per request (2 each — the
        # deferred follower re-probes a position every step until the
        # leader registers it WITHOUT re-counting)
        assert cb.cache_stats["miss_blocks"] == 4


class TestPagedCopy:
    def test_copies_row_and_leaves_rest(self):
        rng = np.random.default_rng(0)
        kc = rng.standard_normal((2, 5, 4, 8)).astype(np.float32)
        vc = rng.standard_normal((2, 5, 4, 8)).astype(np.float32)
        k2, v2 = np.asarray(pa.copy_paged_kv(
            jnp.stack([kc, vc]), jnp.int32(1), jnp.int32(3)))
        np.testing.assert_array_equal(k2[:, 3], kc[:, 1])
        np.testing.assert_array_equal(v2[:, 3], vc[:, 1])
        mask = np.ones(5, bool)
        mask[3] = False
        np.testing.assert_array_equal(k2[:, mask], kc[:, mask])
        np.testing.assert_array_equal(v2[:, mask], vc[:, mask])

    def test_out_of_pool_dst_drops(self):
        kc = np.ones((2, 4, 4, 8), np.float32)
        vc = np.ones((2, 4, 4, 8), np.float32)
        k2, v2 = pa.copy_paged_kv(
            jnp.stack([kc, vc]), jnp.int32(1), jnp.int32(7))
        np.testing.assert_array_equal(np.asarray(k2), kc)
        np.testing.assert_array_equal(np.asarray(v2), vc)


class TestChurnAndObservability:
    def test_refcount_leak_free_after_churn(self):
        eng, V = _tiny_engine()
        rng = np.random.default_rng(13)
        prefix = rng.integers(1, V, 16)
        cb = None
        for wave in range(3):
            prompts = [np.concatenate(
                [prefix, rng.integers(1, V, 2 + j)]) for j in range(3)]
            _, cb, _ = _serve(eng, prompts, [3, 4, 5], ids=f"ch{wave}",
                              cb=cb, prefix_cache=True)
        alloc = cb.allocator
        assert alloc.num_used == 0
        assert alloc._ref == {}
        assert alloc.num_free + alloc.num_pooled == \
            alloc.num_blocks - alloc.reserved
        # every pooled block is still resolvable through the index
        assert alloc.num_pooled <= alloc.num_registered

    def test_eviction_under_pressure_stays_exact(self):
        # pool too small to retain every retired prefix: allocation
        # reclaims LRU blocks mid-run and the outputs must not notice
        eng, V = _tiny_engine()
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, V, 10 + 3 * j) for j in range(4)]
        off, _, _ = _serve(eng, prompts, [4] * 4, ids="evb",
                           num_blocks=8, max_batch=2)
        on, cb, _ = _serve(eng, prompts, [4] * 4, ids="eva",
                           num_blocks=8, max_batch=2, prefix_cache=True)
        assert on == off
        assert cb.allocator.evictions > 0

    def test_counters_gauges_and_explain(self):
        from paddle_tpu import observability as obs
        eng, V = _tiny_engine()
        rng = np.random.default_rng(21)
        p = rng.integers(1, V, 16)
        reg = obs.get_registry()

        def val(name):
            m = reg.get(name)
            return 0.0 if m is None else m.value

        h0, c0 = val("serve_prefix_cache_hits_total"), \
            val("serve_prefix_cache_cow_copies_total")
        _, cb, reqs = _serve(eng, [p, p.copy()], [3, 3], ids="ob",
                             prefix_cache=True)
        assert val("serve_prefix_cache_hits_total") - h0 == \
            cb.cache_stats["hit_blocks"]
        assert val("serve_prefix_cache_cow_copies_total") - c0 == \
            cb.cache_stats["cow_copies"]
        assert reg.get("kv_blocks_prefix_resident") is not None
        # cache_hit events land on the follower's request lane and the
        # explain() digest reports the reused-prefix length
        tr = obs.get_tracer()
        follower = reqs[1].request_id
        hits = [s for s in tr.spans(request=follower)
                if s["name"] == "cache_hit"]
        # whole prompt cached: the last token is handed back to the
        # scheduler, so the reused prefix is 15 of 16 tokens
        assert hits and hits[-1]["args"]["total"] == 15
        assert cb.explain(follower)["cached_prefix_tokens"] == 15

    def test_zero_new_buckets_on_replay(self):
        eng, V = _tiny_engine()
        rng = np.random.default_rng(23)
        prefix = rng.integers(1, V, 16)
        prompts = [np.concatenate([prefix, rng.integers(1, V, 3)]),
                   np.concatenate([prefix, rng.integers(1, V, 5)])]
        _, cb, _ = _serve(eng, prompts, [4, 4], ids="zb0",
                          prefix_cache=True)
        _, cb, _ = _serve(eng, prompts, [4, 4], ids="zb1", cb=cb,
                          prefix_cache=True)       # resume shapes
        warm = set(cb._seen_buckets)
        _, cb, _ = _serve(eng, prompts, [4, 4], ids="zb2", cb=cb,
                          prefix_cache=True)
        assert set(cb._seen_buckets) == warm

    def test_cow_alloc_failure_triggers_flight_recorder(self, tmp_path):
        # the COW-path alloc raises into step()'s grow guard: with no
        # strictly-lower-priority victim to preempt, the failing
        # request degrades to a structured per-request failure (ISSUE
        # 11 — the engine no longer crashes) while the kv_alloc_failure
        # dump still carries the cow_block_index stall evidence; every
        # OTHER request completes untouched
        import traceback

        from paddle_tpu.observability import tracing as tr

        eng, V = _tiny_engine()
        rng = np.random.default_rng(31)
        p = rng.integers(1, V, 16)                 # exactly 2 blocks
        cb = ContinuousBatchingEngine(
            eng, num_blocks=24, block_size=8, max_batch=3,
            prefill_chunk=8, prefix_cache=True)
        fr = tr.get_flight_recorder()
        fr.arm(tmp_path)
        n0 = len(fr.dumps)
        # fail ONLY the alloc issued from inside _cow_block (three live
        # holders of the tail block force the COW; every other alloc
        # works normally)
        orig = cb.allocator.alloc

        def failing_alloc():
            if any(f.name == "_cow_block"
                   for f in traceback.extract_stack()):
                raise type(cb.allocator).OutOfBlocks(
                    "BlockAllocator: out of cache blocks [injected]")
            return orig()

        cb.allocator.alloc = failing_alloc
        reqs = [GenerationRequest(
            np.asarray(p, np.int32).copy(), 4, request_id=f"cf{j}")
            for j in range(3)]
        for r in reqs:
            cb.submit(r)
        try:
            out = cb.run()      # must NOT raise
            # the leader computed its own blocks (no COW on its path);
            # the followers' whole-prompt-cached tail write needed the
            # COW that was injected to fail — all same priority, so no
            # victim existed and each degraded to a per-request failure
            statuses = {r.request_id: out[r.request_id].status
                        for r in reqs}
            assert statuses["cf0"] == "finished", statuses
            assert statuses["cf1"] == "failed"
            assert statuses["cf2"] == "failed"
            ref = eng.generate(np.asarray(p, np.int32)[None, :],
                               max_new_tokens=4)[0, :4].tolist()
            assert list(out["cf0"]) == ref
            assert len(fr.dumps) >= n0 + 1
            dump = tr.load_dump(fr.dumps[-1])
            assert dump["reason"] == "kv_alloc_failure"
            assert any(s["name"] == "stall_alloc"
                       and "cow_block_index" in s["args"]
                       for s in dump["spans"])
            # the failed followers freed every block they held
            assert cb.allocator.num_used == 0
        finally:
            cb.allocator.alloc = orig
            fr.disarm()
