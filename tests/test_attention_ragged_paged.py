"""Ragged paged-attention kernel + continuous-batching serving tests
(interpret mode on CPU — device kernels tested without the device).

Parity ladder:
  * the kernel must be BIT-EXACT vs the plain-JAX work-list reference
    (same packed tiles, same online-softmax order, same FMA contraction),
  * numerically close to an independent dense softmax oracle,
  * and the serving layer's generations must match the dense engine's
    `generate()` token for token.
"""
import numpy as np
import jax
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


def _setup(h, kvh, lens, seed=0, d=32, bs=8, max_nb=6, dtype=np.float32):
    rng = np.random.default_rng(seed)
    b = len(lens)
    nblk = b * max_nb + 3
    q = rng.standard_normal((b, h, d)).astype(dtype)
    kc = rng.standard_normal((kvh, nblk, bs, d)).astype(dtype)
    vc = rng.standard_normal((kvh, nblk, bs, d)).astype(dtype)
    tables = np.stack([rng.choice(nblk, max_nb, replace=False)
                       for _ in range(b)]).astype(np.int32)
    return q, kc, vc, tables, np.asarray(lens, np.int32)


def _dense_softmax_ref(q, kc, vc, tables, lens):
    """Independent oracle: gather each sequence's blocks dense, softmax
    in float64."""
    b, h, d = q.shape
    kvh, _, bs, _ = kc.shape
    g = h // kvh
    out = np.zeros((b, h, d), np.float32)
    for bb in range(b):
        if lens[bb] == 0:
            continue
        ks = np.concatenate([kc[:, t] for t in tables[bb]], axis=1)
        vs = np.concatenate([vc[:, t] for t in tables[bb]], axis=1)
        for hh in range(h):
            kvhh = hh // g
            s = ks[kvhh, :lens[bb]].astype(np.float64) @ \
                q[bb, hh].astype(np.float64) / np.sqrt(d)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[bb, hh] = p @ vs[kvhh, :lens[bb]].astype(np.float64)
    return out


# ragged lengths covering: empty, single token, exact block multiples,
# table-capacity-full, and odd stragglers
RAGGED_LENS = [0, 8 * 3, 1, 8 * 6, 13]

HEAD_LAYOUTS = [
    pytest.param(8, 4, id="gqa2"),   # 2 q heads per kv head
    pytest.param(8, 2, id="gqa4"),
    pytest.param(4, 4, id="mha"),
    pytest.param(4, 1, id="mqa"),
]


class TestRaggedKernel:
    @pytest.mark.parametrize("h,kvh", HEAD_LAYOUTS)
    def test_bit_exact_vs_reference(self, h, kvh):
        q, kc, vc, tables, lens = _setup(h, kvh, RAGGED_LENS)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens))
        ref = pa.ragged_paged_attention_reference(q, kc, vc, tables, lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("h,kvh", HEAD_LAYOUTS)
    def test_close_to_dense_softmax(self, h, kvh):
        q, kc, vc, tables, lens = _setup(h, kvh, RAGGED_LENS, seed=1)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens))
        ref = _dense_softmax_ref(q, kc, vc, tables, lens)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    @pytest.mark.parametrize("pack", [1, 2, 3, 5])
    def test_pack_variants_bit_exact(self, pack):
        q, kc, vc, tables, lens = _setup(8, 4, RAGGED_LENS, seed=3)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), pack=pack)
        ref = pa.ragged_paged_attention_reference(
            q, kc, vc, tables, lens, pack=pack)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_bf16(self):
        q, kc, vc, tables, lens = _setup(8, 4, RAGGED_LENS, seed=4)
        to16 = lambda a: jnp.asarray(a, jnp.bfloat16)
        out = pa.ragged_paged_attention(
            to16(q), jnp.stack([to16(kc), to16(vc)]), jnp.asarray(tables),
            jnp.asarray(lens))
        ref = _dense_softmax_ref(
            np.asarray(to16(q), np.float32), np.asarray(to16(kc), np.float32),
            np.asarray(to16(vc), np.float32), tables, lens)
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=5e-2, atol=5e-2)

    def test_grid_scales_with_actual_blocks(self):
        # THE point of the ragged kernel: grid steps follow the sum of
        # per-sequence block counts, not B x max_blocks
        bs, max_nb = 8, 6
        lens = np.asarray(RAGGED_LENS, np.int32)
        b = len(lens)
        tables = np.arange(b * max_nb, dtype=np.int32).reshape(b, max_nb)
        for pack in (1, 2, 4):
            work, t_real, t_total, _ = pa.build_ragged_work(
                tables, lens, bs, pack)
            expect = sum(-(-int(x) // bs) for x in lens)
            assert t_real == t_total == expect
            assert t_real < b * max_nb
            assert len(work[0]) == t_total
        # bucketing pads but keeps padded entries inert
        work, t_real, t_total, _ = pa.build_ragged_work(
            tables, lens, bs, 2, bucket_to=pa.next_pow2)
        assert t_total == pa.next_pow2(t_real) >= t_real

    def test_bucketed_work_same_output(self):
        q, kc, vc, tables, lens = _setup(8, 4, RAGGED_LENS, seed=5)
        plain = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), pack=2)
        work = pa.build_ragged_work(tables, lens, kc.shape[2], 2,
                                    bucket_to=pa.next_pow2)
        assert work[2] > work[1]  # really padded
        bucketed = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens), pack=2, work=work)
        np.testing.assert_array_equal(np.asarray(plain),
                                      np.asarray(bucketed))
        # a pack that disagrees with the work list must refuse, not
        # silently mis-pack the query tiles
        with pytest.raises(ValueError):
            pa.ragged_paged_attention(
                jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
                jnp.asarray(tables), jnp.asarray(lens), pack=4, work=work)

    def test_full_capacity_row_attends_over_table(self):
        # a row whose len+1 exceeds the table capacity (the decode step
        # right at the boundary: update dropped the write) must walk only
        # the blocks that exist, not index past its table row
        bs, max_nb = 4, 2
        tables = np.arange(6, dtype=np.int32).reshape(3, 2)
        lens = np.asarray([8, 3, 5], np.int32) + 1   # row 0 past capacity
        (ws, _, _, _, wpos, _, _, _, _), t_real, _, _ = pa.build_ragged_work(
            tables, lens, bs, 2)
        assert t_real == 2 + 1 + 2                   # row 0 clamped to 2
        assert max(wpos[ws == 0]) == max_nb - 1
        q, kc, vc, tables2, _ = _setup(8, 4, [0] * 3, d=16, bs=bs,
                                       max_nb=max_nb)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables2), jnp.asarray(lens))
        # equivalent to attending over the capacity tokens
        ref = _dense_softmax_ref(q, kc, vc, tables2,
                                 np.minimum(lens, max_nb * bs))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-3)

    def test_all_empty_batch(self):
        q, kc, vc, tables, lens = _setup(8, 4, [0, 0, 0])
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(lens))
        np.testing.assert_array_equal(np.asarray(out), np.zeros_like(q))

    def test_under_jit_with_prebuilt_work(self):
        q, kc, vc, tables, lens = _setup(8, 4, RAGGED_LENS, seed=6)
        arrs, t_real, t_total, pack = pa.build_ragged_work(
            tables, lens, kc.shape[2], 2)

        @jax.jit
        def run(q, kc, vc, tables, lens, arrs):
            return pa.ragged_paged_attention(
                q, jnp.stack([kc, vc]), tables, lens,
                work=(arrs, t_real, t_total, pack))

        out = run(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                  jnp.asarray(tables), jnp.asarray(lens),
                  tuple(jnp.asarray(a) for a in arrs))
        ref = pa.ragged_paged_attention_reference(
            q, kc, vc, tables, lens, pack=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def _mixed_slab(q0, g, pack, seed, dtype=np.float32, kvh=2, d=32, bs=16):
    """A chunk step's slab as the scheduler hands it over: slot 1
    prefills q0 tokens, slots 0 and 4 decode one, slot 2 is parked
    (q_len 0, its cache left as it is), slot 3 verifies a 1 + 3
    speculative span; the work list is bucketed to twice its power of
    two, so its second half is padding."""
    rng = np.random.default_rng(seed)
    q_lens = np.asarray([1, q0, 0, 4, 1], np.int32)
    ctx = np.asarray([20, q0 + 9, 33, 40, 1], np.int32)  # span included
    b, c = len(q_lens), pa.next_pow2(max(q0, 4))
    max_nb = -(-int(ctx.max()) // bs)
    nblk = b * max_nb + 3
    q = rng.standard_normal((b, c, kvh * g, d)).astype(dtype)
    kc = rng.standard_normal((kvh, nblk, bs, d)).astype(dtype)
    vc = rng.standard_normal((kvh, nblk, bs, d)).astype(dtype)
    tables = rng.permutation(nblk)[:b * max_nb].reshape(
        b, max_nb).astype(np.int32)
    work = pa.build_ragged_work(
        tables, ctx, bs, pack, bucket_to=lambda n: 2 * pa.next_pow2(n),
        q_lens=q_lens)
    assert work[2] >= 2 * work[1] and not work[0][8][work[2] // 2:].any()
    return q, kc, vc, tables, ctx, q_lens, work


class TestLiveQueryRows:
    """A grid step visits only the sub-tiles of the packed query tile
    that hold live rows of its own entry's slot: the results are those
    of the whole-tile algorithm, bit for bit, and a row no slot had live
    comes back zero."""

    @pytest.mark.parametrize("g", [1, 4, 8])
    @pytest.mark.parametrize("pack", [1, 2, 3])
    @pytest.mark.parametrize("q0", [1, 7, 100, 128])
    def test_mixed_slab_bit_exact_vs_reference(self, q0, pack, g):
        q, kc, vc, tables, ctx, q_lens, work = _mixed_slab(
            q0, g, pack, seed=q0 + 10 * pack + g)
        out = np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(ctx), q_lens=q_lens,
            work=work))
        ref = np.asarray(pa.ragged_paged_attention_reference(
            q, kc, vc, tables, ctx, pack=pack, q_lens=q_lens))
        np.testing.assert_array_equal(out, ref)
        dead = np.arange(q.shape[1])[None, :] >= q_lens[:, None]
        assert not out[dead].any() and np.isfinite(out).all()
        assert np.abs(out[~dead]).min(-1).max() > 0   # and the live ones ran

    @pytest.mark.parametrize("q0,pack,g,depth", [
        (100, 2, 4, 1), (128, 2, 4, 3), (7, 3, 8, 1)])
    def test_bf16_and_buffer_depths(self, q0, pack, g, depth):
        import ml_dtypes
        q, kc, vc, tables, ctx, q_lens, work = _mixed_slab(
            q0, g, pack, seed=5, dtype=ml_dtypes.bfloat16)
        out = pa.ragged_paged_attention(
            jnp.asarray(q), jnp.asarray(np.stack([kc, vc])),
            jnp.asarray(tables), jnp.asarray(ctx), q_lens=q_lens,
            work=work, buffer_depth=depth)
        ref = pa.ragged_paged_attention_reference(
            q, kc, vc, tables, ctx, pack=pack, q_lens=q_lens)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(ref, np.float32))

    @pytest.mark.parametrize("q0,pack,g", [
        (128, 2, 4), (100, 3, 1), (7, 2, 8), (1, 1, 4), (100, 1, 8)])
    def test_trips_cover_exactly_the_rows_the_mask_keeps(self, q0, pack, g):
        """`_subtile_trips`, which the kernel loops by and `attn_rows`
        counts by, against the row mask written out per entry: the
        sub-tiles that hold a row whose query sees some of the block are
        lo .. hi - 1, no more and no fewer."""
        *_, ctx, q_lens, work = _mixed_slab(q0, g, pack, seed=1)
        c, bs = pa.next_pow2(max(q0, 4)), 16
        sub, rows = pa.query_subtile(work[3], c, g)
        assert rows % sub == 0 and rows >= work[3] * c * g
        live = visited = 0
        _, _, wr, _, wpos, _, _, wqs, wql = work[0]
        for t in range(work[2]):
            row = np.arange(rows)
            rel = row - wr[t] * c * g
            j = rel // g
            sees = ((rel >= 0) & (rel < c * g) & (j < wql[t])
                    & (wpos[t] * bs <= wqs[t] + j))
            tiles = np.unique(row[sees] // sub)
            trips = pa._subtile_trips(
                np, wr[t], wpos[t], wqs[t], wql[t], chunk=c, group_q=g,
                sub=sub, rows=rows, block_size=bs)
            lo, hi = trips.lo, trips.hi
            assert trips.seen == sees.sum() and hi - lo == len(tiles)
            assert not len(tiles) or (tiles[0], tiles[-1]) == (lo, hi - 1)
            assert trips.start <= lo and trips.rows == min(wql[t], c) * g
            live += sees.sum()
            visited += len(tiles) * sub
        assert (live, visited) == pa.attn_rows(work[0], work[3], c, g, bs)
        assert 0 < live <= visited

    def test_a_tile_no_taller_than_a_sub_tile_is_one_static_trip(self):
        # every decode bucket: pack * G = 8 rows, no loop in the kernel
        assert pa.query_subtile(2, 1, 4) == (8, 8)
        assert pa.query_subtile(2, 8, 4) == (64, 64)
        assert pa.query_subtile(2, 16, 4) == (pa.SUB_ROWS, 128)
        assert pa.query_subtile(3, 5, 8) == (pa.SUB_ROWS, 128)   # 120 rows
        q, kc, vc, tables, lens = _setup(8, 2, RAGGED_LENS)
        jaxpr = jax.make_jaxpr(lambda q, kv: pa.ragged_paged_attention(
            q, kv, tables, lens))(
                jnp.asarray(q), jnp.asarray(np.stack([kc, vc])))
        assert "while" not in [e.primitive.name for e in _eqns(jaxpr.jaxpr)]

    @pytest.mark.parametrize("q0,pack,g", [
        (128, 2, 4), (100, 3, 1), (7, 3, 8), (1, 1, 4)])
    def test_rows_read_out_of_the_tiles_are_the_slabs(self, q0, pack, g):
        """What a wide step does: `tile_rows` picks single cells out of
        the kernel's own output tiles. They are the cells of the
        [B, C, H, D] slab `ragged_paged_attention` lays out, a dead one
        zero, with no slab in between."""
        q, kc, vc, tables, ctx, q_lens, work = _mixed_slab(
            q0, g, pack, seed=3)
        kv = jnp.asarray(np.stack([kc, vc]))
        slab = np.asarray(pa.ragged_paged_attention(
            jnp.asarray(q), kv, jnp.asarray(tables), jnp.asarray(ctx),
            q_lens=q_lens, work=work))
        tiles = pa.ragged_attention_tiles(jnp.asarray(q), kv, work)
        b, c, h, d = q.shape
        assert tiles.shape[:2] == (-(-b // pack), kc.shape[0])
        slot, col = (a.reshape(-1) for a in np.indices((b, c)))
        order = np.random.default_rng(q0).permutation(b * c)
        slot, col = slot[order], col[order]
        live = col < q_lens[slot]
        rows = np.asarray(pa.tile_rows(
            tiles, jnp.asarray(slot), jnp.asarray(col), jnp.asarray(live),
            pack, c, h, d))
        np.testing.assert_array_equal(rows, slab[slot, col])
        assert live.any() and not rows[~live].any()


class TestCacheUpdateBoundary:
    def _setup(self, lens):
        rng = np.random.default_rng(7)
        kvh, nb, bs, d, b, max_nb = 2, 9, 4, 8, 3, 2
        kc = rng.standard_normal((kvh, nb, bs, d)).astype(np.float32)
        vc = rng.standard_normal((kvh, nb, bs, d)).astype(np.float32)
        kn = rng.standard_normal((b, kvh, d)).astype(np.float32)
        vn = rng.standard_normal((b, kvh, d)).astype(np.float32)
        tables = np.arange(b * max_nb, dtype=np.int32).reshape(b, max_nb)
        return kc, vc, kn, vn, tables, np.asarray(lens, np.int32)

    def test_full_row_write_dropped(self):
        # context_lens == table capacity (max_nb * bs == 8): the old code
        # read block_tables[:, 2] (one past the end); now the write drops
        kc, vc, kn, vn, tables, lens = self._setup([8, 3, 8])
        kc2, vc2 = np.asarray(pa.append_paged_kv(
            jnp.stack([kc, vc]), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(tables), jnp.asarray(lens)))
        # row 1 (len 3) landed at its block 0 (table id 2), offset 3
        np.testing.assert_array_equal(kc2[:, tables[1, 0], 3], kn[1])
        np.testing.assert_array_equal(vc2[:, tables[1, 0], 3], vn[1])
        # full rows 0 and 2 changed NOTHING anywhere else
        kc_exp, vc_exp = kc.copy(), vc.copy()
        kc_exp[:, tables[1, 0], 3] = kn[1]
        vc_exp[:, tables[1, 0], 3] = vn[1]
        np.testing.assert_array_equal(kc2, kc_exp)
        np.testing.assert_array_equal(vc2, vc_exp)

    def test_last_slot_still_writable(self):
        kc, vc, kn, vn, tables, lens = self._setup([7, 7, 7])
        kc2, _ = np.asarray(pa.append_paged_kv(
            jnp.stack([kc, vc]), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(tables), jnp.asarray(lens)))
        for b in range(3):
            np.testing.assert_array_equal(kc2[:, tables[b, 1], 3], kn[b])


# the packed-rows writer's cases: (kv heads, key width, value width) and
# what the tile holds
ROWS_CASES = {
    # a span across a block boundary, a parked slot's dead rows in the
    # tile's middle, a row at the table's capacity, dead rows at its end
    "tile": (2, 8, 8),
    # two sequences' rows in one block's 8-row group, not adjacent in
    # the tile: no engine appends so, a scatter allows it
    "shared-group": (2, 8, 8),
    # MiMo's layers: keys 192 wide over values 128 wide in Dc = 256
    # lanes, two kv-head counts, each kind its own block table
    "mimo-full": (1, 192, 128),
    "mimo-window": (2, 192, 128),
    # the device's own kv heads inside a shard_map body, tp = 2
    "tp2": (4, 8, 8),
}


def _rows_case(case, dtype, seed=13):
    """One tile of packed rows for `append_paged_kv_rows`: (the call as
    a thunk, numpy oracle over the two halves)."""
    rng = np.random.default_rng(seed + len(case))
    kvh, dk, dv = ROWS_CASES[case]
    dc = pa.paged_head_dim(max(dk, dv)) if dk != dv else dk
    nb, bs, b, max_nb = 17, 16, 4, 3
    cap = max_nb * bs
    f32 = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
    kc, vc = (f32(rng.standard_normal((kvh, nb, bs, dc))) for _ in "kv")
    tables = rng.permutation(nb - 1)[:b * max_nb].reshape(b, max_nb) \
        .astype(np.int32)
    if case == "mimo-window":       # the window layers' own table
        tables = tables[::-1].copy()
    # (slot, first position, rows, live)
    spans = [(0, 10, 11, True),     # crosses a block and two groups
             (1, 4, 6, False),      # parked: dead in the tile's middle
             (2, cap - 2, 3, True),  # its third row is at the capacity
             (3, 3, 5, True),
             (0, 0, 15, False)]     # the tile's dead end
    if case == "shared-group":
        tables[2, 0] = tables[0, 0]     # slots 0 and 2 write one block
        spans = [(0, 1, 2, True), (1, 20, 3, True), (2, 5, 2, True),
                 (3, 9, 1, True), (0, 7, 1, True), (1, 0, 4, False)]
    slot = np.concatenate([np.full(n, s_, np.int32) for s_, _, n, _ in spans])
    pos = np.concatenate([p0 + np.arange(n, dtype=np.int32)
                          for _, p0, n, _ in spans])
    live = np.concatenate([np.full(n, l_) for _, _, n, l_ in spans])
    kn = f32(rng.standard_normal((len(slot), kvh, dk)))
    vn = f32(rng.standard_normal((len(slot), kvh, dv)))
    want_k, want_v = kc.copy(), vc.copy()
    for r in range(len(slot)):
        if live[r] and pos[r] < cap:
            cell = (slice(None), tables[slot[r], pos[r] // bs], pos[r] % bs)
            want_k[cell], want_v[cell] = 0.0, 0.0   # the lane padding
            want_k[cell + (slice(0, dk),)] = kn[r]
            want_v[cell + (slice(0, dv),)] = vn[r]
    assert (want_k != kc).any()
    j = lambda a: jnp.asarray(a, dtype)
    args = (j(kn), j(vn), jnp.asarray(tables), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(live))
    kv = jnp.stack([j(kc), j(vc)])
    if case != "tp2":
        return lambda: pa.append_paged_kv_rows(kv, *args), (want_k, want_v)
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    heads, rows = P(None, "tp"), P(None, "tp")
    sharded = jax.shard_map(
        pa.append_paged_kv_rows, mesh=mesh,
        in_specs=(heads, rows, rows, P(), P(), P(), P()),
        out_specs=heads, check_vma=False)
    return lambda: sharded(kv, *args), (want_k, want_v)


def _writer_case(writer, dtype, seed=11):
    """Random tables and rows for one writer: (the writer's call as a
    thunk, numpy oracle) over the same data. The rows cover
    the boundary contract: a slot whose length equals the table's
    capacity (its write drops), a chunk with valid_counts 0 (parked), a
    chunk that crosses a block boundary and one that runs into the
    capacity mid-chunk."""
    if writer in ROWS_CASES:
        return _rows_case(writer, dtype)
    rng = np.random.default_rng(seed)
    kvh, nb, bs, d, b, max_nb, c = 2, 17, 4, 8, 4, 3, 6
    cap = max_nb * bs
    half = lambda: np.asarray(jnp.asarray(
        rng.standard_normal((kvh, nb, bs, d)), dtype), np.float32)
    kc, vc = half(), half()
    rows = lambda *shape: np.asarray(jnp.asarray(
        rng.standard_normal(shape), dtype), np.float32)
    tables = rng.permutation(nb - 1)[:b * max_nb].reshape(b, max_nb) \
        .astype(np.int32)
    j = lambda a: jnp.asarray(a, dtype)
    ji = lambda a: jnp.asarray(a, jnp.int32)
    kv = jnp.stack([j(kc), j(vc)])
    want_k, want_v = kc.copy(), vc.copy()

    def put(bb, p, krow, vrow):
        if p < cap:
            want_k[:, tables[bb, p // bs], p % bs] = krow
            want_v[:, tables[bb, p // bs], p % bs] = vrow

    if writer == "decode":
        lens = np.asarray([cap, 3, cap - 1, 0], np.int32)
        kn, vn = rows(b, kvh, d), rows(b, kvh, d)
        for bb in range(b):
            put(bb, int(lens[bb]), kn[bb], vn[bb])
        stacked = lambda: pa.append_paged_kv(
            kv, j(kn), j(vn), ji(tables), ji(lens))
    elif writer == "chunk":
        lens = np.asarray([2, 5, cap - 2, cap], np.int32)
        valid = np.asarray([c, 0, 4, 3], np.int32)
        kn, vn = rows(b, c, kvh, d), rows(b, c, kvh, d)
        for bb in range(b):
            for jj in range(int(valid[bb])):
                put(bb, int(lens[bb]) + jj, kn[bb, jj], vn[bb, jj])
        stacked = lambda: pa.append_paged_kv_chunk(
            kv, j(kn), j(vn), ji(tables), ji(lens), ji(valid))
    elif writer == "rewind":
        old = np.asarray([cap, 7, 5, cap + 2], np.int32)
        new_l = np.asarray([cap - 3, 7, 1, cap - 1], np.int32)
        for bb in range(b):
            for p in range(int(new_l[bb]), int(old[bb])):
                put(bb, p, 0.0, 0.0)
        stacked = lambda: pa.truncate_paged_kv(
            kv, ji(tables), ji(new_l), ji(old), c)
    else:
        src, dst = int(tables[1, 0]), nb - 1
        want_k[:, dst], want_v[:, dst] = kc[:, src], vc[:, src]
        stacked = lambda: pa.copy_paged_kv(kv, ji(src), ji(dst))
    return stacked, (want_k, want_v)


class TestStackedWriters:
    """The writers take one layer's [2, KVH, NB, BS, Dc] cache and
    return it: bit for bit what numpy gives on the two halves."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("writer",
                             ["decode", "chunk", "rewind", "copy",
                              *ROWS_CASES])
    def test_equals_numpy(self, writer, dtype):
        stacked, want = _writer_case(writer, jnp.dtype(dtype))
        got = stacked()
        assert got.shape == (2,) + want[0].shape
        assert got.dtype == jnp.dtype(dtype)
        for h, oracle in enumerate(want):
            np.testing.assert_array_equal(
                np.asarray(got[h], np.float32), oracle)

    def test_copy_out_of_pool_ids_touch_nothing(self):
        # the host allocator's ids are data: a destination past the pool
        # drops, a source past the pool is clamped (and then dropped)
        stacked, _ = _writer_case("copy", jnp.float32)
        kv = np.asarray(stacked())
        nb = kv.shape[2]
        out = pa.copy_paged_kv(jnp.asarray(kv), jnp.int32(1),
                               jnp.int32(nb))
        np.testing.assert_array_equal(np.asarray(out), kv)
        out = pa.copy_paged_kv(jnp.asarray(kv), jnp.int32(nb + 5),
                               jnp.int32(2))
        exp = kv.copy()
        exp[:, :, 2] = kv[:, :, nb - 1]
        np.testing.assert_array_equal(np.asarray(out), exp)

    def test_kernel_refuses_a_single_half(self):
        q, kc, vc, tables, lens = _setup(8, 4, RAGGED_LENS)
        with pytest.raises(ValueError, match="stacked"):
            pa.ragged_paged_attention(
                jnp.asarray(q), jnp.asarray(kc), jnp.asarray(tables),
                jnp.asarray(lens))


class TestReferenceApiOverTheStackedBuffer:
    """`block_multihead_attention` and `block_kv_cache_rewind` keep the
    reference API's separate halves in and out and run the serving
    path's kernel and writers on the stacked buffer in between: held to
    the dense oracle and to numpy."""

    @pytest.mark.parametrize("h,kvh", [
        pytest.param(4, 4, id="mha"), pytest.param(8, 2, id="gqa4")])
    def test_block_multihead_attention(self, h, kvh):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as FI
        lens = [0, 8 * 3, 1, 8 * 6 - 1, 13]
        q, kc, vc, tables, lens = _setup(h, kvh, lens, seed=5)
        b, _, d = q.shape
        rng = np.random.default_rng(6)
        qkv = rng.standard_normal((b, 3, h, d)).astype(np.float32)
        qkv[:, 0] = q
        out, kc2, vc2 = FI.block_multihead_attention(
            paddle.to_tensor(qkv), paddle.to_tensor(kc),
            paddle.to_tensor(vc), paddle.to_tensor(tables),
            paddle.to_tensor(lens))
        want_k, want_v = kc.copy(), vc.copy()
        for bb in range(b):
            blk, off = tables[bb, lens[bb] // 8], lens[bb] % 8
            want_k[:, blk, off] = qkv[bb, 1, :kvh]
            want_v[:, blk, off] = qkv[bb, 2, :kvh]
        np.testing.assert_array_equal(kc2.numpy(), want_k)
        np.testing.assert_array_equal(vc2.numpy(), want_v)
        ref = _dense_softmax_ref(q, want_k, want_v, tables, lens + 1)
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("h,kvh", [
        pytest.param(4, 4, id="mha"), pytest.param(8, 2, id="gqa4")])
    def test_block_kv_cache_rewind(self, h, kvh):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import functional as FI
        _, kc, vc, tables, _ = _setup(h, kvh, [0] * 3, seed=7)
        new_lens = np.array([5, 9, 8 * 6 - 2], np.int32)
        old_lens = np.array([5, 12, 8 * 6 + 1], np.int32)
        kc2, vc2 = FI.block_kv_cache_rewind(
            paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(tables), paddle.to_tensor(new_lens),
            paddle.to_tensor(old_lens), 4)
        want_k, want_v = kc.copy(), vc.copy()
        for bb in range(3):
            # a position past the table's capacity has no row to zero
            for pos in range(new_lens[bb], min(old_lens[bb], 8 * 6)):
                want_k[:, tables[bb, pos // 8], pos % 8] = 0.0
                want_v[:, tables[bb, pos // 8], pos % 8] = 0.0
        assert (want_k != kc).any()
        np.testing.assert_array_equal(kc2.numpy(), want_k)
        np.testing.assert_array_equal(vc2.numpy(), want_v)


class TestBlockAllocator:
    def test_free_list_discipline(self):
        from paddle_tpu.incubate.nn import BlockAllocator
        al = BlockAllocator(6, reserved=1)
        assert al.num_free == 5
        got = [al.alloc() for _ in range(5)]
        assert sorted(got) == [1, 2, 3, 4, 5]  # block 0 never handed out
        with pytest.raises(RuntimeError):
            al.alloc()
        al.free(got[:3])
        assert al.num_free == 3
        with pytest.raises(ValueError):
            al.free([got[0]])      # double free
        with pytest.raises(ValueError):
            al.free([0])           # reserved block
        with pytest.raises(ValueError):
            al.free([99])          # out of pool


def _tiny_engine(seed=0):
    # delegate to the CACHED builder in test_chunked_prefill (identical
    # weights/config for a given seed): the serving test files share one
    # engine and one set of compiled step programs instead of paying the
    # interpret-mode compile bill per file (tier-1 window, BASELINE.md
    # "Tier-1 timing split" ISSUE 5 update)
    from test_chunked_prefill import _tiny_engine as _cached
    return _cached(seed=seed, max_seq_len=32)


class TestContinuousBatching:
    def test_admit_retire_no_leaks_and_parity(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        rng = np.random.default_rng(3)
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=2)
        free0 = cb.allocator.num_free
        # more requests than slots, unequal lengths -> forced queueing,
        # mixed-progress steps, retirement mid-flight
        lengths = [(5, 4), (11, 3), (3, 6), (8, 2)]
        prompts = [rng.integers(1, V, p).astype(np.int32)
                   for p, _ in lengths]
        reqs = [GenerationRequest(p, n)
                for p, (_, n) in zip(prompts, lengths)]
        for r in reqs:
            cb.submit(r)
        out = cb.run()
        # every request produced exactly max_new_tokens
        assert {r.request_id: len(out[r.request_id]) for r in reqs} == \
            {r.request_id: n for r, (_, n) in zip(reqs, lengths)}
        # no cache-slot leaks: free list back to initial size
        assert cb.allocator.num_free == free0
        assert all(r.blocks == [] for r in reqs)
        # token-for-token parity with the dense-cache engine
        for r, p, (_, n) in zip(reqs, prompts, lengths):
            ref = eng.generate(p[None, :], max_new_tokens=n)[0, :n]
            assert np.asarray(out[r.request_id]).tolist() == ref.tolist()

    def test_submit_rejects_impossible(self):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=3, block_size=8,
                                      max_batch=2)
        with pytest.raises(ValueError):  # needs 3 blocks, pool has 2
            cb.submit(GenerationRequest(np.arange(1, 17), 8))
        with pytest.raises(ValueError):  # exceeds capacity
            cb.submit(GenerationRequest(np.arange(1, 30), 8))

    def test_submit_capacity_is_table_not_max_seq_len(self):
        # max_seq_len 32 with block_size 5 -> 6 blocks = 30 usable
        # tokens; a 31-token request must be rejected at submit, not
        # crash the whole batch at the table edge mid-generation
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=5,
                                      max_batch=2)
        with pytest.raises(ValueError):
            cb.submit(GenerationRequest(np.arange(1, 27), 6))  # 31 > 30
        cb.submit(GenerationRequest(np.arange(1, 26), 5))      # 30 fits
        out = cb.run()
        assert [len(v) for v in out.values()] == [5]
        assert cb.allocator.num_free == 8


def _eqns(jaxpr, path=None):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (pjit, shard_map, custom calls, branches).
    With `path` given (start it at ()), pairs of an equation and the
    names of the primitives that enclose it."""
    for eqn in jaxpr.eqns:
        yield eqn if path is None else (eqn, path)
        inner = None if path is None else path + (eqn.primitive.name,)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


def _is_writer(eqn, cache):
    """The writer of new rows: the kernel `kv_rows_write` over a layer's
    stacked buffer, the buffer aliased to its result."""
    if eqn.primitive.name != "pallas_call" \
            or eqn.params["name"] != "kv_rows_write":
        return False
    (src, dst), = eqn.params["input_output_aliases"]
    assert tuple(eqn.invars[src].aval.shape) == cache
    assert tuple(eqn.outvars[dst].aval.shape) == cache
    return True


class TestStepNeverCopiesTheCache:
    """To append one row a step must not read or write a layer's whole
    cache: the rows are written into the donated [2, KVH, NB, BS, Dc]
    buffer, the ragged kernel reads blocks out of that buffer, and the
    buffer is the step's result. A slice of a half (the parent's
    `cache[0]`), or two halves stacked back (`jnp.stack([kc, vc])`),
    is a copy of the cache on the chip: 16 ms of a 28 ms decode step at
    Mistral-7B widths (PERF.md, PR 24 and PR 25)."""

    COPYING = ("concatenate", "slice", "pad", "squeeze")

    def _traced(self, width, max_batch=2):
        from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                            GenerationRequest)
        eng, V = _tiny_engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                      max_batch=max_batch, prefill_chunk=8)
        real, seen = eng._paged_step, {}

        def record(*args):
            seen["args"] = args
            return real(*args)

        eng._paged_step = record    # one real step, to see its arguments
        try:
            cb.submit(GenerationRequest(np.arange(1, 4), 1))
            cb.step()
        finally:
            eng._paged_step = real
        (w, _, slab, q_arr, sel, tables, lens, work, pack, temp, topp,
         key) = seen["args"]
        return cb, real.__wrapped__.trace(
            w, cb.caches, np.zeros((slab.shape[0], width), slab.dtype),
            q_arr, sel, tables, lens, work, pack, temp, topp, key)

    # 8 slots x 64 columns = 512 rows, two row tiles: a WIDE step, whose
    # row-wise layers loop over the live rows' tiles
    WIDE = dict(width=64, max_batch=8)

    @pytest.mark.parametrize("shape", [dict(width=1), dict(width=8), WIDE],
                             ids=["decode", "chunk", "wide"])
    def test_no_cache_sized_copy_and_results_alias_arguments(self, shape):
        cb, traced = self._traced(**shape)
        cache = tuple(cb.caches[0].shape)
        assert len(cache) == 5 and cache[0] == 2
        sized = (cache, cache[1:])
        writers = 0
        for eqn in _eqns(traced.jaxpr.jaxpr):
            shapes = [tuple(getattr(v.aval, "shape", ()))
                      for v in list(eqn.invars) + list(eqn.outvars)]
            if eqn.primitive.name in self.COPYING:
                assert not any(s in sized for s in shapes), (
                    f"{eqn.primitive.name} over a cache or a cache half: "
                    f"{shapes}")
            # nothing else writes a cache: no scatter over one is left
            assert eqn.primitive.name != "scatter" or cache not in shapes
            writers += _is_writer(eqn, cache)
        # the walk did reach the writers: one per layer, into the
        # stacked buffer itself
        assert writers == len(cb.caches)
        text = traced.lower().as_text()
        assert text.count("tf.aliasing_output") == len(cb.caches)

    def test_wide_step_has_no_slab_sized_matmul_or_scatter(self):
        """Outside the two loops of a layer nothing multiplies or
        scatters B x C rows (or the 2 x B x C x KVH index rows of the
        padded writer); inside them a tile's ROW_TILE rows do, and no
        scatter walks the 2 x ROW_TILE x KVH index rows of the writer
        that was. The layers are calls of one traced function."""
        cb, traced = self._traced(**self.WIDE)
        slab = self.WIDE["max_batch"] * self.WIDE["width"]
        kvh = cb.caches[0].shape[1]
        heavy = {"dot_general", "scatter"}
        rows_in_loop, loops, layer_fns = set(), 0, set()
        for eqn, path in _eqns(traced.jaxpr.jaxpr, ()):
            # (the ragged kernel's own loop over its entry's live query
            # sub-tiles is not the step's)
            loops += (eqn.primitive.name == "while"
                      and "pallas_call" not in path)
            if eqn.params.get("name") == "packed_paged_layer":
                layer_fns.add(id(eqn.params["jaxpr"]))
            if eqn.primitive.name not in heavy or "pallas_call" in path:
                continue    # the ragged kernel keeps the slab's geometry
            shapes = [tuple(getattr(v.aval, "shape", ()))
                      for v in list(eqn.invars) + list(eqn.outvars)]
            if eqn.primitive.name == "scatter":
                # index rows: the indices' dims but the last
                walked = int(np.prod(eqn.invars[1].aval.shape[:-1]))
                assert walked < 2 * pa.ROW_TILE * kvh, shapes
            if "while" in path:
                rows_in_loop.update(d for s in shapes for d in s)
                continue
            for s in shapes:
                assert slab not in s and 2 * slab * kvh not in s, (
                    f"{eqn.primitive.name} over the whole slab: {shapes}")
                assert s[:2] != (self.WIDE["max_batch"],
                                 self.WIDE["width"]), shapes
        assert pa.ROW_TILE in rows_in_loop
        assert slab not in rows_in_loop
        # the embedding's loop and two a layer (the walk enters each
        # call), all of them the same function
        assert loops == 1 + 2 * len(cb.caches) and len(layer_fns) == 1
        # the kernel's output is never laid out as a [B, C, H, D] slab:
        # a layer's second loop gathers its tile's rows out of the
        # kernel's own tiles, and nothing selects over the slab
        slab_cells = (self.WIDE["max_batch"], self.WIDE["width"])
        tile_gathers = 0
        for eqn, path in _eqns(traced.jaxpr.jaxpr, ()):
            if "pallas_call" in path:
                continue
            out = tuple(eqn.outvars[0].aval.shape) if eqn.outvars else ()
            if eqn.primitive.name == "select_n":     # [B, C, H, D]
                assert len(out) < 4 or out[:2] != slab_cells, out
            if eqn.primitive.name == "gather" and "while" in path:
                tile_gathers += len(out) == 4 and out[0] == pa.ROW_TILE
        assert tile_gathers == len(cb.caches)

    @pytest.mark.parametrize("width", [1, 8, 16])
    def test_one_tile_step_is_straight_line(self, width):
        """A slab of at most ROW_TILE rows is one tile whatever is live
        in it: no packing, no loop, one writer a layer (the one every
        slab width has) and no scatter."""
        cb, traced = self._traced(width, max_batch=8)
        assert 8 * width <= pa.ROW_TILE
        cache = tuple(cb.caches[0].shape)
        names = [e.primitive.name
                 for e, path in _eqns(traced.jaxpr.jaxpr, ())
                 if "pallas_call" not in path]
        assert "while" not in names and "scatter" not in names
        assert sum(_is_writer(e, cache)
                   for e in _eqns(traced.jaxpr.jaxpr)) == len(cb.caches)
        assert not any(e.params.get("name") == "packed_paged_layer"
                       for e in _eqns(traced.jaxpr.jaxpr))
