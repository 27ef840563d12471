"""A wide paged step computes its live rows only.

A slab of more than ROW_TILE rows (B x C) is packed on the device to its
live tokens, and every row-wise layer — the embedding gather, the norms,
the projections, rope, the cache append, the feed-forward — walks
ceil(live / ROW_TILE) row tiles of the packed buffer. Each case here runs
one such step of a tiny engine on the CPU and holds its sampled tokens,
the logits it picked and every layer's cache to a plain computation over
the whole padded slab, written below in `jax.numpy`; cache rows the step
had no live token for must come back bit for bit.
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


B, C, BS, MAX_NB = 8, 64, 8, 12         # 512 slab rows: two row tiles
V, E, H, G, D, L, F = 64, 32, 4, 2, 16, 2, 48
CAP = MAX_NB * BS                       # 96 positions a table row holds
NB = B * MAX_NB + 1
assert B * C == 2 * pa.ROW_TILE

_ENGINES = {}


def _weights(seed=0):
    rng = np.random.default_rng(seed)

    def mk(*shape, scale=0.08):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    inv = 1.0 / (1e4 ** (np.arange(0, D, 2) / D))
    ang = np.arange(CAP)[:, None] * inv[None]
    rot = np.stack([np.concatenate([f(ang)] * 2, -1)
                    for f in (np.cos, np.sin)]).astype(np.float32)
    return dict(
        ln_scales=[1 + mk(E) for _ in range(L)],
        qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
        qkv_biases=[mk(H + 2 * G, D) for _ in range(L)],
        linear_weights=[mk(H * D, E) for _ in range(L)],
        ffn_ln_scales=[1 + mk(E) for _ in range(L)],
        ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
        ffn2_weights=[mk(F, E) for _ in range(L)],
        embedding=mk(V, E, scale=1.0), lm_head=mk(E, V, scale=0.3),
        rotary_embs=rot[:, None, None])         # [2, 1, 1, CAP, D]


def _engine(tp=1):
    if tp not in _ENGINES:
        from paddle_tpu.inference import FusedMultiTransformerEngine
        _ENGINES[tp] = FusedMultiTransformerEngine(
            _weights(), num_heads=H, head_dim=D, max_seq_len=CAP,
            dtype="float32", norm_type="rmsnorm", activation="swiglu",
            gqa_group_size=G, use_neox_rotary_style=True, tp=tp)
    return _ENGINES[tp]


def _step_inputs(q_lens, lens, seed, c=C):
    """One step's arguments as the scheduler would build them for a
    [B, c] slab, over a cache full of noise (so that an unwritten row is
    told from a written one) and tables that scatter each slot's
    blocks."""
    rng = np.random.default_rng(seed)
    q_lens = np.asarray(q_lens, np.int32)
    lens = np.asarray(lens, np.int32)
    tables = (1 + rng.permutation(NB - 1)[:B * MAX_NB]
              ).reshape(B, MAX_NB).astype(np.int32)
    toks = rng.integers(1, V, (B, c)).astype(np.int32)
    sel = np.maximum(q_lens - 1, 0)[:, None].astype(np.int32)
    dc = pa.paged_head_dim(D)
    caches = [rng.standard_normal((2, G, NB, BS, dc)).astype(np.float32)
              for _ in range(L)]
    work, _, _, pack = pa.build_ragged_work(
        tables, lens + q_lens, BS, pa.default_pack(B, H // G),
        bucket_to=pa.next_pow2, q_lens=q_lens)
    return caches, toks, q_lens, sel, tables, lens, tuple(work), pack


def _eng_caches(eng, caches):
    """Fresh device copies (the step donates them), placed as the
    engine's own `new_paged_caches` places them."""
    like = eng.new_paged_caches(NB, BS)
    return [jax.device_put(jnp.asarray(c), z.sharding)
            for c, z in zip(caches, like)]


def _rms(h, scale):
    return h * jax.lax.rsqrt((h * h).mean(-1, keepdims=True) + 1e-5) * scale


def _padded_reference(w, caches, toks, q_lens, sel, tables, lens):
    """The step over the whole padded [B, C] slab: every column of every
    slot projected and fed forward, the dead ones masked where they could
    be seen (the cache, the keys a query may read)."""
    C = toks.shape[1]           # the slab's width, the module's or a case's
    pos = lens[:, None] + np.arange(C)[None, :]                 # [B, C]
    live = np.arange(C)[None, :] < q_lens[:, None]
    cos, sin = (jnp.asarray(w["rotary_embs"][i, 0, 0])[
        np.minimum(pos, CAP - 1)][:, :, None] for i in (0, 1))

    def rot(t):
        return jnp.concatenate([-t[..., D // 2:], t[..., :D // 2]], -1)

    h = jnp.asarray(w["embedding"])[toks]                       # [B, C, E]
    written = np.zeros((NB, BS), bool)
    bb, jj = np.nonzero(live & (pos < CAP))
    blk, off = tables[bb, pos[bb, jj] // BS], pos[bb, jj] % BS
    written[blk, off] = True
    out = []
    for li in range(L):
        z = _rms(h, w["ln_scales"][li])
        qkv = jnp.einsum("bce,hde->bchd", z, w["qkv_weights"][li]) \
            + w["qkv_biases"][li]
        q, k, v = qkv[:, :, :H], qkv[:, :, H:H + G], qkv[:, :, H + G:]
        q, k = q * cos + rot(q) * sin, k * cos + rot(k) * sin
        cache = np.array(caches[li])
        cache[0][:, blk, off, :D] = np.asarray(k)[bb, jj].transpose(1, 0, 2)
        cache[1][:, blk, off, :D] = np.asarray(v)[bb, jj].transpose(1, 0, 2)
        cache[:, :, blk, off, D:] = 0.0
        out.append(cache)
        # each slot's keys in position order: [B, G, CAP, D]
        ks, vs = (jnp.asarray(cache[i][:, tables][..., :D]).transpose(
            1, 0, 2, 3, 4).reshape(B, G, CAP, D) for i in (0, 1))
        qg = q.reshape(B, C, G, H // G, D)
        s = jnp.einsum("bcgrd,bgtd->bgrct", qg, ks) / np.sqrt(D)
        seen = np.arange(CAP)[None, None, :] <= pos[:, :, None]  # [B, C, T]
        s = jnp.where(seen[:, None, None], s, -1e30)
        ctx = jnp.einsum("bgrct,bgtd->bcgrd", jax.nn.softmax(s, -1), vs)
        ctx = jnp.where(live[:, :, None], ctx.reshape(B, C, H * D), 0.0)
        h = h + ctx @ w["linear_weights"][li]
        a, g = jnp.split(_rms(h, w["ffn_ln_scales"][li])
                         @ w["ffn1_weights"][li], 2, -1)
        h = h + (jax.nn.silu(a) * g) @ w["ffn2_weights"][li]
    logits = h[np.arange(B)[:, None], sel] @ w["lm_head"]       # [B, 1, V]
    return np.asarray(logits), out, written


FULL = [C] * B
CASES = {
    # name: (q_lens, lens)
    "one_chunk_among_decoders": ([1, 1, C, 1, 1, 1, 1, 1],
                                 [9, 30, 0, 17, 2, 31, 8, 25]),
    "two_chunks": ([C, 1, 1, 40, 1, 1, 1, 1], [3, 12, 20, 24, 7, 1, 16, 5]),
    "parked_slot_between_live": ([1, 0, C, 0, 1, 0, 0, 1],
                                 [4, 0, 16, 0, 11, 0, 0, 29]),
    "one_live_row": ([0, 0, 0, 1, 0, 0, 0, 0], [0, 0, 0, 13, 0, 0, 0, 0]),
    "live_eq_tile": ([C, C, C, C, 0, 0, 0, 0], [0, 8, 3, 21, 0, 0, 0, 0]),
    "live_eq_tile_plus_one": ([C, C, C, C, 1, 0, 0, 0],
                              [0, 8, 3, 21, 6, 0, 0, 0]),
    "every_row_live": (FULL, [0, 1, 2, 3, 4, 5, 6, 7]),
    "chunk_crosses_blocks": ([1, 1, 1, 1, 1, 20, 1, 1],
                             [8, 8, 8, 8, 8, 5, 8, 8]),
    # slot 2's span runs 90..99 against a table of 96 positions: the
    # last four rows have nowhere to go and are dropped
    "position_at_capacity": ([1, 1, 10, 1, 30, 1, 1, 1],
                             [8, 8, 90, 8, 11, 8, 8, 95]),
}


def _through_the_op(w, caches, toks, q_lens, sel, tables, lens, work,
                    pack):
    """The same step through `fused_multi_transformer` itself, handed the
    slab as [B, C, E]: the op packs it and hands it back in that
    geometry."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn.functional import fused_multi_transformer
    cts = [Tensor(jnp.asarray(c)) for c in caches]
    out = fused_multi_transformer(
        Tensor(jnp.asarray(w["embedding"])[toks]), w["ln_scales"], None,
        w["qkv_weights"], w["qkv_biases"], w["linear_weights"], None,
        w["ffn_ln_scales"], None, w["ffn1_weights"], None,
        w["ffn2_weights"], None, cache_kvs=cts,
        time_step=Tensor(jnp.zeros((), jnp.int32)),
        seq_lens=Tensor(jnp.asarray(lens)),
        chunk_lens=Tensor(jnp.asarray(q_lens)),
        rotary_embs=jnp.asarray(w["rotary_embs"]), block_tables=tables,
        ragged_work=work, ragged_pack=pack, norm_type="rmsnorm",
        activation="swiglu", use_neox_rotary_style=True, gqa_group_size=G)
    assert out.shape == [B, C, E]
    logits = out.data[np.arange(B)[:, None], sel] @ w["lm_head"]
    return logits, logits.argmax(-1), [c.data for c in cts]


# A 16-wide slab is 128 rows, one row tile: the step is straight-line
# code, and what it computes live rows only in is the ragged kernel, whose
# packed query tile (4 slots x 16 x 2 = 128 rows) is two sub-tiles.
NARROW = 16
assert B * NARROW <= pa.ROW_TILE
assert pa.query_subtile(pa.default_pack(B, H // G), NARROW, H // G)[1] \
    > pa.SUB_ROWS
CASES.update({
    "narrow_chunk_among_decoders": ([1, 1, NARROW, 1, 0, 5, 1, 1],
                                    [9, 30, 7, 17, 0, 40, 8, 25]),
    "narrow_spans_and_padding": ([4, 0, 0, 11, 4, 0, 1, 0],
                                 [20, 0, 0, 3, 33, 0, 64, 0]),
})
WIDTH = {"narrow_chunk_among_decoders": NARROW,
         "narrow_spans_and_padding": NARROW}


@pytest.mark.parametrize("case,entry", [(c, "engine") for c in CASES] + [
    ("two_chunks", "tp2"), ("two_chunks", "op"),
    ("position_at_capacity", "op"), ("parked_slot_between_live", "tp2"),
    ("narrow_chunk_among_decoders", "tp2"),
    ("narrow_spans_and_padding", "tp2")])
def test_wide_step_matches_the_padded_computation(case, entry):
    q_lens, lens = CASES[case]
    args = _step_inputs(q_lens, lens, seed=sorted(CASES).index(case),
                        c=WIDTH.get(case, C))
    caches, toks, q_lens, sel, tables, lens, work, pack = args
    w = _weights()
    want_logits, want_caches, written = _padded_reference(
        w, caches, toks, q_lens, sel, tables, lens)

    if entry == "op":
        logits, got_toks, got_caches = _through_the_op(w, *args)
    else:
        eng = _engine(tp=2 if entry == "tp2" else 1)
        got_toks, got_caches = eng._paged_step(
            eng._w, _eng_caches(eng, caches), toks, q_lens, sel, tables,
            lens, work, pack, np.float32(0.0), np.float32(1.0),
            jax.random.PRNGKey(0))
        logits = want_logits        # the mesh program hands out tokens only
        if entry == "engine":
            logits, *_ = jax.jit(eng._paged_logits, static_argnums=(8,))(
                eng._w, _eng_caches(eng, caches), toks, q_lens, sel,
                tables, lens, work, pack)

    # a slot whose span overran its table attended from the wrong
    # positions (the scheduler never sends one); the others are exact
    judged = (q_lens > 0) & (lens + q_lens <= CAP)
    assert judged.sum() >= 1
    np.testing.assert_allclose(np.asarray(logits)[judged],
                               want_logits[judged], rtol=2e-4, atol=2e-4)
    assert (np.asarray(got_toks)[judged, 0]
            == want_logits[judged, 0].argmax(-1)).all()
    n_written = int((np.minimum(lens + q_lens, CAP) - lens).sum())
    assert written.sum() == n_written
    for got, want, before in zip(got_caches, want_caches, caches):
        got = np.asarray(got)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # no row of a dead column, of a tile past the live ones or of a
        # position past the table was written: bit for bit the noise
        assert (got[:, :, ~written] == before[:, :, ~written]).all()
        assert (got[:, :, written] != before[:, :, written]).any(-1).all()


def test_paged_op_without_chunk_lens_is_one_token_a_row():
    """The documented decode call, x [B, 1, E] and no `chunk_lens`, is
    the chunk call with `chunk_lens = ones(B)`: output and every layer's
    cache bit for bit."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn.functional import fused_multi_transformer
    lens = np.array([9, 30, 0, 17, 2, 31, 8, 25], np.int32)
    ones = np.ones(B, np.int32)
    caches, toks, _, _, tables, _, _, _ = _step_inputs(ones, lens, seed=3)
    work, _, _, pack = pa.build_ragged_work(
        tables, lens + 1, BS, pa.default_pack(B, H // G),
        bucket_to=pa.next_pow2)      # no q_lens: one query a row
    w = _weights()

    def call(ragged_work=tuple(work), **chunk):
        cts = [Tensor(jnp.asarray(c)) for c in caches]
        out = fused_multi_transformer(
            Tensor(jnp.asarray(w["embedding"])[toks[:, :1]]),
            w["ln_scales"], None, w["qkv_weights"], w["qkv_biases"],
            w["linear_weights"], None, w["ffn_ln_scales"], None,
            w["ffn1_weights"], None, w["ffn2_weights"], None,
            cache_kvs=cts, time_step=Tensor(jnp.zeros((), jnp.int32)),
            seq_lens=Tensor(jnp.asarray(lens)),
            rotary_embs=jnp.asarray(w["rotary_embs"]),
            block_tables=tables, ragged_work=ragged_work,
            ragged_pack=pack, norm_type="rmsnorm", activation="swiglu",
            use_neox_rotary_style=True, gqa_group_size=G, **chunk)
        return np.asarray(out.data), [np.asarray(c.data) for c in cts]

    out, got = call()
    want_out, want = call(chunk_lens=Tensor(jnp.asarray(ones)))
    assert out.shape == (B, 1, E) and np.abs(out).max() > 0
    np.testing.assert_array_equal(out, want_out)
    for g, wnt, before in zip(got, want, caches):
        np.testing.assert_array_equal(g, wnt)
        assert (g != before).any()

    # the work list is the host's to build: there is no eager fallback
    with pytest.raises(ValueError, match="ragged_work"):
        call(ragged_work=None)


def test_live_rows_packs_slot_major_and_maps_back():
    q_lens = np.array([3, 0, 2, 0, 1, 0, 0, 0], np.int32)
    rows = pa.live_rows(q_lens, C)
    assert int(rows.n_tiles) == 1 and rows.slot.shape == (B * C,)
    assert np.asarray(rows.slot)[:6].tolist() == [0, 0, 0, 2, 2, 4]
    assert np.asarray(rows.col)[:6].tolist() == [0, 1, 2, 0, 1, 0]
    assert np.asarray(rows.live).sum() == 6
    back = np.asarray(rows.back)
    assert back[0, :3].tolist() == [0, 1, 2] and back[2, :2].tolist() == [3, 4]
    assert back[4, 0] == 5 and back.max() < B * C
    # dead rows' indices stay in range: they are gathered, never used
    assert np.asarray(rows.slot).max() < B and np.asarray(rows.col).max() < C
    for live, tiles in ((0, 0), (1, 1), (256, 1), (257, 2), (512, 2)):
        ql = np.zeros(B, np.int32)
        ql[:live // C] = C
        ql[live // C % B] += live % C
        assert int(pa.live_rows(ql, C).n_tiles) == tiles, live


# -- through the scheduler ----------------------------------------------------------

@pytest.fixture(scope="module")
def mixed_run():
    """Five 70-token prompts and two short ones through the scheduler at
    8 slots x 64-wide chunks: every step's twelve arguments, the
    registry's gain and the tokens served."""
    from paddle_tpu import observability as obs
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)
    old, fa._INTERPRET = fa._INTERPRET, True
    try:
        eng = _engine()
        cb = ContinuousBatchingEngine(eng, num_blocks=NB, block_size=BS,
                                      max_batch=B, prefill_chunk=C)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, V, n).astype(np.int32)
                   for n in (70, 70, 70, 70, 70, 3, 9)]
        reqs = [GenerationRequest(p, 4) for p in prompts]
        real, steps = eng._paged_step, []

        def record(*args):
            # the scheduler's arrays are its own buffers, rewritten in
            # place for the next step: keep copies
            steps.append(tuple(
                a.copy() if isinstance(a, np.ndarray) else a for a in args))
            return real(*args)

        eng._paged_step = record
        snap0 = obs.get_registry().snapshot()
        try:
            for r in reqs:
                cb.submit(r)
            served = cb.run()
        finally:
            eng._paged_step = real
        snap1 = obs.get_registry().snapshot()
        want = [eng.generate(p[None, :], max_new_tokens=4)[0, :4].tolist()
                for p in prompts]
    finally:
        fa._INTERPRET = old
    got = [np.asarray(served[r.request_id]).tolist() for r in reqs]
    return dict(cb=cb, steps=steps, got=got, want=want,
                snaps=(snap0, snap1))


def test_wide_slabs_serve_the_dense_engines_tokens(mixed_run):
    assert mixed_run["got"] == mixed_run["want"]
    assert any(a[2].shape[1] == C for a in mixed_run["steps"])


def test_slab_counter_counts_the_rows_computed(mixed_run):
    """On chunk steps `capacity` rises by the rows the row-wise layers
    computed: ceil(live / ROW_TILE) tiles of a wide slab, the whole
    slab of a narrow one; `live` rises by the grants."""
    def value(snap, kind):
        c = snap.get("serve_slab_tokens_total", {}).get(
            "children", {}).get(kind)
        return c["value"] if c else 0.0

    live = capacity = 0
    seen = set()
    for args in mixed_run["steps"]:
        toks, q_lens = args[2], np.asarray(args[3])
        b, c = toks.shape
        if c == 1 and q_lens.max() <= 1:
            continue            # a decode step: not counted
        n = int(q_lens.sum())
        live += n
        if b * c > pa.ROW_TILE:
            capacity += -(-n // pa.ROW_TILE) * pa.ROW_TILE
            seen.add(("tiled", -(-n // pa.ROW_TILE)))
        else:
            capacity += b * c
            seen.add(("whole", c))
    # five slots prefill 64 tokens at once: two tiles; their last six
    # tokens ride an 8-wide slab, which is one tile as it stands
    assert ("tiled", 2) in seen and ("whole", 8) in seen
    snap0, snap1 = mixed_run["snaps"]
    assert value(snap1, "live") - value(snap0, "live") == live
    assert value(snap1, "capacity") - value(snap0, "capacity") == capacity


def test_step_keeps_its_twelve_arguments_and_its_buckets(mixed_run):
    """What `perfbench/drivers/serve.py` `compile_ahead` unpacks and
    lowers a bucket from: twelve positionals, a slab [max_batch, c], a
    `sel` [max_batch, min(c, 1 + spec_k)], nine work arrays of one
    length t; and no program beyond the (t, c) pairs."""
    cb, buckets = mixed_run["cb"], set()
    for args in mixed_run["steps"]:
        assert len(args) == 12
        (_, caches, toks, q_lens, sel, tables, lens, work, pack, temp,
         topp, key) = args
        c = toks.shape[1]
        assert toks.shape == (B, c) and c & (c - 1) == 0
        assert sel.shape == (B, min(c, 1 + cb.spec_k))
        assert q_lens.shape == lens.shape == (B,)
        assert len(work) == 9 and len({a.shape for a in work}) == 1
        assert isinstance(pack, int) and len(caches) == L
        buckets.add((work[0].shape[0], c))
    assert buckets == set(cb._seen_buckets)
