"""A model whose layers differ, through the engine's paged path: window
layers with a sink beside full layers of another kv-head count, keys
wider than values, a partial rotary embedding on two bases, a value
scale, a dense feed-forward beside routed experts of which this device
holds a share, and a final norm — described per layer
(`incubate.nn.functional.LayerSpec`) and held to the plain reference of
`perfbench/models/mimo_v2_flash.py` on LOGITS.

CPU, float32, interpret-mode kernels, a toy's widths. The tolerance is
float32 rounding through four layers (the engine sums attention block by
block with a running maximum and multiplies experts' rows grouped, the
reference does neither): logits of size ~0.5 agree to ~1e-5, the limit
is 2e-4, and every control — a reference with one of the model's
equations changed — misses it by over ten times."""
import importlib.util
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                    GenerationRequest)
from paddle_tpu.incubate.nn.functional import (MOE_SLAB, ExpertSpec,
                                               expert_ffn)
from paddle_tpu.inference import FusedMultiTransformerEngine
from paddle_tpu.ops.pallas import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


def _family():
    spec = importlib.util.spec_from_file_location(
        "mimo_v2_flash_under_test",
        os.path.join(REPO, "perfbench", "models", "mimo_v2_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


CFG = dict(
    hidden_size=64, num_attention_heads=8, head_dim=24, v_head_dim=16,
    partial_rotary_factor=0.334, num_key_value_heads=2,
    swa_num_key_value_heads=4, intermediate_size=128,
    moe_intermediate_size=32, vocab_size=96, num_hidden_layers=4,
    hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
    sliding_window=8, rope_theta=5e6, swa_rope_theta=1e4,
    experts_routed_over=16, num_experts_per_tok=4, expert_first=4,
    n_routed_experts=4, attention_value_scale=0.707,
    layernorm_epsilon=1e-5, dtype="float32",
    engine=dict(max_seq_len=64))
SEED = 2 ** 31 + 77
BLOCK, CHUNK = 4, 8


def _engines(fam, cfg=CFG, **cb_kw):
    engine = FusedMultiTransformerEngine(
        fam.serve_weights(SEED, cfg), **fam.serve_engine_kwargs(cfg))
    cb = ContinuousBatchingEngine(
        engine, num_blocks=40, block_size=BLOCK, max_batch=2,
        prefill_chunk=CHUNK, **cb_kw)
    return engine, cb


@pytest.fixture(scope="module")
def served():
    """Two requests of unequal length through chunked prefill and on
    through decoding, several windows deep; every step's logits at the
    sampled positions, by request and absolute position."""
    old, fa._INTERPRET = fa._INTERPRET, True
    try:
        fam = _family()
        engine, cb = _engines(fam)
        logits_of = jax.jit(engine._paged_logits, static_argnums=(8,))
        real = engine._paged_step
        seen = {}

        def step(w, caches, slab, q, sel, tables, lens, work, pack, *rest):
            lg, *_ = logits_of(w, caches, slab, q, sel, tables, lens, work,
                              pack)
            lg = np.asarray(lg)
            for i, req in enumerate(cb.slots):
                if req is not None and q[i] > 0:
                    seen[(req.request_id, int(lens[i] + sel[i, 0]))] = \
                        lg[i, 0]
            return real(w, caches, slab, q, sel, tables, lens, work, pack,
                        *rest)

        engine._paged_step = step
        rng = np.random.default_rng(5)
        reqs = [GenerationRequest(rng.integers(1, 96, n), m, request_id=r)
                for r, n, m in (("long", 21, 30), ("short", 9, 20))]
        for r in reqs:
            cb.submit(r)
        cb.run()
        ids = {r.request_id: np.asarray(
            list(r.prompt) + list(cb.finished[r.request_id]), np.int32)
            for r in reqs}
        return fam, seen, ids, cb
    finally:
        fa._INTERPRET = old


def _worst(fam, cfg, seen, ids):
    """Largest |engine logit - reference logit| over everything sampled."""
    worst = 0.0
    for rid, seq in ids.items():
        x, outer = fam.forward(SEED, cfg, seq[None])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(x[0] @ outer["lm_head"].astype(jnp.float32))
        for (r, pos), lg in seen.items():
            if r == rid:
                worst = max(worst, float(np.abs(lg - ref[pos]).max()))
    return worst


def test_the_paged_path_gives_the_references_logits(served):
    fam, seen, ids, cb = served
    # prefill chunks of both requests, then every decoded position
    assert len(seen) >= 21 // CHUNK + 9 // CHUNK + 30 + 20 - 2
    assert max(pos for _, pos in seen) >= 21 + 30 - 2   # six windows deep
    assert _worst(fam, CFG, seen, ids) < TOL


def _patched(fam, name, make):
    real = getattr(fam, name)
    setattr(fam, name, make(real))
    return real


def _no_sink(real):
    def layer_tensors(*a):
        t = dict(real(*a))
        if "sink" in t:
            t["sink"] = jnp.full_like(t["sink"], -1e9)
        return t
    return layer_tensors


def _bias_weighs(real):
    def routing(z, router, router_b, top_k):
        sigma = jax.nn.sigmoid(z @ router) + router_b
        _, sel = jax.lax.top_k(sigma, top_k)
        picked = jnp.take_along_axis(sigma, sel, axis=1)
        w = picked / jnp.sum(picked, axis=1, keepdims=True)
        return jnp.zeros_like(sigma).at[
            jnp.arange(z.shape[0])[:, None], sel].set(w)
    return routing


def _normalised_over_the_held(real):
    def experts(z, t, d, lo=None):
        w = _family().routing(z, t["router"], t["router_b"], d.top_k)
        mine = jnp.zeros_like(w).at[:, d.lo:d.lo + d.held].set(
            w[:, d.lo:d.lo + d.held])
        mine = mine / jnp.maximum(mine.sum(1, keepdims=True), 1e-9)
        out = 0.0
        for e in range(d.held):
            gu = z @ t["w13"][e]
            f = gu.shape[-1] // 2
            out = out + mine[:, d.lo + e, None] * (
                (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ t["w2"][e])
        return out
    return experts


@pytest.mark.parametrize("control", [
    "no_sink", "window_off_by_one", "bias_added_to_the_weights",
    "weights_normalised_over_held_experts", "value_scale_dropped"])
def test_a_reference_with_one_equation_changed_misses_the_tolerance(
        served, control):
    _, seen, ids, _ = served
    fam, cfg = _family(), dict(CFG)
    if control == "no_sink":
        _patched(fam, "layer_tensors", _no_sink)
    elif control == "window_off_by_one":
        cfg["sliding_window"] = CFG["sliding_window"] + 1
    elif control == "bias_added_to_the_weights":
        _patched(fam, "routing", _bias_weighs)
    elif control == "weights_normalised_over_held_experts":
        _patched(fam, "experts", _normalised_over_the_held)
    else:
        cfg["attention_value_scale"] = 1.0
    assert _worst(fam, cfg, seen, ids) > 10 * TOL


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """4 chips x 4 experts: the engine's expert layer told each share in
    turn, summed, is the reference's layer holding all 16 (and each
    share is the reference's share)."""
    fam = _family()
    cfg = dict(CFG, expert_first=0, n_routed_experts=16)
    d = fam.dims(cfg)
    t = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        fam.layer_tensors(fam._key(SEED), 1, d, jnp.dtype("float32")))
    z = jax.random.normal(jax.random.key(3), (37, d.E), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(fam.experts(z, t, d, lo=0))
        total, touched = 0.0, 0
        for lo in range(0, 16, 4):
            part = dict(t, w13=t["w13"][lo:lo + 4], w2=t["w2"][lo:lo + 4])
            got, counts, _ = expert_ffn(
                z, t["router"], t["router_b"], part["w13"], part["w2"],
                ExpertSpec(n_routed=16, top_k=4, lo=lo, held=4),
                jnp.ones(37, bool), jax.nn.silu)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(fam.experts(z, part, d, lo=lo)),
                atol=2e-6)
            total = total + np.asarray(got)
            touched += int(counts.sum())
    assert touched == 37 * 4            # every assignment fell on one share
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert np.abs(whole).max() > 1e-3


# expert_ffn against the family's plain `experts`, by where the router's
# bias sends the assignments (a bias of 10 on an expert puts it among
# every token's top_k; the weights are the scores', the bias selects
# only): rows, top_k, experts held of 16 routed, experts the bias
# favours (held ones are 4 .. 4 + held - 1), share of dead rows, and the
# trips of the slab loop where the case fixes them (SOME: as many as the
# reference's held assignments fill)
SOME = object()
_ROUTINGS = {
    "nothing_falls_here_zero_trips": (80, 4, 4, (0, 1, 2, 3), 0.0, 0),
    "all_held_one_and_a_half_slabs": (
        3 * MOE_SLAB // 8, 4, 8, tuple(range(4, 12)), 0.0, 2),
    "all_held_three_slabs": (
        3 * MOE_SLAB // 4, 4, 8, tuple(range(4, 12)), 0.0, 3),
    "held_assignments_exactly_one_slab": (
        MOE_SLAB // 2, 4, 4, (0, 1, 4, 5), 0.0, 1),
    "one_expert_takes_every_row": (
        MOE_SLAB + 44, 4, 4, (0, 1, 2, 6), 0.0, 2),
    "dead_rows_among_live_ones": (
        3 * MOE_SLAB // 4, 4, 8, tuple(range(4, 12)), 0.35, SOME),
    "free_routing_some_held": (MOE_SLAB // 2, 4, 4, (), 0.0, SOME),
    "fewer_assignment_rows_than_a_slab": (
        MOE_SLAB // 8, 4, 4, (), 0.2, SOME),
}


@pytest.mark.parametrize("routing", list(_ROUTINGS))
def test_every_held_assignment_is_multiplied_whatever_the_distribution(
        routing):
    """Output, per-expert counts and the rows the products were handed,
    in float32 at `highest` precision: slabs of MOE_SLAB sorted rows
    (of all R x top_k where those are fewer), as many as the held
    assignments fill, give what one product over all R x top_k rows
    gave, for any distribution of the assignments."""
    rows, top_k, held, favoured, dead, trips = _ROUTINGS[routing]
    fam = _family()
    d = fam.dims(dict(CFG, expert_first=4, n_routed_experts=held,
                      num_experts_per_tok=top_k))
    t = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        fam.layer_tensors(fam._key(SEED), 1, d, jnp.dtype("float32")))
    t["router_b"] = t["router_b"].at[jnp.asarray(favoured, int)].add(10.0)
    kz, kl = jax.random.split(jax.random.key(11))
    z = jax.random.normal(kz, (rows, d.E), jnp.float32)
    live = jax.random.uniform(kl, (rows,)) >= dead
    with jax.default_matmul_precision("highest"):
        got, counts, handed = jax.jit(
            lambda z, live: expert_ffn(
                z, t["router"], t["router_b"], t["w13"], t["w2"],
                ExpertSpec(n_routed=16, top_k=top_k, lo=d.lo, held=held),
                live, jax.nn.silu))(z, live)
        want = np.asarray(fam.experts(z, t, d)) * np.asarray(live)[:, None]
        sent = np.asarray(fam.routing(
            z, t["router"], t["router_b"], top_k))[:, d.lo:d.lo + held] > 0
    want_counts = (sent & np.asarray(live)[:, None]).sum(0)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    here = int(want_counts.sum())
    width = min(rows * top_k, MOE_SLAB)
    if trips is SOME:
        trips = -(-here // width)
    assert int(handed) == trips * width
    assert trips == -(-here // width)
    assert (width < MOE_SLAB) == (routing.startswith("fewer"))
    if routing.startswith("all_held"):
        assert here == rows * top_k
        # some group lies across a slab's edge
        ends = np.cumsum(want_counts)
        assert any(lo < MOE_SLAB * k < hi for k in range(1, trips)
                   for lo, hi in zip(ends - want_counts, ends))
    elif routing == "held_assignments_exactly_one_slab":
        assert here == MOE_SLAB
    elif routing == "one_expert_takes_every_row":
        assert sorted(want_counts) == [0, 0, 0, rows]
    elif routing == "nothing_falls_here_zero_trips":
        assert here == 0 and not np.asarray(got).any()
    else:
        assert 0 < here < rows * top_k
    assert routing.startswith("nothing") or np.abs(want).max() > 1e-3


def test_window_blocks_come_back_and_both_tables_are_returned(served):
    fam, _, _, done = served
    # after the run above every block of both pools is back
    assert done.allocator.num_used == 0
    assert done.window_allocator.num_used == 0
    engine, cb = _engines(fam)
    # bytes by kind: 2 full layers of 2 kv heads, 2 window layers of 4,
    # rows padded to 128 lanes, float32
    per_head = 2 * BLOCK * 128 * 4
    assert cb.allocator.block_bytes == 2 * 2 * per_head
    assert cb.window_allocator.block_bytes == 2 * 4 * per_head
    assert [c.shape[1:3] for c in cb.caches] == [
        (2, 40), (4, cb.window_allocator.num_blocks),
        (4, cb.window_allocator.num_blocks), (2, 40)]
    # the window pool holds every slot's widest span, and no more
    assert cb.window_allocator.num_blocks == 1 + 2 * 5
    in_land, freed_in_land = [False], []
    real_land, real_free = cb._land, cb.window_allocator.free

    def land(*a):
        in_land[0] = True
        try:
            return real_land(*a)
        finally:
            in_land[0] = False

    def free(blocks):
        blocks = list(blocks)
        if in_land[0] and blocks:
            freed_in_land.extend(blocks)
        return real_free(blocks)

    cb._land, cb.window_allocator.free = land, free
    rng = np.random.default_rng(1)
    long = GenerationRequest(rng.integers(1, 96, 30), 12, request_id="a")
    cb.submit(long)
    held = []
    while cb.step():
        if cb.slots[0] is long:
            held.append((int(cb.lens[0]), len(long.blocks),
                         sorted(long.window_blocks)))
            assert cb.window_allocator.bytes_used == \
                len(long.window_blocks) * cb.window_allocator.block_bytes
    # full layers keep every block; window layers at most the span of a
    # chunk plus the window, and in decode the window's two or three
    assert max(n for _, n, _ in held) == -(-(30 + 12) // BLOCK)
    assert max(len(w) for _, _, w in held) <= 5
    assert all(len(w) <= 3 for ln, _, w in held if ln >= 32)
    # the table rows hold the live blocks and nothing behind them
    late = [w for ln, _, w in held if ln >= 38]
    assert late and all(min(w) >= (38 - 8 + 1) // BLOCK - 1 for w in late)
    assert cb.window_allocator.num_used == 0 and cb.allocator.num_used == 0
    # the commit of a step never gives a window block back: the step in
    # flight (dispatched before it) reads them
    assert freed_in_land == []
    # a preempted request hands both tables' blocks back
    low = GenerationRequest(rng.integers(1, 96, 20), 8, request_id="low",
                            priority=5)
    cb.submit(low)
    for _ in range(4):
        cb.step()
    assert cb.window_allocator.num_used > 0
    cb._preempt_slot(0, "test")
    assert cb.window_allocator.num_used == 0 and cb.allocator.num_used == 0
    assert not cb.window_tables.any() and not cb.tables.any()
    cb.run()
    assert len(cb.finished["low"]) == 8


def test_the_steps_count_their_tokens_and_their_routers_assignments():
    """`serve_tokens_stepped_total` rises by every token a step consumed
    (a request of n prompt tokens and m answers steps n + m - 1), the
    routers' assignments by top_k a token and expert layer, split into
    those on a held expert (the count that rides out with the samples,
    `engine.held_assignments`) and those elsewhere."""
    from paddle_tpu.observability import get_registry

    def value(snap, family, child=""):
        c = snap.get(family, {}).get("children", {}).get(child)
        return c["value"] if c else 0.0

    old, fa._INTERPRET = fa._INTERPRET, True
    try:
        fam = _family()
        engine, cb = _engines(fam)
        assert engine.held_assignments(np.asarray(cb._sampled)) == 0
        snap0 = get_registry().snapshot()
        rng = np.random.default_rng(9)
        sizes = ((13, 6), (5, 9))
        for i, (n, m) in enumerate(sizes):
            cb.submit(GenerationRequest(rng.integers(1, 96, n), m,
                                        request_id=f"r{i}"))
        cb.run()
        snap1 = get_registry().snapshot()
    finally:
        fa._INTERPRET = old
    gain = lambda *a: value(snap1, *a) - value(snap0, *a)
    stepped = sum(n + m - 1 for n, m in sizes)
    assert gain("serve_tokens_stepped_total") == stepped
    here = gain("serve_moe_assignments_total", "here")
    made = here + gain("serve_moe_assignments_total", "elsewhere")
    assert made == stepped * CFG["num_experts_per_tok"] \
        * sum(CFG["moe_layer_freq"])
    # 4 of 16 experts are held: about a quarter of the assignments
    assert 0 < here < made / 2


def test_what_the_description_cannot_serve_is_refused_at_construction():
    fam = _family()
    weights = fam.serve_weights(SEED, CFG)
    kw = fam.serve_engine_kwargs(CFG)
    engine = FusedMultiTransformerEngine(weights, **kw)
    with pytest.raises(ValueError, match="prefix_cache.*ROADMAP M3"):
        ContinuousBatchingEngine(engine, num_blocks=40, block_size=BLOCK,
                                 max_batch=2, prefix_cache=True)
    with pytest.raises(ValueError, match="spec_k.*ROADMAP M3"):
        ContinuousBatchingEngine(engine, num_blocks=40, block_size=BLOCK,
                                 max_batch=2, spec_k=2)
    with pytest.raises(ValueError, match="experts.*ROADMAP M2"):
        FusedMultiTransformerEngine(weights, tp=2, **kw)
    dense = dict(CFG, moe_layer_freq=[0, 0, 0, 0])
    with pytest.raises(ValueError, match="two kinds.*ROADMAP M3"):
        FusedMultiTransformerEngine(fam.serve_weights(SEED, dense), tp=2,
                                    **fam.serve_engine_kwargs(dense))
    with pytest.raises(NotImplementedError, match="paged path only"):
        engine.generate(np.ones((1, 4), np.int64), max_new_tokens=2)
