"""The routed experts multiply the assignments this device holds, in
slabs of MOE_SLAB sorted rows (`incubate.nn.functional.expert_ffn`):
the shape of that program, held in its text so that the gain cannot rot,
and the counter that says how it engages, through the scheduler on the
tiny expert model of `test_serve_block_description.py` (whose
parametrised test holds the function's results to the plain reference).
CPU, float32, interpret-mode kernels."""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                    GenerationRequest)
from paddle_tpu.incubate.nn.functional import (MOE_SLAB, ExpertSpec,
                                               expert_ffn)
from paddle_tpu.inference import FusedMultiTransformerEngine
from paddle_tpu.ops.pallas import flash_attention as fa

from test_serve_block_description import CFG, SEED, _family


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_no_product_gather_or_scatter_of_a_row_tile_passes_a_slab():
    """The gain's shape, held in the program's text: at a row tile's 256
    rows and 8 experts a token, the grouped products' operands are
    MOE_SLAB rows and no gather or scatter is taller than a slab or the
    tile's own 256 token rows (the parent's were all 2048 assignment
    rows); at the decode bucket's 16 rows the same loop runs over one
    slab of the call's own 16 x 8 rows."""
    ex = ExpertSpec(n_routed=256, top_k=8, lo=16, held=16)
    E, F = 64, 32

    def jaxpr(rows):
        f32 = jnp.float32
        return jax.make_jaxpr(
            lambda z, r, rb, w13, w2, live: expert_ffn(
                z, r, rb, w13, w2, ex, live, jax.nn.silu))(
            jnp.zeros((rows, E), f32), jnp.zeros((E, 256), f32),
            jnp.zeros(256, f32), jnp.zeros((16, E, 2 * F), f32),
            jnp.zeros((16, F, E), f32), jnp.ones(rows, bool)).jaxpr

    def tall_and_seen(rows):
        slab = min(rows * ex.top_k, MOE_SLAB)
        tall, seen = [], set()
        for eqn in _eqns(jaxpr(rows)):
            name = eqn.primitive.name
            seen.add(name)
            limit = max(slab, rows)
            if name.startswith("ragged_dot"):
                shapes = [eqn.invars[0].aval.shape,
                          eqn.outvars[0].aval.shape]
                limit = slab
                assert shapes[0][0] == slab
            elif name == "gather":
                shapes = [eqn.outvars[0].aval.shape]
            elif name.startswith("scatter"):
                shapes = [eqn.invars[2].aval.shape]     # the updates
            else:
                continue
            tall += [(name, s) for s in shapes if s and s[0] > limit]
        return tall, seen

    for rows in (256, 16):
        tall, seen = tall_and_seen(rows)
        assert not tall
        assert "while" in seen and not seen & {"scan", "cond"}
        assert any(n.startswith("ragged_dot") for n in seen)
        assert {"gather", "scatter-add"} <= seen
    assert 256 * ex.top_k > MOE_SLAB >= 16 * ex.top_k


def _held_by_position(fam, cfg, seq):
    """The reference's router, layer by layer over one sequence: how many
    of each position's top_k assignments fall on a held expert, [expert
    layers, S]."""
    d, dtype, key = fam.dims(cfg), jnp.dtype(cfg["dtype"]), fam._key(SEED)
    tables = {th: tuple(jnp.asarray(a)
                        for a in fam.rotary_table(th, d.rot, len(seq)))
              for th in set(d.theta)}
    held = []
    with jax.default_matmul_precision("highest"):
        x = fam._f32(fam.outer_tensors(key, d, dtype)["embedding"][
            jnp.asarray(seq)])
        for li in range(d.L):
            t, k = fam.layer_tensors(key, li, d, dtype), fam.kind_of(d, li)
            h = x + fam.attention(x, t, k, d, *tables[d.theta[li]])
            if k.moe:
                w = fam.routing(fam._rms(h, t["ln2"], d.eps), t["router"],
                                t["router_b"], d.top_k)
                held.append(np.asarray(
                    (w[:, d.lo:d.lo + d.held] > 0).sum(1)))
            x = h + fam.feed_forward(h, t, k, d)
    return np.stack(held)


def test_the_steps_count_the_rows_their_grouped_products_were_handed():
    """`serve_moe_slab_rows_total` rises, per expert layer and row tile
    of a step, by the slab (MOE_SLAB rows, or the tile's R x top_k where
    those are fewer) x the slabs the tile's held assignments fill,
    reckoned here from the reference's router over each request's tokens
    and the rows each step held; the held assignments themselves are
    `serve_moe_assignments_total{where="here"}`. Five prompts prefill at
    once in a slab of 8 x 64 rows, so the first steps are wide ones of
    two row tiles."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.ops.pallas.paged_attention import ROW_TILE

    def value(snap, family, child=""):
        c = snap.get(family, {}).get("children", {}).get(child)
        return c["value"] if c else 0.0

    old, fa._INTERPRET = fa._INTERPRET, True
    try:
        fam = _family()
        cfg = dict(CFG, engine=dict(max_seq_len=128))
        engine = FusedMultiTransformerEngine(
            fam.serve_weights(SEED, cfg), **fam.serve_engine_kwargs(cfg))
        cb = ContinuousBatchingEngine(
            engine, num_blocks=100, block_size=8, max_batch=8,
            prefill_chunk=64)
        real, steps = engine._paged_step, []

        def step(w, caches, slab, q, *rest):
            lens = rest[2]
            steps.append((slab.shape, [
                (req.request_id, int(lens[i]), int(q[i]))
                for i, req in enumerate(cb.slots)
                if req is not None and q[i] > 0]))
            return real(w, caches, slab, q, *rest)

        engine._paged_step = step
        snap0 = get_registry().snapshot()
        rng = np.random.default_rng(4)
        reqs = [GenerationRequest(rng.integers(1, 96, n), m,
                                  request_id=f"r{i}")
                for i, (n, m) in enumerate(
                    ((72, 3), (70, 2), (69, 3), (71, 2), (68, 3)))]
        for r in reqs:
            cb.submit(r)
        cb.run()
        snap1 = get_registry().snapshot()
    finally:
        fa._INTERPRET = old
    held = {r.request_id: _held_by_position(
        fam, cfg, np.asarray(list(r.prompt) + list(
            cb.finished[r.request_id]), np.int32)) for r in reqs}
    top_k, computed, live, tiles = CFG["num_experts_per_tok"], 0, 0, set()
    for (b, c), slots in steps:
        # the step's live rows in packed order: slot by slot, each
        # slot's columns ascending; [expert layers, live rows]
        rows = np.concatenate(
            [held[rid][:, at:at + n] for rid, at, n in slots], axis=1)
        tile = ROW_TILE if b * c > ROW_TILE else b * c
        for r0 in range(0, rows.shape[1], tile):
            here = rows[:, r0:r0 + tile].sum(1)      # per expert layer
            live += int(here.sum())
            slab = min(tile * top_k, MOE_SLAB)
            slabs = -(-here // slab)
            tiles.add((tile, tuple(slabs)))
            computed += int(slabs.sum()) * slab
    gain = lambda *a: value(snap1, *a) - value(snap0, *a)
    assert gain("serve_moe_slab_rows_total") == computed
    assert gain("serve_moe_assignments_total", "here") == live
    # wide steps of two tiles, tiles of fewer and of more slabs (more
    # than one among them), narrow calls of less than a slab and layers
    # that got nothing (no trip) were all among them
    assert sum(b * c > ROW_TILE and sum(n for _, _, n in s) > ROW_TILE
               for (b, c), s in steps) >= 1
    most = {max(slabs) for tile, slabs in tiles if tile == ROW_TILE}
    assert len(most) >= 2 and max(most) > 1
    assert any(tile * top_k < MOE_SLAB for tile, _ in tiles)
    assert any(0 in slabs for _, slabs in tiles)
    assert 0 < live < computed
