"""Tensor-parallel serving on the virtual 8-device mesh (interpret
mode on CPU — conftest forces --xla_force_host_platform_device_count=8).

The contract under test: a FusedMultiTransformerEngine built with
``tp > 1`` — weights Megatron-split per inference/tp_layout.py, paged
KV cache and ragged work-list kernel sharded over kv-heads, the three
paged programs shard_map'd over the mesh — is TOKEN-EXACT vs the
single-chip engine in EVERY serving mode, while per-device KV bytes
drop by the TP factor and the bucketed compile keys stay on the same
treadmill (zero new buckets after warmup, per mesh shape).

The matrix: plain / chunked / budgeted / spec / prefix, plus cancel
and preempt-resume, at TP=2 in tier-1; the TP=4 and TP=8 mesh shapes
re-run the core matrix in the slow tier (same engines, heavier
interpret-mode wall). The layout repacking (GQA row blocks, *glu
column pairing) is pinned by direct round-trip tests so a silent
permutation bug cannot hide behind an accidentally-symmetric weight.
"""
import numpy as np
import pytest

# Tier-1 window: ~100s of TP=2 interpret-mode serving on the 1-core CI
# box — runs in the `pytest -m slow` tier (split in BASELINE.md).
pytestmark = pytest.mark.slow

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


# one TP-able tiny shape: 8 q heads / 8 kv heads (GQA packing), so the
# kv-head axis splits evenly at tp = 1/2/4/8 on the 8-device mesh
V, E, H, G, D, L, F = 128, 64, 8, 8, 8, 2, 96
_WEIGHTS = None
_ENGINES = {}
_uid = [0]


def _tag(prefix):
    _uid[0] += 1
    return f"{prefix}{_uid[0]}"


def _weights():
    global _WEIGHTS
    if _WEIGHTS is None:
        rng = np.random.default_rng(0)

        def mk(*shape, scale=0.05):
            return (rng.standard_normal(shape) * scale).astype(np.float32)

        _WEIGHTS = dict(
            ln_scales=[np.ones(E, np.float32) for _ in range(L)],
            qkv_weights=[mk(H + 2 * G, D, E) for _ in range(L)],
            linear_weights=[mk(H * D, E) for _ in range(L)],
            ffn_ln_scales=[np.ones(E, np.float32) for _ in range(L)],
            ffn1_weights=[mk(E, 2 * F) for _ in range(L)],
            ffn2_weights=[mk(F, E) for _ in range(L)],
            embedding=mk(V, E), lm_head=mk(E, V))
    return _WEIGHTS


def _engine(tp):
    """Engines are cached per tp: every test reuses the same compiled
    mesh programs (the warm-bucket treadmill the suite leans on for
    wall time)."""
    if tp not in _ENGINES:
        from paddle_tpu.inference import FusedMultiTransformerEngine
        _ENGINES[tp] = FusedMultiTransformerEngine(
            dict(_weights()), num_heads=H, head_dim=D, max_seq_len=64,
            dtype="float32", norm_type="rmsnorm", activation="swiglu",
            gqa_group_size=G, tp=tp)
    return _ENGINES[tp]


def _cb(tp, **kw):
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    return ContinuousBatchingEngine(_engine(tp), **kw)


def _reqs(tag, workload, seed=7, **req_kw):
    from paddle_tpu.incubate.nn import GenerationRequest
    rng = np.random.default_rng(seed)
    return [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n,
                              request_id=f"{tag}r{j}", **req_kw)
            for j, (p, n) in enumerate(workload)]


WORKLOAD = [(5, 4), (11, 3), (3, 6), (8, 2)]


def _run(cb, reqs):
    for r in reqs:
        cb.submit(r)
    out = cb.run()
    return [list(out[r.request_id]) for r in reqs]


def _ref(mode):
    """Single-chip reference outputs, computed once per mode and
    shared across every tp parametrization."""
    if mode not in _REFS:
        _REFS[mode] = _MODES[mode](1)
    return _REFS[mode]


_REFS = {}


def _mode_plain(tp):
    cb = _cb(tp)
    return _run(cb, _reqs(_tag(f"pl{tp}_"), WORKLOAD))


def _mode_chunked(tp):
    cb = _cb(tp, prefill_chunk=4, token_budget=6)
    return _run(cb, _reqs(_tag(f"ch{tp}_"), WORKLOAD))


def _mode_spec(tp):
    from paddle_tpu.incubate.nn import GenerationRequest
    pattern = [7, 23, 41, 11]
    cb = _cb(tp, max_batch=2, prefill_chunk=8, spec_k=4)
    reqs = [GenerationRequest(np.asarray(pattern * 6, np.int32), 10,
                              request_id=_tag(f"sp{tp}_")),
            GenerationRequest(np.asarray(pattern * 3, np.int32), 10,
                              request_id=_tag(f"sp{tp}_"))]
    toks = _run(cb, reqs)
    return toks + [[cb._step_count, sum(r.spec_drafted for r in reqs),
                    sum(r.spec_accepted for r in reqs)]]


def _mode_prefix(tp):
    from paddle_tpu.incubate.nn import GenerationRequest
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, V, 24).astype(np.int32)
    cb = _cb(tp, prefill_chunk=8, prefix_cache=True)
    reqs = [GenerationRequest(
        np.concatenate([prefix, rng.integers(1, V, 3).astype(np.int32)]),
        4, request_id=_tag(f"pf{tp}_")) for _ in range(4)]
    toks = _run(cb, reqs)
    return toks + [[cb.cache_stats["hit_blocks"],
                    cb.cache_stats["cow_copies"],
                    cb.allocator.high_water]]


_MODES = {"plain": _mode_plain, "chunked": _mode_chunked,
          "spec": _mode_spec, "prefix": _mode_prefix}


class TestLayoutRepack:
    """The permutations that make contiguous PartitionSpec splits
    meaningful — pinned directly, because a wrong permutation can be
    numerically plausible on symmetric random weights."""

    def test_gqa_qkv_roundtrip(self):
        from paddle_tpu.inference.tp_layout import (repack_gqa_qkv,
                                                    unpack_gqa_qkv)
        w = np.arange((H + 2 * G) * D * E, dtype=np.float32).reshape(
            H + 2 * G, D, E)
        for tp in (1, 2, 4, 8):
            rp = repack_gqa_qkv(w, H, G, tp)
            np.testing.assert_array_equal(
                unpack_gqa_qkv(rp, H, G, tp), w)

    def test_gqa_local_blocks_are_valid_packings(self):
        from paddle_tpu.inference.tp_layout import repack_gqa_qkv
        w = np.arange((H + 2 * G) * D * E, dtype=np.float32).reshape(
            H + 2 * G, D, E)
        tp = 4
        rp = repack_gqa_qkv(w, H, G, tp)
        hq, hk = H // tp, G // tp
        rows = hq + 2 * hk
        for d in range(tp):
            blk = rp[d * rows:(d + 1) * rows]
            # local q/k/v rows are the device's global head slices
            np.testing.assert_array_equal(
                blk[:hq], w[d * hq:(d + 1) * hq])
            np.testing.assert_array_equal(
                blk[hq:hq + hk], w[H + d * hk:H + (d + 1) * hk])
            np.testing.assert_array_equal(
                blk[hq + hk:], w[H + G + d * hk:H + G + (d + 1) * hk])

    def test_glu_column_pairing(self):
        from paddle_tpu.inference.tp_layout import repack_glu_ffn1
        w = np.arange(E * 2 * F, dtype=np.float32).reshape(E, 2 * F)
        tp = 4
        rp = repack_glu_ffn1(w, tp)
        fl = F // tp
        for d in range(tp):
            blk = rp[:, d * 2 * fl:(d + 1) * 2 * fl]
            a, g = np.split(blk, 2, axis=-1)
            # local split pairs a-col j with ITS gate col (j + F global)
            np.testing.assert_array_equal(a, w[:, d * fl:(d + 1) * fl])
            np.testing.assert_array_equal(
                g, w[:, F + d * fl:F + (d + 1) * fl])

    def test_kv_head_shard_contract(self):
        assert pa.kv_head_shard(8, 4) == 2
        assert pa.kv_head_shard(8, 4, rank=3) == (6, 2)
        with pytest.raises(ValueError):
            pa.kv_head_shard(6, 4)
        with pytest.raises(ValueError):
            pa.kv_head_shard(8, 4, rank=4)

    def test_engine_rejects_indivisible_tp(self):
        from paddle_tpu.inference import FusedMultiTransformerEngine
        w = _weights()
        with pytest.raises(ValueError, match="divisible"):
            FusedMultiTransformerEngine(
                dict(w), num_heads=H, head_dim=D, max_seq_len=64,
                dtype="float32", norm_type="rmsnorm",
                activation="swiglu", gqa_group_size=G, tp=3)

    def test_engine_rejects_negative_tp(self):
        # a negative width must fail at construction, not serve
        # single-chip while poisoning the mesh-aware health surfaces
        from paddle_tpu.inference import FusedMultiTransformerEngine
        with pytest.raises(ValueError, match="tp must be >= 1"):
            FusedMultiTransformerEngine(
                dict(_weights()), num_heads=H, head_dim=D,
                max_seq_len=64, dtype="float32", norm_type="rmsnorm",
                activation="swiglu", gqa_group_size=G, tp=-2)

    def test_generate_refuses_tp(self):
        with pytest.raises(NotImplementedError, match="tp=1"):
            _engine(2).generate(np.ones((1, 4), np.int32),
                                max_new_tokens=2)


class TestTokenExactTP2:
    """Every serving mode, TP=2 vs single-chip — the tier-1 core."""

    @pytest.mark.parametrize("mode", ["plain", "chunked", "spec",
                                      "prefix"])
    def test_mode(self, mode):
        assert _MODES[mode](2) == _ref(mode)

    def test_cancel_midflight(self):
        # same cancel schedule on both engines: step twice, cancel the
        # longest request mid-decode, drain — partial tokens must match
        def run(tp):
            cb = _cb(tp)
            reqs = _reqs(_tag(f"cx{tp}_"), [(5, 6), (9, 6)])
            for r in reqs:
                cb.submit(r)
            for _ in range(4):
                cb.step()
            assert cb.cancel(reqs[1].request_id)
            cb.run()
            res = cb.finished[reqs[1].request_id]
            return ([list(cb.finished[r.request_id]) for r in reqs],
                    res.status)
        ref = run(1)
        assert ref[1] == "cancelled"
        assert run(2) == ref

    def test_preempt_resume(self):
        # tight pool + a priority-0 arrival preempts the newest low-
        # priority request TO BLOCKS; the resumed generation must be
        # token-exact on both mesh shapes, with the same preemption
        def run(tp):
            cb = _cb(tp, num_blocks=7, max_batch=2)
            low = _reqs(_tag(f"pe{tp}l_"), [(9, 6), (9, 6)], seed=11,
                        priority=2)
            for r in low:
                cb.submit(r)
            cb.step()
            cb.step()
            hi = _reqs(_tag(f"pe{tp}h_"), [(8, 4)], seed=12,
                       priority=0)[0]
            cb.submit(hi)
            cb.run()
            pre = [cb.finished[r.request_id].preemptions for r in low]
            return ([list(cb.finished[r.request_id])
                     for r in low + [hi]], pre)
        ref = run(1)
        assert sum(ref[1]) >= 1, "workload failed to force a preemption"
        assert run(2) == ref


class TestMeshAccounting:
    def test_kv_device_bytes_drop_by_tp(self):
        bs = 8
        single = _engine(1).kv_device_block_bytes(bs)
        # the bytes the cache really stores: rows padded to the lane tile
        assert single == L * 2 * G * bs * pa.paged_head_dim(D) * 4
        for tp in (2, 4, 8):
            assert _engine(tp).kv_device_block_bytes(bs) * tp == single

    def test_step_comm_bytes_aval_math(self):
        eng = _engine(2)
        assert eng.tp_step_comm_bytes(4, 8) == 2 * L * 4 * 8 * E * 4
        assert _engine(1).tp_step_comm_bytes(4, 8) == 0
        # a slab of more than ROW_TILE rows reduces its live tokens' row
        # tiles only: 70 live of 8 x 64 is one tile, 257 are two
        tile = pa.ROW_TILE
        assert eng.tp_step_comm_bytes(4, 8, 3) == 2 * L * 4 * 8 * E * 4
        assert eng.tp_step_comm_bytes(8, 64, 70) == 2 * L * tile * E * 4
        assert eng.tp_step_comm_bytes(8, 64, tile + 1) \
            == eng.tp_step_comm_bytes(8, 64) == 2 * L * 2 * tile * E * 4

    def test_collective_telemetry_lands(self):
        from paddle_tpu import observability as obs
        reg = obs.get_registry()
        fam = reg.get("collective_bytes_total")
        before = (sum(c.value for c in fam._children.values())
                  if fam is not None else 0.0)
        obs.get_tracer().clear()
        cb = _cb(2)
        reqs = _reqs(_tag("ct_"), [(5, 3)])
        _run(cb, reqs)
        # one collective task per DISPATCHED step, each carrying the
        # analytic payload: 2 psums/layer over the step's [B, C, E]
        # slab (C from the matching serve_step span)
        steps = [s for s in obs.get_tracer().spans()
                 if s["name"] == "serve_step"]
        colls = [s for s in obs.get_tracer().spans()
                 if s["name"] == "collective"]
        assert len(colls) == len(steps) > 0
        for st, co in zip(steps, colls):
            assert co["args"]["op"] == "psum"
            assert co["args"]["axis"] == "tp"
            assert co["args"]["nbytes"] == cb.engine.tp_step_comm_bytes(
                cb.max_batch, st["args"]["chunk"])
        expected = sum(co["args"]["nbytes"] for co in colls)
        fam = reg.get("collective_bytes_total")
        delta = sum(c.value for c in fam._children.values()) - before
        assert delta == expected > 0
        # explain() reports comm time AFTER retirement (the figure
        # rides the RequestResult), and the live dict is empty — one
        # entry per request served must not accumulate forever
        ex = cb.explain(reqs[0].request_id)
        assert ex["tp"] == 2 and ex["comm_s"] > 0
        assert cb._comm_seconds == {}
        assert cb.finished[reqs[0].request_id].comm_s == ex["comm_s"]

    def test_gauges_return_to_baseline_after_churn(self):
        from paddle_tpu import observability as obs
        cb = _cb(2, prefill_chunk=8, spec_k=2, prefix_cache=True)
        _run(cb, _reqs(_tag("chn_"), WORKLOAD))
        _run(cb, _reqs(_tag("chn_"), WORKLOAD, seed=9))
        assert cb.allocator.num_used == 0
        assert cb.allocator._ref == {}
        snap = obs.get_registry().snapshot()
        used = snap["kv_device_bytes_used"]["children"]
        assert {k: v["value"] for k, v in used.items()} == \
            {"0": 0.0, "1": 0.0}
        hw = snap["kv_device_bytes_high_water"]["children"]
        assert hw["0"]["value"] == \
            cb.allocator.high_water * cb._kv_dev_block_bytes

    def test_zero_new_buckets_after_warm(self):
        cb = _cb(2, prefill_chunk=4, token_budget=6)
        _run(cb, _reqs(_tag("wb_"), WORKLOAD))
        cb.declare_warm()
        warm = set(cb._seen_buckets)
        _run(cb, _reqs(_tag("wb_"), WORKLOAD, seed=5))
        assert set(cb._seen_buckets) == warm

    def test_healthz_mesh_block_validates(self):
        from paddle_tpu.serving.gateway import validate_healthz
        cb = _cb(2)
        payload = {
            "schema": "paddle_tpu.gateway_healthz/1", "status": "ok",
            "reason": None, "inflight": 0, "queue_depth": 0,
            "steps": 0, "finished": 0,
            "mesh": {"tp": cb.tp, "devices": [
                {"device": r["device"],
                 "kv_bytes_used": r["kv_bytes_used"],
                 "kv_bytes_high_water": r["kv_bytes_high_water"]}
                for r in cb.device_kv_report()]},
        }
        validate_healthz(payload)
        payload["mesh"]["devices"] = payload["mesh"]["devices"][:1]
        with pytest.raises(ValueError, match="exactly tp"):
            validate_healthz(payload)


@pytest.mark.slow
class TestTokenExactWideMesh:
    """TP=4 and TP=8 re-run the core matrix: same single-chip
    references, wider mesh (heavier interpret-mode wall — slow tier,
    per the tier-1 window discipline)."""

    @pytest.mark.parametrize("tp", [4, 8])
    @pytest.mark.parametrize("mode", ["plain", "chunked", "spec",
                                      "prefix"])
    def test_mode(self, tp, mode):
        assert _MODES[mode](tp) == _ref(mode)

    @pytest.mark.parametrize("tp", [4, 8])
    def test_kv_high_water_bytes_are_one_over_tp(self, tp):
        cb1 = _cb(1)
        _run(cb1, _reqs(_tag("hw1_"), WORKLOAD))
        cbt = _cb(tp)
        _run(cbt, _reqs(_tag(f"hw{tp}_"), WORKLOAD))
        assert cb1.allocator.high_water == cbt.allocator.high_water
        hw1 = cb1.device_kv_report()[0]["kv_bytes_high_water"]
        hwt = cbt.device_kv_report()[0]["kv_bytes_high_water"]
        assert hwt * tp == hw1
