"""Sharded pretraining step — the TPU performance path.

Reference analogue: the Fleet hybrid-parallel training step (SURVEY.md §3.4:
fleet.distributed_model + HybridParallelOptimizer + sharding stage-3) and the
auto-parallel static Engine (§3.5). TPU-native design: ONE jitted function
over a jax.sharding.Mesh — parameters carry NamedShardings (TP over 'mp',
ZeRO/FSDP over 'fsdp', replicated over 'dp'), the batch is sharded over
('dp','fsdp') × sequence over 'sp', and GSPMD inserts every collective the
reference implements by hand (allreduce PyLayers, reduce-scatter hooks,
param all-gathers) as compiler ops scheduled on ICI.

The optimizer update is functional AdamW with optimizer states inheriting
the parameter sharding PLUS 'fsdp' partitioning — sharding stage-1/2
semantics (dygraph_sharding_optimizer.py:54) for free.
"""
import re
import math
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..jit.functional import state_arrays, pure_call
from ..observability import instrument as _metrics

__all__ = ["llama_sharding_rules", "gpt_sharding_rules",
           "ernie_sharding_rules", "spec_for_param",
           "make_train_state", "make_train_step", "make_mesh",
           "flagship_config"]


def flagship_config():
    """The one-chip training shape: (LlamaConfig, batch, seq).

    chip_smoke.py, bench.py AND tools/step_profile.py build from HERE —
    the profile evidence must always describe the step being run; a copy
    would silently drift."""
    from .llama import LlamaConfig
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=24, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=2048,
        dtype="bfloat16", fuse_attention_qkv=True, fuse_attention_ffn=True)
    return cfg, 8, 2048


# (name-regex, spec-template) — first match wins. Axis names are logical:
# 'mp' = tensor parallel, 'fsdp' = ZeRO param shard axis. A template dim
# that does not divide the mesh axis size degrades to replicated (same
# fallback the reference applies for non-divisible shards).
def llama_sharding_rules():
    return [
        # [V, H]: vocab over fsdp, hidden over mp. NOT ("mp","fsdp"): that
        # makes the gather output hidden-sharded over fsdp, and resharding
        # that axis into the combined ("dp","fsdp") batch tile is a cross-dim
        # move XLA's SPMD partitioner full-rematerializes (replicate+slice).
        # With hidden over mp the fixups are a plain mp all-gather + dp/fsdp
        # dynamic-slice, both native collectives.
        (r".*embed_tokens\.weight$",        ("fsdp", "mp")),
        (r".*(q_proj|k_proj|v_proj|gate_proj|up_proj|qkv_proj|"
         r"gate_up_fused_proj)\.weight$",
                                            ("fsdp", "mp")),   # column-parallel [in, out]
        (r".*(o_proj|down_proj)\.weight$",  ("mp", "fsdp")),   # row-parallel [in, out]
        (r".*lm_head\.weight$",             ("fsdp", "mp")),
        (r".*norm.*\.weight$",              (None,)),          # replicated
        (r".*",                             (None,)),
    ]


def gpt_sharding_rules():
    return [
        # same rationale as the llama embed rule above: hidden over mp
        # keeps the gather output's fixups native collectives; hidden over
        # fsdp forced involuntary full-remat reshards against the
        # (dp, fsdp) batch tile (observed on the [1, S, H] position-embed
        # broadcast path)
        (r".*word_embeddings\.weight$",     ("fsdp", "mp")),
        (r".*position_embeddings\.weight$", (None, "mp")),
        (r".*(qkv_proj|linear1)\.weight$",  ("fsdp", "mp")),
        (r".*(out_proj|linear2)\.weight$",  ("mp", "fsdp")),
        (r".*(qkv_proj|linear1)\.bias$",    ("mp",)),
        (r".*",                             (None,)),
    ]


def ernie_sharding_rules():
    """TP plan for the BERT/ERNIE encoder family (q/k/v/linear1 column-
    parallel, out_proj/linear2 row-parallel; embeddings hidden-over-mp per
    the llama embed-rule rationale)."""
    return [
        (r".*word_embeddings\.weight$",      ("fsdp", "mp")),
        (r".*(position|token_type)_embeddings\.weight$", (None, "mp")),
        (r".*(q_proj|k_proj|v_proj|linear1)\.weight$",   ("fsdp", "mp")),
        (r".*(out_proj|linear2)\.weight$",   ("mp", "fsdp")),
        (r".*(q_proj|k_proj|v_proj|linear1)\.bias$",     ("mp",)),
        (r".*",                              (None,)),
    ]


def spec_for_param(name, shape, mesh, rules):
    """Resolve the PartitionSpec for one parameter, dropping mesh axes that
    don't divide the corresponding dim (replicate instead of erroring — the
    tiny-config / odd-vocab case)."""
    for pat, template in rules:
        if re.match(pat, name):
            dims = []
            for d, ax in enumerate(template):
                if (ax is not None and ax in mesh.axis_names
                        and d < len(shape)
                        and shape[d] % mesh.shape[ax] == 0
                        and mesh.shape[ax] > 1):
                    dims.append(ax)
                else:
                    dims.append(None)
            # pad to rank
            dims += [None] * (len(shape) - len(dims))
            return P(*dims[: len(shape)])
    return P()


def make_mesh(n_devices=None, dp=None, fsdp=None, mp=None, sp=1, pp=1,
              devices=None):
    """Build a Mesh with the canonical axis order (pp, dp, fsdp, sp, mp).
    Axis order matters on hardware: 'mp' innermost rides the fastest ICI
    links since its per-layer all-reduces are the highest-frequency
    collectives (reference: HybridCommunicateGroup topology order
    fleet/base/topology.py:73-78 — [data, pipe, sharding, sep, model])."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = n_devices or devices.size
    devices = devices[:n]
    if mp is None:
        mp = 1
    if fsdp is None:
        fsdp = 1
    if dp is None:
        dp = n // (mp * fsdp * sp * pp)
    assert pp * dp * fsdp * mp * sp == n, \
        f"pp{pp}*dp{dp}*fsdp{fsdp}*mp{mp}*sp{sp} != {n}"
    arr = devices.reshape(pp, dp, fsdp, sp, mp)
    return Mesh(arr, ("pp", "dp", "fsdp", "sp", "mp"))


def _named(mesh, spec):
    return NamedSharding(mesh, spec)


def make_train_state(model, mesh, rules=None, lr=3e-4, betas=(0.9, 0.95),
                     eps=1e-8, weight_decay=0.1, grad_clip=1.0):
    """Returns (params, opt_state, meta): params placed per the sharding
    rules; AdamW moments inherit the param sharding (stage-1: optimizer
    states are sharded wherever params are)."""
    rules = rules or llama_sharding_rules()
    params, buffers = state_arrays(model)
    specs = {n: spec_for_param(n, p.shape, mesh, rules)
             for n, p in params.items()}
    params = {n: jax.device_put(p, _named(mesh, specs[n]))
              for n, p in params.items()}
    def zeros_like_sharded(p, n):
        return jax.device_put(jnp.zeros(p.shape, jnp.float32),
                              _named(mesh, specs[n]))

    opt_state = {
        "m": {n: zeros_like_sharded(p, n) for n, p in params.items()},
        "v": {n: zeros_like_sharded(p, n) for n, p in params.items()},
        "count": jnp.zeros((), jnp.int32),
    }
    meta = dict(specs=specs, buffers=buffers, lr=lr, betas=betas, eps=eps,
                weight_decay=weight_decay, grad_clip=grad_clip, rules=rules)
    return params, opt_state, meta


def _adamw(params, grads, opt_state, lr, b1, b2, eps, wd, clip):
    gleaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in gleaves))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6)) if clip else 1.0
    count = opt_state["count"] + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(p, g, m, v, decay):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        p32 = p.astype(jnp.float32)
        newp = p32 - lr * (step + (wd * p32 if decay else 0.0))
        return newp.astype(p.dtype), m, v

    # llama/Megatron recipe: no decay on norm scales and biases (rank < 2)
    out = {n: upd(params[n], grads[n], opt_state["m"][n], opt_state["v"][n],
                  params[n].ndim >= 2)
           for n in params}
    new_params = {n: o[0] for n, o in out.items()}
    new_state = {"m": {n: o[1] for n, o in out.items()},
                 "v": {n: o[2] for n, o in out.items()},
                 "count": count}
    return new_params, new_state, gnorm


def _pack_telemetry(loss, gnorm, params, grads, new_params, spec):
    """In-graph per-layer-group telemetry: ONE packed f32 vector —
    [loss, gnorm, then (grad_norm, param_norm, update_norm,
    nonfinite_count) per group in spec order] — so the host fetches
    every per-group figure in ONE bulk transfer on the telemetry
    cadence, never one sync per tensor (the GL109 discipline). Pure
    extra outputs of the step program: the loss/update math is
    untouched, which is what makes telemetry-on loss-bit-exact."""
    rows = []
    for _label, names in spec.groups:
        g2 = p2 = u2 = nf = jnp.float32(0.0)
        for n in names:
            g = grads[n].astype(jnp.float32)
            p = params[n].astype(jnp.float32)
            q = new_params[n].astype(jnp.float32)
            g2 = g2 + jnp.sum(jnp.square(g))
            p2 = p2 + jnp.sum(jnp.square(p))
            u2 = u2 + jnp.sum(jnp.square(q - p))
            nf = nf + jnp.sum((~jnp.isfinite(g)).astype(jnp.float32))
        rows.append(jnp.stack([jnp.sqrt(g2), jnp.sqrt(p2),
                               jnp.sqrt(u2), nf]))
    head = jnp.stack([loss.astype(jnp.float32),
                      gnorm.astype(jnp.float32)])
    return jnp.concatenate([head] + rows)


def make_train_step(model, mesh, meta, donate=True, telemetry=False,
                    telemetry_every=1, monitor=None):
    """Jitted (params, opt_state, batch) -> (params, opt_state, loss, gnorm).
    batch = {input_ids: [B,S] int32, labels: [B,S] int32}, sharded
    ('dp','fsdp') × 'sp' by `shard_batch`.

    ``telemetry=True`` (implied by ``monitor=``) grows the jitted step
    with the packed per-layer-group health vector (`_pack_telemetry`)
    and the step-phase breakdown (data-wait / host / dispatch
    histograms + `train` chrome-lane spans). The vector stays on
    device; every ``telemetry_every`` steps the wrapper fetches it in
    one bulk `np.asarray`, lands the train_group_* gauges, and hands
    the unpacked dict to the ``TrainHealthMonitor`` when one is
    attached. Telemetry must be a pure observer: loss-bit-exact vs
    telemetry-off and compile-count-neutral after warmup — both gated
    by tools/train_monitor.py --check.

    ``run(..., lr_scale=)`` routes through a SECOND jitted program
    with the scale as a traced argument (built on first use — the
    default path's program is byte-identical with or without it);
    testing/faults.py uses it to inject lr-spike faults without
    touching the step treadmill."""
    buffers = meta["buffers"]
    lr, (b1, b2) = meta["lr"], meta["betas"]
    eps, wd, clip = meta["eps"], meta["weight_decay"], meta["grad_clip"]
    telemetry = telemetry or monitor is not None
    spec = None
    if telemetry:
        from ..observability import train_health as _th
        params0, _ = state_arrays(model)
        spec = _th.build_telemetry_spec(
            {n: p.ndim for n, p in params0.items()})
    # AMP-O2 master-weight pattern (reference amp/auto_cast.py O2 +
    # GradScaler master weights): optimizer holds fp32 params, the jitted
    # step computes fwd/bwd in bf16 casts — no loss scaling needed on TPU
    bf16_compute = getattr(getattr(model, "config", None), "dtype",
                           None) == "bfloat16"

    def loss_fn(params, batch):
        if bf16_compute:
            params = {n: (p.astype(jnp.bfloat16)
                          if p.dtype == jnp.float32 and p.ndim >= 2 else p)
                      for n, p in params.items()}
        # keyword call: model families differ in positional signatures
        # (llama: (ids, position_ids, attn_mask, labels); gpt:
        # (ids, position_ids, labels)) — `labels=` is the shared contract
        out = pure_call(model, params, buffers, batch["input_ids"],
                        labels=batch["labels"])
        _, loss = out
        return loss.astype(jnp.float32)

    def _step_impl(params, opt_state, batch, eff_lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_state, gnorm = _adamw(
            params, grads, opt_state, eff_lr, b1, b2, eps, wd, clip)
        if spec is None:
            return new_params, new_state, loss, gnorm
        vec = _pack_telemetry(loss, gnorm, params, grads, new_params,
                              spec)
        return new_params, new_state, loss, gnorm, vec

    def step(params, opt_state, batch):
        return _step_impl(params, opt_state, batch, lr)

    def step_scaled(params, opt_state, batch, lr_scale):
        return _step_impl(params, opt_state, batch, lr * lr_scale)

    donate_argnums = (0, 1) if donate else ()
    with mesh:
        jitted = jax.jit(step, donate_argnums=donate_argnums)
    jitted_scaled = []  # built on first lr_scale= use (fault injection)
    attributed = []     # cost catalog: analyze the step program once
    # step-phase bookkeeping (telemetry mode): host time between
    # dispatches minus whatever the instrumented loader reported as
    # data wait = the python/bookkeeping share of the step
    phase = {"step": 0, "last_exit": None}

    def run(params, opt_state, batch, lr_scale=None):
        # jit traces lazily at the first call — force training mode for the
        # duration so recompute/dropout gates see training=True at trace
        # time, and expose the mesh as the global ProcessMesh so mesh-aware
        # layers (context-parallel ring attention) resolve their axis
        from ..distributed.mesh import ProcessMesh, get_mesh, set_mesh
        was_training = model.training
        model.train()
        prev_mesh = get_mesh()
        set_mesh(ProcessMesh(mesh))
        try:
            if donate:
                from ..device import record_donation
                record_donation("pretrain.train_step", params, opt_state)
            # step-time/throughput telemetry: host wall around the
            # dispatch. jax dispatch is async, so past the first compiled
            # call this measures submission latency — once the device is
            # the bottleneck the queue backpressures and wall time
            # converges to true step time (steady-state tokens/s is
            # right; the first few samples are optimistic).
            ids = batch.get("input_ids") if isinstance(batch, dict) \
                else None
            tokens = int(np.prod(ids.shape)) if ids is not None else 0
            from ..observability import costs as _costs
            catalog = _costs.get_cost_catalog()
            if catalog.enabled and not attributed:
                # once, BEFORE the first dispatch (donation hasn't
                # consumed params/opt_state yet): AOT-analyze the whole
                # fwd+bwd+AdamW program into the cost catalog — flops /
                # bytes / peak HBM under `pretrain_step`, the numbers
                # the train_obs gate brackets. Opt-in: the analysis
                # pays one extra backend compile.
                attributed.append(True)
                with mesh:
                    catalog.analyze_jitted(
                        "pretrain_step", jitted,
                        (params, opt_state, batch))
            host_s = data_wait_s = 0.0
            if spec is not None:
                from ..observability import train_health as _th
                from ..observability import tracing as _tracing
                enter = time.perf_counter()
                data_wait_s = _th.pop_data_wait()
                if phase["last_exit"] is not None:
                    gap = enter - phase["last_exit"]
                    host_s = max(0.0, gap - data_wait_s)
                    _metrics.train_host_seconds().observe(host_s)
                    _tracing.get_tracer().record_span(
                        "train_host", (enter - host_s) * 1e6,
                        host_s * 1e6, request="train",
                        step=phase["step"])
            t0 = time.monotonic()
            with mesh:
                if lr_scale is None:
                    out = jitted(params, opt_state, batch)
                else:
                    if not jitted_scaled:
                        jitted_scaled.append(jax.jit(
                            step_scaled,
                            donate_argnums=donate_argnums))
                    out = jitted_scaled[0](params, opt_state, batch,
                                           jnp.float32(lr_scale))
            dur = time.monotonic() - t0
            _metrics.train_step_seconds().observe(dur)
            _metrics.dispatch_seconds().labels(
                program="pretrain_step").observe(dur)
            _metrics.train_steps_total().inc()
            tok_per_s = None
            if tokens:
                _metrics.train_tokens_total().inc(tokens)
                if dur > 0:
                    tok_per_s = tokens / dur
                    _metrics.train_tokens_per_s().set(tok_per_s)
            if spec is not None:
                out = _telemetry_hook(out, dur, tok_per_s, data_wait_s)
            return out
        finally:
            set_mesh(prev_mesh)
            if not was_training:
                model.eval()

    def _telemetry_hook(out, dispatch_s, tok_per_s, data_wait_s):
        """Host-side telemetry tail of one step: chrome-lane spans
        every step; the ONE bulk vector fetch only on the telemetry
        cadence. Returns the caller-facing 4-tuple."""
        from ..observability import train_health as _th
        from ..observability import tracing as _tracing
        i = phase["step"]
        phase["step"] = i + 1
        rec = _tracing.get_tracer()
        end = time.perf_counter()
        rec.record_span("train_step", (end - dispatch_s) * 1e6,
                        dispatch_s * 1e6, request="train", step=i,
                        data_wait_s=data_wait_s)
        params_out, opt_out, loss, gnorm, vec = out
        if i % max(1, int(telemetry_every)) == 0:
            arr = np.asarray(vec)       # ONE bulk D2H for all groups
            unpacked = spec.unpack(arr.tolist())
            if monitor is not None:
                monitor.observe_step(i, unpacked["loss"],
                                     unpacked["gnorm"],
                                     groups=unpacked["groups"],
                                     tokens_per_s=tok_per_s)
            else:
                _th.record_telemetry(unpacked)
        phase["last_exit"] = time.perf_counter()
        return params_out, opt_out, loss, gnorm

    run._jitted = jitted
    run._telemetry_spec = spec
    run._monitor = monitor
    return run


def shard_batch(batch, mesh):
    """Place a host batch dict on the mesh: batch dim over (dp, fsdp),
    sequence dim over sp (sequence-data parallel; reference SEP axis)."""
    spec = P(("dp", "fsdp"), "sp")

    def put(x):
        x = jnp.asarray(x)
        s = spec if x.ndim >= 2 else P(("dp", "fsdp"))
        return jax.device_put(x, _named(mesh, s))

    return {k: put(v) for k, v in batch.items()}
