"""The training driver: `make_mesh` -> `make_train_state` ->
`make_train_step`, one compiled step with its state, fed a fresh seeded
batch from the host every step.

Set-up builds that one object, puts the seed's master weights into it,
and drives it through its first steps with the window's own call and
feed; those steps are what the reference later follows. The window then
continues with the same object. The reference runs after the program's
state is freed: float32, `highest`, the program's AdamW.
"""
import gc
import os
import statistics
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "lib"))
import measure  # noqa: E402
import traffic  # noqa: E402

TRACE_STEPS = 6          # steps the profiler records in a traced window
IN_FLIGHT = 2            # steps dispatched ahead of the last one waited for


def batch_of(seed, index, cfg, mix):
    """Step `index`'s batch under `seed`: rows that all differ, labels
    the ids shifted by one."""
    ids = traffic.rng_for(seed, 21, index).integers(
        0, cfg["vocab_size"],
        (cfg["train"]["batch"], mix["seq"])).astype(np.int32)
    return {"input_ids": ids, "labels": np.roll(ids, -1, axis=1)}


def worst_leaf(prog, ref):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    med = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        gap = abs(prog[k] - r) / max(r, med)
        if not gap <= worst:            # NaN wins
            worst, where = gap, k
    return worst, where


def compare(prog, ref, limits):
    rows = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        rows.append(dict(name=f"loss_step{i}", value=abs(a - b),
                         limit=limits["loss_abs_max"],
                         program=a, reference=b))
    g, gl = worst_leaf(prog["grad_norms"], ref["grad_norms"])
    rows.append(dict(name="grad_norm_worst_leaf", value=g,
                     limit=limits["grad_norm_rel_max"], leaf=str(gl)))
    u, ul = worst_leaf(prog["update_norms"], ref["update_norms"])
    rows.append(dict(name="update_norm_worst_leaf", value=u,
                     limit=limits["update_norm_rel_max"], leaf=str(ul)))
    for r in rows:
        r["ok"] = bool(r["value"] <= r["limit"])
    return rows


class Program:
    """The compiled step with its state: the one object set-up builds and
    the window drives."""

    def __init__(self, ctx):
        import jax
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, pretrain
        cfg, family = ctx["config"], ctx["family"]
        self.cfg, self.family, self.seed = cfg, family, ctx["seed"]
        self.mix = ctx["traffic"]
        self.pretrain = pretrain
        hp = cfg["train"]["adamw"]
        self.b1 = hp["beta1"]
        model = LlamaForCausalLM(LlamaConfig(
            **family.train_config_kwargs(cfg)))
        self.mesh = pretrain.make_mesh(n_devices=len(ctx["devices"]),
                                       **cfg["train"].get("mesh", {}))
        params, self.opt, meta = pretrain.make_train_state(
            model, self.mesh, lr=hp["lr"], betas=(hp["beta1"], hp["beta2"]),
            eps=hp["eps"], weight_decay=hp["weight_decay"],
            grad_clip=hp["grad_clip"])
        self.names = tuple(params)
        self.shardings = {n: p.sharding for n, p in params.items()}
        for p in params.values():       # the model's own draw: not used
            p.delete()
        self.params = self.seeded_params()
        self.step = pretrain.make_train_step(model, self.mesh, meta)
        self.loss = None

    def seeded_params(self):
        import jax
        fresh = self.family.train_params(self.seed, self.cfg, self.names)
        return {n: jax.device_put(fresh[n], self.shardings[n])
                for n in self.names}

    def run_step(self, index):
        data = self.pretrain.shard_batch(
            batch_of(self.seed, index, self.cfg, self.mix), self.mesh)
        self.params, self.opt, self.loss, _ = self.step(
            self.params, self.opt, data)
        return self.loss

    def leaf_norms(self, tree, scale=1.0):
        import jax
        import jax.numpy as jnp
        norms = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for n, x in t.items()})(tree)
        return {self.family.canonical_name(n): float(v) * scale
                for n, v in norms.items()}

    def update_norms(self):
        import jax
        import jax.numpy as jnp
        p0 = self.seeded_params()
        norms = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum(jnp.square(
            a[n] - b[n]))) for n in a})(self.params, p0)
        return {self.family.canonical_name(n): float(v)
                for n, v in norms.items()}


def first_steps(prog, n):
    """The program's numbers the reference is held against: each step's
    loss, the first gradient as the optimizer got it (its first moment
    after one step is (1 - beta1) times it), the parameters' change."""
    out = dict(losses=[])
    for i in range(n):
        out["losses"].append(float(prog.run_step(i)))
        if i == 0:
            out["grad_norms"] = prog.leaf_norms(prog.opt["m"],
                                                1.0 / (1.0 - prog.b1))
    out["update_norms"] = prog.update_norms()
    return out


def window(prog, ctx, first_index):
    """Steps for `seconds`, a bounded number in flight, ending when the
    last one has finished. Returns (steps, elapsed seconds, trace facts)."""
    import jax
    seconds = ctx["seconds"]
    pending, steps, facts = [], 0, {}
    trace_at = max(2, int(0.3 * seconds / max(ctx.get("step_s", 0.2), 1e-3)))
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        if ctx["trace"] and steps == trace_at:
            jax.block_until_ready(prog.params)
            jax.profiler.start_trace(ctx["trace_dir"])
            facts["trace_on"] = time.monotonic() - t0
        pending.append(prog.run_step(first_index + steps))
        steps += 1
        if len(pending) > IN_FLIGHT:
            pending.pop(0).block_until_ready()
        if ctx["trace"] and steps == trace_at + TRACE_STEPS:
            jax.block_until_ready(prog.params)
            jax.profiler.stop_trace()
            facts["trace_off"] = time.monotonic() - t0
            facts["trace_steps"] = TRACE_STEPS
    jax.block_until_ready(prog.params)
    elapsed = time.monotonic() - t0
    if ctx["trace"] and "trace_off" not in facts and "trace_on" in facts:
        jax.profiler.stop_trace()
        facts["trace_off"] = elapsed
        facts["trace_steps"] = steps - trace_at
    return steps, elapsed, facts


def run(ctx):
    import jax
    cfg, family = ctx["config"], ctx["family"]
    t, limits = cfg["train"], cfg["check"]
    n_ref = int(limits["ref_steps"])
    tokens_per_step = t["batch"] * ctx["traffic"]["seq"]
    if ctx.get("control"):
        # the control: the reference in the lower precision, in the
        # program's place
        prog_numbers = family.ref_train(
            ctx["seed"], cfg, [batch_of(ctx["seed"], i, cfg, ctx["traffic"])
                               for i in range(n_ref)],
            matmul=ctx["control"])
        metrics, facts, steps = {"setup_s": 0.0}, {}, 0
        facts["device"] = measure.device_facts(ctx["devices"])
    else:
        prog = Program(ctx)
        prog_numbers = first_steps(prog, n_ref)
        ts = time.monotonic()
        float(prog.run_step(n_ref))              # a step's time, for pacing
        ctx["step_s"] = time.monotonic() - ts
        print(f"[setup] first steps' losses {prog_numbers['losses']}, one "
              f"step {ctx['step_s']:.3f} s", flush=True)
        setup_s = time.monotonic() - ctx["t_start"]
        c0 = ctx["watch"].mark()
        steps, elapsed, facts = window(prog, ctx, n_ref + 1)
        win = ctx["watch"].diff(c0, ctx["watch"].mark())
        facts["window_compiles"] = win["compiles"] + win["lowers"]
        last_loss = float(prog.loss)
        facts["device"] = measure.device_facts(ctx["devices"])
        facts.update(steps=steps, elapsed_s=elapsed, last_loss=last_loss)
        metrics = {"setup_s": setup_s,
                   "train_tokens_per_s": steps * tokens_per_step / elapsed}
        del prog
        gc.collect()
        jax.clear_caches()
    t_ref = time.perf_counter()
    ref = family.ref_train(ctx["seed"], cfg,
                           [batch_of(ctx["seed"], i, cfg, ctx["traffic"])
                            for i in range(n_ref)])
    rows = compare(prog_numbers, ref, limits)
    rows.append(dict(name="reference_s", value=time.perf_counter() - t_ref,
                     limit="-", ok=True))
    if not ctx.get("control"):
        rows.append(dict(name="window_compiles",
                         value=facts["window_compiles"], limit=0,
                         ok=facts["window_compiles"] == 0
                         or ctx["rehearse"]))
        rows.append(dict(name="last_loss_finite", value=facts["last_loss"],
                         limit="finite",
                         ok=bool(np.isfinite(facts["last_loss"]))))
    counts = dict(attempted=steps, failed=0)
    return dict(metrics=metrics, counts=counts, facts=facts, records=[],
                checks=rows, correct=all(r["ok"] for r in rows))
