"""The Mistral family for the benchmark: weights from a seed, the plain
reference, and the counts of operations and bytes.

Mistral-7B-v0.3 (huggingface.co/mistralai/Mistral-7B-v0.3, config.json):
pre-norm decoder blocks of RMSNorm -> grouped-query attention with rotary
positions (rotate-half, theta 1e6, no sliding window) -> residual ->
RMSNorm -> SwiGLU feed-forward -> residual; a final RMSNorm; an untied
output head. Nothing here imports the program: the program gets the
weights this file makes, and the reference is this file's own float32
`jax.numpy` under `default_matmul_precision("highest")`.

One departure, stated in the serving configurations' `assumed`: the
program's serving engine has no final norm before its head, so the
serving reference has none either (the training model has it, and so has
the training reference).

Weights are kept in the layouts the seed makes them in, which are the
serving engine's; the equations below read them as the published
matrices (W_q is `qkv[:H]` as [H*D, E], W_o^T is `o_t`, and so on).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02          # config.json's initializer_range
NORM_JITTER = 0.05       # norm scales 1 + 0.05 n: a dropped scale shows


def dims(cfg):
    """(E, H, G, D, F, V, L) of a configuration file."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def _key(seed, *stream):
    seed = int(seed)
    k = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    for s in stream:
        k = jax.random.fold_in(k, s)
    return k


def _layer(key, d, dtype):
    """One block's tensors from its key, in `dtype`."""
    E, H, G, D, F, V, L = d
    ks = jax.random.split(key, 6)

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * INIT_STD).astype(dtype)

    def scale(k):
        return (1.0 + NORM_JITTER * jax.random.normal(
            k, (E,), jnp.float32)).astype(dtype)

    return dict(qkv=mat(ks[0], H + 2 * G, D, E), o_t=mat(ks[1], H * D, E),
                gate_up_t=mat(ks[2], E, 2 * F), down_t=mat(ks[3], F, E),
                ln1=scale(ks[4]), ln2=scale(ks[5]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def layer_tensors(key, layer, d, dtype):
    return _layer(jax.random.fold_in(key, layer), d, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def outer_tensors(key, d, dtype):
    """Embedding [V, E], head [E, V] and the final norm's scale."""
    E, H, G, D, F, V, L = d
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    emb = (jax.random.normal(ke, (V, E), jnp.float32) * INIT_STD)
    head = (jax.random.normal(kh, (E, V), jnp.float32) * INIT_STD)
    norm = 1.0 + NORM_JITTER * jax.random.normal(kn, (E,), jnp.float32)
    return dict(embedding=emb.astype(dtype), lm_head=head.astype(dtype),
                norm=norm.astype(dtype))


def rotary_table(cfg, length):
    """cos and sin [length, D] in float32, rotate-half layout."""
    D = cfg["head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, D, 2) / D))
    ang = np.arange(length)[:, None] * inv[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1).astype(np.float32)
    sin = np.concatenate([np.sin(ang)] * 2, -1).astype(np.float32)
    return cos, sin


# -- what the program is handed ------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _serve_weights(key, d, dtype):
    E, H, G, D, F, V, L = d
    layers = [_layer(jax.random.fold_in(key, li), d, dtype)
              for li in range(L)]
    outer = outer_tensors.__wrapped__(key, d, dtype)
    return dict(
        ln_scales=[t["ln1"] for t in layers],
        qkv_weights=[t["qkv"] for t in layers],
        linear_weights=[t["o_t"] for t in layers],
        ffn_ln_scales=[t["ln2"] for t in layers],
        ffn1_weights=[t["gate_up_t"] for t in layers],
        ffn2_weights=[t["down_t"] for t in layers],
        embedding=outer["embedding"], lm_head=outer["lm_head"])


def serve_weights(seed, cfg):
    """The serving engine's weight dict, made on the device in one jitted
    call from the seed, in the type it is served in."""
    w = _serve_weights(_key(seed), dims(cfg), jnp.dtype(cfg["dtype"]))
    cos, sin = rotary_table(cfg, cfg["engine"]["max_seq_len"])
    w["rotary_embs"] = jnp.asarray(
        np.stack([cos, sin])[:, None, None], jnp.float32)
    return w


def serve_engine_kwargs(cfg):
    E, H, G, D, F, V, L = dims(cfg)
    return dict(num_heads=H, head_dim=D, dtype=cfg["dtype"],
                max_seq_len=cfg["engine"]["max_seq_len"],
                gqa_group_size=G, norm_type="rmsnorm", activation="swiglu",
                use_neox_rotary_style=True)


def train_config_kwargs(cfg):
    """Keyword arguments of the program's LlamaConfig for these widths."""
    E, H, G, D, F, V, L = dims(cfg)
    t = cfg["train"]
    return dict(vocab_size=V, hidden_size=E, intermediate_size=F,
                num_hidden_layers=L, num_attention_heads=H,
                num_key_value_heads=G, rms_norm_eps=cfg["rms_norm_eps"],
                max_position_embeddings=cfg["max_position_embeddings"],
                rope_theta=cfg["rope_theta"], dtype=cfg["dtype"],
                tie_word_embeddings=False, recompute=t["recompute"],
                fuse_attention_qkv=True, fuse_attention_ffn=True,
                initializer_range=INIT_STD)


def canonical_name(program_name):
    """The reference's leaf for one of the training program's parameter
    names (fused qkv and fused gate/up, [in, out] Linear weights)."""
    n = program_name
    if n.endswith("embed_tokens.weight"):
        return ("embedding", None)
    if n.endswith("lm_head.weight"):
        return ("lm_head", None)
    if ".layers." in n:
        li = int(n.split(".layers.")[1].split(".")[0])
        for tail, leaf in (("qkv_proj.weight", "qkv"),
                           ("o_proj.weight", "o_t"),
                           ("gate_up_fused_proj.weight", "gate_up_t"),
                           ("down_proj.weight", "down_t"),
                           ("input_layernorm.weight", "ln1"),
                           ("post_attention_layernorm.weight", "ln2")):
            if n.endswith(tail):
                return (leaf, li)
    if n.endswith("norm.weight"):
        return ("norm", None)
    raise KeyError(f"no reference leaf for program parameter {n!r}")


@functools.partial(jax.jit, static_argnums=(1, 2))
def _train_tree(key, d, names):
    """{program name: float32 array in the program's layout}."""
    E, H, G, D, F, V, L = d
    layers = {}
    outer = outer_tensors.__wrapped__(key, d, jnp.float32)
    out = {}
    for n in names:
        leaf, li = canonical_name(n)
        if li is None:
            out[n] = outer[leaf]
            continue
        if li not in layers:
            layers[li] = _layer(jax.random.fold_in(key, li), d, jnp.float32)
        t = layers[li][leaf]
        if leaf == "qkv":       # [H+2G, D, E] -> Linear [E, (H+2G)*D]
            t = t.reshape(-1, E).T
        out[n] = t
    return out


def train_params(seed, cfg, names):
    """The training program's float32 master weights from the seed, by
    the program's own parameter names."""
    return _train_tree(_key(seed), dims(cfg), tuple(names))


# -- the plain reference ---------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def _block(x, t, cos, sin, d, eps, mm):
    """One decoder block on one sequence x [S, E], float32."""
    E, H, G, D, F, V, L = d
    S = x.shape[0]
    z = _rms(x, t["ln1"], eps)
    w_qkv = t["qkv"].reshape((H + 2 * G) * D, E)
    qkv = mm(z, w_qkv.T).reshape(S, H + 2 * G, D)
    q = _rope(qkv[:, :H], cos, sin)
    k = _rope(qkv[:, H:H + G], cos, sin)
    v = qkv[:, H + G:]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):            # one kv head and its H/G query heads
        qg, kg, vg = args       # [S, r, D], [S, D], [S, D]
        s = jnp.einsum("srd,td->rst", qg, kg) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return jnp.einsum("rst,td->srd", p, vg)

    r = H // G
    # checkpointed: backward keeps one group's [r, S, S] scores at a time
    ctx = jax.lax.map(jax.checkpoint(group), (q.reshape(S, G, r, D).transpose(1, 0, 2, 3),
                              k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    ctx = ctx.transpose(1, 0, 2, 3).reshape(S, H * D)
    x = x + mm(ctx, t["o_t"])
    z = _rms(x, t["ln2"], eps)
    gu = mm(z, t["gate_up_t"])
    return x + mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], t["down_t"])


def _plain_mm(a, b):
    return a @ b


def _q8(x):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8_mm(a, b):
    """The control's matmul: operands through float8_e4m3fn with a
    per-tensor scale, products accumulated in float32, and the same in
    the two matmuls of the backward pass — what an fp8 training path
    does."""
    return _q8(a) @ _q8(b)


def _fp8_fwd(a, b):
    return _fp8_mm(a, b), (a, b)


def _fp8_bwd(res, g):
    a, b = res
    return _q8(g) @ _q8(b).T, _q8(a).T @ _q8(g)


_fp8_mm.defvjp(_fp8_fwd, _fp8_bwd)


MATMULS = {"float32": _plain_mm, "fp8": _fp8_mm}


@functools.partial(jax.jit, static_argnums=(4, 5))
def _ref_layer(x, t, cos, sin, d, eps):
    t = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    return jax.lax.map(lambda xi: _block(xi, t, cos, sin, d, eps, _plain_mm),
                       x)


@jax.jit
def _ref_gaps(h, head, toks):
    logits = h @ head.astype(jnp.float32)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, toks[:, None], 1)[:, 0]
    return best - got, logits.argmax(-1) == toks


def served_token_gaps(seed, cfg, streams, length=None, pad_to=128):
    """Teacher forcing: the reference once over each prompt with its
    served tokens. `streams` is [(prompt ids, served ids)]. Returns, per
    stream, (gap of each served token's reference logit below that row's
    best, whether it is the row's argmax). Layer by layer, the weights of
    one layer at a time made from the seed in the served type and widened
    to float32, so it fits beside nothing and needs nothing of the
    program's."""
    d = dims(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    key = _key(seed)
    longest = max(len(p) + len(t) for p, t in streams)
    # one padded length per mix where the caller knows its longest request:
    # one compiled reference, found in the cache by every later run
    S = -(-max(longest, length or 0) // pad_to) * pad_to
    ids = np.zeros((len(streams), S), np.int32)
    for i, (p, t) in enumerate(streams):
        ids[i, :len(p) + len(t)] = np.concatenate([p, t])
    cos, sin = (jnp.asarray(a) for a in rotary_table(cfg, S))
    with jax.default_matmul_precision("highest"):
        outer = outer_tensors(key, d, dtype)
        x = outer["embedding"].astype(jnp.float32)[jnp.asarray(ids)]
        for li in range(d[-1]):
            x = _ref_layer(x, layer_tensors(key, li, d, dtype), cos, sin, d,
                           cfg["rms_norm_eps"])
        out = []
        for i, (p, t) in enumerate(streams):
            rows = x[i, len(p) - 1:len(p) - 1 + len(t)]
            gap, is_best = _ref_gaps(rows, outer["lm_head"],
                                        jnp.asarray(t, jnp.int32))
            out.append((np.asarray(gap), np.asarray(is_best)))
    return out


# -- the training reference --------------------------------------------------------

def _ref_loss(params, ids, labels, d, eps, cos, sin, mm):
    """Mean next-token cross entropy of the whole model, float32.
    params: dict(embedding, lm_head, norm, layers=[block tensors])."""
    def row(args):
        rid, rlab = args
        x = params["embedding"][rid]
        for t in params["layers"]:
            x = jax.checkpoint(
                lambda x, t: _block(x, t, cos, sin, d, eps, mm))(x, t)
        x = _rms(x, params["norm"], eps)
        logits = mm(x, params["lm_head"])
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.sum(lse - jnp.take_along_axis(
            logits, rlab[:, None], 1)[:, 0])
    return jnp.sum(jax.lax.map(row, (ids, labels))) / ids.size


def _leaves(params):
    """{(leaf, layer): array} in the naming of `canonical_name`."""
    out = {(k, None): params[k] for k in ("embedding", "lm_head", "norm")}
    for li, t in enumerate(params["layers"]):
        for k, v in t.items():
            out[(k, li)] = v
    return out


def ref_train(seed, cfg, batches, matmul="float32"):
    """The reference through the first len(batches) steps: float32 model,
    loss and gradient, the program's AdamW with its clip. Returns
    dict(losses, grad_norms {leaf: norm of the first clipped gradient},
    update_norms {leaf: norm of the parameters' change after the steps},
    median_grad_norm, median_update_norm)."""
    d = dims(cfg)
    E, H, G, D, F, V, L = d
    hp = cfg["train"]["adamw"]
    lr, b1, b2 = hp["lr"], hp["beta1"], hp["beta2"]
    eps_a, wd, clip = hp["eps"], hp["weight_decay"], hp["grad_clip"]
    eps = cfg["rms_norm_eps"]
    mm = MATMULS[matmul]
    key = _key(seed)
    S = batches[0]["input_ids"].shape[1]
    cos, sin = (jnp.asarray(a) for a in rotary_table(cfg, S))

    def fresh():
        outer = outer_tensors(key, d, jnp.float32)
        return dict(outer, layers=[layer_tensors(key, li, d, jnp.float32)
                                   for li in range(L)])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, count, ids, labels):
        loss, g = jax.value_and_grad(_ref_loss)(p, ids, labels, d, eps,
                                                cos, sin, mm)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, clip / (gn + 1e-6))
        count = count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            s = (m / c1) / (jnp.sqrt(v / c2) + eps_a)
            return p - lr * (s + (wd * p if p.ndim >= 2 else 0.0)), m, v

        flat_p, tree = jax.tree_util.tree_flatten(p)
        out = [upd(a, b, c, e) for a, b, c, e in zip(
            flat_p, jax.tree_util.tree_leaves(g),
            jax.tree_util.tree_leaves(m), jax.tree_util.tree_leaves(v))]
        gnorms = jax.tree_util.tree_unflatten(
            tree, [jnp.sqrt(jnp.sum(jnp.square(x))) * scale
                   for x in jax.tree_util.tree_leaves(g)])
        un = lambda i: jax.tree_util.tree_unflatten(
            tree, [o[i] for o in out])
        return un(0), un(1), un(2), count, loss, gnorms

    with jax.default_matmul_precision("highest"):
        p = fresh()
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
        m, v, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, first = [], None
        for b in batches:
            p, m, v, count, loss, gn = step(
                p, m, v, count, jnp.asarray(b["input_ids"]),
                jnp.asarray(b["labels"]))
            losses.append(float(loss))
            if first is None:
                first = {k: float(x) for k, x in _leaves(gn).items()}
        del m, v
        p0 = fresh()
        upd = {k: float(jnp.sqrt(jnp.sum(jnp.square(a - _leaves(p0)[k]))))
               for k, a in _leaves(p).items()}
    return dict(losses=losses, grad_norms=first, update_norms=upd)


# -- counts of operations and bytes ------------------------------------------------

def matmul_params(cfg):
    """Parameters that take part in a matrix multiplication per token:
    the blocks' projections and the head; the embedding is a lookup."""
    E, H, G, D, F, V, L = dims(cfg)
    block = (H + 2 * G) * D * E + H * D * E + 2 * F * E + F * E
    return L * block + E * V


def train_flops_per_token(cfg, seq):
    """Operations the forward and backward passes need per token: 6 per
    matmul parameter, and causal attention's two matmuls forward (half of
    the full square) and four backward; recomputation not counted."""
    E, H, G, D, F, V, L = dims(cfg)
    attn_fwd = 2 * 2 * seq * H * D / 2.0
    return 6.0 * matmul_params(cfg) + 3.0 * attn_fwd * L


def flash_cost(cfg, batch, seq):
    """(operations, bytes) one layer's flash forward + backward needs:
    causal QK^T and PV forward, four matmuls backward; q, k, v, o read or
    written once forward, and q, k, v, o, do read and dq, dk, dv written
    backward, in the compute type."""
    E, H, G, D, F, V, L = dims(cfg)
    item = jnp.dtype(cfg["dtype"]).itemsize
    fwd = 2 * 2 * batch * H * seq * seq * D / 2.0
    qo, kv = batch * seq * H * D * item, batch * seq * G * D * item
    fwd_bytes = 2 * qo + 2 * kv
    bwd_bytes = 4 * qo + 4 * kv
    return 3.0 * fwd, float(fwd_bytes + bwd_bytes)


def weight_bytes(cfg, layers=None):
    """Bytes of the served weights."""
    E, H, G, D, F, V, L = dims(cfg)
    item = jnp.dtype(cfg["dtype"]).itemsize
    L = L if layers is None else layers
    block = (H + 2 * G) * D * E + H * D * E + 3 * F * E + 2 * E
    return (L * block + 2 * E * V) * item


def kv_bytes_per_token(cfg):
    E, H, G, D, F, V, L = dims(cfg)
    return L * 2 * G * D * jnp.dtype(cfg["dtype"]).itemsize
