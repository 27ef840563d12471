"""The MiMo-V2-Flash family for the benchmark: weights from a seed, the
plain reference, and the counts of operations and bytes.

MiMo-V2-Flash (huggingface.co/XiaomiMiMo/MiMo-V2-Flash, config.json):
pre-norm decoder blocks, `h = x + Attn_l(RMSNorm(x))`, `y = h +
FFN_l(RMSNorm(h))`, a final RMSNorm and an untied head; eps 1e-5; no
biases. Per layer `hybrid_layer_pattern[l]` picks the attention and
`moe_layer_freq[l]` the feed-forward:

- attention, both kinds: 64 query heads of 192, KVH key heads of 192 and
  KVH value heads of 128, `v = attention_value_scale * (z Wv)`; the
  first 64 dimensions of each q and k head (`int(0.334 * 192)`) are
  rotated, rotate-half pairing, `inv_freq_i = theta^(-2i/64)`; scores
  `q . k / sqrt(192)`, query head h reading kv head `h // (64 / KVH)`;
  output `concat_h(sum_u p_u v_u) Wo`, Wo [64 x 128, 4096].
  Full (pattern 0): KVH 4, theta 5e6, `u <= t`, `p = softmax(s)`.
  Window (1): KVH 8, theta 1e4, `t - 128 < u <= t`, and a learned sink
  logit per query head in the denominator only:
  `p_u = exp(s_u) / (exp(sink_h) + sum_u' exp(s_u'))`.
- dense feed-forward (`moe_layer_freq` 0): `(silu(z Wg) * (z Wu)) Wd`.
- experts (1): `sigma = sigmoid(z Wr)` over all 256 in float32; S = the 8
  largest of `sigma + b` (b selects and does not weigh); `w_e = sigma_e /
  sum_{e' in S} sigma_e'`; `sum_{e in S} w_e (silu(z W1_e) * (z W3_e))
  W2_e`. One chip of the deployment holds experts lo .. lo + held - 1
  and computes `sum_{e in S, held} w_e E_e(z)` with `w_e` normalised
  over all of S: that partial sum goes on to the next layer, here and in
  the program alike; nothing stands in for the absent chips.

Left out: the three multi-token-prediction layers (not in config.json).
Nothing here imports the program: the program gets the weights this file
makes, and the reference is this file's own float32 `jax.numpy` under
`default_matmul_precision("highest")`, no kernel, cache or batching, one
layer's weights made from the seed at a time.

Weights are kept in the layouts the seed makes them in, which are the
serving engine's; the equations read them as the published matrices
(`qkv` rows are Wq^T, Wk^T, Wv^T one under another, `o_t` is Wo,
`gate_up_t` is Wg|Wu side by side, `w13` the held experts' W1|W3).
"""
import functools
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02          # normal weights
NORM_JITTER = 0.05       # norm scales 1 + 0.05 n: a dropped scale shows
BIAS_STD = 0.1           # the router's correction bias b: not zero, so
#                          that adding it to the weights shows. It decides
#                          the selection (the eight largest sigmas of 256
#                          lie 0.01 apart), so each chip's share of the
#                          experts gets the SAME values, `share_biases`
SINK_MEAN, SINK_STD = 4.0, 1.0   # sink logits: beside scores of std ~1.6
#                          over 128 keys a sink of e^4 takes about a tenth
#                          of the softmax's mass, so dropping it shows


class Dims(typing.NamedTuple):
    """The shape of a configuration file, hashable for jit."""
    E: int               # hidden_size
    H: int               # num_attention_heads
    Dk: int              # head_dim (queries and keys)
    Dv: int              # v_head_dim
    rot: int             # rotated dimensions of a head
    F: int               # intermediate_size (the dense GLU)
    Fe: int              # moe_intermediate_size (one expert)
    V: int               # vocab_size (this chip's slice)
    L: int               # num_hidden_layers
    kvh: tuple           # kv heads per layer
    window: tuple        # window per layer (0: full attention)
    theta: tuple         # rope base per layer
    moe: tuple           # per layer: routed experts or a dense GLU
    routed: int          # experts the router scores
    top_k: int           # num_experts_per_tok
    lo: int              # first expert held here
    held: int            # experts held here
    shares: int          # chips a layer's experts are dealt over
    vscale: float        # attention_value_scale
    eps: float


class Kind(typing.NamedTuple):
    """What tells one layer's program from another's: layers of one kind
    share their compiled weight maker and reference block."""
    kvh: int
    window: int          # 0: full attention
    moe: bool


def kind_of(d, li):
    return Kind(d.kvh[li], d.window[li], d.moe[li])


def dims(cfg):
    L = cfg["num_hidden_layers"]
    win = tuple(bool(x) for x in cfg["hybrid_layer_pattern"][:L])
    return Dims(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Dk=cfg["head_dim"], Dv=cfg["v_head_dim"],
        rot=int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], L=L,
        kvh=tuple(cfg["swa_num_key_value_heads"] if w
                  else cfg["num_key_value_heads"] for w in win),
        window=tuple(cfg["sliding_window"] if w else 0 for w in win),
        theta=tuple(float(cfg["swa_rope_theta"] if w else cfg["rope_theta"])
                    for w in win),
        moe=tuple(bool(x) for x in cfg["moe_layer_freq"][:L]),
        routed=cfg["experts_routed_over"],
        top_k=cfg["num_experts_per_tok"], lo=cfg["expert_first"],
        held=cfg["n_routed_experts"],
        shares=cfg.get("deployment_chips", 1),
        vscale=float(cfg["attention_value_scale"]),
        eps=float(cfg["layernorm_epsilon"]))


def _key(seed, *stream):
    seed = int(seed)
    k = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    for s in stream:
        k = jax.random.fold_in(k, s)
    return k


def _layer(key, k, d, dtype):
    """A layer's tensors from its key, in `dtype`; k is its `Kind`."""
    ks = jax.random.split(key, 10)
    g = k.kvh

    def mat(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * INIT_STD).astype(dtype)

    def scale(k):
        return (1.0 + NORM_JITTER * jax.random.normal(
            k, (d.E,), jnp.float32)).astype(dtype)

    t = dict(qkv=mat(ks[0], (d.H + g) * d.Dk + g * d.Dv, d.E),
             o_t=mat(ks[1], d.H * d.Dv, d.E),
             ln1=scale(ks[2]), ln2=scale(ks[3]))
    if k.window:
        t["sink"] = (SINK_MEAN + SINK_STD * jax.random.normal(
            ks[4], (d.H,), jnp.float32)).astype(dtype)
    if k.moe:
        t["router"] = mat(ks[5], d.E, d.routed)
        t["router_b"] = share_biases(ks[6], d).astype(dtype)
        # all `routed` experts are the model's; the first axis is cut to
        # the ones held, each from its own key so that a share holds the
        # same expert whatever else it holds
        ek = jax.vmap(lambda e: jax.random.fold_in(ks[7], e))(
            d.lo + jnp.arange(d.held))
        t["w13"] = jax.vmap(lambda e: mat(e, d.E, 2 * d.Fe))(ek)
        t["w2"] = jax.vmap(
            lambda e: mat(jax.random.fold_in(e, 1), d.Fe, d.E))(ek)
    else:
        t["gate_up_t"] = mat(ks[8], d.E, 2 * d.F)
        t["down_t"] = mat(ks[9], d.F, d.E)
    return t


def share_biases(key, d):
    """The router's correction bias [routed]: each of the deployment's
    `shares` chips gets the same multiset, BIAS_STD times the normal
    distribution's quantiles at (i + 0.5) / width, in an order of its own
    drawn from the key. With free draws the seed decided how many of the
    held experts any token reaches (7.4-9.2 of 16 in a 128-row chunk step
    over twelve seeds; 8.3-8.7 dealt so), and with that the step's time:
    the same work for every seed, as the traffic's sizes are dealt
    (`lib/traffic.py`)."""
    width = d.routed // d.shares
    values = BIAS_STD * jax.scipy.special.ndtri(
        (jnp.arange(width, dtype=jnp.float32) + 0.5) / width)
    return jax.vmap(lambda k: jax.random.permutation(k, values))(
        jax.random.split(key, d.shares)).reshape(-1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_tensors(key, li, k, d, dtype):
    return _layer(jax.random.fold_in(key, li), k, d, dtype)


def layer_tensors(key, li, d, dtype):
    """Layer li's tensors: one compiled maker for each kind of layer."""
    return _layer_tensors(key, li, kind_of(d, li), d, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def outer_tensors(key, d, dtype):
    """Embedding [V, E], head [E, V] and the final norm's scale."""
    ke, kh, kn = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    emb = jax.random.normal(ke, (d.V, d.E), jnp.float32) * INIT_STD
    head = jax.random.normal(kh, (d.E, d.V), jnp.float32) * INIT_STD
    norm = 1.0 + NORM_JITTER * jax.random.normal(kn, (d.E,), jnp.float32)
    return dict(embedding=emb.astype(dtype), lm_head=head.astype(dtype),
                norm=norm.astype(dtype))


def rotary_table(theta, rot, length):
    """cos and sin [length, rot] in float32, rotate-half layout."""
    inv = 1.0 / (theta ** (np.arange(0, rot, 2) / rot))
    ang = np.arange(length)[:, None] * inv[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1).astype(np.float32)
    sin = np.concatenate([np.sin(ang)] * 2, -1).astype(np.float32)
    return cos, sin


# -- what the program is handed ------------------------------------------------

def serve_weights(seed, cfg):
    """The serving engine's weight dict in the type it is served in, made
    on the device from the seed a layer at a time (a layer's float32
    draws are 2 GB before they are cast)."""
    d, dtype, key = dims(cfg), jnp.dtype(cfg["dtype"]), _key(seed)
    layers = [layer_tensors(key, li, d, dtype) for li in range(d.L)]
    outer = outer_tensors(key, d, dtype)
    thetas = sorted(set(d.theta))
    tables = [rotary_table(th, d.rot, cfg["engine"]["max_seq_len"])
              for th in thetas]
    return dict(
        ln_scales=[t["ln1"] for t in layers],
        qkv_weights=[t["qkv"] for t in layers],
        linear_weights=[t["o_t"] for t in layers],
        ffn_ln_scales=[t["ln2"] for t in layers],
        ffn1_weights=[t.get("w13", t.get("gate_up_t")) for t in layers],
        ffn2_weights=[t.get("w2", t.get("down_t")) for t in layers],
        router_weights=[t.get("router") for t in layers],
        router_biases=[t.get("router_b") for t in layers],
        attn_sinks=[t.get("sink") for t in layers],
        final_norm_scale=outer["norm"],
        embedding=outer["embedding"], lm_head=outer["lm_head"],
        rotary_embs=tuple(jnp.asarray(
            np.stack([cos, sin])[:, None, None], jnp.float32)
            for cos, sin in tables))


def layer_descriptions(cfg):
    """The engine's per-layer block description: a dict per layer."""
    d = dims(cfg)
    thetas = sorted(set(d.theta))
    return [dict(
        kv_heads=d.kvh[li], v_head_dim=d.Dv, window=d.window[li] or None,
        sink=bool(d.window[li]), rope=thetas.index(d.theta[li]),
        value_scale=d.vscale, activation="swiglu",
        experts=dict(n_routed=d.routed, top_k=d.top_k, lo=d.lo,
                     held=d.held) if d.moe[li] else None)
        for li in range(d.L)]


def serve_engine_kwargs(cfg):
    d = dims(cfg)
    return dict(num_heads=d.H, head_dim=d.Dk, dtype=cfg["dtype"],
                max_seq_len=cfg["engine"]["max_seq_len"],
                norm_type="rmsnorm", use_neox_rotary_style=True,
                layers=layer_descriptions(cfg))


# -- the plain reference ---------------------------------------------------------

def _f32(a):
    """A served tensor widened where it is used: the reference runs
    beside the engine in a sweep, so it keeps a layer's weights in the
    served type and widens one matrix (one expert) at a time."""
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, cos, sin):
    """Rotate the first cos.shape[-1] dimensions of each head of x
    [S, heads, D], rotate-half pairing; the others pass through."""
    rot = cos.shape[-1]
    xr, rest = x[..., :rot], x[..., rot:]
    half = rot // 2
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate(
        [xr * cos[:, None, :] + turned * sin[:, None, :], rest], -1)


def attention(x, t, kind, d, cos, sin):
    """The attention sublayer of a layer of that kind on one sequence x
    [S, E] (the residual is the caller's)."""
    S, g, window = x.shape[0], kind.kvh, kind.window
    z = _rms(x, t["ln1"], d.eps)
    qkv = z @ _f32(t["qkv"]).T
    q, k, v = jnp.split(qkv, [d.H * d.Dk, (d.H + g) * d.Dk], axis=-1)
    q = _rope(q.reshape(S, d.H, d.Dk), cos, sin)
    k = _rope(k.reshape(S, g, d.Dk), cos, sin)
    v = d.vscale * v.reshape(S, g, d.Dv)
    pos = jnp.arange(S)
    seen = pos[None, :] <= pos[:, None]                 # u <= t
    if window:
        seen &= pos[None, :] > pos[:, None] - window
    r = d.H // g

    def group(args):            # one kv head and its H/G query heads
        qg, kg, vg, sinks = args        # [r, S, Dk], [S, Dk], [S, Dv], [r]

        def head(args):         # one query head: [S, S] scores at a time
            qh, sink = args
            s = jnp.where(seen, qh @ kg.T / math.sqrt(d.Dk), -jnp.inf)
            if window:          # the sink: in the denominator only
                s = jnp.concatenate(
                    [s, jnp.broadcast_to(sink, (S, 1))], -1)
            return jax.nn.softmax(s, -1)[:, :S] @ vg

        return jax.lax.map(head, (qg, sinks))           # [r, S, Dv]

    sink = _f32(t["sink"]).reshape(g, r) if window \
        else jnp.zeros((g, r), x.dtype)
    ctx = jax.lax.map(group, (
        q.reshape(S, g, r, d.Dk).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), sink))   # [g, r, S, Dv]
    return ctx.transpose(2, 0, 1, 3).reshape(S, d.H * d.Dv) @ _f32(t["o_t"])


def routing(z, router, router_b, top_k):
    """[S, routed] weights of the experts each token is sent to, 0
    elsewhere: sigmoid scores, the top_k largest of score + bias, the
    scores of those normalised to 1."""
    sigma = jax.nn.sigmoid(z @ _f32(router))
    _, sel = jax.lax.top_k(sigma + _f32(router_b), top_k)
    picked = jnp.take_along_axis(sigma, sel, axis=1)
    w = picked / jnp.sum(picked, axis=1, keepdims=True)
    return jnp.zeros_like(sigma).at[
        jnp.arange(z.shape[0])[:, None], sel].set(w)


def experts(z, t, d, lo=None):
    """The held experts' share of the expert feed-forward on z [S, E]:
    t["w13"] / t["w2"] hold experts lo .. lo + their first axis - 1."""
    lo = d.lo if lo is None else lo
    w = routing(z, t["router"], t["router_b"], d.top_k)
    held = t["w13"].shape[0]
    w = jax.lax.dynamic_slice_in_dim(w, lo, held, axis=1)      # [S, held]

    def one(acc, args):
        w13, w2, we = args
        gu = z @ _f32(w13)
        f = gu.shape[-1] // 2
        return acc + we[:, None] * (
            (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ _f32(w2)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z), (t["w13"], t["w2"], w.T))
    return out


def feed_forward(h, t, k, d):
    z = _rms(h, t["ln2"], d.eps)
    if k.moe:
        return experts(z, t, d)
    gu = z @ _f32(t["gate_up_t"])
    return (jax.nn.silu(gu[:, :d.F]) * gu[:, d.F:]) @ _f32(t["down_t"])


def block(x, t, k, d, cos, sin):
    """One decoder block of kind k on one sequence x [S, E], float32."""
    h = x + attention(x, t, k, d, cos, sin)
    return h + feed_forward(h, t, k, d)


_ref_layer = jax.jit(block, static_argnums=(2, 3))


def forward(seed, cfg, ids):
    """The reference's hidden states after the final norm, a [S, E]
    float32 array per sequence, for token ids [B, S]: layer by layer, one layer's weights
    made from the seed in the served type and widened at a time."""
    d, dtype, key = dims(cfg), jnp.dtype(cfg["dtype"]), _key(seed)
    S = ids.shape[1]
    tables = {th: tuple(jnp.asarray(a)
                        for a in rotary_table(th, d.rot, S))
              for th in set(d.theta)}
    with jax.default_matmul_precision("highest"):
        outer = outer_tensors(key, d, dtype)
        # between layers the sequences wait on the host: beside a live
        # engine the device holds one layer's weights and one sequence
        xs = [np.asarray(_f32(outer["embedding"][jnp.asarray(row)]))
              for row in ids]
        for li in range(d.L):
            t = layer_tensors(key, li, d, dtype)
            xs = [np.asarray(_ref_layer(x, t, kind_of(d, li), d,
                                        *tables[d.theta[li]]))
                  for x in xs]       # a sequence at a time
        return [_rms(jnp.asarray(x), outer["norm"], d.eps) for x in xs], outer


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
    best, whether it is the row's argmax)."""
    longest = max(len(p) + len(t) for p, t in streams)
    # one padded length per mix where the caller knows its longest request:
    # one compiled reference, found in the cache by every later run
    S = -(-max(longest, length or 0) // pad_to) * pad_to
    ids = np.zeros((len(streams), S), np.int32)
    for i, (p, t) in enumerate(streams):
        ids[i, :len(p) + len(t)] = np.concatenate([p, t])
    x, outer = forward(seed, cfg, ids)
    out = []
    with jax.default_matmul_precision("highest"):
        for i, (p, t) in enumerate(streams):
            rows = x[i][len(p) - 1:len(p) - 1 + len(t)]
            gap, is_best = _ref_gaps(rows, outer["lm_head"],
                                     jnp.asarray(t, jnp.int32))
            out.append((np.asarray(gap), np.asarray(is_best)))
    return out


# -- counts of operations and bytes ------------------------------------------------

def token_matmul_flops(cfg):
    """Operations every stepped token needs whatever it is routed to:
    the blocks' q/k/v and output projections, the dense layers' GLU and
    the expert layers' routers (2 per multiply-add)."""
    d = dims(cfg)
    total = 0
    for li in range(d.L):
        g = d.kvh[li]
        total += ((d.H + g) * d.Dk + g * d.Dv) * d.E + d.H * d.Dv * d.E
        total += d.E * d.routed if d.moe[li] else 3 * d.F * d.E
    return 2.0 * total


def expert_flops(cfg):
    """Operations of one (token, held expert) assignment: W1, W3, W2."""
    d = dims(cfg)
    return 2.0 * 3 * d.Fe * d.E


def head_flops(cfg):
    """Operations of one sampled position: this chip's vocabulary slice."""
    d = dims(cfg)
    return 2.0 * d.E * d.V


def attention_pair_flops(cfg):
    """(full, window): operations one (query, key) pair costs a model's
    layers of that kind together, q . k and p v over all query heads."""
    d = dims(cfg)
    one = 2.0 * d.H * (d.Dk + d.Dv)
    n_win = sum(bool(w) for w in d.window)
    return one * (d.L - n_win), one * n_win


def weight_bytes(cfg):
    """Bytes of the served weights (this chip's share)."""
    d = dims(cfg)
    n = 2 * d.E * d.V + d.E
    for li in range(d.L):
        g = d.kvh[li]
        n += ((d.H + g) * d.Dk + g * d.Dv) * d.E + d.H * d.Dv * d.E
        n += 2 * d.E + (d.H if d.window[li] else 0)
        n += (d.E * d.routed + d.routed + d.held * 3 * d.Fe * d.E) \
            if d.moe[li] else 3 * d.F * d.E
    return n * jnp.dtype(cfg["dtype"]).itemsize
