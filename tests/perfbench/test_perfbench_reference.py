"""The plain reference against the program's own dense op at a tiny size,
and the weights the benchmark hands the program."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_fixtures import BENCH


@pytest.fixture(scope="module")
def fam():
    spec = importlib.util.spec_from_file_location(
        "fam_mistral", os.path.join(BENCH, "models", "mistral.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CFG = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
           vocab_size=128, rms_norm_eps=1e-5, rope_theta=10000.0,
           dtype="float32", engine=dict(max_seq_len=64))


def test_the_same_seed_gives_the_same_weights(fam):
    a = fam.serve_weights(2 ** 31 + 7, CFG)
    b = fam.serve_weights(2 ** 31 + 7, CFG)
    c = fam.serve_weights(2 ** 31 + 8, CFG)
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(a))
    assert np.array_equal(a["qkv_weights"][1], b["qkv_weights"][1])
    assert not np.array_equal(a["qkv_weights"][1], c["qkv_weights"][1])
    assert a["qkv_weights"][0].shape == (4 + 2 * 2, 16, 64)
    assert a["ffn1_weights"][0].shape == (64, 2 * 96)
    # a layer's tensors made alone equal the ones made in one call
    t = fam.layer_tensors(fam._key(2 ** 31 + 7), 1, fam.dims(CFG),
                          jnp.dtype("float32"))
    assert np.array_equal(t["qkv"], a["qkv_weights"][1])
    assert np.array_equal(t["down_t"], a["ffn2_weights"][1])


def test_reference_agrees_with_fused_multi_transformer(fam):
    """Teacher forcing over the program's dense op: where the program's
    own float32 logits put a token first, the reference's gap is ~0."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn.functional import fused_multi_transformer
    seed = 17
    w = fam.serve_weights(seed, CFG)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 128, 9)
    ids = np.concatenate([prompt, rng.integers(1, 128, 12)])[None]
    out = fused_multi_transformer(
        Tensor(w["embedding"][ids]), w["ln_scales"], None, w["qkv_weights"],
        None, w["linear_weights"], None, w["ffn_ln_scales"], None,
        w["ffn1_weights"], None, w["ffn2_weights"], None,
        rotary_embs=w["rotary_embs"], gqa_group_size=2, norm_type="rmsnorm",
        activation="swiglu", use_neox_rotary_style=True)
    logits = np.asarray(out.data @ w["lm_head"])[0]
    rows = logits[len(prompt) - 1:-1]
    cont = ids[0, len(prompt):]
    want = rows.max(-1) - rows[np.arange(len(cont)), cont]
    (gap, best), = fam.served_token_gaps(seed, CFG, [(prompt, cont)])
    assert np.allclose(gap, want, atol=2e-5), (gap, want)
    assert (best == (rows.argmax(-1) == cont)).all()
    greedy = rows.argmax(-1)[:1]        # the one token both contexts share
    (gap, best), = fam.served_token_gaps(seed, CFG, [(prompt, greedy)])
    assert best.all() and float(gap.max()) == 0.0


def test_counts(fam):
    real = dict(CFG, hidden_size=4096, intermediate_size=14336,
                num_attention_heads=32, num_key_value_heads=8, head_dim=128,
                num_hidden_layers=2, vocab_size=32768, dtype="bfloat16")
    block = 48 * 128 * 4096 + 4096 * 4096 + 3 * 14336 * 4096
    assert fam.matmul_params(real) == 2 * block + 4096 * 32768
    per_tok = fam.train_flops_per_token(real, 4096)
    assert per_tok == 6 * fam.matmul_params(real) + 3 * 2 * 4096 * 32 * 128 * 2
    ops, nbytes = fam.flash_cost(real, 1, 4096)
    assert ops == 3 * 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    assert nbytes == 6 * 4096 * 32 * 128 * 2 + 6 * 4096 * 8 * 128 * 2
    assert fam.kv_bytes_per_token(real) == 2 * 2 * 8 * 128 * 2
