# graftlint-corpus-expect: none
# graftlint-corpus-rule: GL101 GL102 GL103 GL104 GL301 GL302 GL401 GL402 GL403
"""False-positive tripwire: the CORRECT spellings of every pattern the
rules hunt. If any rule fires here, it drifted into noise."""
import os

import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.framework.compat import shard_map  # the sanctioned route

GOOD_SPEC = pl.BlockSpec((8, 128), lambda i: (i, 0))
LEADING_ONE = pl.BlockSpec((1, 256), lambda i: (0, i))


def update_paged_kv_cache_fixed(cache, new, block_tables, context_lens,
                                block_size, max_nb):
    blk_idx = jnp.minimum(context_lens // block_size, max_nb - 1)
    blk_ids = jnp.take_along_axis(block_tables, blk_idx[:, None],
                                  axis=1)[:, 0]
    nb = cache.shape[1]
    blk_ids = jnp.where(context_lens >= max_nb * block_size, nb, blk_ids)
    offs = context_lens % block_size
    return cache.at[:, blk_ids, offs].set(new, mode="drop")


def copy_window_clamped(src_ref, dst_ref, lens_ref, i):
    start = jnp.minimum(lens_ref[i] * 8, src_ref.shape[0] - 8)
    dst_ref[...] = src_ref[pl.ds(start, 8)]


def fully_manual(fn, jm, specs):
    return shard_map(fn, mesh=jm, in_specs=specs, out_specs=specs)


def read_env_at_call_time():
    return os.environ.get("PADDLE_DEBUG", "0")


def no_shared_default(x, acc=None):
    acc = [] if acc is None else acc
    acc.append(x)
    return acc


_INTERPRET = False  # tests flip this


def _interpret():
    return _INTERPRET


def kernel_call_routed(kernel, x, out_shape):
    # the sanctioned interpret-mode spelling: helper, not a literal
    return pl.pallas_call(kernel, out_shape=out_shape,
                          interpret=_interpret())(x)


def host_side_record(engine_step_seconds):
    # observability records OUTSIDE jit are exactly what the contract
    # wants — must not trip GL105
    from paddle_tpu import observability as obs
    obs.get_registry().histogram("step_seconds").observe(
        engine_step_seconds)
    obs.get_registry().counter("steps_total").inc()


import jax  # noqa: E402
import paddle_tpu.observability  # noqa: E402,F401


@jax.jit
def jitted_non_observability_call(x):
    # the dotted import above binds the bare name `paddle_tpu`; a
    # paddle_tpu.* call inside jit that is NOT under .observability must
    # stay clean (GL105 matches the full dotted prefix, not the root)
    return paddle_tpu.nn.functional.relu(x)


from paddle_tpu.observability import tracing  # noqa: E402


@jax.jit
def jitted_region(x):
    # `tracing.device_scope` names the ops traced under it (a named
    # scope and a frontend attribute): the one observability call meant
    # for jitted code — must not trip GL105
    with tracing.device_scope("ffn"):
        return x * 2


@jax.jit
def mxu_dot_with_accumulator(a, b):
    # the sanctioned MXU spellings: accumulator stated (GL106 clean) —
    # and a non-dot `.dot`-free einsum must never trip the rule either
    s = jnp.dot(a, b, preferred_element_type=jnp.float32)
    s = jax.lax.dot_general(s, b, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return jnp.einsum("ij,jk->ik", s, b)
