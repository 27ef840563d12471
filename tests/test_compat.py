"""framework/compat resolver coverage.

resolve_shard_map and resolve_compiler_params are the two places the
whole tree takes jax's moving names from. The one installation is jax
0.9.0; these tests pin what the tree relies on there:

* `axis_names` is a SET (a tuple is refused by jax.shard_map);
* fully-manual and partial-auto calls both run and compute correct
  collectives;
* resolve_compiler_params is `pltpu.CompilerParams`, constructible with
  the shared contract kwarg (vmem_limit_bytes).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.framework.compat import (resolve_compiler_params,
                                         resolve_shard_map)


def _mesh(shape, names):
    n = int(np.prod(shape))
    devs = np.array(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, names)


class TestResolveShardMap:
    def test_resolves_to_jax_shard_map(self):
        assert resolve_shard_map() is jax.shard_map

    def test_fully_manual_passthrough(self):
        """axis_names covering the whole mesh."""
        sm = resolve_shard_map()
        mesh = _mesh((8,), ("dp",))
        x = jnp.arange(8.0)
        out = sm(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                 in_specs=P("dp"), out_specs=P(),
                 axis_names=frozenset({"dp"}), check_vma=False)(x)
        # local shard is [1]; psum over dp -> 0+1+...+7 == 28, replicated
        np.testing.assert_allclose(np.asarray(out), [28.0])

    def test_fully_manual_no_axis_names(self):
        """The classic call shape (no axis_names at all) passes through."""
        sm = resolve_shard_map()
        mesh = _mesh((4, 2), ("dp", "mp"))
        x = jnp.arange(8.0).reshape(4, 2)
        out = sm(lambda v: jax.lax.psum(v, "mp"), mesh=mesh,
                 in_specs=P("dp", "mp"), out_specs=P("dp"),
                 check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(x).sum(1, keepdims=True))

    def test_partial_auto_runs(self):
        """Manual over `dp` only on a (dp, mp) mesh: `mp` stays auto
        (under jit, as every in-tree partial-auto site is)."""
        sm = resolve_shard_map()
        mesh = _mesh((4, 2), ("dp", "mp"))
        x = jnp.arange(8.0).reshape(4, 2)
        out = jax.jit(sm(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P(),
                         axis_names={"dp"}, check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(x).sum(0, keepdims=True))

    def test_axis_names_tuple_is_refused(self):
        """The break that took tp>1 serving down on this jax: keep the
        tree on sets."""
        sm = resolve_shard_map()
        mesh = _mesh((8,), ("dp",))
        with pytest.raises(TypeError):
            sm(lambda v: v, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
               axis_names=("dp",), check_vma=False)(jnp.arange(8.0))


class TestResolveCompilerParams:
    def test_resolves_to_compiler_params(self):
        from jax.experimental.pallas import tpu as pltpu
        assert resolve_compiler_params() is pltpu.CompilerParams  # graftlint: disable=GL102 - pins which class the resolver returns

    def test_shared_contract_constructible(self):
        obj = resolve_compiler_params()(vmem_limit_bytes=1 << 20)
        assert obj.vmem_limit_bytes == 1 << 20

    def test_pallas_tuning_routes_through_resolver(self):
        from paddle_tpu.ops.pallas.autotune import VMEM_LIMIT, cparams
        obj = cparams()
        assert obj.vmem_limit_bytes == VMEM_LIMIT
        assert isinstance(obj, resolve_compiler_params())
