"""The ragged kernel, compiled for a v5e that is described and not
attached (the TPU's compiler is installed here): Mosaic refuses what the
CPU interpreter lets through — a slice off the sublane tiling, a block
shape it cannot lay out, more VMEM than a kernel may use — and it
refuses in seconds, without the chip. The shapes are the chat cell's
(Mistral-7B: 8 kv heads x 4 x 128, 128-token blocks, 16 slots, pack 2)
over its chunk widths, and two whose packed tile has to be padded to
whole sub-tiles. Nothing runs: a compile that passes says nothing about
results or times.

The topology is described inside a fixture, never at import (one
process at a time may load the TPU's library; the other workers only
collect this file)."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def _mosaic_not_the_interpreter():
    # `init_platform()` leaves the kernels in interpret mode for the
    # rest of a CPU process; here the kernel itself is what is compiled
    old = fa._INTERPRET
    fa._INTERPRET = False
    yield
    fa._INTERPRET = old


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:     # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, *, c, t, kvh=8, g=4, d=128, bs=128, nb=321, b=16,
             pack=2, dtype=jnp.bfloat16, depth=2, window=None, sink=False,
             v_dim=None):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def call(q, kv, tables, lens, q_lens, sink_h, *work):
        if window is not None:      # a window layer builds its own list
            work = pa.window_work(tables, lens, q_lens, window=window,
                                  block_size=bs, chunk=c, pack=pack)
        return pa.ragged_paged_attention(
            q, kv, tables, lens, work=(work, None, work[0].shape[0], pack),
            q_lens=q_lens, buffer_depth=depth, window=window,
            sink=sink_h if sink else None, v_dim=v_dim)

    args = [sds((b, c, kvh * g, d), dtype),
            sds((2, kvh, nb, bs, pa.paged_head_dim(d)), dtype),
            sds((b, 36), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32), sds((kvh * g,), dtype)] \
        + [sds((t,), jnp.int32)] * 9
    compiled = jax.jit(call).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [1, 8, 16, 128])
def test_chat_cell_widths_compile_for_a_v5e(one_chip, c):
    # c = 1 and 8: the tile is one static sub-tile (8 and 64 rows);
    # c = 16: two sub-tiles; c = 128: sixteen, the widest bucket
    _compile(one_chip, c=c, t=32)


@pytest.mark.parametrize("kw", [
    dict(c=5, t=16, g=8, pack=3),                       # 120 rows -> 128
    dict(c=7, t=16, g=1, pack=11, b=11, dtype=jnp.float32),  # 77 -> 128
    dict(c=128, t=64, depth=1),
    dict(c=64, t=64, kvh=2, g=2, d=64, bs=16, nb=257, b=8, pack=4),
], ids=["padded_bf16", "padded_f32", "depth1", "smoke_tp4_shard"])
def test_other_tilings_compile_for_a_v5e(one_chip, kw):
    _compile(one_chip, **kw)


@pytest.mark.parametrize("kw", [
    dict(c=1, t=16, kvh=8, g=8, window=128, sink=True),
    dict(c=16, t=16, kvh=8, g=8, window=128, sink=True),
    dict(c=128, t=16, kvh=8, g=8, window=128, sink=True),
    dict(c=1, t=64, kvh=4, g=16),
    dict(c=128, t=64, kvh=4, g=16),
], ids=["window_decode", "window_c16", "window_c128", "full_decode",
        "full_c128"])
def test_mixed_window_and_full_layers_compile_for_a_v5e(one_chip, kw):
    # keys 192 wide in 256-lane cache rows, values 128 (their own lane
    # tile is all the kernel moves), 64 query heads over 8 kv heads with
    # a window and a sink, or over 4 without; pack 1, as 16 slots give
    _compile(one_chip, d=192, v_dim=128, pack=1, nb=65, **kw)


@pytest.mark.parametrize("rows", [256, 16], ids=["tile", "decode"])
@pytest.mark.parametrize("kw", [
    dict(kvh=8, nb=321, bs=128, dk=128, dv=128),
    dict(kvh=4, nb=289, bs=256, dk=192, dv=128),
    dict(kvh=8, nb=33, bs=256, dk=192, dv=128),
    dict(kvh=2, nb=257, bs=16, dk=64, dv=64),
], ids=["mistral", "mimo_full", "mimo_window", "smoke_tp4_shard"])
def test_the_writer_of_new_rows_compiles_for_a_v5e(one_chip, kw, rows):
    """`append_paged_kv_rows` at each served cache's shape: the kernel's
    DMAs address whole 8-row tiles of the cache where it lies (Mosaic
    refuses a one-row slice of it), and the donated cache is the result:
    no temporary the cache's size."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    dc = pa.paged_head_dim(max(kw["dk"], kw["dv"]))
    cache = sds((2, kw["kvh"], kw["nb"], kw["bs"], dc), jnp.bfloat16)
    args = [cache, sds((rows, kw["kvh"], kw["dk"]), jnp.bfloat16),
            sds((rows, kw["kvh"], kw["dv"]), jnp.bfloat16),
            sds((16, 18), jnp.int32), sds((rows,), jnp.int32),
            sds((rows,), jnp.int32), sds((rows,), jnp.bool_)]
    compiled = jax.jit(pa.append_paged_kv_rows, donate_argnums=0).trace(
        *args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "%kv_rows_write" in text and "tpu_custom_call" in text
    assert "%paged_step" not in text    # the ragged kernel's reader's name
    cache_bytes = 2 * kw["kvh"] * kw["nb"] * kw["bs"] * dc * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 8
