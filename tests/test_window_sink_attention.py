"""The ragged kernel told about a window, a sink logit and values
narrower than the keys (CPU interpret mode), against a dense softmax
written out in numpy: window layers of a model that mixes them with full
layers hand the kernel a device-built work list that starts at the first
block the window touches (`window_work`), K rows 192 wide in a 256-lane
cache and V rows of 128."""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _dense(q, k_seq, v_seq, lens, q_lens, window, sink, scale):
    """q [B, C, H, Dk]; k_seq/v_seq per slot [len+q, KVH, D*]; the
    equations of a window layer with a sink, float64."""
    b, c, h, _ = q.shape
    out = np.zeros((b, c, h, v_seq[0].shape[-1]))
    for s in range(b):
        kvh = k_seq[s].shape[1]
        for j in range(int(q_lens[s])):
            t = int(lens[s]) + j
            lo = 0 if window is None else max(0, t - window + 1)
            for hh in range(h):
                kk = k_seq[s][lo:t + 1, hh // (h // kvh)].astype(np.float64)
                vv = v_seq[s][lo:t + 1, hh // (h // kvh)].astype(np.float64)
                sc = kk @ q[s, j, hh].astype(np.float64) * scale
                m = sc.max()
                e = np.exp(sc - m)
                den = e.sum() + (0.0 if sink is None
                                 else np.exp(float(sink[hh]) - m))
                out[s, j, hh] = (e / den) @ vv
    return out


def _case(rng, *, b, c, kvh, g, dk, dv, bs, window, lens, q_lens,
          with_sink):
    h = kvh * g
    dc = pa.paged_head_dim(max(dk, dv))
    max_nb = 8
    nb = b * max_nb + 1
    tables = np.zeros((b, max_nb), np.int32)
    cache = np.zeros((2, kvh, nb, bs, dc), np.float32)
    k_seq, v_seq, nxt = [], [], 1
    for s in range(b):
        n = int(lens[s] + q_lens[s])
        k = rng.standard_normal((n, kvh, dk)).astype(np.float32)
        v = rng.standard_normal((n, kvh, dv)).astype(np.float32)
        k_seq.append(k)
        v_seq.append(v)
        lo = 0 if window is None else int(max(0, lens[s] - window + 1)) // bs
        for j in range(lo, -(-n // bs)):    # blocks behind the window: freed
            tables[s, j] = nxt
            rows = slice(j * bs, min((j + 1) * bs, n))
            cache[0, :, nxt, :rows.stop - rows.start, :dk] = \
                k[rows].transpose(1, 0, 2)
            cache[1, :, nxt, :rows.stop - rows.start, :dv] = \
                v[rows].transpose(1, 0, 2)
            nxt += 1
    q = rng.standard_normal((b, c, h, dk)).astype(np.float32)
    sink = rng.standard_normal(h).astype(np.float32) if with_sink else None
    return q, cache, tables, k_seq, v_seq, sink


@pytest.mark.parametrize("kw", [
    dict(b=3, c=1, lens=[5, 40, 0], q_lens=[1, 1, 0]),
    dict(b=3, c=8, lens=[0, 37, 21], q_lens=[8, 1, 5]),
    dict(b=2, c=16, lens=[30, 3], q_lens=[16, 9]),
], ids=["decode", "mixed", "chunk"])
def test_window_sink_and_narrow_values_match_the_dense_softmax(rng, kw):
    window, bs, kvh, g, dk, dv = 12, 8, 2, 4, 192, 128
    lens, q_lens = np.asarray(kw["lens"]), np.asarray(kw["q_lens"])
    q, cache, tables, k_seq, v_seq, sink = _case(
        rng, b=kw["b"], c=kw["c"], kvh=kvh, g=g, dk=dk, dv=dv, bs=bs,
        window=window, lens=lens, q_lens=q_lens, with_sink=True)
    pack = 2
    work = pa.window_work(tables, lens, q_lens, window=window,
                          block_size=bs, chunk=kw["c"], pack=pack)
    n = kw["b"] * pa.window_entries(kw["c"], window, bs)
    assert all(a.shape == (n,) for a in work)
    got = pa.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(cache), tables, lens + q_lens,
        work=(work, None, n, pack), q_lens=jnp.asarray(q_lens),
        window=window, sink=jnp.asarray(sink), v_dim=dv)
    assert got.shape == q.shape[:3] + (dv,)
    want = _dense(q, k_seq, v_seq, lens, q_lens, window, sink,
                  1.0 / np.sqrt(dk))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_plain_call_is_untouched_by_the_new_arguments(rng):
    """No window, no sink, one width: the same kernel gives full causal
    attention over a host-built list, as it always did."""
    bs, kvh, g, d = 8, 2, 2, 128
    lens, q_lens = np.asarray([11, 0, 26]), np.asarray([4, 0, 1])
    q, cache, tables, k_seq, v_seq, _ = _case(
        rng, b=3, c=4, kvh=kvh, g=g, dk=d, dv=d, bs=bs, window=None,
        lens=lens, q_lens=q_lens, with_sink=False)
    got = pa.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(cache), tables, lens + q_lens,
        q_lens=q_lens)
    want = _dense(q, k_seq, v_seq, lens, q_lens, None, None,
                  1.0 / np.sqrt(d))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_window_span_frees_what_no_later_query_reads():
    lo, hi = pa.window_span(np, np.asarray([0, 5, 127, 128, 300]),
                            np.asarray([1, 8, 1, 128, 1]), 128, 128)
    assert lo.tolist() == [0, 0, 0, 0, 1]
    assert hi.tolist() == [0, 0, 0, 1, 2]
    assert pa.window_entries(1, 128, 128) == 2
    assert pa.window_entries(128, 128, 128) == 3
