"""chip_smoke.py and the platform helper, on the CPU asked for by name.

The smoke's trainer and server legs run here at tiny widths through the
same functions the chip run calls (so the script cannot rot between chip
sessions); the platform helper must refuse a machine with no TPU unless
`JAX_PLATFORMS` names the CPU; and a flash kernel that fails to lower
must propagate out of `F.flash_attention` instead of falling back to the
XLA reference.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from paddle_tpu.framework import platform as plat
from paddle_tpu.ops.pallas import flash_attention as fa

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_SERVE = dict(V=128, E=64, H=4, G=2, D=16, L=2, F=96, dtype="float32",
                  max_seq_len=64, block_size=8, num_blocks=65, scale=0.05,
                  requests=[(5, 6), (20, 4), (9, 2), (13, 6), (3, 3)])


@pytest.fixture
def interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


class TestSmokeLegs:
    def test_trainer_leg_tiny(self):
        from paddle_tpu.models import LlamaConfig
        losses, _ = chip_smoke.trainer_leg(
            LlamaConfig.tiny(dtype="float32"), 4, 64, dict(n_devices=1))
        assert len(losses) == 3 and losses[-1] < losses[0]

    def test_server_leg_tiny(self, interpret):
        chip_smoke.server_leg(TINY_SERVE)

    @pytest.mark.parametrize("rows", [64, 16], ids=["tile", "decode"])
    def test_writer_at_tiny(self, interpret, rows, capsys):
        # keys wider than values, as the second family's, at toy sizes
        chip_smoke.writer_at("tiny", (2, 40, 16, 24, 16),
                             np.dtype("float32"), rows=rows, timed=2)
        assert "the scatter" in capsys.readouterr().out

    def test_wrong_tokens_fail_the_reference_check(self):
        rows = np.zeros((2, 8), np.float32)
        rows[:, 3] = 1.0
        chip_smoke._near_argmax(rows, [3, 3], 0.05, "ok")
        with pytest.raises(chip_smoke.SmokeFailure, match="below"):
            chip_smoke._near_argmax(rows, [3, 5], 0.05, "bad")

    def test_close_rejects_zero_and_nan(self):
        ref = np.ones((4, 4), np.float32)
        chip_smoke._close("same", ref, ref, 1e-3)
        with pytest.raises(chip_smoke.SmokeFailure, match="all zero"):
            chip_smoke._close("zero", np.zeros_like(ref), ref, 10.0)
        with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
            chip_smoke._close("nan", ref * np.nan, ref, 10.0)


class TestPlatformHelper:
    def test_cpu_by_name_runs_and_exits_nonzero(self):
        """`JAX_PLATFORMS=cpu python chip_smoke.py`: says platform=cpu in
        its first line, prints no result, exits non-zero."""
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=REPO_ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
        assert proc.returncode != 0
        lines = proc.stdout.splitlines()
        assert "platform=cpu" in lines[0]
        assert '"ok"' not in proc.stdout

    def test_raises_without_a_tpu_unless_cpu_is_named(self, monkeypatch):
        # this process's backend is the CPU; only the env differs
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="found platform 'cpu'"):
            plat.init_platform()
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        old = fa._INTERPRET
        try:
            assert plat.init_platform() == "cpu"
            assert fa._INTERPRET is True
        finally:
            fa._INTERPRET = old

    def test_cache_dir_is_env_or_checkout(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert plat.compile_cache_dir() == "/some/where"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert plat.compile_cache_dir() == os.path.join(REPO_ROOT,
                                                        ".jax_cache")


class TestNoFallbackBehindFlash:
    def test_kernel_error_propagates(self, monkeypatch):
        """With the flash path selected (as on the TPU), a kernel that
        raises at lowering must surface — the removed `except: pass`
        would have trained on the XLA reference and said nothing."""
        import paddle_tpu as paddle
        from paddle_tpu.nn.functional import attention as attn

        class Boom(RuntimeError):
            pass

        def broken(*a, **k):
            raise Boom("Mosaic refuses this block shape")

        monkeypatch.setattr(attn, "_flash_available", lambda: True)
        monkeypatch.setattr(fa, "flash_attention_bshd", broken)
        x = paddle.to_tensor(np.zeros((1, 8, 2, 16), np.float32))
        with pytest.raises(Boom):
            attn.flash_attention(x, x, x, causal=True)

    def test_backend_error_is_not_cached_as_false(self, monkeypatch):
        import jax
        from paddle_tpu.nn.functional import attention as attn

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        attn._flash_available.cache_clear()
        monkeypatch.setattr(jax, "devices", no_backend)
        try:
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                attn._flash_available()
        finally:
            attn._flash_available.cache_clear()

    def test_flash_kernel_runs_per_shard_under_a_mesh(self, monkeypatch,
                                                      interpret):
        """Under a multi-device mesh the kernel goes through a fully
        manual shard_map (GSPMD cannot partition a Mosaic kernel): batch
        over (dp, fsdp), heads over mp — same numbers as the reference."""
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.distributed.mesh import (ProcessMesh, get_mesh,
                                                 set_mesh)
        from paddle_tpu.models import pretrain
        from paddle_tpu.nn.functional import attention as attn

        monkeypatch.setattr(attn, "_flash_available", lambda: True)
        rng = np.random.default_rng(0)
        q, k, v = (rng.standard_normal((4, 32, 4, 16)).astype(np.float32)
                   for _ in range(3))
        want = np.asarray(attn._sdpa_ref(q, k, v, causal=True))
        mesh = pretrain.make_mesh(8, dp=2, fsdp=2, mp=2)
        prev = get_mesh()
        set_mesh(ProcessMesh(mesh))
        try:
            got = jax.jit(lambda a, b, c: attn.flash_attention(
                paddle.to_tensor(a), paddle.to_tensor(b),
                paddle.to_tensor(c), causal=True)[0].data)(q, k, v)
        finally:
            set_mesh(prev)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
