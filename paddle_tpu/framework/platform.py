"""The one place an entry point learns which device it runs on.

This system is written for the TPU. A script that finds no chip and
carries on in the Pallas interpreter reports success for a program
nobody deploys, so every entry point (`chip_smoke.py`, `bench.py`, the
examples, the serving tools) calls `init_platform()` before its first
other use of jax: it raises unless jax found a TPU — or the caller asked
for the CPU by name (`JAX_PLATFORMS=cpu`, which is how the tests and the
CPU gates run) — and on the TPU it places the persistent compilation
cache.

Importing this module touches no backend.
"""
import os

__all__ = ["init_platform", "compile_cache_dir"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """Directory of jax's persistent compilation cache:
    `JAX_COMPILATION_CACHE_DIR` where set (jax reads it itself), else
    `<checkout>/.jax_cache`. A fixed path: it is part of the cache key,
    so a directory that moves never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")


def init_platform():
    """Initialise jax's backend and return its platform name.

    `tpu`: the compile cache is placed (it exists to save chip time; on
    the CPU it buys little and XLA:CPU's loader logs a machine-feature
    warning on every hit) and `tpu` is returned. `cpu` with
    `JAX_PLATFORMS` naming `cpu`: the Pallas kernels are switched to
    interpret mode and `cpu` is returned. Anything else raises
    RuntimeError naming the platform found."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu":
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              compile_cache_dir())
        return platform
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if platform == "cpu" and "cpu" in (p.strip() for p in asked):
        from ..ops.pallas import flash_attention
        flash_attention._INTERPRET = True
        return platform
    raise RuntimeError(
        f"no TPU: jax found platform {platform!r} "
        f"({jax.devices()[0].device_kind}). This program runs on the "
        "chip; to run it on the CPU with the Pallas kernels interpreted, "
        "ask for that by name with JAX_PLATFORMS=cpu")
