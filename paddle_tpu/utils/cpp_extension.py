"""Custom C++ op toolchain (reference: paddle/fluid/framework/
custom_operator.cc PD_BUILD_OP ABI + python/paddle/utils/cpp_extension/ —
user-compiled ops loaded at runtime; SURVEY.md §2.13 item 19).

TPU-native design: a custom op's C++ kernel runs on the HOST (the TPU
compute path is XLA; host kernels enter the graph as io_callback-free
pure callbacks). The ABI is a C struct view of dense tensors:

    #include "paddle_tpu_ext.h"
    extern "C" void my_relu(const PTTensor* ins, int n_in,
                            PTTensor* outs, int n_out) { ... }

`load()` compiles sources with g++ into a shared library; `custom_op()`
wraps an exported symbol as a framework op (jax.pure_callback under jit,
direct call in eager), with an optional user-supplied backward op —
the same forward/backward pairing PD_BUILD_OP/PD_BUILD_GRAD_OP gives."""
import ctypes
import hashlib
import os
import subprocess
import tempfile

import numpy as np

_HEADER = """\
// paddle_tpu custom-op ABI (reference: paddle/phi/api/ext/op_meta_info.h
// PD_BUILD_OP surface, collapsed to a C struct view of dense tensors).
#pragma once
#include <stdint.h>

extern "C" {
typedef struct {
  void* data;          // dense buffer, row-major
  int64_t dims[8];
  int32_t ndim;
  int32_t dtype;       // 0=f32 1=f64 2=i32 3=i64 4=u8 5=bool
} PTTensor;
}
"""

_DTYPES = {0: np.float32, 1: np.float64, 2: np.int32, 3: np.int64,
           4: np.uint8, 5: np.bool_}
_DTYPE_IDS = {np.dtype(v): k for k, v in _DTYPES.items()}


class PTTensor(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("dims", ctypes.c_int64 * 8),
                ("ndim", ctypes.c_int32),
                ("dtype", ctypes.c_int32)]


def include_dir():
    """Directory containing paddle_tpu_ext.h (written on demand)."""
    d = os.path.join(tempfile.gettempdir(), "paddle_tpu_ext_include")
    os.makedirs(d, exist_ok=True)
    hdr = os.path.join(d, "paddle_tpu_ext.h")
    if not os.path.exists(hdr):
        with open(hdr, "w") as f:
            f.write(_HEADER)
    return d


def load(name, sources, extra_cxx_cflags=None, build_directory=None,
         verbose=False):
    """Compile `sources` into a shared library and return a handle exposing
    its extern-C symbols (reference cpp_extension.load). Rebuilds only when
    sources change (content hash)."""
    build_dir = build_directory or os.path.join(
        tempfile.gettempdir(), "paddle_tpu_extensions")
    os.makedirs(build_dir, exist_ok=True)
    h = hashlib.sha256()
    for s in sources:
        with open(s, "rb") as f:
            h.update(f.read())
    out = os.path.join(build_dir, f"{name}_{h.hexdigest()[:12]}.so")
    if not os.path.exists(out):
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
               f"-I{include_dir()}", *(extra_cxx_cflags or []),
               *sources, "-o", out + ".tmp"]
        if verbose:
            print(" ".join(cmd))
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(out + ".tmp", out)
    return CustomOpModule(name, out)


def _to_pt(arr):
    t = PTTensor()
    t.data = arr.ctypes.data
    for i, d in enumerate(arr.shape):
        t.dims[i] = d
    t.ndim = arr.ndim
    t.dtype = _DTYPE_IDS[arr.dtype]
    return t


class CustomOpModule:
    def __init__(self, name, lib_path):
        self.name = name
        self.lib_path = lib_path
        self._lib = ctypes.CDLL(lib_path)

    def _call_symbol(self, symbol, arrays, out_shapes, out_dtypes):
        fn = getattr(self._lib, symbol)
        ins = [np.ascontiguousarray(a) for a in arrays]
        outs = [np.empty(s, dtype=np.dtype(d))
                for s, d in zip(out_shapes, out_dtypes)]
        in_structs = (PTTensor * len(ins))(*[_to_pt(a) for a in ins])
        out_structs = (PTTensor * len(outs))(*[_to_pt(a) for a in outs])
        fn(in_structs, len(ins), out_structs, len(outs))
        return outs

    def custom_op(self, symbol, out_shapes_fn, out_dtypes_fn=None,
                  backward_symbol=None):
        """Wrap an exported symbol as a framework op.

        out_shapes_fn(*in_shapes) -> list of output shapes (the InferShape
        role of PD_BUILD_OP); out_dtypes_fn likewise for dtypes (defaults
        to the first input's dtype). backward_symbol, if given, is called
        with (inputs..., grad_outputs...) and must produce one grad per
        input (the PD_BUILD_GRAD_OP pairing)."""
        import jax
        from ..core.dispatch import apply_op
        from ..core.tensor import Tensor

        mod = self

        def run_fwd(*arrays):
            shapes = out_shapes_fn(*[a.shape for a in arrays])
            dtypes = (out_dtypes_fn(*[a.dtype for a in arrays])
                      if out_dtypes_fn else
                      [arrays[0].dtype] * len(shapes))
            return mod._call_symbol(symbol, [np.asarray(a) for a in arrays],
                                    shapes, dtypes)

        def host_call(*arrays):
            import jax.numpy as jnp
            if not any(isinstance(a, jax.core.Tracer) for a in arrays):
                # eager: run the host kernel directly (no callback channel
                # needed)
                outs = [jnp.asarray(o) for o in run_fwd(*arrays)]
                return tuple(outs) if len(outs) > 1 else outs[0]
            shapes = out_shapes_fn(*[a.shape for a in arrays])
            dtypes = (out_dtypes_fn(*[a.dtype for a in arrays])
                      if out_dtypes_fn else
                      [arrays[0].dtype] * len(shapes))
            result_shape = [jax.ShapeDtypeStruct(s, d)
                            for s, d in zip(shapes, dtypes)]
            outs = jax.pure_callback(
                lambda *xs: tuple(run_fwd(*xs)), tuple(result_shape),
                *arrays)
            return outs if len(outs) > 1 else outs[0]

        if backward_symbol is None:
            def impl(*arrays):
                return host_call(*arrays)

            def op(*tensors):
                return apply_op(f"custom_{symbol}", impl, tensors, {},
                                differentiable=False)
            return op

        # Custom backward. Two paths:
        # - eager: the framework tape gets a GradNode whose vjp calls the
        #   backward symbol directly on host arrays (works on every
        #   backend — no callback channel).
        # - traced (jit/to_static): jax.custom_vjp over pure_callback
        #   (needs a PJRT backend with host send/recv support).
        @jax.custom_vjp
        def core(*arrays):
            return host_call(*arrays)

        def core_fwd(*arrays):
            return host_call(*arrays), arrays

        def core_bwd(res, g):
            gs = g if isinstance(g, (tuple, list)) else (g,)
            all_in = tuple(res) + tuple(gs)
            shapes = [a.shape for a in res]
            dtypes = [a.dtype for a in res]
            result_shape = [jax.ShapeDtypeStruct(s, d)
                            for s, d in zip(shapes, dtypes)]
            grads = jax.pure_callback(
                lambda *xs: tuple(mod._call_symbol(
                    backward_symbol, [np.asarray(x) for x in xs],
                    shapes, dtypes)),
                tuple(result_shape), *all_in)
            return tuple(grads)

        core.defvjp(core_fwd, core_bwd)

        def op(*tensors):
            import jax.numpy as jnp
            from ..core import autograd as ag
            from ..core.autograd import GradNode
            from ..core.tensor import Tensor

            leaves = [t if isinstance(t, Tensor) else Tensor(t)
                      for t in tensors]
            arrays = [t.data for t in leaves]
            if any(isinstance(a, jax.core.Tracer) for a in arrays):
                def impl(*arrs):
                    return core(*arrs)
                return apply_op(f"custom_{symbol}", impl, tuple(leaves), {})

            outs_raw = [jnp.asarray(o) for o in run_fwd(*arrays)]
            record = ag.is_grad_enabled() and any(
                not t.stop_gradient for t in leaves)
            if not record:
                wrapped = [Tensor(o, stop_gradient=True) for o in outs_raw]
                return tuple(wrapped) if len(wrapped) > 1 else wrapped[0]

            diff_idx = [i for i, t in enumerate(leaves)
                        if not t.stop_gradient]
            diff = [leaves[i] for i in diff_idx]
            in_shapes = [a.shape for a in arrays]
            in_dtypes = [a.dtype for a in arrays]

            def vjp_fn(g):
                gs = g if isinstance(g, (tuple, list)) else (g,)
                all_in = [np.asarray(a) for a in arrays] + \
                    [np.asarray(x) for x in gs]
                grads = mod._call_symbol(backward_symbol, all_in,
                                         in_shapes, in_dtypes)
                return tuple(jnp.asarray(grads[i]) for i in diff_idx)

            node = GradNode(f"custom_{symbol}", vjp_fn, diff,
                            [(o.shape, o.dtype) for o in outs_raw])
            wrapped = []
            for i, o in enumerate(outs_raw):
                t = Tensor(o, stop_gradient=False)
                t._node = node
                t._out_idx = i
                wrapped.append(t)
            return tuple(wrapped) if len(wrapped) > 1 else wrapped[0]

        return op


def get_build_directory():
    return os.path.join(tempfile.gettempdir(), "paddle_tpu_extensions")


class CppExtension:
    """Extension descriptor (reference utils/cpp_extension/cpp_extension.py
    CppExtension — a setuptools.Extension configured for paddle headers).
    Holds sources + flags for `setup` to build with the same toolchain as
    `load`."""

    def __init__(self, sources, *args, name=None, extra_compile_args=None,
                 include_dirs=None, **kwargs):
        self.sources = list(sources)
        self.name = name
        extra = extra_compile_args or []
        if isinstance(extra, dict):  # reference accepts {'cxx': [...]}
            extra = extra.get("cxx", [])
        self.extra_compile_args = list(extra)
        self.include_dirs = list(include_dirs or [])


def CUDAExtension(sources, *args, **kwargs):
    """Source-compat alias (reference CUDAExtension): there is no CUDA
    toolchain on this backend — .cu sources are rejected, C++ sources
    build exactly like CppExtension (the TPU compute path is XLA/Pallas;
    custom native ops are host-side C++)."""
    cu = [s for s in sources if s.endswith((".cu", ".cuh"))]
    if cu:
        raise RuntimeError(
            f"CUDAExtension: CUDA sources {cu} cannot build on the TPU "
            "backend; implement device code as Pallas kernels and keep "
            "C++ for host-side ops (use CppExtension)")
    return CppExtension(sources, *args, **kwargs)


def setup(name=None, ext_modules=None, **kwargs):
    """Offline build entry (reference cpp_extension.setup): builds each
    extension now and registers an importable module under the build
    directory (the reference delegates to setuptools' build_ext with its
    paddle-specific compiler wrapper; here the `load` pipeline IS the
    compiler wrapper, so setup = eager load + import registration)."""
    import sys

    exts = ext_modules or []
    if isinstance(exts, CppExtension):
        exts = [exts]
    mods = []
    for i, ext in enumerate(exts):
        mod_name = ext.name or name or f"custom_ext_{i}"
        module = load(mod_name, ext.sources,
                      extra_cxx_cflags=ext.extra_compile_args +
                      [f"-I{d}" for d in ext.include_dirs])
        sys.modules[mod_name] = module
        mods.append(module)
    return mods if len(mods) != 1 else mods[0]
