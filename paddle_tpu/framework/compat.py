"""Top-level framework compat surface: dtype objects/introspection, Place
classes, dlpack, printoptions, misc predicates (reference:
python/paddle/framework/dtype.py, python/paddle/base/core Place types,
python/paddle/tensor/attribute.py)."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes

from ..core.dtypes import convert_dtype
from ..core.tensor import Tensor
from ..core import random as _random

__all__ = [
    "dtype", "iinfo", "finfo", "float8_e4m3fn", "float8_e5m2", "pstring",
    "raw", "CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "XPUPlace",
    "CustomPlace", "TPUPlace", "in_dynamic_mode", "LazyGuard",
    "is_floating_point", "is_complex", "is_integer", "broadcast_shape",
    "create_parameter", "tolist", "set_printoptions",
    "disable_signal_handler", "check_shape", "from_dlpack", "to_dlpack",
    "get_cuda_rng_state", "set_cuda_rng_state", "batch",
    "resolve_shard_map", "shard_map", "resolve_compiler_params",
    "inf", "nan", "pi", "e", "newaxis",
]

inf = float("inf")
nan = float("nan")
pi = math.pi
e = math.e
newaxis = None


def resolve_shard_map():
    """`jax.shard_map` (keywords `axis_names`, a set, and `check_vma`).
    Every in-tree user takes shard_map from here, so there is one
    import site to change when jax moves it (graftlint GL101)."""
    return jax.shard_map


shard_map = jax.shard_map


def resolve_compiler_params():
    """`pltpu.CompilerParams`. Every kernel builds its Mosaic parameters
    through here (graftlint GL102). Lazy pltpu import: this module is
    imported before the Pallas tier and must not pull it in at
    package-import time."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams


float8_e4m3fn = ml_dtypes.float8_e4m3fn
float8_e5m2 = ml_dtypes.float8_e5m2

# sentinel dtypes the reference exposes for string/raw tensors
pstring = "pstring"
raw = "raw"


def dtype(d):
    """paddle.dtype — normalizes any dtype spec to the canonical numpy dtype
    (the reference's paddle.dtype VarType enum constructor)."""
    return convert_dtype(d)


class iinfo:
    """Integer dtype info (reference paddle.iinfo)."""

    def __init__(self, d):
        info = np.iinfo(convert_dtype(d))
        self.min = int(info.min)
        self.max = int(info.max)
        self.bits = int(info.bits)
        self.dtype = str(np.dtype(info.dtype))

    def __repr__(self):
        return f"iinfo(min={self.min}, max={self.max}, bits={self.bits}, dtype={self.dtype})"


class finfo:
    """Float dtype info; ml_dtypes handles bfloat16/float8 (reference
    paddle.finfo)."""

    def __init__(self, d):
        d = d if d in (float8_e4m3fn, float8_e5m2) else convert_dtype(d)
        info = ml_dtypes.finfo(d)
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.resolution = float(info.resolution)
        self.smallest_normal = float(info.smallest_normal)
        self.tiny = float(info.tiny)
        self.bits = int(info.bits)
        self.dtype = str(np.dtype(d))

    def __repr__(self):
        return f"finfo(min={self.min}, max={self.max}, eps={self.eps}, dtype={self.dtype})"


class _Place:
    _kind = "undefined"

    def __init__(self, device_id=0):
        self.device_id = device_id

    def get_device_id(self):
        return self.device_id

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))


class CPUPlace(_Place):
    """Host placement (reference paddle.CPUPlace)."""
    _kind = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(_Place):
    """Source-compat accelerator placement: maps to the local TPU device
    (reference paddle.CUDAPlace — code written against it runs unchanged)."""
    _kind = "tpu"


class TPUPlace(_Place):
    _kind = "tpu"


class CUDAPinnedPlace(_Place):
    """Pinned-host placement: PJRT manages pinned staging buffers, so this is
    host placement with transfer intent."""
    _kind = "cpu"


class XPUPlace(_Place):
    _kind = "tpu"


class CustomPlace(_Place):
    _kind = "custom"

    def __init__(self, dev_type, device_id=0):
        super().__init__(device_id)
        self.dev_type = dev_type


def in_dynamic_mode():
    """True outside static-program capture (reference in_dynamic_mode)."""
    from .. import static
    return not getattr(static, "_static_mode", False)


class LazyGuard:
    """Defer parameter initialization until first use (reference LazyGuard).
    On this stack parameter init is a host-side jnp computation that XLA
    runs lazily already; the guard records intent so nn.Layer skips eager
    initializer RNG draws inside the scope."""
    _active = False

    def __enter__(self):
        LazyGuard._active = True
        return self

    def __exit__(self, *exc):
        LazyGuard._active = False
        return False


def is_floating_point(x):
    d = x.dtype if isinstance(x, Tensor) else convert_dtype(x)
    return jnp.issubdtype(d, jnp.floating)


def is_complex(x):
    d = x.dtype if isinstance(x, Tensor) else convert_dtype(x)
    return jnp.issubdtype(d, jnp.complexfloating)


def is_integer(x):
    d = x.dtype if isinstance(x, Tensor) else convert_dtype(x)
    return jnp.issubdtype(d, jnp.integer)


def broadcast_shape(x_shape, y_shape):
    return list(jnp.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Top-level parameter factory (reference paddle.create_parameter)."""
    from ..nn import initializer as I
    from ..core.tensor import Parameter
    init = default_initializer
    if init is None and attr is not None:
        init = getattr(attr, "initializer", None)
    if init is None:
        init = I.Constant(0.0) if is_bias else I.XavierNormal()
    shape = tuple(int(s) for s in shape)
    d = convert_dtype(dtype)
    if LazyGuard._active:
        data = jnp.zeros(shape, d)
    else:
        data = init(shape, d)
    return Parameter(data, trainable=True, name=name)


def tolist(x):
    return x.tolist() if isinstance(x, Tensor) else np.asarray(x).tolist()


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr formatting (reference paddle.set_printoptions); tensors
    print through numpy, so numpy printoptions are the single knob."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


def disable_signal_handler():
    """No-op: this runtime installs no signal handlers (the reference's C++
    layer hooks SIGSEGV etc. for stack dumps)."""


def check_shape(shape):
    """Validate a shape argument (reference utils.check_shape): ints or a
    1-D int tensor, entries >= -1."""
    if isinstance(shape, Tensor):
        if shape.ndim > 1:
            raise ValueError("shape tensor must be 1-D")
        shape = shape.tolist()
    for s in shape:
        if isinstance(s, Tensor):
            s = int(s)
        if not isinstance(s, (int, np.integer)):
            raise TypeError(f"shape entries must be int, got {type(s)}")
        if s < -1:
            raise ValueError(f"shape entries must be >= -1, got {s}")


def from_dlpack(capsule):
    return Tensor(jnp.from_dlpack(capsule))


def to_dlpack(x):
    arr = x.data if isinstance(x, Tensor) else jnp.asarray(x)
    return arr.__dlpack__()


def get_cuda_rng_state():
    """Device RNG state (maps to the PRNG key chain; reference
    get_cuda_rng_state returns per-GPU generator states)."""
    return [_random.get_rng_state()]


def set_cuda_rng_state(states):
    _random.set_rng_state(states[0] if isinstance(states, (list, tuple))
                          else states)


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batch reader (reference
    python/paddle/batch.py)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched
