"""paddle.inference parity: Config / create_predictor / Predictor.

Reference: paddle/fluid/inference/api/analysis_predictor.h:101 +
python/paddle/inference (SURVEY.md §2.11). The reference predictor loads a
program, runs ~300 IR fusion passes, plans memory reuse, and executes with
zero-copy IO handles. On TPU that whole pipeline IS XLA: load the
jit.save artifact, jit-compile the restored layer (AOT per input shape,
cached), and keep IO as device-resident arrays. Precision switches map to
dtype casts (bf16 is the TPU-native mode)."""
import os
import pickle

import numpy as np

__all__ = ["Config", "PrecisionType", "create_predictor", "Predictor"]


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class Config:
    """Mirror of paddle.inference.Config's commonly-used surface."""

    def __init__(self, prog_file=None, params_file=None):
        # accept a prefix ("model/infer"), a model dir, or explicit files
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self.model_prefix = prog_file
        self.params_file = params_file
        self._precision = PrecisionType.Float32
        self._memory_optim = True
        self._glog_info = True
        self._device = None
        self._cache_dir = None

    # -- device / precision ------------------------------------------------
    def enable_tpu(self, precision=PrecisionType.Bfloat16):
        self._device = "tpu"
        self._precision = precision

    def enable_use_gpu(self, memory_pool_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        # source-compat shim: GPU requests run on whatever PJRT device exists
        self._device = "tpu"
        self._precision = precision

    def disable_gpu(self):
        self._device = "cpu"

    def set_cpu_math_library_num_threads(self, n):
        pass  # XLA owns threading

    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def disable_glog_info(self):
        self._glog_info = False

    def set_optim_cache_dir(self, d):
        self._cache_dir = d

    def precision(self):
        return self._precision


class _IOHandle:
    """Zero-copy tensor handle (reference ZeroCopyTensor): the array stays
    device-resident between copy_from_cpu and run."""

    def __init__(self, name):
        self.name = name
        self._array = None
        self._shape = None   # declared via reshape() before data arrives
                             # (the C-API contract: reshape then copy)

    def reshape(self, shape):
        self._shape = list(shape)
        if self._array is not None:
            self._array = self._array.reshape(shape)

    def copy_from_cpu(self, arr):
        import jax
        a = np.asarray(arr)
        if self._shape is not None and list(a.shape) != self._shape:
            a = a.reshape(self._shape)
        self._array = jax.device_put(a)

    def share_external_data(self, tensor):
        self._array = tensor.data if hasattr(tensor, "data") else tensor

    def copy_to_cpu(self):
        return np.asarray(self._array)

    def shape(self):
        if self._array is not None:
            return list(self._array.shape)
        return list(self._shape) if self._shape else []


class Predictor:
    def __init__(self, config: Config):
        from ..jit.io import load as jit_load
        self._config = config
        self._layer = jit_load(config.model_prefix)
        if hasattr(self._layer, "eval"):
            self._layer.eval()
        if config.precision() in (PrecisionType.Bfloat16,
                                  PrecisionType.Half) \
                and hasattr(self._layer, "to"):
            # cast params to the serving dtype (bf16: MXU-native)
            self._cast_params(config.precision())
        self._inputs = {}
        self._outputs = {}
        self._compiled = {}
        self._n_inputs = None

    def _cast_params(self, dtype):
        from ..core.tensor import Tensor
        import jax.numpy as jnp
        for _, p in self._layer.named_parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(dtype)

    # -- IO handles (reference get_input_handle/get_output_handle) --------
    def get_input_names(self):
        if self._n_inputs is None:
            return ["x0"]
        return [f"x{i}" for i in range(self._n_inputs)]

    def get_output_names(self):
        return sorted(self._outputs.keys())

    def get_input_handle(self, name):
        return self._inputs.setdefault(name, _IOHandle(name))

    def get_output_handle(self, name):
        return self._outputs.setdefault(name, _IOHandle(name))

    # -- execution ---------------------------------------------------------
    def run(self, inputs=None):
        """Execute. Either positional `inputs` (list of numpy arrays —
        convenience path) or pre-filled input handles."""
        import jax
        from ..core.tensor import Tensor
        from ..jit.functional import state_arrays, pure_call

        if inputs is not None:
            for i, a in enumerate(inputs):
                self.get_input_handle(f"x{i}").copy_from_cpu(a)

        def _order(name):  # numeric order: x2 before x10
            return (0, int(name[1:])) if name[1:].isdigit() else (1, name)

        handles = [self._inputs[k] for k in sorted(self._inputs, key=_order)]
        empty = [h.name for h in handles if h._array is None]
        if empty:
            raise RuntimeError(
                f"input handles never filled: {empty} — call "
                "copy_from_cpu on every input before run()")
        arrays = [h._array for h in handles]
        self._n_inputs = len(arrays)
        if self._config.precision() in (PrecisionType.Bfloat16,
                                        PrecisionType.Half):
            import jax.numpy as jnp
            arrays = [a.astype(self._config.precision())
                      if a.dtype == jnp.float32 else a for a in arrays]

        key = tuple((a.shape, str(a.dtype)) for a in arrays)
        if key not in self._compiled:
            params, buffers = state_arrays(self._layer)
            # deliberate snapshot, NOT a self.* capture (GL108): the
            # layer is the static module SKELETON — every array it owns
            # (params AND buffers) flows through jit arguments below; a
            # live self._layer reference inside the jitted closure
            # would pin whatever the attribute pointed at when each
            # shape first compiled
            layer = self._layer

            def fn(params, buffers, *xs):
                return pure_call(layer, params, buffers, *xs)

            self._compiled[key] = (jax.jit(fn), params, buffers)
        fn, params, buffers = self._compiled[key]
        out = fn(params, buffers, *arrays)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        for i, o in enumerate(outs):
            self.get_output_handle(f"out{i}")._array = o
        return [np.asarray(o) for o in outs]

    def clone(self):
        return Predictor(self._config)


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


class DataType:
    """IO dtype enum (reference paddle.inference.DataType)."""
    FLOAT32 = "float32"
    FLOAT16 = "float16"
    BFLOAT16 = "bfloat16"
    INT64 = "int64"
    INT32 = "int32"
    INT8 = "int8"
    UINT8 = "uint8"
    BOOL = "bool"


class PlaceType:
    """IO placement enum (reference paddle.inference.PlaceType)."""
    UNK = -1
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM = 3
    TPU = 3


Tensor = _IOHandle  # reference exposes the IO handle type as inference.Tensor


class PredictorPool:
    """Fixed-size predictor pool (reference PredictorPool): each entry is a
    clone sharing the compiled executables."""

    def __init__(self, config, size=1):
        self._preds = [Predictor(config) for _ in range(size)]

    def retrieve(self, idx):
        return self._preds[idx]


class XpuConfig:
    """Accelerator sub-config placeholder (reference XpuConfig); TPU memory
    is managed by PJRT so fields are recorded but not enforced."""

    def __init__(self):
        self.device_id = 0
        self.l3_size = 0


def get_version():
    from .. import __version__
    return __version__


def get_num_bytes_of_data_type(dtype):
    import numpy as np
    return np.dtype({"bfloat16": "uint16"}.get(dtype, dtype)).itemsize


def get_trt_compile_version():
    """No TensorRT on TPU — the XLA compiler fills that role."""
    return (0, 0, 0)


def get_trt_runtime_version():
    return (0, 0, 0)


def convert_to_mixed_precision(model_file, params_file, mixed_model_file,
                               mixed_params_file, mixed_precision,
                               backend=None, keep_io_types=True,
                               black_list=None):
    """Re-save a jit.save artifact with params cast to the target precision
    (reference convert_to_mixed_precision pass)."""
    import pickle
    import numpy as np
    import ml_dtypes
    import os
    if isinstance(mixed_precision, str):
        key = mixed_precision.lower()
    else:  # PrecisionType enum/string constants
        key = str(mixed_precision).lower()
    target = {"float16": np.float16, "half": np.float16,
              "precisiontype.half": np.float16,
              "bfloat16": ml_dtypes.bfloat16}.get(key, ml_dtypes.bfloat16)
    with open(params_file, "rb") as f:
        state = pickle.load(f)

    def cast(v):
        a = np.asarray(v)
        return a.astype(target) if a.dtype == np.float32 else a
    state = {k: cast(v) for k, v in state.items()}
    os.makedirs(os.path.dirname(mixed_params_file) or ".", exist_ok=True)
    with open(mixed_params_file, "wb") as f:
        pickle.dump(state, f)
    if os.path.exists(model_file) and model_file != mixed_model_file:
        import shutil
        shutil.copy(model_file, mixed_model_file)


def _get_phi_kernel_name(op_name):
    """Kernel-name mapping probe (reference _get_phi_kernel_name); ops here
    map 1:1 to registry names."""
    return op_name


def _arg_signature(args, kwargs, static_argnums=()):
    """Hashable shape/dtype signature of a jitted call — the same
    information that keys jax's executable cache, computed host-side:
    array leaves collapse to (shape, dtype) so VALUES never over-key
    (a work list with different block ids is the same program), while
    STATIC args keep their values (they key the compile). Used by the
    dispatch wrappers to attribute cost analyses once per signature."""
    import jax

    static = {i: a for i, a in enumerate(args) if i in set(static_argnums)}
    dyn = tuple(a for i, a in enumerate(args) if i not in static)

    def freeze(x):
        leaves, treedef = jax.tree_util.tree_flatten(x)
        return (str(treedef), tuple(
            (tuple(l.shape), str(l.dtype))
            if hasattr(l, "shape") and hasattr(l, "dtype")
            else ("py", type(l).__name__) for l in leaves))

    def freeze_static(x):
        leaves, treedef = jax.tree_util.tree_flatten(x)
        return (str(treedef), tuple(leaves))

    return (freeze((dyn, kwargs or {})),
            tuple((i, freeze_static(a)) for i, a in sorted(static.items())))


# host-side fault-injection point (paddle_tpu/testing/faults.py): a
# per-program dispatch delay in seconds, applied on the HOST before the
# compiled call is enqueued. This is how the chaos harness makes a step
# "slow/stalled" deterministically without touching the device program
# — the delay lands inside the dispatch span, so the flight recorder
# and the dispatch_seconds{program} histogram see exactly what a real
# stall would look like. Empty in production; never consulted under a
# tracer (the wrapper is plain host code).
_dispatch_delay = {}


def set_dispatch_delay(program, delay_s):
    """Testing hook: stall `program`'s dispatches by `delay_s` host
    seconds (0/None clears). Returns the previous value so callers can
    restore — the fault injector scopes it per step."""
    prev = _dispatch_delay.get(program)
    if not delay_s:
        _dispatch_delay.pop(program, None)
    else:
        _dispatch_delay[program] = float(delay_s)
    return prev


def _dispatch_span(name, fn, static_argnums=()):
    """Host-side span around a compiled program's dispatch (tracing.py
    ring; perf_counter timebase). jax dispatch is async: the measured
    interval covers trace/lower/compile (first call per bucket — which
    is why `paged_step` spans make recompiles visible on the timeline)
    plus enqueue, NOT device completion. The wrapper is plain host code
    wrapping the jitted callable, so the record never runs under a
    tracer (the GL105 contract). The duration also lands in the
    `dispatch_seconds{program}` histogram so the windowed time-series
    layer (observability/timeseries.py) can answer "did DISPATCH get
    slower over the last N seconds" — the signal that separates a
    model-side regression from queueing in the SLO engine's view.

    When the cost catalog is enabled (observability/costs.py — opt-in:
    an analysis pays one extra backend compile), the FIRST call per
    arg signature additionally AOT-analyzes the program and lands its
    FLOPs/bytes/HBM in the catalog — the signature set mirrors jax's
    own executable-cache keys, so analyses happen exactly at the cache
    misses the compile watch sees, BEFORE the call so donated buffers
    are still alive for lowering."""
    import time as _time

    from ..observability import costs as _costs
    from ..observability import instrument as _instrument
    from ..observability import tracing as _tracing

    seen = set()
    seen_gen = [None]

    def call(*args, **kwargs):
        catalog = _costs.get_cost_catalog()
        if catalog.enabled:
            if seen_gen[0] != catalog.generation:
                # the catalog was reset: warm signatures must
                # re-attribute or the cleared gauges stay empty until
                # an unseen shape arrives (possibly never)
                seen.clear()
                seen_gen[0] = catalog.generation
            try:
                sig = _arg_signature(args, kwargs, static_argnums)
            except Exception:
                sig = None
            if sig is not None and sig not in seen:
                seen.add(sig)
                catalog.analyze_jitted(name, fn, args, kwargs,
                                       signature=f"sig{len(seen)}")
        t0 = _time.perf_counter()
        delay = _dispatch_delay.get(name)
        if delay:
            # injected stall (testing hook above): inside the span and
            # the histogram on purpose — evidence looks like the fault
            _time.sleep(delay)
        out = fn(*args, **kwargs)
        dur = _time.perf_counter() - t0
        _tracing.get_tracer().record_span(name, t0 * 1e6, dur * 1e6)
        _instrument.dispatch_seconds().labels(program=name).observe(dur)
        return out

    call.__wrapped__ = fn
    return call


__all__ += ["FusedMultiTransformerEngine", "set_dispatch_delay"]
__all__ += ["DataType", "PlaceType", "Tensor", "PredictorPool", "XpuConfig",
            "get_version", "get_num_bytes_of_data_type",
            "get_trt_compile_version", "get_trt_runtime_version",
            "convert_to_mixed_precision", "_get_phi_kernel_name"]


class FusedMultiTransformerEngine:
    """Serving engine over the fused_multi_transformer op (role of the
    reference's fused_multi_transformer-based inference stack:
    AnalysisPredictor + fused decoder passes). Holds per-layer weight lists
    + embedding/lm_head, compiles ONE prefill program and ONE decode-step
    program (caches donated, so XLA updates them in place in HBM), and
    serves greedy generation.

    weights: dict with keys matching fused_multi_transformer's list args
    (ln_scales, qkv_weights, ...), plus 'embedding' [V, E] and 'lm_head'
    [E, V]. All values may be paddle Tensors or jax arrays. A
    'final_norm_scale' [E], where the dict has one, is the norm applied
    before the head.

    ``layers`` is the per-layer block description of a model whose
    layers differ (a dict or `LayerSpec` per layer: kv-head count, value
    width, window, sink, rotary table, value scale, activation, routed
    experts; `incubate.nn.functional.LayerSpec`). The paged
    step is compiled from it; without it `gqa_group_size` and
    `activation` describe every layer, which is the description with one
    kind of layer. A model with window layers keeps two block tables a
    sequence (every block for its full layers; the blocks the window
    touches for its window layers), its caches have one shape per
    layer, and it serves through the paged path only.

    ``tp > 1`` shards the PAGED serving path over a one-axis tensor-
    parallel device mesh (inference/tp_layout.py): qkv/ffn1 weights
    split column-wise (per-head / per-feature), out-proj/ffn2 split
    row-wise with one psum each per layer, and the paged KV cache —
    plus the ragged work-list kernel's grid — shards over KV HEADS, so
    per-device cache HBM drops by the TP factor. The three paged
    programs (`_paged_step`/`_paged_rewind`/`_paged_copy`) become
    shard_map'd mesh programs with the SAME host-facing signatures and
    compile-key treadmill: the host-side scheduler stays single-brain
    and drives the whole mesh with one dispatch per step. Requires
    `num_heads`, kv heads, and the FFN width all divisible by tp, and
    tp visible devices. The dense `generate()` path is deliberately
    NOT mesh-aware (serving runs through ContinuousBatchingEngine);
    token-exactness vs a single-chip engine is gated by
    tools/serve_bench --tp and tests/test_serve_tp.py.
    """

    def __init__(self, weights, num_heads, head_dim, max_seq_len=2048,
                 norm_type="layernorm", activation="gelu",
                 use_neox_rotary_style=False, dtype="bfloat16",
                 gqa_group_size=-1, weight_quant=None, tp=1,
                 kv_buffer_depth=None, autotune_cache=None, layers=None):
        import jax
        import jax.numpy as jnp
        from ..incubate.nn.functional import (ExpertSpec, LayerSpec,
                                              fused_multi_transformer)
        from ..observability import tracing

        def arr(v):
            from ..core.tensor import Tensor as _T
            if v is None:       # a layer without that tensor
                return None
            a = v.data if isinstance(v, _T) else jnp.asarray(v)
            return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
                else a

        self._w = {k: ([arr(x) for x in v] if isinstance(v, (list, tuple))
                       else arr(v)) for k, v in weights.items()}
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.max_seq_len = max_seq_len
        self._dtype = dtype
        self._n_layers = len(self._w["qkv_weights"])
        # GQA (reference fused_transformer.py:1009): kv heads < q heads;
        # the cache is allocated at the kv-head count
        self._gqa = gqa_group_size if gqa_group_size and gqa_group_size > 0 \
            else 0
        # the per-layer block description the layer loop reads: given,
        # or the one kind of layer the global arguments describe
        if layers is None:
            specs = (LayerSpec(kv_heads=self._gqa,
                               activation=activation),) * self._n_layers
        else:
            specs = tuple(
                sp if isinstance(sp, LayerSpec) else LayerSpec(**dict(
                    sp, head_dim=head_dim, experts=ExpertSpec(**sp["experts"])
                    if sp.get("experts") else None))
                for sp in layers)
            if len(specs) != self._n_layers:
                raise ValueError(
                    f"{len(specs)} layer descriptions for "
                    f"{self._n_layers} layers of weights")
        self.layer_specs = specs
        windows = {sp.window for sp in specs if sp.window}
        if len(windows) > 1:
            raise ValueError(
                "a model keeps at most two block tables a sequence: one "
                "for its full-attention layers, one for window layers "
                f"of ONE window size, not {sorted(windows)} (ROADMAP M3)")
        # the window its window layers attend over, None without any
        self.window = windows.pop() if windows else None
        kw = dict(norm_type=norm_type, layers=specs,
                  use_neox_rotary_style=use_neox_rotary_style)
        # tensor-parallel serving (tp_layout.py): weights repacked +
        # device_put onto a one-axis mesh, and the paged programs below
        # become shard_map'd mesh programs. paged_kw is the PER-DEVICE
        # view the shard_map body computes with: local head counts and
        # the two row-parallel psums per layer.
        self.tp = int(tp) if tp else 1
        if self.tp < 1:
            # reject at construction like the divisibility errors: a
            # negative width would serve single-chip while poisoning
            # every mesh-aware surface (healthz mesh.tp, per-device
            # gauges) downstream
            raise ValueError(f"tp must be >= 1, got {tp}")
        self._mesh = None
        self._w_specs = None
        paged_kw = kw
        if self.tp > 1:
            import numpy as _np
            from jax.sharding import Mesh
            from ..ops.pallas.paged_attention import kv_head_shard
            from .tp_layout import validate_tp
            if any(sp.experts for sp in specs):
                raise ValueError(
                    "tp > 1 with routed experts: inference/tp_layout.py "
                    "knows column/row splits and kv-head shards, no "
                    "expert placement (ROADMAP M2)")
            if len({sp.kv_heads for sp in specs}) > 1 or self.window:
                raise ValueError(
                    "tp > 1 with two kinds of attention layer: "
                    "inference/tp_layout.py shards one kv-head count "
                    "(ROADMAP M3)")
            kvh_n = self._gqa or num_heads
            ffn_dim = int(self._w["ffn2_weights"][0].shape[0])
            validate_tp(num_heads, kvh_n, ffn_dim, self.tp)
            kv_head_shard(kvh_n, self.tp)   # same grid on every device
            devs = jax.devices()
            if len(devs) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} devices, "
                    f"have {len(devs)}")
            self._mesh = Mesh(_np.array(devs[:self.tp]), ("tp",))
            paged_kw = dict(kw)
            if self._gqa:       # each device's share of the kv heads
                paged_kw["layers"] = tuple(
                    sp._replace(kv_heads=sp.kv_heads // self.tp)
                    for sp in specs)
            paged_kw["_tp_reduce"] = lambda x: jax.lax.psum(x, "tp")
            if weight_quant == "int4":
                # the row-parallel specs split the PACKED nibble axis
                # (lin [K/2, E] / ffn2 [F/2, E]): each device's
                # contiguous row span must cover whole (2i, 2i+1)
                # nibble pairs or its unpack reconstructs rows that
                # straddle the device boundary
                for what, n in (("num_heads*head_dim",
                                 num_heads * head_dim),
                                ("dim_feedforward", ffn_dim)):
                    if (n // self.tp) % 2 != 0:
                        raise ValueError(
                            f"int4 weight_quant with tp={self.tp} needs "
                            f"{what}/tp ({n}//{self.tp}) even — packed "
                            "int4 rows split in (2i, 2i+1) pairs")
        # weight-only quantized serving: pack the matmul weights at load
        # (int4 = half the int8 tier's weight HBM) and dequantize inside
        # the op, fused into the operand load
        self.weight_quant = weight_quant
        tp_dequant = None
        if weight_quant in ("int4", "int8"):
            # int4 on TPU: the Pallas weight-only GEMM FIRST
            # (ops/pallas/quant_matmul.py — streams the packed bytes,
            # unpacks in-registers; the XLA nibble-unpack path was the
            # round-4 0.41x regression, the kernel makes it 1.16x).
            # Packed weights REPLACE self._w's lists so they flow as
            # program ARGUMENTS (closure capture would inline ~350 MB of
            # constants into the compile payload). int8 stays on the XLA
            # dequant path (measured equal-or-better: XLA fuses the
            # int8->bf16 convert into the operand load). The Pallas GEMM
            # is single-chip only: under tp the XLA dequant path runs
            # per-device on the weight shards instead.
            mm = None
            if weight_quant == "int4" and self.tp == 1 \
                    and jax.devices()[0].platform == "tpu":
                try:
                    mm = self._build_quant_mm(weights, dtype)
                except ValueError:
                    mm = None  # indivisible shape: dequant fallback below
            if mm is not None:
                kw["_mm"] = mm
            else:
                import numpy as _np
                from ..incubate.nn.functional import (_unpack_int4,
                                                      quantize_int4)
                qscales = {}

                def _quant(kind, ws, axis):
                    packed, scs = [], []
                    for t in ws:
                        a = _np.asarray(t, _np.float32)
                        if weight_quant == "int4":
                            pk, sc = quantize_int4(a, axis=axis)
                        else:
                            m = _np.moveaxis(a, axis, -1)
                            sc = _np.abs(m).max(-1, keepdims=True) / 127.0 \
                                + 1e-9
                            pk = _np.clip(_np.round(m / sc), -127, 127
                                          ).astype(_np.int8)
                            pk = _np.moveaxis(pk, -1, axis)
                            sc = _np.moveaxis(sc, -1, axis)
                        packed.append(jnp.asarray(pk))
                        scs.append(jnp.asarray(sc))
                    qscales[kind] = scs
                    return packed

                # quantization happens GLOBALLY (pre-shard, from the
                # full weights) in every case — under tp the per-device
                # shards are then exact row/column slices of the SAME
                # packed values + scales the dense engine serves, which
                # is what makes quantized tensor-parallel serving
                # token-exact vs the dense weight_quant generate()
                self._w["qkv_weights"] = _quant(
                    "qkv", self._w["qkv_weights"], -1)
                # the INPUT axis: the first of a [K, N] matrix, the
                # middle one of a layer's stacked experts [held, K, N]
                self._w["linear_weights"] = _quant(
                    "lin", self._w["linear_weights"], -2)
                self._w["ffn1_weights"] = _quant(
                    "f1", self._w["ffn1_weights"], -2)
                self._w["ffn2_weights"] = _quant(
                    "f2", self._w["ffn2_weights"], -2)
                cdt = dtype
                if self.tp == 1:
                    def dq(w, kind, li):
                        sc = qscales[kind][li]
                        if weight_quant == "int4":
                            full = _unpack_int4(
                                w, axis=-1 if kind == "qkv" else -2)
                        else:
                            full = w
                        return (full.astype(jnp.float32) * sc).astype(cdt)

                    kw["_dequant"] = dq
                else:
                    # tensor-parallel: the scales become WEIGHTS —
                    # tp_layout shards each alongside its packed
                    # projection (qkv/ffn1 scales follow their repack +
                    # split; lin/ffn2 scales are per-OUTPUT-channel so
                    # they replicate) — and dequantization runs
                    # per-device at the top of the shard_map'd step
                    # body, reconstructing exactly this device's shard
                    # of the dense engine's dequantized weights
                    self._w["qkv_wscales"] = qscales["qkv"]
                    self._w["linear_wscales"] = qscales["lin"]
                    self._w["ffn1_wscales"] = qscales["f1"]
                    self._w["ffn2_wscales"] = qscales["f2"]
                    is4 = weight_quant == "int4"

                    def tp_dequant(w):
                        w = dict(w)
                        for key, skey, axis in (
                                ("qkv_weights", "qkv_wscales", -1),
                                ("linear_weights", "linear_wscales", -2),
                                ("ffn1_weights", "ffn1_wscales", -2),
                                ("ffn2_weights", "ffn2_wscales", -2)):
                            scs = w.pop(skey)
                            w[key] = [
                                ((_unpack_int4(p, axis=axis) if is4
                                  else p).astype(jnp.float32)
                                 * sc).astype(cdt)
                                for p, sc in zip(w[key], scs)]
                        return w
        if self.tp > 1:
            from .tp_layout import shard_serving_weights
            self._w, self._w_specs = shard_serving_weights(
                self._w, self._mesh, num_heads, kvh_n,
                activation.endswith("glu"), self.tp)
        # KV DMA pipeline depth for the ragged kernel: an explicit arg
        # wins, else the committed autotune cache's winner for this
        # engine's shape class, else the classic double buffer. Resolved
        # ONCE here (closure into the paged step) — zero per-step cost.
        from ..ops.pallas import autotune as _autotune
        self._autotune_cache = None if autotune_cache is None \
            else _autotune.load_serve_cache(autotune_cache)
        if kv_buffer_depth is None:
            kvh_l = specs[0].kv_heads or num_heads
            cfg = _autotune.serve_winner_for_engine(
                self._autotune_cache, kvh_l, num_heads // kvh_l,
                head_dim, dtype) if self._autotune_cache else None
            kv_buffer_depth = cfg["buffer_depth"] if cfg else 2
        self.kv_buffer_depth = int(kv_buffer_depth)
        paged_kw["kv_buffer_depth"] = self.kv_buffer_depth

        def lists(w):
            def g(name):
                return w.get(name) or None
            return (w["ln_scales"], g("ln_biases"), w["qkv_weights"],
                    g("qkv_biases"), w["linear_weights"], g("linear_biases"),
                    w["ffn_ln_scales"], g("ffn_ln_biases"), w["ffn1_weights"],
                    g("ffn1_biases"), w["ffn2_weights"], g("ffn2_biases"))

        def per_layer(w):
            """The per-layer tensors only some layers have (None where a
            layer has none)."""
            return dict(router_weights=w.get("router_weights"),
                        router_biases=w.get("router_biases"),
                        attn_sinks=w.get("attn_sinks"))

        # the routed-expert layers' descriptions, for the scheduler's
        # counters (its host arithmetic needs their count and top_k)
        self.expert_specs = tuple(
            sp.experts for sp in specs if sp.experts is not None)

        def head(h, w):
            """The final norm, where the weights have one, and the
            untied head: h [..., E] -> logits [..., V]."""
            g = w.get("final_norm_scale")
            if g is not None:
                from ..incubate.nn.functional import _ln, _rms
                h = _rms(h, 1e-5, g) if norm_type == "rmsnorm" else \
                    _ln(h, 1e-5, g, w.get("final_norm_bias"))
            return h @ w["lm_head"]

        def select(logits, temp, topp, key):
            """Greedy when temp<=0, else temperature + nucleus (top-p)
            sampling (reference top_p_sampling op semantics) — all traced,
            so the whole sampled decode stays one device program."""
            import jax
            greedy = jnp.argmax(logits, -1)
            lg = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
            sl = jnp.flip(jnp.sort(lg, -1), -1)
            ps = jax.nn.softmax(sl, -1)
            csum = jnp.cumsum(ps, -1)
            # last sorted index whose PRECEDING mass is still < top_p
            k_idx = jnp.sum((csum - ps) < topp, -1) - 1
            thresh = jnp.take_along_axis(
                sl, jnp.maximum(k_idx, 0)[..., None], -1)
            filt = jnp.where(lg >= thresh, lg, -jnp.inf)
            samp = jax.random.categorical(key, filt, -1)
            return jnp.where(temp <= 0.0, greedy, samp)

        def prefill(w, caches, ids, temp, topp, key, lens=None):
            h = w["embedding"][ids]
            from ..core.tensor import Tensor
            cts = [Tensor(c) for c in caches]
            out = fused_multi_transformer(
                Tensor(h), *lists(w), cache_kvs=cts,
                seq_lens=None if lens is None else Tensor(lens),
                rotary_embs=w.get("rotary_embs"), **per_layer(w), **kw)
            if lens is None:
                logits = head(out.data[:, -1], w)
            else:
                # ragged prompts: each row's LAST VALID hidden state
                bidx = jnp.arange(out.data.shape[0])
                logits = head(out.data[bidx, lens - 1], w)
            return select(logits, temp, topp, key), [c.data for c in cts]

        def step(w, caches, tok, t, temp, topp, key, lens=None):
            h = w["embedding"][tok][:, None]
            from ..core.tensor import Tensor
            cts = [Tensor(c) for c in caches]
            out = fused_multi_transformer(
                Tensor(h), *lists(w), cache_kvs=cts,
                time_step=Tensor(t),
                seq_lens=None if lens is None else Tensor(lens),
                rotary_embs=w.get("rotary_embs"), **per_layer(w), **kw)
            logits = head(out.data[:, 0], w)
            return select(logits, temp, topp, key), [c.data for c in cts]

        def steps(w, caches, tok, t0, n, temp, topp, key, lens0=None):
            # whole decode loop as ONE device program (lax.scan): a
            # per-token jit call pays a host->device dispatch round trip
            # each step.
            # Ragged mode: per-sequence lengths ride the carry and advance
            # each step (the op's seq_lens contract)
            import jax

            def body(carry, i):
                tk, cs, ln = carry
                tk2, cs2 = step(w, cs, tk, t0 + i, temp, topp,
                                jax.random.fold_in(key, i), lens=ln)
                ln2 = None if ln is None else ln + 1
                return (tk2, cs2, ln2), tk2

            (_, caches_f, _), toks = jax.lax.scan(
                body, (tok, caches, lens0), jnp.arange(n))
            return toks, caches_f  # toks [n, B]

        def paged_step(w, caches, toks, qlens, sel, tables, lens, rwork,
                       rpack, temp, topp, key):
            """One continuous-batching step over the PAGED cache: toks
            [B, C] is each slot's token slab for this step — decode
            slots carry one token in column 0, prefill slots up to C
            prompt-chunk tokens — and qlens [B] says how many columns
            are valid per slot (0 parks the slot: nothing written,
            nothing sampled that matters). tables/lens are the host
            allocator's view BEFORE the step, rwork the flattened ragged
            work list (built host-side from lens + qlens with
            q_lens=qlens). Mixed-progress slots — some consuming whole
            prompt chunks, some deep into decode, some idle — all
            advance in this ONE compiled program; the bucketed
            (work-list length, chunk-width) pair is the only shape that
            varies step to step, so the program count stays
            O(log max_blocks * log chunk). Samples only the positions
            the caller will read — `sel` [B, W] holds per-slot slab
            column indices (the chunk-final position for prefill slots,
            the whole 1+K drafted span for speculative verification:
            column j's sample is the model's next-token choice after
            slab column j, exactly what greedy acceptance compares
            drafts against) — and returns [B, W] tokens. W is bounded
            by 1 + spec_k, NOT the chunk width, so a 256-token prefill
            chunk still pays for one lm_head position per slot.
            Padding columns of sel repeat a valid index; their samples
            are computed and ignored.

            A WIDE slab (B x C > ROW_TILE rows; one or two slots
            prefill a chunk, the others decode one token) computes its
            live rows only: `live_rows(qlens, C)` packs them to the
            front of a [B x C]-row buffer, and the embedding gather
            here, like every row-wise layer of the stack, walks
            ceil(qlens.sum() / ROW_TILE) tiles of it — a trip count
            read on the device, so arguments, shapes and buckets are
            what they were. A slab of at most ROW_TILE rows is one tile
            whatever is live: straight-line code, no packing."""
            logits, caches, counts, handed = paged_logits(
                w, caches, toks, qlens, sel, tables, lens, rwork, rpack)
            with tracing.device_scope("sampler"):
                toks_out = select(logits, temp, topp, key)
                if counts is not None:
                    # the assignments that fell on a held expert and
                    # the rows the grouped products were handed, each
                    # over the expert layers, ride out beside the step's
                    # samples, in row 0 of two columns past them
                    # (`held_assignments` and `product_rows` read them):
                    # the host fetches one array
                    cols = jnp.zeros((toks_out.shape[0], 2),
                                     toks_out.dtype)
                    toks_out = jnp.concatenate(
                        [toks_out, cols.at[0].set(jnp.stack(
                            [counts.sum(), handed.sum()]
                        ).astype(toks_out.dtype))], axis=1)
            return toks_out, caches

        def paged_logits(w, caches, toks, qlens, sel, tables, lens, rwork,
                         rpack):
            """`paged_step` up to its sampler: the logits [B, W, V] at
            the slab columns `sel` names, the appended caches, each
            expert layer's assignments per held expert, [expert layers,
            held], and the rows its grouped products were handed,
            [expert layers] (None and None without experts)."""
            from ..ops.pallas.paged_attention import (
                ROW_TILE, live_rows, over_row_tiles, put_row_tile,
                row_tile)
            if tp_dequant is not None:
                # quantized tensor-parallel serving: reconstruct this
                # device's dense weight shards from the packed bytes +
                # scales (runs inside the shard_map body, on shards)
                w = tp_dequant(w)
            emb = w["embedding"]
            rows = None
            with tracing.device_scope("embed"):
                if toks.shape[0] * toks.shape[1] > ROW_TILE:
                    rows = live_rows(qlens, toks.shape[1])
                    ids = toks[rows.slot, rows.col]              # [R]
                    h = over_row_tiles(
                        rows.n_tiles,
                        lambda r0, h: put_row_tile(
                            h, emb[row_tile(ids, r0)], r0),
                        jnp.zeros((ids.shape[0], emb.shape[1]),
                                  emb.dtype))[None]              # [1, R, E]
                else:
                    h = emb[toks]                                # [B, C, E]
            from ..core.tensor import Tensor
            cts = [Tensor(c) for c in caches]
            out = fused_multi_transformer(
                Tensor(h), *lists(w), cache_kvs=cts,
                time_step=Tensor(jnp.zeros((), jnp.int32)),
                seq_lens=Tensor(lens), chunk_lens=Tensor(qlens),
                rotary_embs=w.get("rotary_embs"),
                block_tables=tables, ragged_work=rwork,
                ragged_pack=rpack, _live_rows=rows,
                **per_layer(w), **paged_kw)
            counts = handed = None
            if isinstance(out, tuple):
                out, counts, handed = out[0], out[1].data, out[2].data
            with tracing.device_scope("head"):
                bidx = jnp.arange(toks.shape[0])[:, None]
                if rows is None:
                    picked = out.data[bidx, sel]             # [B, W, E]
                else:
                    picked = out.data[0][rows.back[bidx, sel]]
                logits = head(picked, w)                     # [B, W, V]
            return logits, [c.data for c in cts], counts, handed

        def feed_tokens(slab, prev, fed):
            """The token slab of a step dispatched before the previous
            step's samples were read: column 0 of every slot `fed` marks
            is that slot's previous sample, `prev[:, 0]`, which never
            left the device; everything else is the slab the host built.
            One small program a slab shape, dispatched ahead of
            `paged_step`, whose own programs it leaves as they were."""
            return slab.at[:, 0].set(
                jnp.where(fed, prev[:, 0], slab[:, 0]))

        def paged_copy(caches, src_block, dst_block):
            """Duplicate one physical cache block across every layer in
            ONE jitted program — the serving engine's copy-on-write
            primitive (automatic prefix caching: a request appending
            into a block other requests still read writes into a
            private copy instead). Block ids are traced scalars, so one
            compile covers every (src, dst) pair ever copied."""
            from ..ops.pallas.paged_attention import copy_paged_kv
            return [copy_paged_kv(c, src_block, dst_block) for c in caches]

        def paged_rewind(caches, tables, new_lens, old_lens, span):
            """Roll every layer's paged cache back from old_lens to
            new_lens (zero the rejected speculative span) in ONE jitted
            program; `span` is static, the serving engine passes its
            bucketed slab width so the compile keys stay on the same
            O(log chunk) treadmill as the step itself."""
            from ..ops.pallas.paged_attention import truncate_paged_kv
            return [truncate_paged_kv(c, tables, new_lens, old_lens, span)
                    for c in caches]

        import jax
        self._prefill = jax.jit(prefill, donate_argnums=(1,))
        self._step = jax.jit(step, donate_argnums=(1,))
        self._steps = jax.jit(steps, static_argnums=(4,),
                              donate_argnums=(1,))
        # serving-path programs get host-side dispatch spans: the
        # continuous-batching engine's per-request lanes line up against
        # these on one chrome timeline (a slow step with a fat
        # `paged_step` span on its first bucket sighting = compile)
        if self.tp == 1:
            jit_paged_step = jax.jit(paged_step, static_argnums=(8,),
                                     donate_argnums=(1,))
            jit_paged_rewind = jax.jit(paged_rewind, static_argnums=(4,),
                                       donate_argnums=(0,))
            jit_paged_copy = jax.jit(paged_copy, donate_argnums=(0,))
        else:
            # mesh programs: the SAME paged bodies run per-device under
            # shard_map — weights arrive as their layout shards, the
            # caches as kv-head shards, every host-built array (slab,
            # sel, tables, lens, work list) replicated — and the
            # sampled tokens come back replicated, so the host reads
            # ONE array exactly as in the single-chip case. Static args
            # (rpack / rewind span) stay OUTSIDE the shard_map via
            # closure, keeping the bucketed compile-key treadmill
            # identical per mesh shape. check_vma=False: the per-layer
            # psums make the residual stream replicated by construction
            # (the replication checker cannot see through the Pallas
            # kernel).
            from ..framework.compat import resolve_shard_map
            from jax.sharding import PartitionSpec as _P
            _shard_map = resolve_shard_map()
            mesh = self._mesh
            w_specs = self._w_specs
            n_layers = self._n_layers
            cspecs = [_P(None, "tp")] * n_layers
            rep = _P()

            def paged_step_tp(w, caches, toks, qlens, sel, tables, lens,
                              rwork, rpack, temp, topp, key):
                def local(w, caches, toks, qlens, sel, tables, lens,
                          rwork, temp, topp, key):
                    return paged_step(w, caches, toks, qlens, sel,
                                      tables, lens, rwork, rpack, temp,
                                      topp, key)
                f = _shard_map(
                    local, mesh=mesh,
                    in_specs=(w_specs, cspecs, rep, rep, rep, rep, rep,
                              (rep,) * 9, rep, rep, rep),
                    out_specs=(rep, cspecs),
                    axis_names={"tp"}, check_vma=False)
                return f(w, caches, toks, qlens, sel, tables, lens,
                         rwork, temp, topp, key)

            def paged_rewind_tp(caches, tables, new_lens, old_lens,
                                span):
                def local(caches, tables, new_lens, old_lens):
                    return paged_rewind(caches, tables, new_lens,
                                        old_lens, span)
                f = _shard_map(
                    local, mesh=mesh,
                    in_specs=(cspecs, rep, rep, rep), out_specs=cspecs,
                    axis_names={"tp"}, check_vma=False)
                return f(caches, tables, new_lens, old_lens)

            def paged_copy_tp(caches, src_block, dst_block):
                f = _shard_map(
                    paged_copy, mesh=mesh,
                    in_specs=(cspecs, rep, rep), out_specs=cspecs,
                    axis_names={"tp"}, check_vma=False)
                return f(caches, src_block, dst_block)

            jit_paged_step = jax.jit(paged_step_tp, static_argnums=(8,),
                                     donate_argnums=(1,))
            jit_paged_rewind = jax.jit(paged_rewind_tp,
                                       static_argnums=(4,),
                                       donate_argnums=(0,))
            jit_paged_copy = jax.jit(paged_copy_tp, donate_argnums=(0,))
        self._paged_step = _dispatch_span(
            "paged_step", jit_paged_step, static_argnums=(8,))
        # the step up to its sampler, as traced (per device under tp):
        # what tests/test_paged_live_rows.py holds to a plain computation
        self._paged_logits = paged_logits
        self._paged_rewind = _dispatch_span(
            "paged_rewind", jit_paged_rewind, static_argnums=(4,))
        self._paged_copy = _dispatch_span("paged_copy", jit_paged_copy)
        # under tp the fed slab is replicated over the mesh, where the
        # step's samples it reads already are
        self._feed_tokens = jax.jit(
            feed_tokens, out_shardings=self._replicated())

    def _replicated(self):
        """The sharding of a host-built step input: None on one device,
        replicated over the mesh under tp."""
        if self.tp == 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self._mesh, PartitionSpec())

    def new_sampled(self, batch):
        """A step's samples before any step ran: zeros in the shape
        `_paged_step` leaves its own ([batch, 1], two columns wider where
        the model has experts), for `_feed_tokens` to read when no slot
        is fed."""
        import jax
        import jax.numpy as jnp
        z = jnp.zeros((batch, 3 if self.expert_specs else 1), jnp.int32)
        return z if self.tp == 1 else jax.device_put(z, self._replicated())

    def held_assignments(self, sampled):
        """The (token, expert) assignments that fell on a held expert in
        the step whose fetched samples these are, over the expert
        layers; None for a model without experts."""
        return int(sampled[0, -2]) if self.expert_specs else None

    def product_rows(self, sampled):
        """The rows the experts' grouped products were handed in that
        step, over the expert layers and row tiles (`expert_ffn`: as
        many slabs of MOE_SLAB sorted rows, or of a narrower call's
        R x top_k, as its held assignments fill); None for a model
        without experts."""
        return int(sampled[0, -1]) if self.expert_specs else None

    def _build_quant_mm(self, weights, dtype):
        """Repack the projection weights into the Pallas kernel's int4
        K x N layout and REPLACE self._w's lists with them (they flow as
        program arguments); returns the _mm(z2d, w, kind, li) hook running
        the weight-only GEMM. Matrix forms (trans_qkvw layouts):
        qkv [ht, hd, E] -> [E, ht*hd]; lin [H*D, E]; ffn1 [E, 2F];
        ffn2 [F, E] — per-output-channel scales (small; closure-carried).
        int4-only: int8 serves from the XLA dequant path."""
        import numpy as _np
        import jax.numpy as jnp
        from ..core.tensor import Tensor as _T
        from ..ops.pallas.quant_matmul import (pack_int4_blocked,
                                               pick_block_n,
                                               weight_only_matmul)

        def matrix(kind, a):
            a = _np.asarray(a, _np.float32)
            if kind == "qkv":          # [ht, hd, E] -> [E, ht*hd]
                return a.reshape(-1, a.shape[-1]).T
            return a                   # already [K, N]

        qkv0 = _np.asarray(weights["qkv_weights"][0])
        qkv_out = tuple(qkv0.shape[:-1])   # (ht, hd) GQA / (3, H, D) MHA
        new_lists = {}
        scales = {}
        blocks = {}
        for kind, key in (("qkv", "qkv_weights"), ("lin", "linear_weights"),
                          ("f1", "ffn1_weights"), ("f2", "ffn2_weights")):
            packed_l, sc_l = [], []
            for t in weights[key]:
                w = matrix(kind, t.numpy() if isinstance(t, _T) else t)
                bn = pick_block_n(w.shape[1], "int4")
                if bn is None:
                    raise ValueError(f"{kind} N={w.shape[1]}: no legal "
                                     "kernel block")
                blocks[kind] = bn
                packed, sc = pack_int4_blocked(w, block_n=bn)
                packed_l.append(jnp.asarray(packed))
                sc_l.append(jnp.asarray(sc))
            new_lists[key] = packed_l
            scales[kind] = sc_l
        self._w.update(new_lists)

        def mm(z2d, w, kind, li):
            return weight_only_matmul(z2d.astype(dtype), w,
                                      scales[kind][li], quant="int4",
                                      block_n=blocks[kind],
                                      out_dtype=dtype)

        mm.qkv_out = qkv_out
        return mm

    def new_caches(self, batch_size, dtype=None):
        import jax.numpy as jnp
        dtype = dtype or self._dtype
        kvh = self._gqa or self._w["qkv_weights"][0].shape[1]
        return [jnp.zeros((2, batch_size, kvh, self.max_seq_len,
                           self.head_dim), dtype)
                for _ in range(self._n_layers)]

    def new_paged_caches(self, num_blocks, block_size, dtype=None,
                         window_blocks=None):
        """Per-layer paged KV caches [2, KVH, NB, block_size, Dc]
        for the continuous-batching serving path
        (incubate.nn.ContinuousBatchingEngine owns the block allocators
        that hand slices of these out to requests). Each layer's shape
        is its own description's: its kv-head count, and NB =
        `num_blocks` for a layer of block table 0, `window_blocks` for a
        window layer (table 1), whose pool is another. Dc is the wider
        of the key and value widths rounded up to the 128-lane tile the
        ragged kernel DMAs (`paged_head_dim`; pad lanes stay zero):
        one Dc for both halves, so values narrower than the keys pay
        the keys' lanes. Under tp > 1 each
        layer's cache is placed sharded over KV HEADS — the GLOBAL
        (logical) shape is unchanged, each device holds a
        [2, KVH/tp, num_blocks, block_size, Dc] shard, so the host-side
        allocator keeps one flat block-id space while per-device cache
        HBM is 1/tp of the single-chip figure."""
        import jax.numpy as jnp
        from ..ops.pallas.paged_attention import paged_head_dim
        dtype = dtype or self._dtype
        if self.window and not window_blocks:
            raise ValueError(
                "a model with window layers needs its window pool's size")
        shapes = [(2, sp.kv_heads or self.num_heads,
                   window_blocks if sp.table else num_blocks, block_size,
                   paged_head_dim(max(self.head_dim, sp.v_head_dim)))
                  for sp in self.layer_specs]
        if self.tp > 1:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = NamedSharding(self._mesh, P(None, "tp"))
            return [jax.device_put(jnp.zeros(shape, dtype), sh)
                    for shape in shapes]
        return [jnp.zeros(shape, dtype) for shape in shapes]

    # -- tensor-parallel accounting (host math; tp == 1 degenerates) ------
    def kv_device_block_bytes(self, block_size, table=0):
        """Bytes ONE allocator block of block table `table` occupies PER
        DEVICE across the cache shards of that table's layers: per layer
        2(K,V) x KVH/tp x block_size x Dc x itemsize (Dc = the
        lane-padded row the cache really stores).
        The per-device KV high-water in bytes is
        `allocator.high_water * this` — the capacity win the TP gate
        asserts (1/tp of the single-chip figure for the same
        workload)."""
        import jax.numpy as jnp
        from ..ops.pallas.paged_attention import paged_head_dim
        itemsize = jnp.dtype(self._dtype).itemsize
        return sum(
            2 * ((sp.kv_heads or self.num_heads) // self.tp)
            * int(block_size)
            * paged_head_dim(max(self.head_dim, sp.v_head_dim)) * itemsize
            for sp in self.layer_specs if sp.table == table)

    def tp_step_comm_bytes(self, batch, width, live=None):
        """Analytic per-step collective payload of the TP paged step:
        two row-parallel psums per layer, each reducing the partial
        activations of the rows the step computes — the [batch, width]
        slab, or a wide slab's `live` tokens' row tiles (`step_rows`)
        — the aval math the serving loop hands the comm-task registry
        so `collective_bytes_total{op="psum",axis="tp"}` attributes the
        step's comms cost without a device round trip. 0 when tp == 1
        (no collectives in the program)."""
        if self.tp <= 1:
            return 0
        import jax.numpy as jnp
        from ..ops.pallas.paged_attention import step_rows
        e = int(self._w["embedding"].shape[1])
        itemsize = jnp.dtype(self._dtype).itemsize
        return (2 * self._n_layers * step_rows(int(batch), int(width), live)
                * e * itemsize)

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_p=1.0, seed=None, prompt_lens=None):
        """Generation: greedy by default; temperature>0 enables
        temperature + nucleus sampling (reference top_p_sampling
        semantics), seeded for reproducibility. input_ids: [B, S] int
        array. Returns [B, N].

        prompt_lens (optional [B] ints): ragged-batch mode — input_ids is
        RIGHT-padded to a common width and each row's true prompt length
        is given here; every row prefills over its own length and decodes
        at its own cache slot / rotary position, reproducing its unpadded
        single-sequence generation exactly. Each length must satisfy
        0 < len <= input_ids.shape[1]."""
        import numpy as np
        import jax
        import jax.numpy as jnp
        if self.tp > 1:
            raise NotImplementedError(
                "generate() serves the dense single-chip cache; a "
                "tensor-parallel engine serves through "
                "ContinuousBatchingEngine's paged path (token-exact vs "
                "a tp=1 engine's generate() — the serve_tp gate pins "
                "it). Build the reference engine with tp=1.")
        if seed is None:
            from ..core import random as _rng
            key = _rng.next_key()
        else:
            key = jax.random.PRNGKey(int(seed))
        temp = jnp.float32(temperature)
        topp = jnp.float32(top_p)
        ids = jnp.asarray(input_ids, jnp.int32)
        b, s = ids.shape
        if s + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_seq_len ({self.max_seq_len}); raise max_seq_len or "
                "shorten the request")
        caches = self.new_caches(b)
        kp, kd = jax.random.split(key)
        lens = None
        if prompt_lens is not None:
            lens_np = np.asarray(prompt_lens)
            if lens_np.shape != (b,):
                raise ValueError(
                    f"prompt_lens must be shape [{b}], got {lens_np.shape}")
            if (lens_np <= 0).any() or (lens_np > s).any():
                raise ValueError(
                    f"prompt_lens must be in (0, {s}] (the padded width); "
                    f"got {lens_np.tolist()}")
            lens = jnp.asarray(lens_np, jnp.int32)
        tok, caches = self._prefill(self._w, caches, ids, temp, topp, kp,
                                    lens)
        if max_new_tokens == 1:
            return np.asarray(tok)[:, None]
        # bucket the scanned step count to powers of two so varying request
        # lengths reuse a handful of compiled decode programs instead of
        # recompiling the whole stack per distinct n (overshoot tokens are
        # computed then dropped; the cache slots they touched are beyond
        # the returned horizon and rewritten by any later decode)
        need = max_new_tokens - 1
        bucket = 1
        while bucket < need:
            bucket *= 2
        bucket = min(bucket, self.max_seq_len - s)
        toks, caches = self._steps(self._w, caches, tok,
                                   jnp.asarray(s, jnp.int32), bucket,
                                   temp, topp, kd, lens)
        return np.concatenate([np.asarray(tok)[:, None],
                               np.asarray(toks).T[:, :need]], axis=1)
