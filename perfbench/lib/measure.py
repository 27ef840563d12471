"""Small tools every driver shares: compile accounting from jax's own
monitoring events, the table of peaks, device facts, percentiles, and
the result line."""
import json
import math
import os
import statistics
import sys
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))

# jax.monitoring event names (jax 0.9)
_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileWatch:
    """Counts what jax compiles, traces and finds in its persistent cache,
    from the moment it is installed. `mark()` returns a snapshot;
    differences of snapshots give a phase's share."""

    def __init__(self):
        self._lock = threading.Lock()
        self.c = dict(compiles=0, compile_s=0.0, traces=0, trace_s=0.0,
                      lowers=0, lower_s=0.0, cache_hits=0, cache_misses=0)

    def install(self):
        from jax import monitoring

        def on_duration(event, duration, **kw):
            with self._lock:
                if event == _COMPILE:
                    self.c["compiles"] += 1
                    self.c["compile_s"] += duration
                elif event == _TRACE:
                    self.c["traces"] += 1
                    self.c["trace_s"] += duration
                elif event == _LOWER:
                    self.c["lowers"] += 1
                    self.c["lower_s"] += duration

        def on_event(event, **kw):
            with self._lock:
                if event == _HIT:
                    self.c["cache_hits"] += 1
                elif event == _MISS:
                    self.c["cache_misses"] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def mark(self):
        with self._lock:
            return dict(self.c)

    @staticmethod
    def diff(a, b):
        return {k: b[k] - a[k] for k in a}


def cache_everything():
    """Let the persistent cache keep every program, the sub-second ones
    too: a run after the first then compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks_for(device_kind):
    with open(os.path.join(os.path.dirname(_HERE), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json: add its "
            "published peaks with their source, do not assume them")
    return table[device_kind]


def device_facts(devices):
    """The `device` object of the result line, before trace fields."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def percentile(values, q):
    """q in [0, 100], linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def spread(values):
    """(Q3 - Q1) / median with Python's exclusive quartiles: the
    contract's measure of a metric's run-to-run spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def metric(value, unit):
    return {"value": float(value), "unit": unit}


def emit(result):
    """The result line: one JSON object, the last line of stdout."""
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        if k not in result:
            raise KeyError(f"result line lacks {k!r}")
    sys.stdout.flush()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
