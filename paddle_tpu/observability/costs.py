"""Per-program cost catalog: where the FLOPs, bytes, and HBM go.

PR 8 made the serving stack answer "how slow"; nothing in the repo
answered "how fast SHOULD it be". XLA already knows: every compiled
executable carries a cost analysis (flops, bytes accessed) and a memory
analysis (argument / output / temp sizes), and jax exposes both on the
AOT artifacts (``jitted.lower(...).compile()``). This module turns them
into registry metrics and a queryable catalog:

* ``program_flops{program}`` / ``program_bytes{program}`` /
  ``program_peak_hbm{program}`` gauges, plus argument/output/temp size
  gauges — straight from ``cost_analysis()`` / ``memory_analysis()``.
* ``program_arithmetic_intensity{program}`` — flops per byte accessed,
  the roofline x-coordinate: below the machine's ridge point the
  program is bandwidth-bound, above it compute-bound.
* ``program_mfu{program}`` / ``program_roofline_frac{program}`` —
  achieved model-flops-utilization and fraction of the roofline
  attainable rate, derived against the ``dispatch_seconds{program}``
  latency histograms the dispatch wrappers feed (PR 8).

Attribution is OPT-IN (``get_cost_catalog().enabled = True``): jax's
AOT ``lower().compile()`` does NOT share the jit executable cache on
this jax, so an analysis pays one extra backend compile per program
signature. The dispatch wrappers therefore analyze only at their own
cache misses — exactly the moments a compile already happened — and
only while enabled, so the serving hot path stays untouched by default
(one flag check per call).

Graceful degradation is the contract: a backend whose artifacts lack
``cost_analysis``/``memory_analysis`` (or a process without jax at all
— the selfcheck's bare container) records nothing and raises nothing;
``record()`` with host numbers works everywhere, which is how the
stdlib-only selfcheck exercises the full catalog path.
"""
import os
import threading

from .metrics import get_registry

__all__ = [
    "CostCatalog", "get_cost_catalog", "peak_flops", "peak_bandwidth",
    "program_flops", "program_bytes", "program_peak_hbm",
    "program_arg_bytes", "program_out_bytes", "program_temp_bytes",
    "program_intensity", "program_mfu", "program_roofline_frac",
    "cost_analyses_total",
]


# -- gauge accessors (re-fetched through the registry per record, the
#    instrument.py convention — reset() can never orphan a handle) --------

def program_flops():
    return get_registry().gauge(
        "program_flops",
        help="XLA cost-analysis flops of the compiled program (last "
             "analyzed signature)", labels=("program",))


def program_bytes():
    return get_registry().gauge(
        "program_bytes",
        help="XLA cost-analysis bytes accessed (HBM traffic) of the "
             "compiled program", labels=("program",))


def program_peak_hbm():
    return get_registry().gauge(
        "program_peak_hbm_bytes",
        help="argument + output + temp bytes the executable holds live "
             "(XLA memory analysis)", labels=("program",))


def program_arg_bytes():
    return get_registry().gauge(
        "program_argument_bytes",
        help="executable argument size (XLA memory analysis)",
        labels=("program",))


def program_out_bytes():
    return get_registry().gauge(
        "program_output_bytes",
        help="executable output size (XLA memory analysis)",
        labels=("program",))


def program_temp_bytes():
    return get_registry().gauge(
        "program_temp_bytes",
        help="executable temp/scratch size (XLA memory analysis)",
        labels=("program",))


def program_intensity():
    return get_registry().gauge(
        "program_arithmetic_intensity",
        help="flops per byte accessed — the roofline x-coordinate "
             "(below the ridge point = bandwidth-bound)",
        labels=("program",))


def program_mfu():
    return get_registry().gauge(
        "program_mfu",
        help="achieved model-flops-utilization: cost-analysis flops / "
             "dispatch latency / device peak flops",
        labels=("program",))


def program_roofline_frac():
    return get_registry().gauge(
        "program_roofline_frac",
        help="achieved flops rate / roofline-attainable rate "
             "min(peak_flops, intensity * peak_bandwidth)",
        labels=("program",))


def cost_analyses_total():
    return get_registry().counter(
        "cost_analyses_total",
        help="compiled-artifact cost/memory analyses performed "
             "(one extra backend compile each — cache-miss-time only)",
        labels=("program",))


# -- device peaks for MFU / roofline ---------------------------------------
# (device-kind substring, peak flops/s, peak HBM bytes/s) — bf16 MXU peaks
# from published TPU specs; first substring match wins. A TPU whose kind
# matches no row is an error, never a default. Off the TPU a NOMINAL pair
# keeps MFU a well-defined ratio the CPU gates can bounds-check:
# interpret-mode numbers are coverage evidence, not speed claims.
_TPU_PEAKS = (
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5", 197e12, 819e9),          # v5e / "v5 lite"
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
)
_NOMINAL_PEAK = (1e11, 2e10)        # 100 GFLOP/s, 20 GB/s

_peak_cache = None
_peak_lock = threading.Lock()


def _resolve_peaks():
    """(peak_flops/s, peak_bytes/s) for the current backend. Env
    overrides (PADDLE_TPU_PEAK_FLOPS / PADDLE_TPU_PEAK_BYTES_PER_S) win;
    without jax installed the nominal pair comes back. On platform
    `tpu` a `device_kind` the table does not know raises."""
    flops = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    bw = os.environ.get("PADDLE_TPU_PEAK_BYTES_PER_S")
    if flops and bw:
        return float(flops), float(bw)
    f, b = _NOMINAL_PEAK
    try:
        import jax
    except ImportError:
        jax = None
    if jax is not None:
        d = jax.devices()[0]
        if d.platform == "tpu":
            kind = d.device_kind.lower()
            for sub, pf, pb in _TPU_PEAKS:
                if sub in kind:
                    f, b = pf, pb
                    break
            else:
                raise RuntimeError(
                    f"no peak entry for TPU device_kind {d.device_kind!r}: "
                    "add it to costs._TPU_PEAKS with its source, or set "
                    "PADDLE_TPU_PEAK_FLOPS and PADDLE_TPU_PEAK_BYTES_PER_S")
    return (float(flops) if flops else f, float(bw) if bw else b)


def peak_flops():
    return _peaks()[0]


def peak_bandwidth():
    return _peaks()[1]


def _peaks():
    global _peak_cache
    with _peak_lock:
        if _peak_cache is None:
            _peak_cache = _resolve_peaks()
        return _peak_cache


def _normalize_cost_analysis(ca):
    """jax returns a dict (Lowered) or a per-device list of dicts
    (Compiled); normalize to one dict or None."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return ca if isinstance(ca, dict) else None


class CostCatalog:
    """Host-side catalog of per-program cost/memory entries.

    ``record()`` takes plain numbers (works without jax — the selfcheck
    path); ``analyze_compiled()`` / ``analyze_jitted()`` pull them from
    jax AOT artifacts with graceful no-ops on backends lacking the
    analyses. One entry per program name; re-analysis (a new signature
    of the same program) updates the entry and appends to its
    per-signature history, so the gauges always show the LAST analyzed
    signature while ``entries()`` keeps every bucket seen."""

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self._programs = {}
        self._registry = registry
        self.enabled = False        # dispatch wrappers consult this
        # bumped by reset(): dispatch wrappers key their seen-signature
        # sets on it, so a reset re-attributes warm programs instead of
        # leaving the cleared catalog empty until an unseen shape shows
        self.generation = 0

    def _reg(self):
        return self._registry if self._registry is not None \
            else get_registry()

    # -- recording --------------------------------------------------------
    def record(self, program, flops=None, bytes_accessed=None,
               arg_bytes=None, out_bytes=None, temp_bytes=None,
               peak_hbm=None, signature=None, source="manual"):
        """Record one program's cost/memory numbers and set the gauges.
        ``peak_hbm`` defaults to arg + out + temp (the bytes the
        executable holds live at once). Returns the catalog entry."""
        program = str(program)
        if peak_hbm is None and None not in (arg_bytes, out_bytes,
                                             temp_bytes):
            peak_hbm = float(arg_bytes) + float(out_bytes) \
                + float(temp_bytes)
        intensity = None
        if flops and bytes_accessed:
            intensity = float(flops) / float(bytes_accessed)
        entry = {
            "program": program,
            "flops": None if flops is None else float(flops),
            "bytes_accessed": None if bytes_accessed is None
            else float(bytes_accessed),
            "arg_bytes": None if arg_bytes is None else float(arg_bytes),
            "out_bytes": None if out_bytes is None else float(out_bytes),
            "temp_bytes": None if temp_bytes is None else float(temp_bytes),
            "peak_hbm": None if peak_hbm is None else float(peak_hbm),
            "intensity": intensity,
            "source": str(source),
        }
        with self._lock:
            prev = self._programs.get(program)
            sigs = dict(prev["signatures"]) if prev else {}
            if signature is not None:
                sigs[str(signature)] = {
                    k: entry[k] for k in ("flops", "bytes_accessed",
                                          "peak_hbm")}
            entry["signatures"] = sigs
            entry["analyses"] = (prev["analyses"] if prev else 0) + 1
            self._programs[program] = entry
        gauges = (
            (program_flops, "program_flops", entry["flops"]),
            (program_bytes, "program_bytes", entry["bytes_accessed"]),
            (program_peak_hbm, "program_peak_hbm_bytes",
             entry["peak_hbm"]),
            (program_arg_bytes, "program_argument_bytes",
             entry["arg_bytes"]),
            (program_out_bytes, "program_output_bytes",
             entry["out_bytes"]),
            (program_temp_bytes, "program_temp_bytes",
             entry["temp_bytes"]),
            (program_intensity, "program_arithmetic_intensity",
             entry["intensity"]),
        )
        for accessor, name, value in gauges:
            if value is not None:
                self._family(accessor, name).labels(
                    program=program).set(value)
        self._family(cost_analyses_total, "cost_analyses_total",
                     kind="counter").labels(program=program).inc()
        return dict(entry)

    def _family(self, accessor, name, kind="gauge"):
        """The named family on this catalog's registry: the module
        accessor (full help text) on the process registry, a bare
        same-named family on a private one (tests/selfcheck)."""
        if self._registry is None:
            return accessor()
        ctor = self._registry.counter if kind == "counter" \
            else self._registry.gauge
        return ctor(name, labels=("program",))

    # -- jax-artifact analysis (lazy jax; graceful no-ops) ----------------
    def analyze_compiled(self, program, artifact, signature=None,
                         source="compiled"):
        """Pull cost/memory analyses off a jax AOT artifact (a
        ``Compiled``; a ``Lowered`` gives cost analysis only). Returns
        the catalog entry, or None when the backend offers neither
        analysis — the graceful-no-op contract."""
        ca = ma = None
        try:
            ca = _normalize_cost_analysis(artifact.cost_analysis())
        except Exception:
            ca = None
        try:
            ma = artifact.memory_analysis()
        except Exception:
            ma = None
        if ca is None and ma is None:
            return None
        kw = {}
        if ca is not None:
            kw["flops"] = ca.get("flops")
            kw["bytes_accessed"] = ca.get("bytes accessed")
        if ma is not None:
            kw["arg_bytes"] = getattr(ma, "argument_size_in_bytes", None)
            kw["out_bytes"] = getattr(ma, "output_size_in_bytes", None)
            kw["temp_bytes"] = getattr(ma, "temp_size_in_bytes", None)
        if all(v is None for v in kw.values()):
            return None
        return self.record(program, signature=signature, source=source,
                           **kw)

    def analyze_jitted(self, program, jitted, args=(), kwargs=None,
                       signature=None):
        """AOT-lower + compile a jitted callable on the given args and
        catalog the result. Pays ONE extra backend compile (the AOT
        cache is separate from the jit call cache on this jax) — call
        at cache-miss time only. Never raises: an un-lowerable call or
        an analysis-less backend returns None."""
        try:
            lowered = jitted.lower(*args, **(kwargs or {}))
            compiled = lowered.compile()
        except Exception:
            return None
        return self.analyze_compiled(program, compiled,
                                     signature=signature, source="aot")

    # -- derived MFU / roofline -------------------------------------------
    def derive(self, dispatch_q=0.5, registry=None,
               peak_flops_override=None, peak_bw_override=None):
        """Compute achieved MFU and roofline fraction for every cataloged
        program against its ``dispatch_seconds{program}`` latency (the
        q-quantile), set the gauges, and return {program: {...}}.

        Dispatch latency measures trace+enqueue, not device completion
        (jax dispatch is async) — on a backpressured steady state the two
        converge; a blocked caller (block_until_ready inside the
        measured wall, as tools/cost_report.py's pretrain leg does)
        makes the MFU exact."""
        reg = registry if registry is not None else self._reg()
        pf = peak_flops_override if peak_flops_override is not None \
            else peak_flops()
        pb = peak_bw_override if peak_bw_override is not None \
            else peak_bandwidth()
        hist = reg.get("dispatch_seconds")
        out = {}
        with self._lock:
            programs = {k: dict(v) for k, v in self._programs.items()}
        for name, entry in programs.items():
            if not entry.get("flops"):
                continue
            lat = None
            if hist is not None:
                child = hist._children.get((name,))
                if child is not None and child.count:
                    lat = child.quantile(dispatch_q)
            if not lat or lat <= 0:
                continue
            achieved = entry["flops"] / lat
            mfu = achieved / pf if pf > 0 else None
            frac = None
            if entry.get("intensity"):
                attainable = min(pf, entry["intensity"] * pb)
                frac = achieved / attainable if attainable > 0 else None
            row = {"dispatch_s": lat, "achieved_flops_per_s": achieved,
                   "mfu": mfu, "roofline_frac": frac}
            out[name] = row
            # program names are the code's own jitted-program catalog
            # (paged_step, pretrain_step, ...): a fixed set bounded by
            # the source, not by traffic
            if mfu is not None:
                self._family(program_mfu, "program_mfu").labels(
                    program=name).set(mfu)      # graftlint: disable=GL112
            if frac is not None:
                self._family(program_roofline_frac,
                             "program_roofline_frac").labels(
                                 program=name).set(frac)  # graftlint: disable=GL112
        return out

    # -- reading ----------------------------------------------------------
    def entries(self):
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}

    def table(self, dispatch_q=0.5, registry=None):
        """Report rows, one per program: the cost_report.py surface."""
        derived = self.derive(dispatch_q=dispatch_q, registry=registry)
        rows = []
        for name, e in sorted(self.entries().items()):
            d = derived.get(name, {})
            rows.append({
                "program": name,
                "flops": e["flops"],
                "bytes_accessed": e["bytes_accessed"],
                "peak_hbm": e["peak_hbm"],
                "arg_bytes": e["arg_bytes"],
                "out_bytes": e["out_bytes"],
                "temp_bytes": e["temp_bytes"],
                "intensity": e["intensity"],
                "signatures": len(e["signatures"]),
                "analyses": e["analyses"],
                "dispatch_s": d.get("dispatch_s"),
                "mfu": d.get("mfu"),
                "roofline_frac": d.get("roofline_frac"),
            })
        return rows

    # every family record()/derive() writes; reset() zeroes their
    # children so a cleared program never keeps exporting stale numbers
    # (the record_census stale-data contract)
    _FAMILIES = ("program_flops", "program_bytes",
                 "program_peak_hbm_bytes", "program_argument_bytes",
                 "program_output_bytes", "program_temp_bytes",
                 "program_arithmetic_intensity", "program_mfu",
                 "program_roofline_frac")

    def reset(self):
        with self._lock:
            self._programs.clear()
            self.generation += 1
        reg = self._reg()
        for fam_name in self._FAMILIES:
            fam = reg.get(fam_name)
            if fam is None:
                continue
            for key in list(fam._children):
                fam.labels(program=key[0]).set(0)


_catalog = CostCatalog()


def get_cost_catalog():
    """The process-wide catalog the dispatch wrappers and the pretrain
    step attribute into (opt-in: set ``.enabled = True`` first)."""
    return _catalog
