"""Trace-safety rules (GL1xx).

GL101 reconstructs the PR 1 import skew: a single `from jax import
shard_map` at module scope raised at import time on jax 0.4.x and took
43 of 47 test files out of the collection — silently. Every shard_map
user must route through `paddle_tpu.framework.compat.resolve_shard_map`.

GL102 is the same class of version skew for Pallas compiler params: jax
renamed `pltpu.TPUCompilerParams` -> `pltpu.CompilerParams`; spelling
either directly binds the code to one side of the rename. Route through
`framework.compat.resolve_compiler_params`.

GL103 flags host-side operations inside jit-decorated functions: `print`
traces zero times or once (not per step), `.item()` forces a blocking
device sync per call, and `np.*` calls silently constant-fold at trace
time — all three are almost never what the author meant inside a traced
function.

GL104 flags a literal `interpret=True` at a Pallas call site: the
CPU-debug escape hatch left hard-coded ships an interpreted (100-1000x
slower) kernel to the chip with zero symptoms beyond slowness. Every
kernel file routes the flag through a module-level `_interpret()` /
`_interpret_mode()` helper (ops/pallas/blockwise_ce.py:49) that tests
flip — a ROADMAP "candidate next rule", now a rule.

GL105 is the static half of the observability host-side-only contract:
a `paddle_tpu.observability` record call (metrics OR tracing spans)
inside a jit-decorated function fires at trace time (once, not per step
— a counter that silently stops counting) or crashes on the tracer
coercion. The runtime half is the `float()` guard in
observability/metrics.py, shared by tracing.py.

GL108 reconstructs the int4 compile-payload bloat hazard documented by
hand in inference/__init__.py: a jitted function that CLOSES OVER a
large array (a `self.` attribute or a module-level array constant)
instead of taking it as an argument inlines the whole tensor into the
compiled program as a constant — ~350 MB of packed weights in the int4
case — and silently pins the STALE value (a later update to the
attribute never reaches the compiled program). Arrays flow as
arguments; closures carry only small config scalars.

GL109 is the transfer-per-step analogue of GL103's `.item()`, on the
HOST side of the serving hot loop: `float(x)` / `int(x)` / `np.asarray(x)`
on the result of a compiled device program inside a `for`/`while` loop
blocks on a device->host transfer EVERY iteration. One scalar cast per
slot per step turned the PR-1 serving loop into a latency ladder the
profiler showed as a picket fence of tiny D2H copies. The clean idiom
(continuous_batching.step): ONE `np.asarray(out)` bulk transfer, then
free host math — which is also why a whole-array `np.asarray` of a value
produced INSIDE the same loop never flags, while scalar casts always do
and a loop-invariant `np.asarray` (result bound outside the loop) flags
as a hoistable repeated transfer.

GL110 flags dict/set membership on — or dict keying by — a jax device
array: `x in some_set`, `d[x]`, `d.get(x)`, `s.add(x)` where `x` is a
compiled program's result. Hashing/equality on an Array forces a
blocking device sync per probe AND compares by value-of-the-moment — a
donated or mutated buffer silently changes the key under the container,
so the same logical token can miss its own index entry. The prefix
index hashes HOST token ints for exactly this reason
(continuous_batching.block_key: `tuple(int(t) for t in tokens)` over
host lists — the clean idiom the corpus tripwires pin); a device result
laundered through one bulk `np.asarray()` is host data and never flags.

GL111 flags wall-clock interval arithmetic: a `time.time()` difference
used as a duration (`time.time() - t0`, `now - start` where both came
from `time.time()`), or a `time.time()` value fed to a latency
histogram's `.observe()`. `time.time()` steps under NTP slew/adjtime —
a negative or wildly wrong "latency" lands in the histograms exactly
when the fleet's clocks are being corrected. The repo's latency
bookkeeping deliberately splits `time.monotonic()` for intervals from
`time.perf_counter()` for the span/profiler timebase; wall clock is for
TIMESTAMPING only (`"time": time.time()` in dump metadata, filename
stamps — never flagged) and for cross-process freshness checks against
stamps another host wrote (wall clock is the only shared timebase —
those sites carry an explicit disable comment).

GL112 flags unbounded metric label cardinality: a `.labels(x=...)`
call fed from a loop variable, an f-string interpolating a loop
variable, or request-scoped identity (`request_id`/`rid`/prompt
content) grows one child series PER DISTINCT VALUE, forever — a
long-lived serve loop leaks registry memory and blows up every
Prometheus scrape, silently. Labels must come from small FIXED sets
(status/reason literals) or values bounded BY CONSTRUCTION — the
serve_bucket_recompiles bucket label is the canonical clean case: the
interpolated values are pow2-bucketed, so the set is O(log) even
though the site sits in the serve loop; the rule reads an f-string
whose interpolations are function CALLS as exactly that bucketing
idiom (the corpus tripwire pins it).
"""
import ast
import re

from ..core import in_pallas, rule
# shared AST helpers live with the phase-1 engine; re-exported here for
# the other rule families that import them from this module
from ..project import _attr_chain, _is_jitish, own_scope_walk  # noqa: F401

_own_scope_walk = own_scope_walk

# the one module allowed to touch raw jax shard_map / CompilerParams
# spellings: it IS the resolver
COMPAT_MODULE = "paddle_tpu/framework/compat.py"


@rule("GL101", "raw-shard-map-import", "trace-safety")
def raw_shard_map_import(ctx):
    """`from jax import shard_map` (or any direct jax.experimental.shard_map
    import/use) outside framework/compat.py."""
    if ctx.path == COMPAT_MODULE:
        return
    msg = ("raw jax shard_map import: on jax 0.4.x this raises at import "
           "time and (if reachable from a test module) silently removes the "
           "module from collection — route through "
           "paddle_tpu.framework.compat.resolve_shard_map")
    for node in ctx.walk():
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax", "jax.experimental") and any(
                    a.name == "shard_map" for a in node.names):
                yield ctx.finding("GL101", node, msg), node
            elif mod == "jax.experimental.shard_map":
                yield ctx.finding("GL101", node, msg), node
        elif isinstance(node, ast.Import):
            if any(a.name == "jax.experimental.shard_map"
                   for a in node.names):
                yield ctx.finding("GL101", node, msg), node
        elif isinstance(node, ast.Attribute):
            if _attr_chain(node) == "jax.experimental.shard_map":
                yield ctx.finding("GL101", node, msg), node


@rule("GL102", "compiler-params-direct", "trace-safety")
def compiler_params_direct(ctx):
    """Direct `pltpu.CompilerParams` / `pltpu.TPUCompilerParams` attribute
    access outside the compat resolver."""
    if ctx.path == COMPAT_MODULE:
        return
    for node in ctx.walk():
        if (isinstance(node, ast.Attribute)
                and node.attr in ("CompilerParams", "TPUCompilerParams")):
            yield ctx.finding(
                "GL102", node,
                f"direct pltpu.{node.attr}: jax renamed TPUCompilerParams "
                "-> CompilerParams across releases; use "
                "framework.compat.resolve_compiler_params() so either jax "
                "works"), node


@rule("GL103", "host-op-in-jit", "trace-safety")
def host_op_in_jit(ctx):
    """print / .item() / numpy calls inside a jax.jit- or pjit-decorated
    function: print fires at trace time (zero or one time, not per step),
    .item() forces a device sync, np.* constant-folds under the trace."""
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(_is_jitish(d) for d in fn.decorator_list):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                yield ctx.finding(
                    "GL103", node,
                    f"print() inside jitted `{fn.name}` runs at trace time, "
                    "not per step — use jax.debug.print for runtime "
                    "values"), node
            elif isinstance(f, ast.Attribute) and f.attr == "item" \
                    and not node.args:
                yield ctx.finding(
                    "GL103", node,
                    f".item() inside jitted `{fn.name}` forces a blocking "
                    "host sync (and fails on traced values) — keep values "
                    "on device"), node
            elif isinstance(f, ast.Attribute):
                root = f.value
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name) \
                        and root.id in ctx.numpy_aliases:
                    yield ctx.finding(
                        "GL103", node,
                        f"numpy call `{_attr_chain(f)}` inside jitted "
                        f"`{fn.name}` constant-folds at trace time — use "
                        "jnp/lax so it runs per step on device"), node


@rule("GL104", "pallas-interpret-literal", "trace-safety",
      applies=in_pallas)
def interpret_literal(ctx):
    """Hard-coded `interpret=True` at a call site — route through the
    kernel module's `_interpret()`/`_interpret_mode()` helper so tests
    flip ONE switch and production never ships the interpreter."""
    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg == "interpret" and isinstance(kw.value, ast.Constant) \
                    and kw.value.value is True:
                yield ctx.finding(
                    "GL104", node,
                    "literal interpret=True at a Pallas call site: the "
                    "CPU-debug flag left hard-coded runs the kernel "
                    "interpreted (orders of magnitude slower) everywhere "
                    "— route it through the module's _interpret()/"
                    "_interpret_mode() helper (ops/pallas/"
                    "blockwise_ce.py:49)"), node


def _is_observability_module(mod, level):
    """True when an ImportFrom module path names the observability
    package or any of its submodules (tracing, metrics, ...), absolute
    (`paddle_tpu.observability.tracing`) or relative
    (`...observability.tracing`). Exact path-segment match, so a
    user-named `my_observability` module can't trip the rule."""
    parts = mod.split(".")
    if "observability" not in parts:
        return False
    return level > 0 or parts[0] == "paddle_tpu"


def _observability_names(ctx):
    """Names this module binds to paddle_tpu.observability (the metrics
    registry AND the tracing span recorder — both are host-side rings):
    module aliases (watch via attribute chains), directly imported
    symbols (watch via bare calls), and — for a bare dotted import,
    which binds only `paddle_tpu` — full dotted prefixes (a bare
    `paddle_tpu` alias would flag every paddle_tpu.* call in the
    file)."""
    mod_aliases, symbols, dotted = set(), set(), set()
    for node in ctx.walk():
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "paddle_tpu.observability" or \
                        a.name.startswith("paddle_tpu.observability."):
                    if a.asname:
                        mod_aliases.add(a.asname)
                    else:
                        dotted.add("paddle_tpu.observability")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            # absolute or relative (`from ...observability import x`)
            if mod == "paddle_tpu" and any(
                    a.name == "observability" for a in node.names):
                for a in node.names:
                    if a.name == "observability":
                        mod_aliases.add(a.asname or "observability")
            elif _is_observability_module(mod, node.level):
                # `from ...observability import tracing` binds a module,
                # `from paddle_tpu.observability.tracing import span` a
                # function — either way a call rooted at the bound name
                # records host-side state
                for a in node.names:
                    symbols.add(a.asname or a.name)
    return mod_aliases, symbols, dotted


def _call_root(expr):
    """Base Name of a call chain: `obs.counter("x").inc()` -> `obs`
    (peels Attribute and Call layers)."""
    while True:
        if isinstance(expr, ast.Attribute):
            expr = expr.value
        elif isinstance(expr, ast.Call):
            expr = expr.func
        elif isinstance(expr, ast.Name):
            return expr.id
        else:
            return None


# the one observability call that is MEANT for traced code: it records
# nothing on the host, it names the ops traced under it
_DEVICE_SIDE = frozenset({"device_scope"})


@rule("GL105", "observability-record-in-jit", "trace-safety")
def observability_in_jit(ctx):
    """paddle_tpu.observability calls inside a jit-decorated function:
    metrics are host-side only — under the trace a record fires once
    (at trace time) or dies on the tracer->float coercion."""
    mod_aliases, symbols, dotted = _observability_names(ctx)
    if not mod_aliases and not symbols and not dotted:
        return
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not any(_is_jitish(d) for d in fn.decorator_list):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _DEVICE_SIDE:
                continue
            root = _call_root(node.func)
            hit = root in mod_aliases or root in symbols
            if not hit and dotted:
                text = ast.unparse(node.func)
                hit = any(text.startswith(p + ".") for p in dotted)
            if hit:
                yield ctx.finding(
                    "GL105", node,
                    f"observability call inside jitted `{fn.name}`: "
                    "metrics and tracing spans record host-side state — "
                    "under jit this fires at trace time (not per step) "
                    "or crashes on the tracer->float guard. Record "
                    "outside the jitted function (observability/"
                    "metrics.py + tracing.py contract)"), node


def _jitted_functions(ctx):
    """Every FunctionDef the file jits: decorator form (`@jax.jit`,
    `@partial(jax.jit, ...)`) plus call-binding form (`jax.jit(fn, ...)`
    where `fn` is a function defined in this file — the engines' idiom:
    `self._step = jax.jit(step, donate_argnums=(1,))`)."""
    defs = {}
    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    jitted = []
    seen = set()
    for node in ctx.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(_is_jitish(d) for d in node.decorator_list):
            if id(node) not in seen:
                seen.add(id(node))
                jitted.append(node)
        elif isinstance(node, ast.Call) and _is_jitish(node.func) \
                and node.args and isinstance(node.args[0], ast.Name):
            for fn in defs.get(node.args[0].id, ()):
                if id(fn) not in seen:
                    seen.add(id(fn))
                    jitted.append(fn)
    return jitted


def _array_aliases(ctx):
    """Names bound to numpy OR jax.numpy in this module (`np`, `jnp`,
    ...) — the constructors whose module-level results are almost
    certainly arrays."""
    aliases = set(ctx.numpy_aliases)
    for node in ctx.walk():
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("jax.numpy",) and a.asname:
                    aliases.add(a.asname)
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "") == "jax":
                for a in node.names:
                    if a.name == "numpy":
                        aliases.add(a.asname or "numpy")
    return aliases


def _module_array_names(ctx):
    """Module-level `NAME = <call rooted at np/jnp>` bindings: the
    array constants a jitted function must take as arguments, not close
    over. Calls only — `DIM = 128` or `SHAPE = (8, 128)` never match."""
    aliases = _array_aliases(ctx)
    if not aliases:
        return set()
    out = set()
    for stmt in ctx.tree.body:
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        if not isinstance(value, ast.Call):
            continue
        if _call_root(value.func) not in aliases:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
    return out


def _param_names(a):
    names = {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


def _local_names(fn):
    """Names the function binds in its OWN scope: parameters plus
    anything assigned/bound directly in its body (a local shadowing a
    module-level array is the function's own business). Names bound only
    inside a nested def/lambda live in that scope and must NOT mask an
    outer capture — GL108 resolves nested scopes recursively."""
    names = _param_names(fn.args)
    for node in _own_scope_walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx,
                                                     (ast.Store,
                                                      ast.Del)):
            names.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names.add(node.name)
    return names


@rule("GL108", "jit-closure-capture", "trace-safety")
def jit_closure_capture(ctx):
    """A jitted function closing over a `self.` attribute or a
    module-level array constant: the array is inlined into the compiled
    program as a CONSTANT (the int4 compile-payload bloat — ~350 MB of
    packed weights in the program image) and later updates to the
    captured value silently never reach the compiled code. Pass arrays
    as arguments (donate if appropriate)."""
    module_arrays = _module_array_names(ctx)
    for fn in _jitted_functions(ctx):
        flagged_attrs = set()
        flagged_names = set()
        # (scope, names visible in it) — nested defs/lambdas inherit the
        # enclosing locals (closure semantics) plus their own bindings,
        # so an inner local never masks an OUTER capture and an inner
        # fn's own shadow of a module array is its own business.
        scopes = [(fn, _local_names(fn))]
        while scopes:
            scope, locals_ = scopes.pop()
            for node in _own_scope_walk(scope):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                    scopes.append(
                        (node, locals_ | _local_names(node)))
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "self" \
                        and "self" not in locals_ \
                        and node.attr not in flagged_attrs:
                    flagged_attrs.add(node.attr)
                    yield ctx.finding(
                        "GL108", node,
                        f"jitted `{fn.name}` closes over "
                        f"`self.{node.attr}`: "
                        "a captured array is baked into the compiled "
                        "program as a constant (compile-payload bloat — "
                        "the int4 case was ~350 MB) and later updates "
                        "to the attribute never reach the compiled code "
                        "— pass it as an argument "
                        "(inference/__init__.py passes `self._w` as "
                        "the `w` arg for exactly this reason)"), node
                elif isinstance(node, ast.Name) \
                        and isinstance(node.ctx, ast.Load) \
                        and node.id in module_arrays \
                        and node.id not in locals_ \
                        and node.id not in flagged_names:
                    flagged_names.add(node.id)
                    yield ctx.finding(
                        "GL108", node,
                        f"jitted `{fn.name}` closes over module-level "
                        f"array `{node.id}`: the array is inlined into "
                        "the compiled program as a constant (payload "
                        "bloat + silently stale on rebind) — pass it "
                        "as an argument"), node


def _jit_bound_names(ctx):
    """Names (plain or `self.`-attribute) this file binds to a compiled
    program: any assignment whose RHS contains a `jax.jit(...)` /
    `pjit(...)` call — covers `step = jax.jit(fn)`, `self._paged_step =
    _dispatch_span("...", jax.jit(fn, ...))`, and decorator-factory
    wrappers. A CALL of one of these names is a device dispatch."""
    out = set()
    for stmt in ctx.walk():
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        if not any(isinstance(n, ast.Call) and _is_jitish(n.func)
                   for n in ast.walk(value)):
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
            elif isinstance(t, ast.Attribute):
                out.add(t.attr)
    return out


# the serving engines' compiled-program attribute convention
# (inference/__init__.py binds them in its own module; a caller file —
# continuous_batching.py — sees only `self.engine._paged_step(...)`)
_DEVICE_ATTR_PREFIX = "_paged_"


def _is_device_call(node, jit_names):
    """Call of a compiled program: a jit-bound name from THIS file, or
    the cross-module `engine._paged_*` serving convention."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Name):
        return f.id in jit_names
    if isinstance(f, ast.Attribute):
        return f.attr in jit_names or f.attr.startswith(_DEVICE_ATTR_PREFIX)
    return False


def _root_name(expr):
    """Base Name of a subscript/attribute chain: `out[i, 0]` -> `out`."""
    while isinstance(expr, (ast.Subscript, ast.Attribute)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


# jax.Array attributes that return plain HOST objects — accessing them
# neither transfers nor keeps the result on device, so `out.shape`,
# `out.dtype.name`, `out.shape[0]` are host values, not device bindings
_HOST_META_ATTRS = frozenset({
    "shape", "dtype", "ndim", "size", "nbytes", "itemsize", "device",
    "devices", "sharding", "weak_type", "is_deleted"})


def _touches_host_meta(expr):
    """True when the subscript/attribute chain reads a host metadata
    attribute anywhere (`out.shape[0]` -> host int, not device)."""
    while isinstance(expr, (ast.Subscript, ast.Attribute)):
        if isinstance(expr, ast.Attribute) and \
                expr.attr in _HOST_META_ATTRS:
            return True
        expr = expr.value
    return False


def _device_bindings(fn, jit_names, np_aliases):
    """{name: [assign nodes]} for names bound from a device call in
    `fn`, minus names laundered host-side via a whole-array
    `np.asarray(x)` / `np.array(x)` rebind (the clean bulk-transfer
    idiom clears the name). Only NUMPY's asarray launders —
    `jnp.asarray` keeps the value on device."""
    bound = {}
    cleared = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        if isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Attribute) \
                and node.value.func.attr in ("asarray", "array") \
                and isinstance(node.value.func.value, ast.Name) \
                and node.value.func.value.id in np_aliases:
            # host-side laundering — checked FIRST so the one-line bulk
            # idiom `out = np.asarray(self._paged_step(...))` binds a
            # host copy, not a device value, even though a device call
            # sits inside the assign
            for t in node.targets:
                if isinstance(t, ast.Name):
                    cleared.add(t.id)
        elif any(_is_device_call(n, jit_names)
                 for n in ast.walk(node.value)):
            for t in node.targets:
                names = t.elts if isinstance(t, ast.Tuple) else [t]
                for el in names:
                    if isinstance(el, ast.Name):
                        bound.setdefault(el.id, []).append(node)
    # propagate through pure access: `tok = out[0, 0]` is still a device
    # value when `out` is (slicing/attribute access never transfers) —
    # fixpoint over the function's assignments. Host METADATA attributes
    # (`out.shape`, `.dtype`, ...) are plain host objects and stop the
    # propagation.
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign) or not isinstance(
                    node.value, (ast.Name, ast.Subscript, ast.Attribute)):
                continue
            if _touches_host_meta(node.value):
                continue
            root = _root_name(node.value)
            if root not in bound or root in cleared:
                continue
            for t in node.targets:
                names = t.elts if isinstance(t, ast.Tuple) else [t]
                for el in names:
                    if isinstance(el, ast.Name) and el.id not in bound \
                            and el.id not in cleared:
                        bound[el.id] = [node]
                        changed = True
    return {k: v for k, v in bound.items() if k not in cleared}


@rule("GL109", "host-sync-in-serve-loop", "trace-safety")
def host_sync_in_serve_loop(ctx):
    """float()/int()/np.asarray() on a compiled-program result inside a
    for/while loop: every iteration blocks on a device->host transfer
    (the serving-loop analogue of GL103's .item()). Convert ONCE with a
    bulk np.asarray() and do host math on the copy."""
    jit_names = _jit_bound_names(ctx)
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        dev = _device_bindings(fn, jit_names, ctx.numpy_aliases)
        if not dev:
            continue
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.While, ast.ListComp,
                                     ast.SetComp, ast.DictComp,
                                     ast.GeneratorExp)):
                continue
            lo = loop.lineno
            hi = getattr(loop, "end_lineno", lo)
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or len(node.args) != 1:
                    continue
                f = node.func
                root = _root_name(node.args[0])
                if root not in dev:
                    continue
                if isinstance(f, ast.Name) and f.id in ("float", "int"):
                    yield ctx.finding(
                        "GL109", node,
                        f"{f.id}() of device result `{root}` inside a "
                        "loop: one blocking device->host transfer PER "
                        "ITERATION — np.asarray() the whole array once "
                        "before the loop and cast from the host copy "
                        "(continuous_batching.step's toks2 idiom)"), node
                elif isinstance(f, ast.Attribute) \
                        and f.attr in ("asarray", "array") \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id in ctx.numpy_aliases \
                        and all(not (lo <= b.lineno <= hi)
                                for b in dev[root]):
                    yield ctx.finding(
                        "GL109", node,
                        f"np.{f.attr}() of device result `{root}` "
                        "inside a loop, but the result is produced "
                        "OUTSIDE it: the same device->host transfer "
                        "repeats every iteration — hoist the conversion "
                        "above the loop"), node


_DICT_SET_CALLS = {"dict", "set", "frozenset", "OrderedDict",
                   "defaultdict", "Counter"}


def _dict_set_names(ctx):
    """Plain and `self.`-attribute names this file ever binds to a dict
    or set (literal, comprehension, or stdlib constructor) — the
    containers whose __contains__/__getitem__/.get/.add HASH their
    argument."""
    out = set()
    for stmt in ctx.walk():
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        hashy = isinstance(value, (ast.Dict, ast.Set, ast.DictComp,
                                   ast.SetComp))
        if not hashy and isinstance(value, ast.Call):
            f = value.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            hashy = name in _DICT_SET_CALLS
        if not hashy:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
            elif isinstance(t, ast.Attribute):
                out.add(t.attr)
    return out


def _container_name(expr):
    """`d` / `self._index` -> the name GL110 matched against
    _dict_set_names; None for anything it can't see through."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


_GL110_MSG = (
    "forces a blocking device->host sync per probe (Array.__hash__/"
    "__eq__) and compares by value-of-the-moment — a donated/mutated "
    "buffer changes the key under the container. Hash HOST data "
    "instead: one bulk np.asarray(), then int()/tuple() keys "
    "(continuous_batching.block_key hashes host token ints for exactly "
    "this reason)")


@rule("GL110", "device-array-hash-key", "trace-safety")
def device_array_hash_key(ctx):
    """Dict/set membership on — or dict keying by — a jax device array
    (a compiled program's un-laundered result): `x in s`, `d[x]`,
    `d.get(x)`, `s.add(x)`. Hashing an Array forces a device sync per
    probe and keys on the value-of-the-moment; the prefix index's
    block_key hashes host token bytes for exactly this reason."""
    jit_names = _jit_bound_names(ctx)
    containers = _dict_set_names(ctx)
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        dev = _device_bindings(fn, jit_names, ctx.numpy_aliases)
        if not dev:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Compare):
                # membership: the HASHED/COMPARED operand is the left
                # side of each `in`/`not in` (works on sets, dicts, and
                # lists — a device value on either side of `in` syncs)
                for i, op in enumerate(node.ops):
                    if not isinstance(op, (ast.In, ast.NotIn)):
                        continue
                    left = node.left if i == 0 else node.comparators[i - 1]
                    root = _root_name(left)
                    if root in dev and not _touches_host_meta(left):
                        yield ctx.finding(
                            "GL110", node,
                            f"membership test on device result `{root}` "
                            + _GL110_MSG), node
            elif isinstance(node, ast.Subscript):
                if _container_name(node.value) not in containers:
                    continue        # array indexing is not hashing
                sl = node.slice
                elts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
                for e in elts:
                    root = _root_name(e)
                    if root in dev and not _touches_host_meta(e):
                        yield ctx.finding(
                            "GL110", node,
                            f"dict/set keyed by device result `{root}` "
                            + _GL110_MSG), node
                        break
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("get", "add", "setdefault",
                                           "pop", "discard") \
                    and node.args \
                    and _container_name(node.func.value) in containers:
                root = _root_name(node.args[0])
                if root in dev and not _touches_host_meta(node.args[0]):
                    yield ctx.finding(
                        "GL110", node,
                        f".{node.func.attr}() keyed by device result "
                        f"`{root}` " + _GL110_MSG), node


def _is_time_time_call(node):
    """A direct `time.time()` call expression."""
    return (isinstance(node, ast.Call) and not node.args
            and not node.keywords
            and _attr_chain(node.func) == "time.time")


def _walltime_names_own(scope):
    """Names (and `self.x` attribute names) bound to a bare
    `time.time()` in `scope`'s OWN lexical body (nested function bodies
    are separate scopes — a `t0 = time.time()` in one function must not
    poison an unrelated `t0 = time.monotonic()` elsewhere in the file):
    `t0 = time.time()`, `self._start = time.time()`. Arithmetic on the
    stamp at the assignment (`time.time() + 5` — a deadline) does NOT
    mark the name: deadlines are compared, not subtracted, and marking
    them would flag the `while time.time() < deadline` idiom's
    bookkeeping."""
    names, attrs = set(), set()
    walk = _own_scope_walk(scope) if isinstance(
        scope, (ast.FunctionDef, ast.AsyncFunctionDef)) else (
            n for st in scope.body for n in _module_scope_walk(st))
    for node in walk:
        if isinstance(node, ast.Assign) and _is_time_time_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    attrs.add(t.attr)
    return names, attrs


def _module_scope_walk(node):
    """ast.walk pruned at def/lambda boundaries (class bodies run at
    module scope, so they are walked; a def is yielded — its name binds
    here — but its body is never descended into, even when the def
    itself is the statement the walk starts from)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


_GL111_MSG = (
    "wall clock steps under NTP slew — a negative or wildly wrong "
    "interval lands exactly when the fleet's clocks are corrected. Use "
    "time.monotonic() for durations (time.perf_counter() on the "
    "span/profiler timebase); time.time() is for timestamping only. A "
    "cross-process freshness check against a stamp another host wrote "
    "is the one legitimate case — suppress it with a comment saying so")


@rule("GL111", "wallclock-interval", "trace-safety")
def wallclock_interval(ctx):
    """`time.time()` differences used as durations, and `time.time()`
    values fed to `.observe()`. Timestamping (`"time": time.time()`
    dict metadata, filename stamps, deadline comparisons) never flags.
    Name taint is scoped: a plain name counts as wall-clock only where
    its `= time.time()` binding is lexically visible (own function +
    enclosing chain + module level); `self.x` attribute stamps stay
    file-wide (assignment and use commonly sit in different methods)."""
    module_names, _ = _walltime_names_own(ctx.tree)
    # attribute stamps are collected FILE-wide: `self._t0 = time.time()`
    # in one method is read in another by design
    attrs = set()
    for n in ctx.walk():
        if isinstance(n, ast.Assign) and _is_time_time_call(n.value):
            for t in n.targets:
                if isinstance(t, ast.Attribute):
                    attrs.add(t.attr)
    fn_scope = {}   # FunctionDef -> (own walltime names, own assigned)

    def scope_of(fn):
        if fn not in fn_scope:
            wall = _walltime_names_own(fn)[0]
            assigned = {a.arg for a in fn.args.args
                        + fn.args.kwonlyargs + fn.args.posonlyargs}
            for n in _own_scope_walk(fn):
                if isinstance(n, ast.Name) and isinstance(
                        n.ctx, (ast.Store, ast.Del)):
                    assigned.add(n.id)
            fn_scope[fn] = (wall, assigned)
        return fn_scope[fn]

    def names_for(node):
        # lexical visibility with SHADOWING: walk the enclosing chain
        # outermost-first; a scope that rebinds a name (param or any
        # non-walltime assignment) clears the outer taint — a local
        # `start = time.monotonic()` is not the module's `start` stamp
        visible = set(module_names)
        for fn in reversed(ctx.enclosing_functions(node)):
            wall, assigned = scope_of(fn)
            visible = (visible - assigned) | wall
        return visible

    def is_walltime(node, names):
        if _is_time_time_call(node):
            return True
        if isinstance(node, ast.Name) and node.id in names:
            return True
        if isinstance(node, ast.Attribute) and node.attr in attrs:
            return True
        return False

    for node in ctx.walk():
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            names = names_for(node)
            if is_walltime(node.left, names) \
                    or is_walltime(node.right, names):
                yield ctx.finding(
                    "GL111", node,
                    "time.time() difference used as a duration: "
                    + _GL111_MSG), node
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "observe" and node.args:
            # a BARE wall-clock stamp observed into a histogram (a
            # subtraction inside the arg already flagged above)
            if is_walltime(node.args[0], names_for(node)):
                yield ctx.finding(
                    "GL111", node,
                    "time.time() value fed to a histogram: an absolute "
                    "wall-clock stamp is not a latency, and "
                    + _GL111_MSG), node


# identifiers that carry per-request identity: one label child per
# request = unbounded cardinality wherever the site sits
_GL112_UNBOUNDED = {"request_id", "rid", "prompt", "prompt_text",
                    "user_id", "session_id", "trace_id"}

_GL112_MSG = (
    "grows one metric child PER DISTINCT VALUE forever — a long-lived "
    "serve loop leaks registry memory and bloats every scrape. Label "
    "values must come from small fixed sets (status/reason literals) "
    "or be bounded by construction; bucket first (next_pow2-style — an "
    "f-string whose interpolations are function calls reads as that "
    "idiom), or put per-request identity in SPANS "
    "(tracing.event(request=...)), never in metric labels")


def _gl112_loop_targets(ctx, node):
    """Names bound by every lexically-enclosing for-loop/comprehension
    of `node` — the per-iteration values a .labels() in the loop body
    would mint a fresh child for."""
    out = set()
    cur = ctx.parent(node)
    while cur is not None:
        if isinstance(cur, ast.For):
            for el in ast.walk(cur.target):
                if isinstance(el, ast.Name):
                    out.add(el.id)
        elif isinstance(cur, (ast.ListComp, ast.SetComp, ast.DictComp,
                              ast.GeneratorExp)):
            for gen in cur.generators:
                for el in ast.walk(gen.target):
                    if isinstance(el, ast.Name):
                        out.add(el.id)
        cur = ctx.parent(cur)
    return out


def _gl112_ident(expr):
    """The per-request-identity name an expression carries, if any:
    `request_id`, `req.request_id`, `str(rid)` all count — identity
    laundered through str()/repr() is still one child per request."""
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
            and expr.func.id in ("str", "repr", "format") and expr.args:
        expr = expr.args[0]
    if isinstance(expr, ast.Name) and expr.id in _GL112_UNBOUNDED:
        return expr.id
    if isinstance(expr, ast.Attribute) and expr.attr in _GL112_UNBOUNDED:
        return expr.attr
    return None


_GL113_LOOPFN = re.compile(
    r"(serve|stream|step|pump|drain|poll|worker|loop|run|drive|tick)",
    re.IGNORECASE)

# exception types broad enough to swallow a cancellation / real
# failure alongside whatever the author meant to catch
_GL113_BROAD = {"Exception", "BaseException", "RuntimeError"}

# a handler that invokes the structured-terminal machinery is the
# resilience layer doing its job: per-request failure paths are named
# like these across the engine/gateway (_fail_slot, _finish_slot,
# _terminal_queued, cancel, operator_abort_dump, close, ...)
_GL113_OK_CALL = ("fail", "finish", "terminal", "abort", "reject",
                  "cancel", "shed", "retire", "close", "shutdown",
                  "record_result")

_GL113_MSG = (
    "a broad except inside a serve/step/stream loop that neither "
    "re-raises nor records a structured terminal status silently "
    "converts a real failure (including a cancellation) into an "
    "infinite retry — the loop spins, the request never terminates, "
    "and nothing lands in engine.finished or on the timeline. "
    "Re-raise, narrow the exception type, or record the structured "
    "terminal status (the resilience layer's per-request-failure "
    "discipline: _fail_slot/_finish_slot-style calls, or an event "
    "carrying status=/reason=)")


def _gl113_broad(handler):
    """Does this except clause catch one of the broad types?"""
    t = handler.type
    if t is None:
        return True                  # bare except: broadest of all
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        name = e.id if isinstance(e, ast.Name) else (
            e.attr if isinstance(e, ast.Attribute) else None)
        if name in _GL113_BROAD:
            return True
    return False


def _gl113_records_terminal(handler):
    """Does the handler body re-raise, or call into the structured
    terminal-status machinery (a call with a status=/reason= keyword,
    or a callee whose name spells a terminal action)?"""
    for st in handler.body:
        for n in ast.walk(st):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                if any(kw.arg in ("status", "reason")
                       for kw in n.keywords if kw.arg):
                    return True
                fname = n.func.attr if isinstance(n.func, ast.Attribute) \
                    else (n.func.id if isinstance(n.func, ast.Name)
                          else "")
                low = fname.lower()
                if any(tok in low for tok in _GL113_OK_CALL):
                    return True
    return False


@rule("GL113", "swallowed-cancellation", "trace-safety")
def swallowed_cancellation(ctx):
    """Broad `except` (Exception / BaseException / RuntimeError / bare)
    inside a loop of a serve/step/stream-shaped function that neither
    re-raises nor records a structured terminal status. The ISSUE-11
    resilience discipline enforced statically: degradation must be
    per-request and VISIBLE — a swallowed failure in a serving loop is
    an infinite retry with no evidence trail."""
    seen = set()
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _GL113_LOOPFN.search(fn.name):
            continue
        for loop in _own_scope_walk(fn):
            if not isinstance(loop, (ast.While, ast.For, ast.AsyncFor)):
                continue
            for sub in ast.walk(loop):
                if not isinstance(sub, ast.Try):
                    continue
                for h in sub.handlers:
                    if id(h) in seen:
                        continue
                    seen.add(id(h))
                    if _gl113_broad(h) \
                            and not _gl113_records_terminal(h):
                        yield ctx.finding(
                            "GL113", h,
                            f"broad except in the `{fn.name}` loop "
                            "swallows cancellations/failures: "
                            + _GL113_MSG), h


_GL120_CTORS = ("Mesh", "NamedSharding")

_GL120_MSG = (
    "a FRESH Mesh/NamedSharding per call is a new jit cache key — the "
    "dispatch it feeds recompiles (or at best re-hashes device lists) "
    "every step, and device enumeration at construction is a host-side "
    "stall in the hot loop. Build the mesh and shardings ONCE at "
    "construction time and close over them (inference/__init__.py "
    "builds self._mesh in the ctor; new_paged_caches hoists its "
    "NamedSharding above the per-layer comprehension)")


def _gl120_callee(node):
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


@rule("GL120", "inline-mesh-in-hot-path", "trace-safety")
def inline_mesh_in_hot_path(ctx):
    """Mesh()/NamedSharding() constructed on the serving hot path:
    inside a for/while loop that also dispatches a compiled program
    (the step loop), or anywhere in a serve/step-loop-shaped function
    that dispatches one (the per-call wrapper — it runs per request by
    construction). Construction time (`__init__`, module level, setup
    loops that only device_put) never flags: that is the RIGHT place
    to build them."""
    jit_names = _jit_bound_names(ctx)
    flagged = set()
    for fn in ctx.walk():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name == "__init__":
            continue
        dispatches = any(_is_device_call(n, jit_names)
                         for n in _own_scope_walk(fn))
        # (a) the step loop: a ctor call inside a loop that also
        # dispatches — the canonical picket-fence shape
        for loop in _own_scope_walk(fn):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            if not any(_is_device_call(n, jit_names)
                       for n in ast.walk(loop)):
                continue
            for node in ast.walk(loop):
                if isinstance(node, ast.Call) \
                        and _gl120_callee(node) in _GL120_CTORS \
                        and id(node) not in flagged:
                    flagged.add(id(node))
                    yield ctx.finding(
                        "GL120", node,
                        f"{_gl120_callee(node)}() constructed inside "
                        f"`{fn.name}`'s dispatch loop: " + _GL120_MSG), node
        # (b) the per-call wrapper: a serve/step-shaped function that
        # dispatches a compiled program builds its mesh per CALL even
        # when the ctor sits outside any lexical loop
        if not dispatches or not _GL113_LOOPFN.search(fn.name):
            continue
        for node in _own_scope_walk(fn):
            if isinstance(node, ast.Call) \
                    and _gl120_callee(node) in _GL120_CTORS \
                    and id(node) not in flagged:
                flagged.add(id(node))
                yield ctx.finding(
                    "GL120", node,
                    f"{_gl120_callee(node)}() constructed per call of "
                    f"the dispatching `{fn.name}`: " + _GL120_MSG), node


@rule("GL112", "metric-label-cardinality", "trace-safety")
def metric_label_cardinality(ctx):
    """`.labels(x=...)` fed from a loop variable, an f-string
    interpolating a loop variable, or request-scoped identity
    (request_id / raw prompt content): unbounded label cardinality.
    Bucketed interpolations (function calls inside the f-string) and
    fixed literal labels never flag."""
    for node in ctx.walk():
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "labels" and node.keywords):
            continue
        loop_vars = None    # computed lazily: parent walks aren't free
        for kw in node.keywords:
            if kw.arg is None:
                continue            # **kwargs: opaque, let it pass
            v = kw.value
            why = None
            ident = _gl112_ident(v)
            if ident is not None:
                why = (f"label `{kw.arg}` carries per-request identity "
                       f"`{ident}`")
            else:
                if loop_vars is None:
                    loop_vars = _gl112_loop_targets(ctx, node)
                if isinstance(v, ast.Name) and v.id in loop_vars:
                    why = (f"label `{kw.arg}` is the enclosing loop's "
                           f"variable `{v.id}`")
                elif isinstance(v, ast.JoinedStr):
                    for part in v.values:
                        if not isinstance(part, ast.FormattedValue):
                            continue
                        e = part.value
                        pid = _gl112_ident(e)
                        if pid is not None:
                            why = (f"label `{kw.arg}` interpolates "
                                   f"per-request identity `{pid}`")
                            break
                        if isinstance(e, ast.Name) and e.id in loop_vars:
                            why = (f"label `{kw.arg}` interpolates the "
                                   f"enclosing loop's variable `{e.id}` "
                                   "unbucketed")
                            break
            if why:
                yield ctx.finding("GL112", node, why + ": "
                                  + _GL112_MSG), node
