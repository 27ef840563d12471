"""Pallas kernel bounds rules (GL3xx) — scoped to kernel files
("pallas" in the path) plus the self-test corpus.

GL301 reconstructs the PR 1 `update_paged_kv_cache` hazard: an `.at[...]`
update (or `pl.ds` slice) whose index came from DATA — a block-table
lookup, a gather — with no visible clamp between the lookup and the
memory access. On TPU the OOB access doesn't fault; it aliases whichever
block the clamped gather hands back and corrupts another sequence's KV
cache. The rule demands the guard be *visible*: a clamping call
(`jnp.minimum`/`jnp.clip`/`jnp.where`/`%`) in the index expression or in
the local assignment feeding it, a `mode=` kwarg on the `.set`/`.add`
(scatter drop/fill semantics), or the whole access sitting under a
`@pl.when(...)` guard.

The dynamic-index model is one-step local taint, on purpose (this is a
linter, not an abstract interpreter): an index is dynamic if it contains
a data lookup (`tables[i]`-shaped Subscript), a call that is neither a
clamp nor a grid query, or a local name assigned from such an expression
without a clamp. Bare names and arithmetic over them (grid counters,
block offsets) don't trip it — the hazard class is indices read from
data, which is exactly what the PR 1 bug was.

GL302 checks literal block shapes against the (8, 128) TPU tile: a
trailing dim not divisible by 128 or a second-minor not divisible by 8
wastes the tile (Mosaic pads to the full tile) and several ops refuse
the layout outright — see /opt/skills/guides/pallas_guide.md.
"""
import ast

from ..core import rule, in_pallas

# calls that clamp/guard an index into range
_CLAMP_CALLS = {"minimum", "clip", "where", "mod", "remainder",
                "clamp", "rem"}     # lax.clamp, lax.rem: a kernel's scalars
# calls fine to see inside an index expression: grid coordinates are
# bounded by the grid, dtype casts don't change the value class
_SAFE_CALLS = {"program_id", "num_programs", "astype", "int32", "int64",
               "len", "range", "cdiv"}


def _callee_attr(call):
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _slice_only(s):
    """True for reshape-style subscripts (x[None, :], x[:, :1]) that don't
    look a value up by a computed position."""
    elts = s.elts if isinstance(s, ast.Tuple) else [s]
    for e in elts:
        if isinstance(e, ast.Slice):
            ok = all(p is None or isinstance(p, ast.Constant)
                     for p in (e.lower, e.upper, e.step))
            if not ok:
                return False
        elif not (isinstance(e, ast.Constant)
                  and (e.value is None or isinstance(e.value, int))):
            return False
    return True


def _has_clamp(expr):
    for n in ast.walk(expr):
        if isinstance(n, ast.Call) and _callee_attr(n) in _CLAMP_CALLS:
            return True
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod):
            return True
    return False


def _is_dynamic(expr, tainted):
    for n in ast.walk(expr):
        if isinstance(n, ast.Subscript) and not _slice_only(n.slice):
            return True
        if isinstance(n, ast.Call):
            a = _callee_attr(n)
            if a not in _CLAMP_CALLS and a not in _SAFE_CALLS:
                return True
        if isinstance(n, ast.Name) and n.id in tainted:
            return True
    return False


def _tainted_names(fn):
    """Local names fed by an unclamped data lookup. A clamping assignment
    to the same name wins regardless of order — the paged-cache pattern
    clamps on a reassignment (`blk_ids = jnp.where(full, nb, blk_ids)`),
    and a linter false negative on a self-overwrite beats flagging the
    clamp line itself."""
    taints, clamps = set(), set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign):
            targets = [t for t in n.targets if isinstance(t, ast.Name)]
            val = n.value
        elif isinstance(n, ast.AugAssign) and isinstance(n.target, ast.Name):
            targets, val = [n.target], n.value
        else:
            continue
        if not targets:
            continue
        if _has_clamp(val):
            clamps.update(t.id for t in targets)
        elif _is_dynamic(val, set()):
            taints.update(t.id for t in targets)
    return taints - clamps


def _under_pl_when(ctx, node):
    for fn in ctx.enclosing_functions(node):
        for d in fn.decorator_list:
            if isinstance(d, ast.Call) and _callee_attr(d) == "when":
                return True
    return False


def _scatter_mode_kwarg(ctx, node):
    """node is `x.at[i]`; True when it feeds `.set/.add(..., mode=...)`."""
    p = ctx.parent(node)
    if isinstance(p, ast.Attribute) and p.attr in (
            "set", "add", "get", "max", "min", "mul", "apply"):
        call = ctx.parent(p)
        return (isinstance(call, ast.Call)
                and any(k.arg == "mode" for k in call.keywords))
    return False


@rule("GL301", "pallas-unclamped-dynamic-index", "pallas-bounds",
      applies=in_pallas)
def unclamped_dynamic_index(ctx):
    """Dynamic `.at[...]` / `pl.ds` index with no visible clamp/guard —
    the update_paged_kv_cache OOB shape."""
    msg = ("dynamic {what} index is not visibly clamped/guarded: an OOB "
           "index doesn't fault on TPU, it aliases another block (the PR 1 "
           "update_paged_kv_cache corruption). Clamp it (jnp.minimum/"
           "jnp.clip/jnp.where/%), scatter with mode='drop', or guard the "
           "access with @pl.when")
    taint_cache = {}

    def tainted_for(node):
        fns = ctx.enclosing_functions(node)
        if not fns:
            return set()
        fn = fns[0]
        if fn not in taint_cache:
            taint_cache[fn] = _tainted_names(fn)
        return taint_cache[fn]

    for node in ctx.walk():
        # x.at[IDX] — jnp functional updates and ref.at DMA slices alike
        if isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Attribute) \
                and node.value.attr == "at":
            idx = node.slice
            if not _is_dynamic(idx, tainted_for(node)) or _has_clamp(idx):
                continue
            if _scatter_mode_kwarg(ctx, node) or _under_pl_when(ctx, node):
                continue
            yield ctx.finding("GL301", node,
                              msg.format(what=".at[]")), node
        # pl.ds(start, size)
        elif isinstance(node, ast.Call) \
                and _callee_attr(node) in ("ds", "dslice") and node.args:
            start = node.args[0]
            if not _is_dynamic(start, tainted_for(node)) \
                    or _has_clamp(start):
                continue
            if _under_pl_when(ctx, node):
                continue
            yield ctx.finding("GL301", node,
                              msg.format(what="pl.ds start")), node


@rule("GL302", "pallas-block-shape-tile", "pallas-bounds", applies=in_pallas)
def block_shape_tile(ctx):
    """Literal BlockSpec block shapes whose trailing dims don't divide the
    (8, 128) TPU tile."""
    for node in ctx.walk():
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            (f.id if isinstance(f, ast.Name) else "")
        if name != "BlockSpec" or not node.args:
            continue
        shape = node.args[0]
        if not isinstance(shape, ast.Tuple) or len(shape.elts) < 2:
            continue
        if not all(isinstance(e, ast.Constant) and isinstance(e.value, int)
                   for e in shape.elts):
            continue  # symbolic shapes: can't judge statically
        dims = [e.value for e in shape.elts]
        last, second = dims[-1], dims[-2]
        bad = []
        if last % 128:
            bad.append(f"minor dim {last} % 128 != 0")
        if second != 1 and second % 8:
            bad.append(f"second-minor dim {second} % 8 != 0")
        if bad:
            yield ctx.finding(
                "GL302", node,
                f"block shape {tuple(dims)} vs the (8, 128) TPU tile: "
                + "; ".join(bad)
                + " — Mosaic pads to the full tile (wasted VMEM/compute) "
                  "and some ops refuse the layout"), node
