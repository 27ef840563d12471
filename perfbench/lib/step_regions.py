"""A serving step on the device, taken apart by KIND OF STEP and by
REGION: what the per-layer metrics `chunk_*` and `decode_step_device_ms`
read, and what `tools/trace_by_scope.py` prints.

The program names every op of `jit_paged_step` with one innermost region
of `paddle_tpu.observability.tracing.STEP_REGIONS` (REGIONS below is that
tuple: this file also runs laid over a checkout that has no such tuple,
and a test holds the two equal). The name is a path component of the
op's `tf_op` (`lib/xplane.py`), e.g.
`jit(paged_step)/jit(packed_paged_layer)/rows_before/while/body/kv_write/scatter`.

- **Steps by kind.** `annotations.step_windows` joins the k-th
  `jit_paged_step` module event to the k-th `serve.dispatch w..c<slab>`;
  a slab one column wide is a DECODE step, any wider a CHUNK step, as
  `serve_step_kind_seconds{kind}` tells them apart in these cells. An op
  belongs to the step inside whose program it starts.
- **Self time.** The `XLA Ops` line holds a `%while` event that covers
  its body's ops, so an op's time is its duration less the ops nested in
  it (`nest`); self times add up to the program's busy time, and what is
  left of the module event is the device's idle time inside the program.
- **Region.** The innermost REGIONS component of the op's own `tf_op`;
  else (a copy the compiler put in, a fusion that kept no metadata) the
  region of the nearest op it runs inside that has one; else `unnamed`.
  A `%while` event comes without a `tf_op` on the chip; its own is read
  back from its body's ops, whose `tf_op`s begin with it (`loop_scopes`).
  XLA's grouped-product kernels (`%ragged-dot-*`) keep no `tf_op` and go
  to `moe_experts` by name, as `moe_share_pct.mixedlen` takes them.
- **The scatter rule.** The v5e compiler leaves the cache writer's
  scatter without metadata: in a wide step it sits in the layer's first
  row-tile loop (`rows_before`: projection, rope, the cache append, the
  q pack), in a narrow chunk step at the program's top level; one of
  the sixteen of a `chat` step carries the loop's own `tf_op`
  (`.../rows_before/while`). So an op that nothing names more closely
  than `rows_before`, or that nothing names at all, and whose
  instruction is an in-place scatter of new rows into a buffer is the
  writer's: a `fusion` whose result has the shape of
  one of its own operands (the buffer, updated where it lies), beside an
  `s32[n]` operand (the row indices) and an operand of the result's
  element type whose leading dimension is n (the new rows). It is
  counted under `kv_write` (`is_cache_scatter`). The experts'
  scatter-add is shaped alike and inherits `moe_slabs`, which the rule
  leaves alone. Everything else that a loop's name is all there is to
  say of stays under `rows_before` / `rows_after`, which are rows of
  their own in every table, and counts as unnamed in
  `chunk_unnamed_pct`.

One parse of the trace a run: `table(ctx)` is memoised on the xplane's
path, and the seven readers share it. A program that says nothing (no
`tf_op` names a region) gives the step times and None for the regions.
"""
import collections
import functools
import re
import statistics
import sys
import time

import annotations
import trace as xtrace
import xplane

REGIONS = (
    "embed", "rows_before", "qkv_proj", "rope", "kv_write", "q_pack",
    "attention", "rows_after", "out_proj", "ffn", "moe_route",
    "moe_experts", "moe_slabs", "head", "sampler")
UNNAMED = "unnamed"
# the row of a metric -> the regions it sums
GROUPS = {
    "proj": ("embed", "qkv_proj", "rope", "q_pack", "out_proj"),
    "kv_write": ("kv_write",),
    "attn": ("attention",),
    "ffn": ("ffn", "moe_route", "moe_experts", "moe_slabs"),
    "head": ("head",),
    "sampler": ("sampler",),
    # no name but a loop's, or none at all
    "rest": (UNNAMED, "rows_before", "rows_after"),
}
DECODE_SLAB = 1
LEAST_STEPS = 10        # of a kind, as `step_ms.chunk.chat` asks

_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


# (both cached: a trace's ~10^6 events share a few thousand texts)
@functools.lru_cache(maxsize=None)
def named_region(scope):
    """The innermost REGIONS component of a `tf_op`, or None."""
    for part in reversed(scope.split("/")):
        if part in REGIONS:
            return part
    return None


@functools.lru_cache(maxsize=None)
def is_cache_scatter(text):
    """Whether an instruction's text (`%name = result fusion(operands),
    ...`) is shaped like an in-place scatter of new rows into a buffer:
    the module docstring's rule."""
    head, sep, rest = text.partition(" fusion(")
    if not sep:
        return False
    result = _SHAPE.findall(head.partition("=")[2])
    operands = _SHAPE.findall(rest.partition("), ")[0])
    if len(result) != 1 or result[0] not in operands:
        return False
    dtype = result[0][0]
    index_rows = {dims for dt, dims in operands
                  if dt == "s32" and dims and "," not in dims}
    return any(dt == dtype and (dt, dims) != result[0]
               and dims.split(",")[0] in index_rows
               for dt, dims in operands)


def nest(steps, ops):
    """Every op that starts inside a step, in order of time, as
    (step index, text, scope, dur_ns, self_ns, depth, index of the row
    of the op it runs inside or None): `steps` are sorted (start, end,
    width), `ops` (text, scope, start, dur)."""
    rows, stack, k, cur = [], [], 0, None
    for text, scope, s, d in sorted(ops, key=lambda o: (o[2], -o[3])):
        while k < len(steps) and steps[k][1] <= s:
            k += 1
        if k == len(steps):
            break
        if steps[k][0] > s:
            continue
        if cur != k:
            cur, stack = k, []
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            rows[stack[-1][1]][4] -= d
        stack.append((s + d, len(rows)))
        rows.append([k, text, scope, d, d, len(stack) - 1,
                     stack[-2][1] if len(stack) > 1 else None])
    return [(k, text, scope, d, max(own, 0), depth, up)
            for k, text, scope, d, own, depth, up in rows]


def loop_scopes(rows):
    """{row index: `tf_op`} for the `%while` rows that have none of
    their own: the profiler's event of a loop carries no metadata (my
    chip run, PR 42), but its body's ops do, and their `tf_op`s begin
    with the loop's (`<loop>/while/body/...`), so the loop's is what
    all of them share, up to its last `/while`."""
    shared = {}
    for i in range(len(rows) - 1, -1, -1):      # children before parents
        _, text, scope, _, _, _, up = rows[i]
        scope = scope or shared.get(i, "")
        # (a kernel XLA put in names itself, `ragged-dot-none`: no loop's)
        if up is None or "/while/body/" not in scope or rows[up][2] \
                or not rows[up][1].startswith("%while"):
            continue
        old = shared.get(up)
        if old is None:
            shared[up] = scope
        else:
            n = next((k for k, (a, b) in enumerate(zip(old, scope))
                      if a != b), min(len(old), len(scope)))
            shared[up] = old[:n]
    return {i: s[:s.rfind("/while") + len("/while")]
            for i, s in shared.items() if "/while" in s}


def regions_of(rows):
    """The region of each of `nest`'s rows, in their order."""
    out, loops = [], loop_scopes(rows)
    for i, (_, text, scope, _, _, _, up) in enumerate(rows):
        if text.lstrip("%").startswith("ragged-dot"):
            region = "moe_experts"
        else:
            region = named_region(scope or loops.get(i, ""))
            if region is None:
                region = out[up] if up is not None else UNNAMED
            if region in ("rows_before", UNNAMED) and is_cache_scatter(text):
                region = "kv_write"
        out.append(region)
    return out


def kind_of(width):
    return "decode" if width == DECODE_SLAB else "chunk"


def reduce(steps, ops):
    """{kind: {steps, program_ms (mean module event), regions: {region:
    mean self ms a step}, groups: {GROUPS' row: ms a step}, sum_ms (all
    self time), idle_ms (program less sum), named (whether any op's own
    `tf_op` names a region)}} for the kinds that have a step."""
    steps = sorted(steps)
    rows = nest(steps, ops)
    return by_kind(steps, rows, regions_of(rows))


def by_kind(steps, rows, regions):
    """`reduce` over rows already nested and given their regions."""
    total = collections.defaultdict(lambda: collections.defaultdict(int))
    named = set()
    for (k, _, scope, _, own, _, _), region in zip(rows, regions):
        kind = kind_of(steps[k][2])
        total[kind][region] += own
        if named_region(scope) is not None:
            named.add(kind)
    out = {}
    for kind in ("decode", "chunk"):
        program = [(b - a) / 1e6 for a, b, c in steps if kind_of(c) == kind]
        if not program:
            continue
        ms = {region: ns / 1e6 / len(program)
              for region, ns in total[kind].items()}
        groups = {row: sum(ms.get(r, 0.0) for r in parts)
                  for row, parts in GROUPS.items()}
        out[kind] = dict(
            steps=len(program), program_ms=statistics.mean(program),
            regions=dict(sorted(ms.items(), key=lambda kv: -kv[1])),
            groups=groups, sum_ms=sum(ms.values()),
            idle_ms=statistics.mean(program) - sum(ms.values()),
            named=kind in named)
    return out


_TABLES = {}    # xplane path -> reduce()'s result: one parse a run


def table(ctx):
    """`reduce` over the run's own trace; {} where there is none."""
    try:
        path = xtrace.find_xplane(ctx["trace_dir"])
    except FileNotFoundError:
        return {}
    if path not in _TABLES:
        t0 = time.perf_counter()
        _TABLES.clear()
        _TABLES[path] = reduce(annotations.step_windows(ctx["trace"]),
                               xplane.scoped_ops(path))
        print(f"[step_regions] {path}: steps by kind and region in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
    return _TABLES[path]


def _kind(ctx, kind):
    row = table(ctx).get(kind)
    return row if row and row["steps"] >= LEAST_STEPS else None


def step_device_ms(ctx, kind):
    """Mean `jit_paged_step` module time of the slice's steps of `kind`;
    None under LEAST_STEPS of them."""
    row = _kind(ctx, kind)
    return row["program_ms"] if row else None


def group_ms(ctx, group, kind="chunk"):
    """Mean self ms a step of `kind` in the regions of GROUPS[group];
    None under LEAST_STEPS steps, from a program that names no region,
    or where no op of the steps lies in any of the group's regions."""
    row = _kind(ctx, kind)
    if not row or not row["named"]:
        return None
    if not any(r in row["regions"] for r in GROUPS[group]):
        return None
    return row["groups"][group]


def unnamed_pct(ctx, kind="chunk"):
    """GROUPS["rest"] over all self time of the steps of `kind`, in
    percent; None as `group_ms`."""
    row = _kind(ctx, kind)
    if not row or not row["named"] or not row["sum_ms"]:
        return None
    return 100.0 * row["groups"]["rest"] / row["sum_ms"]
