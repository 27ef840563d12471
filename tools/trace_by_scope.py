#!/usr/bin/env python
"""A traced serving run's device time by KIND OF STEP, so that the table
comes back from the chip and not the trace (a traced 4 s of serving is a
55-125 MB xplane, more than `chiprun_out/` brings back).

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds 48 --trace 1
    JAX_PLATFORMS=cpu python3 tools/trace_by_scope.py .perfbench/trace

The per-layer metrics share a slice out over ALL its steps, and a slice
is whatever mix of decode and chunk steps its four seconds hold. Here
every op that starts inside a step's own `jit_paged_step` program is
given to that step, the steps are grouped by the slab width of the
`serve.dispatch w<work>c<slab>` that launched them (c1: decode; the
widest: the chunk step), and an op's SELF time (its duration less the
ops nested in it: a `%while` holds its body's ops) goes to its scope.
Printed, one JSON object a line: per width the steps, the program's mean
/ median / least / longest ms and the mean ms a step by scope; then for
the decode width and the widest, the ops by total time with their depth,
the op they lie in, calls a step and mean / median / p10 / p90 ms.

Reads the trace with the benchmark's own readers (`perfbench/lib`:
`trace.load`, `annotations.step_windows`, `xplane.scoped_ops`).
"""
import argparse
import collections
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))

# scope needle -> the row it is counted under, first match wins
SCOPES = (("/moe_route", "moe_route"), ("/moe_experts", "moe_experts"),
          ("/kv_write", "kv_write"), ("/attention", "attention"),
          ("/ffn", "ffn"), ("/head", "head"), ("/sampler", "sampler"))


def kind(text, scope):
    """The row an op's self time goes to: XLA's grouped-product kernels
    by name (they keep no scope), then the innermost-known scope, then a
    loop's own time, then `no scope`."""
    name = text.lstrip("%")
    if name.startswith("ragged-dot"):
        return "grouped product"
    for needle, row in SCOPES:
        if needle in scope:
            return row
    return "while (own)" if name.startswith("while") else "no scope"


def nest(steps, ops):
    """[(step index, text, scope, dur_ns, self_ns, depth, name of the op
    above or '')] for every op that starts inside a step: `steps` are
    sorted (start, end, width), `ops` (text, scope, start, dur)."""
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
        row = [s + d, k, text, scope, d, d, len(stack),
               stack[-1][2].split(" ")[0] if stack else ""]
        if stack:
            stack[-1][5] -= d
        rows.append(row)
        stack.append(row)
    return [(k, text, scope, d, max(own, 0), depth, up)
            for _, k, text, scope, d, own, depth, up in rows]


def reduce(steps, ops, top=45):
    """{"widths": [per slab width: steps, program ms, ms a step by
    scope], "ops": {width: [the ops by total time]}} for the decode
    width and the widest."""
    steps = sorted(steps)
    rows = nest(steps, ops)
    by_step = collections.defaultdict(lambda: collections.defaultdict(int))
    table = collections.defaultdict(list)
    for k, text, scope, d, own, depth, up in rows:
        by_step[k][kind(text, scope)] += own
        table[(steps[k][2], depth, text.split(" ")[0], up)].append(
            (d, text, scope))
    widths = sorted({c for _, _, c in steps})
    out = {"widths": [], "ops": {}}
    for c in widths:
        ks = [k for k in range(len(steps)) if steps[k][2] == c]
        program = [(steps[k][1] - steps[k][0]) / 1e6 for k in ks]
        scopes = collections.defaultdict(float)
        for k in ks:
            for row, ns in by_step[k].items():
                scopes[row] += ns / 1e6 / len(ks)
        out["widths"].append(dict(
            c=c, steps=len(ks), program_mean=statistics.mean(program),
            program_median=statistics.median(program),
            program_min=min(program), program_max=max(program),
            by_scope=dict(sorted(scopes.items(), key=lambda kv: -kv[1]))))
    for c in {widths[0], widths[-1]} if widths else ():
        n = sum(1 for s in steps if s[2] == c)
        best = sorted(((key, v) for key, v in table.items() if key[0] == c),
                      key=lambda kv: -sum(x[0] for x in kv[1]))[:top]
        out["ops"][c] = []
        for (_, depth, name, up), v in best:
            ms = sorted(x[0] / 1e6 for x in v)
            out["ops"][c].append(dict(
                op=name, depth=depth, within=up, calls_a_step=len(v) / n,
                total=sum(ms), mean=statistics.mean(ms),
                median=statistics.median(ms), p10=ms[len(ms) // 10],
                p90=ms[len(ms) * 9 // 10], scope=v[0][2][-60:],
                text=v[0][1][:110]))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a traced run's .perfbench/trace")
    ap.add_argument("--top", type=int, default=45)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "perfbench", "lib"))
    import annotations
    import trace as xtrace
    import xplane
    path = xtrace.find_xplane(args.trace_dir)
    trace = xtrace.load(path, keep_lines=lambda plane, line: (
        plane.startswith(xtrace.DEVICE_PREFIX)
        and line in (xtrace.OPS_LINE, xtrace.MODULES_LINE)
    ) or plane.startswith(xtrace.HOST_PREFIX))
    out = reduce(annotations.step_windows(trace), xplane.scoped_ops(path),
                 args.top)
    r = lambda v: round(v, 4) if isinstance(v, float) else v
    for w in out["widths"]:
        print(json.dumps({k: ({n: r(x) for n, x in v.items()}
                              if isinstance(v, dict) else r(v))
                          for k, v in w.items()}))
    for c, ops in sorted(out["ops"].items()):
        for op in ops:
            print(json.dumps(dict(c=c, **{k: r(v) for k, v in op.items()})))
    return 0


if __name__ == "__main__":
    sys.exit(main())
