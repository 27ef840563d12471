"""A traced serving run's device time by KIND OF STEP, so that the table
comes back from the chip and not the trace (a traced 4 s of serving is a
55-125 MB xplane, more than `chiprun_out/` brings back).

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds 48 --trace 1
    JAX_PLATFORMS=cpu python3 tools/trace_by_scope.py .perfbench/trace

The run's own line already carries a chunk step and a decode step by
region (`chunk_step_device_ms`, `chunk_proj_ms`, `chunk_kv_write_ms`,
`chunk_attn_ms`, `chunk_ffn_ms`, `chunk_unnamed_pct`,
`decode_step_device_ms`); this prints what lies under them, with the
same reader (`perfbench/lib/step_regions.py`: an op belongs to the step
inside whose `jit_paged_step` program it starts, its SELF time, its
duration less the ops nested in it, goes to its region, and an op
without metadata takes the region of the op it runs inside). Printed,
one JSON object a line: per kind of step (decode: a slab one column
wide; chunk: any wider) the steps, the program's mean ms, the mean ms a
step by region and by the metrics' groups, their sum and the idle time
inside the program; per slab width the steps, the program's mean /
median / least / longest ms and the mean ms a step by region; then for
the decode width and the widest, the ops by total time with their
region, depth, the op they lie in, calls a step and mean / median / p10
/ p90 ms; the widest width's ops that no region but a loop's names
(`rest`), whole instruction texts; last the stepper thread's own
annotations (`host`: ms a dispatched step under each name, and
`serve.telemetry`, which is nested in `serve.commit`, as a share of it:
what the step's counters cost the host).

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
sys.path.insert(0, os.path.join(ROOT, "perfbench", "lib"))

import step_regions  # noqa: E402


def reduce(steps, ops, top=45):
    """{"kinds": step_regions' table, "widths": [per slab width: steps,
    program ms, ms a step by region], "ops": {width: [the ops by total
    time]}, "rest": [the widest width's ops under no region but a
    loop's]} for the decode width and the widest."""
    steps = sorted(steps)
    rows = step_regions.nest(steps, ops)
    regions = step_regions.regions_of(rows)
    by_step = collections.defaultdict(lambda: collections.defaultdict(int))
    table = collections.defaultdict(list)
    for (k, text, scope, d, own, depth, up), region in zip(rows, regions):
        by_step[k][region] += own
        table[(steps[k][2], depth, text.split(" ")[0],
               rows[up][1].split(" ")[0] if up is not None else "")
              ].append((d, own, text, scope, region))
    widths = sorted({c for _, _, c in steps})
    out = {"kinds": step_regions.by_kind(steps, rows, regions),
           "widths": [], "ops": {}, "rest": []}
    for c in widths:
        ks = [k for k in range(len(steps)) if steps[k][2] == c]
        program = [(steps[k][1] - steps[k][0]) / 1e6 for k in ks]
        by = collections.defaultdict(float)
        for k in ks:
            for row, ns in by_step[k].items():
                by[row] += ns / 1e6 / len(ks)
        out["widths"].append(dict(
            c=c, steps=len(ks), program_mean=statistics.mean(program),
            program_median=statistics.median(program),
            program_min=min(program), program_max=max(program),
            by_region=dict(sorted(by.items(), key=lambda kv: -kv[1]))))
    for c in {widths[0], widths[-1]} if widths else ():
        n = sum(1 for s in steps if s[2] == c)
        mine = [(key, v) for key, v in table.items() if key[0] == c]
        best = sorted(mine, key=lambda kv: -sum(x[0] for x in kv[1]))[:top]
        out["ops"][c] = []
        for (_, depth, name, up), v in best:
            ms = sorted(x[0] / 1e6 for x in v)
            out["ops"][c].append(dict(
                op=name, region=v[0][4], depth=depth, within=up,
                calls_a_step=len(v) / n, total=sum(ms),
                mean=statistics.mean(ms), median=statistics.median(ms),
                p10=ms[len(ms) // 10], p90=ms[len(ms) * 9 // 10],
                scope=v[0][3][-60:], text=v[0][2][:110]))
        if c == widths[-1]:
            rest = [(key, v) for key, v in mine
                    if v[0][4] in step_regions.GROUPS["rest"]]
            for (_, depth, name, up), v in sorted(
                    rest, key=lambda kv: -sum(x[1] for x in kv[1]))[:top]:
                out["rest"].append(dict(
                    op=name, region=v[0][4], depth=depth, within=up,
                    calls_a_step=len(v) / n,
                    self_ms_a_step=sum(x[1] for x in v) / 1e6 / n,
                    scope=v[0][3], text=v[0][2][:400]))
    return out


def host_turn(events):
    """{"steps", "ms_a_step": {name: ms}, "telemetry_pct_of_commit"} of
    the stepper thread's events (`annotations.stepper_events`), a step
    being one `serve.dispatch`."""
    total = collections.defaultdict(int)
    for a, b, name in events:
        total[name.split(" ", 1)[0]] += b - a
    steps = sum(1 for _, _, n in events if n.startswith("serve.dispatch"))
    if not steps:
        return {}
    return dict(
        steps=steps,
        ms_a_step={n: ns / 1e6 / steps for n, ns in sorted(total.items())},
        telemetry_pct_of_commit=100.0 * total["serve.telemetry"]
        / total["serve.commit"] if total["serve.commit"] else None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a traced run's .perfbench/trace")
    ap.add_argument("--top", type=int, default=45)
    args = ap.parse_args(argv)
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

    def r(v):
        if isinstance(v, dict):
            return {k: r(x) for k, x in v.items()}
        return round(v, 4) if isinstance(v, float) else v
    for kind, row in out["kinds"].items():
        print(json.dumps(dict(kind=kind, **r(row))))
    for w in out["widths"]:
        print(json.dumps(r(w)))
    for c, ops in sorted(out["ops"].items()):
        for op in ops:
            print(json.dumps(dict(c=c, **r(op))))
    for op in out["rest"]:
        print(json.dumps(dict(rest=True, **r(op))))
    print(json.dumps(dict(host=r(host_turn(
        annotations.stepper_events(trace))))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
