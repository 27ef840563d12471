"""Share of the serving steps' busy time on the device spent routing
tokens to experts and multiplying the held experts: ops under the scopes
`moe_route` (router scores in float32, the top-k, the weights) and
`moe_experts` (grouping by expert, gathers, the activation, the weighted
scatter back), and the grouped matrix products themselves, which XLA
rewrites into its own kernels (`%ragged-dot-metadata.N`,
`%ragged-dot-none.N`) and leaves without the scope they were traced
under: those are taken by name. Only ops that start inside a step's own
`jit_paged_step` program count, on both sides of the share
(`annotations.step_windows`). The scope is in the op's metadata, which
lib/xplane.py reads from the trace file; a program without experts (the
parent's, another family's) has neither scope nor kernel: nothing to
read, None."""
import annotations
import trace as xtrace
import xplane


def read(ctx):
    steps = sorted((a, b) for a, b, _ in
                   annotations.step_windows(ctx["trace"]))
    if not steps:
        return None
    try:
        ops = xplane.scoped_ops(xtrace.find_xplane(ctx["trace_dir"]))
    except FileNotFoundError:
        return None
    k, inside = 0, []
    for op in sorted(ops, key=lambda op: op[2]):
        while k < len(steps) and steps[k][1] <= op[2]:
            k += 1
        if k < len(steps) and steps[k][0] <= op[2]:
            inside.append(op)
    hit = [(s, s + d) for name, scope, s, d in inside
           if "/moe_" in scope or name.lstrip("%").startswith("ragged-dot")]
    busy = xtrace.union_ns([(s, s + d) for _, _, s, d in inside])
    if not hit or not busy:
        return None
    return 100.0 * xtrace.union_ns(hit) / busy
