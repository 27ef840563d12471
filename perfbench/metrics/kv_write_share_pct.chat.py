"""Share of the device's busy time IN DECODE STEPS spent in ops under
the scope `kv_write`: since PR 25 the new rows stacked and one scatter
per layer into the donated cache buffer (3.4-3.7%); until then also the
cache's halves sliced out and stacked back, two whole-cache copies a
layer (52.6-57.7%). The scope is in the op's metadata, which
lib/xplane.py reads from the trace file itself; a decode step is one the
stepper thread dispatched with a slab one column wide (`serve.dispatch
w..c1`: chat decodes without drafts), and its ops are those that start
inside its own `jit_paged_step` program on the device
(`annotations.step_windows`: the k-th program is the k-th dispatch's,
whatever step the host was reading meanwhile). Over all steps
the share would follow the slice's mix of chunk and decode steps (a
chunk step is ten times a decode step, and its writer's scatter of the
whole slab carries no scope: PERF.md, Open questions), not the decode
writer."""
import annotations
import xplane

DECODE_SLAB = 1


def read(ctx):
    decode = [(a, b) for a, b, slab in annotations.step_windows(ctx["trace"])
              if slab == DECODE_SLAB]
    if not decode:
        return None
    return xplane.scope_share_pct(ctx["trace_dir"], "/kv_write/",
                                  within=decode)
