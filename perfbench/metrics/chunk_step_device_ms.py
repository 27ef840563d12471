"""Mean device time of a CHUNK step's `jit_paged_step` program in the
traced slice (a slab wider than one column: some slot prefills), from
the device's `XLA Modules` line joined to the host's `serve.dispatch
w..c..` (`annotations.step_windows`); nothing under 10 chunk steps. The
step that sets `itl_ms.p95`, on the device's own clock, where
`paged_step_device_ms.chat` is a median over all buckets (a decode
step's) and `step_ms.chunk.chat` the host's cadence of the same steps."""
import step_regions


def read(ctx):
    return step_regions.step_device_ms(ctx, "chunk")
