"""Share of the device's busy time in the traced window spent in the
ragged paged-attention kernel. The kernel has no name of its own yet: its
events are the Mosaic custom calls of the program the source names
`paged_step` (`%paged_step.N = ... custom-call(...)`)."""
import readers


def read(ctx):
    return readers.op_share_pct(ctx, "%paged_step", " custom-call(")
