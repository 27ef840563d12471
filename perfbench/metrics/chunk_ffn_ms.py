"""Mean self time a CHUNK step spends in the feed-forward: the regions
`ffn` (its norm, the dense products, the residual), `moe_route`,
`moe_experts` with XLA's grouped-product kernels (`%ragged-dot-*`, by
name) and `moe_slabs` (the slab loop's own time), ms a step
(`lib/step_regions.py`); nothing under 10 chunk steps."""
import step_regions


def read(ctx):
    return step_regions.group_ms(ctx, "ffn")
