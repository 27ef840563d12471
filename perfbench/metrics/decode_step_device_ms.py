"""Mean device time of a DECODE step's `jit_paged_step` program in the
traced slice (a slab one column wide), as `chunk_step_device_ms` reads
the chunk steps: what `paged_step_device_ms.chat`'s median over all
buckets approximates; nothing under 10 decode steps."""
import step_regions


def read(ctx):
    return step_regions.step_device_ms(ctx, "decode")
