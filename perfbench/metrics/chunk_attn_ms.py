"""Mean self time a CHUNK step spends in the region `attention`: the
ragged kernel, the gather that lays the packed q rows into the slab's
geometry, a window layer's work list, and `tile_rows` reading each row
tile's ctx rows out of the kernel's tiles, ms a step
(`lib/step_regions.py`); nothing under 10 chunk steps."""
import step_regions


def read(ctx):
    return step_regions.group_ms(ctx, "attn")
