"""Mean self time a CHUNK step spends appending to the paged cache:
ops of the region `kv_write`, and the fusions without metadata that are
shaped like the writer's scatter, in a wide step's first row-tile loop
or at a narrow step's top level (`lib/step_regions.py`, the scatter
rule), ms a step; nothing under 10 chunk steps. `kv_write_share_pct.chat` reads decode steps
only."""
import step_regions


def read(ctx):
    return step_regions.group_ms(ctx, "kv_write")
