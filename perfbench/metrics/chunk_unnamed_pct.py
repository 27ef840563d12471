"""Share of a CHUNK step's self time on the device that no region
names: ops under `unnamed`, and those that only a row-tile loop's name
reaches (`rows_before`, `rows_after`: the loops' own bookkeeping and
fusions that kept no metadata and that no rule of `lib/step_regions.py`
names), over all self time of the slice's chunk steps, in percent;
nothing under 10 chunk steps or from a program that names no region."""
import step_regions


def read(ctx):
    return step_regions.unnamed_pct(ctx)
