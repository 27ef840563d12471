"""Programs the persistent compilation cache did not hold (0 from the
second run in a checkout on)."""


def read(ctx):
    return float(ctx["compile_total"]["cache_misses"])
