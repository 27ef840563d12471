"""Median device time of the paged step program's executions in the
traced window (`XLA Modules` lane, module named after `paged_step`)."""
import readers


def read(ctx):
    return readers.module_ms(ctx, "paged_step")
