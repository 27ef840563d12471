"""Median device time of the train step program's executions in the
traced window (`XLA Modules` lane, module named after `step`)."""
import readers


def read(ctx):
    return readers.module_ms(ctx, "jit_step")
