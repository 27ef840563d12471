"""Steps the stepper made in the whole run: a count, read from the
driver's facts. Stands for the per-layer metric a later PR appends."""


def read(ctx):
    return float(ctx["out"]["facts"]["engine"]["steps"])
