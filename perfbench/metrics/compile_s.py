"""Seconds jax spent tracing, lowering and compiling in the whole run
(jax.monitoring durations; nested traces count in their callers too)."""


def read(ctx):
    c = ctx["compile_total"]
    return c["trace_s"] + c["lower_s"] + c["compile_s"]
