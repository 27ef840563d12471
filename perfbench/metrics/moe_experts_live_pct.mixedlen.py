"""Held experts that got a token over held experts whose weights the
step multiplied, summed over the window's steps and expert layers
(registry: serve_moe_experts_total{state}). An expert that is computed
without a token streams 50 MB of weights for nothing; a program that
skips them reads 100. None where no step routed anything."""
import readers


def read(ctx):
    computed = readers.counter_delta(ctx, "serve_moe_experts_total",
                                     "computed")
    if not computed:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_moe_experts_total", "touched") / computed
