"""Mean wait of a request for the step in flight: `stepper.submit()`
called by the gateway with a validated request, to `engine.submit()` run
between two steps on the stepper thread (registry:
gateway_handoff_seconds, sum over count of the window)."""
import annotations


def read(ctx):
    return annotations.hist_mean_ms(ctx, "gateway_handoff_seconds")
