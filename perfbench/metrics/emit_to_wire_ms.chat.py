"""Mean time of a token event from its emission on the stepper thread to
its SSE frame drained to the socket on the loop thread (registry:
gateway_emit_to_wire_seconds, one observation per token event)."""
import annotations


def read(ctx):
    return annotations.hist_mean_ms(ctx, "gateway_emit_to_wire_seconds")
