"""Share of the device's idle time in the traced window under none of the
stepper's annotated states: the instrumentation's own health (the states
tile the thread, so this is the trace's two edges and little else)."""
import annotations


def read(ctx):
    return annotations.idle_share_pct(ctx, ("unattributed",))
