"""Share of the device's idle time in the traced window that lies under
the host's own work of a scheduler turn on the stepper thread: commands,
schedule, build, dispatch, commit (the program's profiler annotations)."""
import annotations


def read(ctx):
    return annotations.idle_share_pct(ctx, annotations.HOST_STEP)
