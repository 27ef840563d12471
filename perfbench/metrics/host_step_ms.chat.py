"""Mean host time per serving step in the window (registry:
serve_host_phase_seconds, phases schedule + build + dispatch + commit)."""
import readers


def read(ctx):
    return readers.host_step_ms(ctx)
