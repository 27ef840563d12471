"""Work entries (one cache block of one slot) a window layer's kernel
call visits over those a full layer's visits, each summed over the
window's steps (registry: serve_attn_entries_total{kind}): the window
bound on the kernel's grid. None where the model has one kind of
attention layer."""
import readers


def read(ctx):
    full = readers.counter_delta(ctx, "serve_attn_entries_total", "full")
    if not full:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_attn_entries_total", "window") / full
