"""Cache blocks the window layers hold over the blocks the full layers
hold, each summed over the window's steps (registry:
serve_kv_block_steps_total{kind}): 100 would mean the window layers give
nothing back behind their window. None where the model keeps one block
table."""
import readers


def read(ctx):
    full = readers.counter_delta(ctx, "serve_kv_block_steps_total", "full")
    if not full:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_kv_block_steps_total", "window") / full
