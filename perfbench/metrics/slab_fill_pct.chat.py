"""How full the chunk steps' padded [max_batch, width] token slab ran:
live tokens (the slots' q_lens) over capacity, summed over the window's
chunk steps (registry: serve_slab_tokens_total{kind})."""
import readers


def read(ctx):
    capacity = readers.counter_delta(ctx, "serve_slab_tokens_total",
                                     "capacity")
    if not capacity:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_slab_tokens_total", "live") / capacity
