"""How full the rows the chunk steps' row-wise layers computed ran: live
tokens (the slots' q_lens) over `capacity`, summed over the window's
chunk steps (registry: serve_slab_tokens_total{kind}). Since PR 29
`capacity` is ceil(live / 256) x 256 for a slab wider than ROW_TILE = 256
rows, which is packed to its live tokens on the device, and max_batch x
width for a narrower one: the computed tiles, not the padded [max_batch,
width] slab (43.5-44.2% where the padded reading was 7.6-8.5)."""
import readers


def read(ctx):
    capacity = readers.counter_delta(ctx, "serve_slab_tokens_total",
                                     "capacity")
    if not capacity:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_slab_tokens_total", "live") / capacity
