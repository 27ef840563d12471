"""How full the ragged kernel's query sub-tiles ran on the chunk steps:
the live query rows of the work lists' entries (the rows whose query
sees some of the entry's cache block) over the rows of the sub-tiles the
kernel multiplied for them, summed over the window's chunk steps
(registry: serve_attn_rows_total{kind})."""
import readers


def read(ctx):
    visited = readers.counter_delta(ctx, "serve_attn_rows_total", "visited")
    if not visited:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_attn_rows_total", "live") / visited
