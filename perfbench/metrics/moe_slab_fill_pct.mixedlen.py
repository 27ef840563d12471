"""How full the rows of the experts' grouped products ran: the
assignments that fell on a held expert (registry:
serve_moe_assignments_total{where=here}) over the rows the products were
handed (serve_moe_slab_rows_total: whole slabs of the sorted held
assignments, as many as they fill, none where nothing fell here), each
summed over the window's steps, expert layers and row tiles. The
window's reading is the decode steps' more than the chunk steps': nine
steps in ten are decode steps, whose few held assignments a layer (about
two, from four live rows) still take a whole slab of 16 x 8 rows, so a
chunk tile's fill (a quarter to a half) moves the share little. None for
a program without the counter (the parent's, another family's)."""
import readers


def read(ctx):
    handed = readers.counter_delta(ctx, "serve_moe_slab_rows_total")
    if not handed:
        return None
    return 100.0 * readers.counter_delta(
        ctx, "serve_moe_assignments_total", "here") / handed
