"""95th percentile of submit-to-admission over the window's requests,
from the scheduler's `queue_wait` spans."""
import measure
import readers


def read(ctx):
    mine = readers.window_request_ids(ctx)
    waits = [s["dur_us"] / 1e3 for s in readers.spans_of(ctx, "queue_wait")
             if s["request"] in mine]
    return measure.percentile(waits, 95) if len(waits) >= 20 else None
