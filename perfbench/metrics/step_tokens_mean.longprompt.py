"""Output tokens emitted per serving step, mean over the window
(registry: serve_tokens_total over the count of serve_step_seconds)."""
import readers


def read(ctx):
    toks = readers.counter_delta(ctx, "serve_tokens_total")
    _, steps = readers.hist_delta(ctx, "serve_step_seconds")
    return toks / steps if steps else None
