"""Mean decode step as the scheduler waits for it, dispatch to tokens on
the host (registry: serve_step_kind_seconds{kind=decode}: the slab no
wider than 1 + spec_k and no slot prefilling)."""
import annotations


def read(ctx):
    return annotations.hist_mean_ms(ctx, "serve_step_kind_seconds",
                                    "decode")
