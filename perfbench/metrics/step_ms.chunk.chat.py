"""Mean chunk step (some slot prefills), dispatch to tokens on the host:
the step the tail of the gap between tokens follows (registry:
serve_step_kind_seconds{kind=chunk}); nothing under 10 chunk steps."""
import annotations


def read(ctx):
    return annotations.hist_mean_ms(ctx, "serve_step_kind_seconds",
                                    "chunk", least=10)
