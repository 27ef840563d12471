"""Steps dispatched while the step before them was still in flight, over
all steps dispatched in the window, in percent: a step dispatched into a
drained device (a lull, or a scheduler that reads each step before it
builds the next) pays the host's turn as a gap between tokens. Registry:
serve_steps_dispatched_total{mode=ahead|drained}; nothing under 10
steps, and nothing from a program without the counter."""
import readers

LEAST_STEPS = 10


def read(ctx):
    ahead = readers.counter_delta(ctx, "serve_steps_dispatched_total",
                                  "ahead")
    steps = ahead + readers.counter_delta(
        ctx, "serve_steps_dispatched_total", "drained")
    return 100.0 * ahead / steps if steps >= LEAST_STEPS else None
