"""Chunk steps (some slot prefills) over all steps of the window, in
percent: the share of the gaps between tokens that a chunk step sets, so
how far `itl_ms.p95` sits from the edge between the two populations (at
5% the percentile steps from one to the other with the seed). Counts of
the registry's serve_step_kind_seconds{kind}; nothing under 10 steps.

Why lower is better, though the share rises with the offered rate: a
cell's traffic is fixed, so between a PR and its parent the same prompt
tokens arrive at the same times, and a lower share means they were
prefilled in fewer steps (more slots' chunks sharing one step, a wider
chunk), so fewer gaps between tokens are a chunk step's. Between two
rates it says which regime the cell is in and judges nothing."""
import readers

LEAST_STEPS = 10


def read(ctx):
    _, chunk = readers.hist_delta(ctx, "serve_step_kind_seconds", "chunk")
    _, decode = readers.hist_delta(ctx, "serve_step_kind_seconds", "decode")
    steps = chunk + decode
    return 100.0 * chunk / steps if steps >= LEAST_STEPS else None
