"""Client time from sending a request to its first token, less the
engine's own submit-to-first-token for the same request id (the
`first_token` span): what gateway, SSE and the stepper hand-off add."""
import statistics

import readers


def read(ctx):
    engine = {s["request"]: s["args"].get("ttft_s")
              for s in readers.spans_of(ctx, "first_token")}
    gaps = []
    for rid, r in readers.window_request_ids(ctx).items():
        if rid in engine and engine[rid] is not None and r["events"]:
            client = r["events"][0][0] - r["sent"]
            gaps.append((client - engine[rid]) * 1e3)
    return statistics.median(gaps) if len(gaps) >= 10 else None
