"""What the program says of itself, read back for the per-layer metrics:
the host states it annotates into the profiler's trace
(`paddle_tpu.observability.tracing.annotation` / `PhaseMarks`: the
stepper thread is always in exactly one of `STATES`), laid against the
device's idle gaps; and means of its registry's histograms over the
window. With a program that says nothing (no annotation in the trace, no
such family in the registry) every function returns None.
"""
import readers
import trace as xtrace

# the host's own work of one scheduler turn: the chip waits for it
HOST_STEP = ("stepper.commands", "serve.schedule", "serve.build",
             "serve.dispatch", "serve.commit")
# no work, and the wait for the device: the host waits here
WAITS = ("stepper.idle", "serve.fetch")
STATES = HOST_STEP + WAITS


def state_of(name):
    """`serve.dispatch w512c128` -> `serve.dispatch`; None for any event
    that is not one of the stepper's states (`serve.telemetry`, nested in
    `serve.commit`, among them)."""
    head = name.split(" ", 1)[0]
    return head if head in STATES else None


def stepper_events(trace):
    """[(start_ns, end_ns, name)] in order of time: the `serve.*` and
    `stepper.*` events of the one host line that holds the stepper's
    states (found by `serve.schedule`), names whole."""
    for plane in trace["planes"]:
        if not plane["name"].startswith(xtrace.HOST_PREFIX):
            continue
        for line in plane["lines"]:
            rows = [(s, s + d, n) for n, s, d in line["events"]
                    if n.startswith(("serve.", "stepper."))]
            if any(r[2] == "serve.schedule" for r in rows):
                return sorted(rows)
    return []


def stepper_states(trace):
    """[(start_ns, end_ns, state)] in order of time: the stepper's
    events that are states, each under its state's name."""
    return [(a, b, state) for a, b, n in stepper_events(trace)
            if (state := state_of(n)) is not None]


def step_windows(trace):
    """[(start_ns, end_ns, slab width)], one for each step that lies
    whole in the trace: from the opening of its `serve.dispatch
    w<work>c<slab>` to the close of the `serve.fetch` of the same bucket
    that follows it. The scheduler is synchronous (a step's tokens are
    on the host before the next step is built), so the device ran this
    step's program, and no other step's, inside the interval. A step the
    trace's edge cuts is left out."""
    out, opened = [], None
    for a, b, n in stepper_events(trace):
        head, _, bucket = n.partition(" ")
        if head == "serve.dispatch" and bucket:
            opened = (a, bucket)
        elif head == "serve.fetch" and opened and opened[1] == bucket:
            out.append((opened[0], b, int(bucket.rpartition("c")[2])))
            opened = None
    return out


def idle_by_state(trace, device=0):
    """Seconds of one device's idle time — the gaps between its `XLA Ops`
    over their own span, as `trace.busy` takes it — under each of the
    stepper's states: {state: s, ..., "unattributed": s, "idle": s}.
    None without annotations or ops."""
    states = stepper_states(trace)
    planes = xtrace.device_planes(trace)
    ops = xtrace.line_of(planes[device], xtrace.OPS_LINE) \
        if device < len(planes) else None
    if not states or ops is None or not ops["events"]:
        return None
    iv = [(s, s + d) for _, s, d in ops["events"]]
    gaps = xtrace.gaps_ns(iv, min(s for s, _ in iv), max(e for _, e in iv))
    under = dict.fromkeys(STATES, 0)
    k = 0
    for g0, g1 in gaps:
        while k < len(states) and states[k][1] <= g0:
            k += 1
        j = k
        while j < len(states) and states[j][0] < g1:
            cover = min(states[j][1], g1) - max(states[j][0], g0)
            if cover > 0:
                under[states[j][2]] += cover
            j += 1
    idle = sum(b - a for a, b in gaps)
    under["unattributed"] = idle - sum(under.values())
    under["idle"] = idle
    return {state: ns / 1e9 for state, ns in under.items()}


def idle_share_pct(ctx, states):
    """Share of the device's idle time in the traced window that lies
    under `states` (names of STATES, or "unattributed"), in percent."""
    by = idle_by_state(ctx["trace"])
    if by is None or not by["idle"]:
        return None
    return 100.0 * sum(by[s] for s in states) / by["idle"]


def hist_mean_ms(ctx, family, child="", least=1):
    """Mean of what a histogram of the program's registry gained inside
    the window, in ms; None under `least` observations."""
    total, n = readers.hist_delta(ctx, family, child)
    return total / n * 1e3 if n >= least else None
