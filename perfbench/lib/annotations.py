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


# the jitted step's own name, as the device's `XLA Modules` line has it
STEP_MODULE = "paged_step"


def step_windows(trace, device=0):
    """[(start_ns, end_ns, slab width)], one for each step whose program
    ran whole inside the trace: the interval is the step's own
    `jit_paged_step` event on the device's `XLA Modules` line, the width
    that of the `serve.dispatch w<work>c<slab>` that launched it. The
    two are joined in order, the k-th module event to the k-th dispatch:
    the device runs the steps in the order they were dispatched, and a
    program cannot start before the dispatch that launched it opened, so
    the module events that start before the trace's first dispatch (a
    step in flight when the profiler started) are stepped over. Not by
    the `serve.fetch` that follows a dispatch: since the scheduler looks
    one step ahead that fetch waits for the step BEFORE, and a window
    from dispatch to fetch held the tail of one step and the head of the
    next. A dispatch whose program the trace's edge cuts gets none."""
    planes = xtrace.device_planes(trace)
    line = xtrace.line_of(planes[device], xtrace.MODULES_LINE) \
        if device < len(planes) else None
    if line is None:
        return []
    modules = sorted((s, s + d) for n, s, d in line["events"]
                     if STEP_MODULE in n)
    out, k = [], 0
    for opened, _, name in stepper_events(trace):
        head, _, bucket = name.partition(" ")
        if head != "serve.dispatch" or not bucket:
            continue
        while k < len(modules) and modules[k][0] < opened:
            k += 1
        if k == len(modules):
            break
        out.append((*modules[k], int(bucket.rpartition("c")[2])))
        k += 1
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
