"""What the per-layer readers share. A reader is `read(ctx) -> number or
None`; ctx is the run's context: `out` (the driver's records, facts and
counts), `trace` (plain data, see trace.py), `busy`, `peaks`, `config`,
`traffic`, `family`, `seconds`. A reader that finds nothing to read
returns None and the harness leaves its metric out of the line."""
import statistics

import trace as xtrace


def hist_gain(reg0, reg1, family, child=""):
    """(sum, count) a histogram child gained between two snapshots of
    the program's registry."""
    def get(snap):
        c = snap.get(family, {}).get("children", {}).get(child)
        return (c["sum"], c["count"]) if c else (0.0, 0)
    (s0, n0), (s1, n1) = get(reg0), get(reg1)
    return s1 - s0, n1 - n0


def counter_gain(reg0, reg1, family, child=""):
    def get(snap):
        c = snap.get(family, {}).get("children", {}).get(child)
        return c["value"] if c else 0.0
    return get(reg1) - get(reg0)


def hist_delta(ctx, family, child=""):
    """(sum, count) a histogram child of the program's registry gained
    inside the window."""
    f = ctx["out"]["facts"]
    return hist_gain(f["reg0"], f["reg1"], family, child)


def counter_delta(ctx, family, child=""):
    f = ctx["out"]["facts"]
    return counter_gain(f["reg0"], f["reg1"], family, child)


def window_steps(reg0, reg1):
    """The steps between two snapshots by kind, and by whether the step
    before was still in flight when each was dispatched: what the
    driver prints with every run's counts and the two regime readers
    (`chunk_step_share_pct.chat`, `steps_ahead_pct.chat`) divide."""
    return dict(
        steps={k: hist_gain(reg0, reg1, "serve_step_kind_seconds", k)[1]
               for k in ("decode", "chunk")},
        dispatched={k: counter_gain(reg0, reg1,
                                    "serve_steps_dispatched_total", k)
                    for k in ("ahead", "drained")})


def host_step_ms(ctx):
    """Mean host time of a serving step in the window: the scheduler's
    schedule, build, dispatch and commit phases (the fifth phase, fetch,
    is the wait for the device)."""
    total, steps = 0.0, 0
    for phase in ("schedule", "build", "dispatch", "commit"):
        s, n = hist_delta(ctx, "serve_host_phase_seconds", phase)
        total, steps = total + s, max(steps, n)
    return total / steps * 1e3 if steps else None


def module_ms(ctx, needle, stat=statistics.median):
    """Median device time of the executions of the program whose module
    name contains `needle` (the jitted function's own name)."""
    d = xtrace.event_durations(ctx["trace"], xtrace.MODULES_LINE, needle)
    return stat(d) * 1e3 if d else None


def spans_of(ctx, name):
    return [s for s in ctx["out"]["facts"].get("spans", [])
            if s["name"] == name]


def window_request_ids(ctx):
    tag = f"s{ctx['seed']}"
    return {f"{tag}-{r['index']}": r for r in ctx["out"]["records"]
            if r["phase"] == "window"}


def roofline_pct(ctx, ops, nbytes, seconds, dtype="bfloat16"):
    """Least time the chip could take for (ops, bytes) over the time it
    took, in percent, and which bound applies."""
    p = ctx["peaks"]
    t_ops = ops / p["flops_per_s"][dtype]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound


def op_share_pct(ctx, *needles):
    """Share of the device's busy time in the traced window spent in the
    `XLA Ops` events whose name contains every needle, in percent."""
    planes = xtrace.device_planes(ctx["trace"])
    line = xtrace.line_of(planes[0], xtrace.OPS_LINE) if planes else None
    if line is None:
        return None
    hit = [(s, s + d) for n, s, d in line["events"]
           if all(x in n for x in needles)]
    busy = xtrace.union_ns([(s, s + d) for _, s, d in line["events"]])
    if not hit or not busy:
        return None
    return 100.0 * xtrace.union_ns(hit) / busy
