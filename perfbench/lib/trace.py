"""From a profiler trace to numbers: busy and idle time of a device,
the time of named programs and kernels, and the longest idle gaps.

A trace is first read into plain data — `{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}` — by `load()`,
from the profiler's `.xplane.pb` (or from a `.json` / `.json.gz` dump of
the same structure, which is how the recorded trace beside the tests is
kept). Planes and lines are found by NAME: a device plane is one whose
name starts with `/device:TPU:`, its ops lie on the line named `XLA Ops`
and its programs on `XLA Modules`; nothing here knows a pid.
"""
import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, keep_lines=None):
    """Read a trace into plain data. `keep_lines(plane, line)` may drop
    lines while reading (a serving trace has millions of host events)."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_lines is not None and not keep_lines(plane.name,
                                                         line.name):
                continue
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def line_of(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo, hi):
    """The idle gaps of [lo, hi) left by the union of the intervals, as
    (start, end) pairs."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def busy(trace, lo_ns=None, hi_ns=None):
    """Per device plane: seconds in which an op ran, over [lo, hi) (the
    span of the plane's own ops where not given). Returns
    dict(busy_s=mean over planes, window_s, per_device=[...])."""
    per, window = [], None
    for plane in device_planes(trace):
        ops = line_of(plane, OPS_LINE)
        if ops is None or not ops["events"]:
            continue
        iv = [(s, s + d) for _, s, d in ops["events"]]
        lo = min(s for s, _ in iv) if lo_ns is None else lo_ns
        hi = max(e for _, e in iv) if hi_ns is None else hi_ns
        iv = [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]
        per.append(union_ns(iv) / 1e9)
        window = (hi - lo) / 1e9 if window is None else max(
            window, (hi - lo) / 1e9)
    if not per:
        return None
    return {"busy_s": sum(per) / len(per), "window_s": window,
            "per_device": per}


def event_durations(trace, line_name, needle, device=0):
    """Durations in seconds of the events on `line_name` of one device
    plane whose name contains `needle`."""
    planes = device_planes(trace)
    if device >= len(planes):
        return []
    line = line_of(planes[device], line_name)
    if line is None:
        return []
    return [d / 1e9 for n, _, d in line["events"] if needle in n]


def top_ops(trace, n=10, device=0):
    """[[name, seconds], ...]: the device ops that took most time."""
    planes = device_planes(trace)
    if device >= len(planes):
        return []
    line = line_of(planes[device], OPS_LINE)
    if line is None:
        return []
    total = {}
    for name, _, d in line["events"]:
        total[name] = total.get(name, 0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def host_activity(trace, start_ns, end_ns, exclude=()):
    """Name of the host event that covers most of [start, end): what the
    host was doing while the device sat idle."""
    best, best_cover = "unattributed", 0
    for plane in trace["planes"]:
        if not plane["name"].startswith(HOST_PREFIX):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                cover = min(s + d, end_ns) - max(s, start_ns)
                if cover > best_cover and not any(x in name for x in exclude):
                    best, best_cover = name, cover
    return best


def idle_gaps(trace, n=10, device=0, lo_ns=None, hi_ns=None):
    """[[what the host was doing, seconds], ...] for the longest idle
    gaps of one device, merged by host activity."""
    planes = device_planes(trace)
    if device >= len(planes):
        return []
    ops = line_of(planes[device], OPS_LINE)
    if ops is None or not ops["events"]:
        return []
    iv = [(s, s + d) for _, s, d in ops["events"]]
    lo = min(s for s, _ in iv) if lo_ns is None else lo_ns
    hi = max(e for _, e in iv) if hi_ns is None else hi_ns
    gaps = sorted(gaps_ns(iv, lo, hi), key=lambda g: g[0] - g[1])[:200]
    by = {}
    for s, e in gaps:
        what = host_activity(trace, s, e)
        by[what] = by.get(what, 0) + (e - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def exposed_seconds(trace, needle, device=0):
    """Seconds of ops matching `needle` (collectives) during which no
    other op ran on that device."""
    planes = device_planes(trace)
    if device >= len(planes):
        return None
    ops = line_of(planes[device], OPS_LINE)
    if ops is None:
        return None
    coll = [(s, s + d) for n, s, d in ops["events"] if needle in n]
    other = [(s, s + d) for n, s, d in ops["events"] if needle not in n]
    if not coll:
        return None
    both = union_ns(coll + other)
    return (both - union_ns(other)) / 1e9


def summary(trace):
    """Names by hand: planes, lines, and each line's commonest events —
    what to look at before writing a reader against a new trace."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names = {}
            for n, _, d in line["events"]:
                c = names.setdefault(n, [0, 0])
                c[0] += 1
                c[1] += d
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]),
                        "top": [[k, v[0], v[1] / 1e9] for k, v in top]})
    return out


def _main(argv):
    """python trace.py <trace dir> <out.json> [slice seconds]: the names
    in a trace, and optionally a short slice of it as plain data."""
    import sys
    t = load(find_xplane(argv[0]))
    out = {"summary": summary(t)}
    with open(argv[1], "w") as f:
        json.dump(out, f)
    for row in out["summary"]:
        print(row["plane"], "|", row["line"], "|", row["events"], "events")
        for name, n, sec in row["top"][:6]:
            print(f"    {name[:100]}  x{n}  {sec:.4f} s")
    if len(argv) > 2:
        dev = device_planes(t)
        ops = line_of(dev[0], OPS_LINE)["events"]
        lo = ops[len(ops) // 2][1]
        hi = lo + int(float(argv[2]) * 1e9)
        keep = lambda e: e[1] >= lo and e[1] + e[2] <= hi
        out["slice"] = {"planes": [
            {"name": p["name"], "lines": [
                {"name": l["name"], "events": [e for e in l["events"]
                                               if keep(e)]}
                for l in p["lines"]]} for p in t["planes"]]}
        with open(argv[1], "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    import sys
    _main(sys.argv[1:])
