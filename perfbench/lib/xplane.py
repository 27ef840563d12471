"""What `trace.py`'s plain data leaves out of an `.xplane.pb`: the scope
a device op ran under. An `XLA Ops` event's NAME is the HLO instruction's
text without its metadata; the `jax.named_scope` path (HLO `op_name`)
lies in the `tf_op` stat of the event's METADATA entry, e.g.
`jit(paged_step)/kv_write/concatenate:`, which `jax.profiler.ProfileData`
does not hand out. Two programs may name an instruction alike under
different scopes, so the scope is joined by metadata id, per event.

The file is a protobuf (tsl/profiler/protobuf/xplane.proto); this reads
the few fields it needs from the wire format and skips the rest, the
host planes' millions of events among them, without a generated module.

The one generated module for that schema in this installation is
`tensorflow.tsl.profiler.protobuf.xplane_pb2`, and importing it runs
`tensorflow/__init__`: 8.6 s and 4900 modules on the sandbox's CPU, and
TensorFlow's runtime loaded into the process that holds the chip through
jax. The hundred lines below stand for that dependency.
"""
import bisect

import trace as xtrace

SCOPE_STAT = "tf_op"


def varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(buf, lo=0, hi=None):
    """(field number, wire type, value) over one message: a varint's
    value, or the (start, end) of a length-delimited field; fixed-width
    fields are stepped over."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        tag, i = varint(buf, i)
        no, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = varint(buf, i)
            yield no, wire, v
        elif wire == 2:
            n, i = varint(buf, i)
            yield no, wire, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf, spans):
    """(key, (start, end) of the value message) of map<int64, Message>
    entries."""
    for lo, hi in spans:
        key = value = None
        for no, _, v in fields(buf, lo, hi):
            if no == 1:
                key = v
            elif no == 2:
                value = v
        if value is not None:
            yield key, value


def scoped_ops(path, line_name=xtrace.OPS_LINE, device=0):
    """[(instruction text, scope, start_ns, dur_ns)] of one device
    plane's line: every event with the `tf_op` of its metadata ('' where
    it has none). `device` counts the planes named `/device:TPU:`."""
    with open(path, "rb") as f:
        buf = f.read()
    seen = -1
    for no, _, plane in fields(buf):
        if no != 1:
            continue
        name, lines, event_md, stat_md = None, [], [], []
        for n, _, v in fields(buf, *plane):
            if n == 2:
                name = _text(buf, v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                event_md.append(v)
            elif n == 5:
                stat_md.append(v)
        if not (name or "").startswith(xtrace.DEVICE_PREFIX):
            continue
        seen += 1
        if seen == device:
            return _line_ops(buf, lines, event_md, stat_md, line_name)
    return []


def _line_ops(buf, lines, event_md, stat_md, line_name):
    scope_ids = set()
    for key, span in _map_entries(buf, stat_md):
        for n, _, v in fields(buf, *span):
            if n == 2 and _text(buf, v) == SCOPE_STAT:
                scope_ids.add(key)
    meta = {}       # metadata id -> (instruction text, scope)
    for key, span in _map_entries(buf, event_md):
        text, scope = "", ""
        for n, _, v in fields(buf, *span):
            if n == 2:
                text = _text(buf, v)
            elif n == 5:
                sid = value = None
                for m, _, w in fields(buf, *v):
                    if m == 1:
                        sid = w
                    elif m == 5:
                        value = w
                if sid in scope_ids and value is not None:
                    scope = _text(buf, value)
        meta[key] = (text, scope)
    out = []
    for lo, hi in lines:
        name, t0_ns, events = None, 0, []
        for n, _, v in fields(buf, lo, hi):
            if n == 2:
                name = _text(buf, v)
            elif n == 3:
                t0_ns = v
            elif n == 4:
                events.append(v)
        if name != line_name:
            continue
        for span in events:
            mid = offset_ps = dur_ps = 0
            for n, _, v in fields(buf, *span):
                if n == 1:
                    mid = v
                elif n == 2:
                    offset_ps = v
                elif n == 3:
                    dur_ps = v
            text, scope = meta.get(mid, ("", ""))
            out.append((text, scope, t0_ns + offset_ps // 1000,
                        dur_ps // 1000))
    return out


def scope_share_pct(trace_dir, needle, within=None):
    """Share of the device's busy time (the union of its `XLA Ops`) spent
    in ops whose scope contains `needle`, in percent; None when the
    trace holds no such op. `within`, a list of (start_ns, end_ns) that
    do not overlap, keeps only the ops that start inside one of them,
    on both sides of the share."""
    try:
        ops = scoped_ops(xtrace.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    if within is not None:
        within = sorted(within)
        starts = [a for a, _ in within]

        def inside(t):
            k = bisect.bisect_right(starts, t)
            return k > 0 and t < within[k - 1][1]
        ops = [op for op in ops if inside(op[2])]
    hit = [(s, s + d) for _, scope, s, d in ops if needle in scope]
    busy = xtrace.union_ns([(s, s + d) for _, _, s, d in ops])
    if not hit or not busy:
        return None
    return 100.0 * xtrace.union_ns(hit) / busy
