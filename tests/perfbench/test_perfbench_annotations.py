"""The per-layer readers that read what the program says of itself
(ISSUE 24), each against a small hand-made trace or registry whose answer
is computed by hand (data/annotated_trace.json, in ns), and each against
a program that says nothing, where it must return None and not raise.
"""
import json
import os
import struct

import pytest

from perfbench_fixtures import HERE, REPO, real  # noqa: F401

import annotations
import manifest as mf
import xplane

with open(os.path.join(HERE, "data", "annotated_trace.json")) as _f:
    DATA = json.load(_f)

MANIFEST = mf.Manifest(os.path.join(REPO, "BENCHMARK.json"))
NEW = ["handoff_wait_ms.chat", "emit_to_wire_ms.chat", "step_ms.decode.chat",
       "step_ms.chunk.chat", "slab_fill_pct.chat", "idle_host_step_pct.chat",
       "idle_unattributed_pct.chat", "kv_write_share_pct.chat",
       "flash_attn_share_pct.seq4k"]


def reader(name):
    entry = [m for m in MANIFEST.per_layer if m["name"] == name]
    assert len(entry) == 1, f"{name} is not in BENCHMARK.json"
    return MANIFEST.reader(entry[0])


# -- the registry's window ------------------------------------------------------------

def hist(total, count):
    return {"children": {"": {"sum": total, "count": count,
                              "bucket_counts": []}}}


def registry(**families):
    """A snapshot: family=hist(...) or family={child: (sum, count)} or
    family={child: value}."""
    snap = {}
    for name, children in families.items():
        if "children" in children:
            snap[name] = children
            continue
        snap[name] = {"children": {
            k: ({"sum": v[0], "count": v[1]} if isinstance(v, tuple)
                else {"value": v}) for k, v in children.items()}}
    return snap


def window(reg0, reg1, **more):
    return dict({"out": {"facts": {"reg0": reg0, "reg1": reg1}}}, **more)


REG0 = registry(
    gateway_handoff_seconds=hist(1.0, 10),
    gateway_emit_to_wire_seconds=hist(0.2, 100),
    serve_step_kind_seconds={"decode": (5.0, 100), "chunk": (1.0, 3)},
    serve_slab_tokens_total={"live": 1000.0, "capacity": 10000.0})
REG1 = registry(
    gateway_handoff_seconds=hist(1.9, 40),              # 0.9 s over 30
    gateway_emit_to_wire_seconds=hist(0.5, 200),        # 0.3 s over 100
    serve_step_kind_seconds={"decode": (8.1, 200),      # 3.1 s over 100
                             "chunk": (2.4, 13)},       # 1.4 s over 10
    serve_slab_tokens_total={"live": 1900.0,            # 900 of
                             "capacity": 28000.0})      # 18 000


@pytest.mark.parametrize("name,want", [
    ("handoff_wait_ms.chat", 30.0),
    ("emit_to_wire_ms.chat", 3.0),
    ("step_ms.decode.chat", 31.0),
    ("step_ms.chunk.chat", 140.0),
    ("slab_fill_pct.chat", 5.0),
])
def test_registry_readers_take_the_windows_mean(name, want):
    assert reader(name)(window(REG0, REG1)) == pytest.approx(want)


def test_nine_chunk_steps_are_too_few_for_a_mean():
    reg1 = registry(serve_step_kind_seconds={"decode": (8.1, 200),
                                             "chunk": (2.2, 12)})
    assert reader("step_ms.chunk.chat")(window(REG0, reg1)) is None
    assert reader("step_ms.decode.chat")(window(REG0, reg1)) \
        == pytest.approx(31.0)


# -- idle time under the stepper's states ----------------------------------------------
# chat's device is idle in [1500, 2500), [3000, 3200) and [3500, 5000):
# 2700 ns of the 4500 its ops span. Under the stepper thread's states:
#   gap 1: fetch 100, commit 400, schedule 100, build 150, dispatch 150,
#          fetch 100
#   gap 2: fetch 200
#   gap 3: fetch 100, commit 100, stepper.idle 1000, commands 100,
#          nothing 50 (4800-4850), schedule 100, build 30, dispatch 20
IDLE = {"serve.commit": 500, "serve.schedule": 200, "serve.build": 180,
        "serve.dispatch": 170, "stepper.commands": 100, "serve.fetch": 500,
        "stepper.idle": 1000, "unattributed": 50, "idle": 2700}


def test_idle_gaps_are_charged_to_the_state_that_covers_them():
    got = annotations.idle_by_state(DATA["chat"])
    assert got == {k: pytest.approx(v / 1e9) for k, v in IDLE.items()}
    # the states tile the idle time: nothing is counted twice, and
    # serve.telemetry, nested in serve.commit, is no state of its own
    assert sum(v for k, v in got.items() if k != "idle") \
        == pytest.approx(got["idle"])


@pytest.mark.parametrize("name,want", [
    ("idle_host_step_pct.chat", 100.0 * 1150 / 2700),   # the five host states
    ("idle_unattributed_pct.chat", 100.0 * 50 / 2700),
])
def test_idle_readers(name, want):
    assert reader(name)({"trace": DATA["chat"]}) == pytest.approx(want)


def test_the_stepper_line_is_found_by_its_events_not_its_name():
    states = annotations.stepper_states(DATA["chat"])
    assert [s[2] for s in states][:3] == ["serve.fetch", "serve.commit",
                                          "serve.schedule"]
    assert len(states) == 12        # telemetry and the python frame left out
    assert annotations.state_of("serve.dispatch w512c128") == "serve.dispatch"
    assert annotations.state_of("serve.telemetry") is None
    assert annotations.state_of("gateway.sse_write") is None


def test_flash_share_counts_the_named_kernels_only():
    # 300 + 200 in the two flash kernels of 1100 busy; the ConcatBitcast
    # custom call is no kernel of ours
    got = reader("flash_attn_share_pct.seq4k")({"trace": DATA["train"]})
    assert got == pytest.approx(100.0 * 500 / 1100)


# -- the scope of a device op, from the trace file itself ----------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def encode_xspace(space):
    """The hand-made XSpace of the data file in the wire format of
    tsl/profiler/protobuf/xplane.proto (the inverse of lib/xplane.py)."""
    out = b""
    for plane in space["planes"]:
        ids = {name: int(k) for k, name in plane["stat_metadata"].items()}
        body = _field(1, 7) + _field(2, plane["name"])
        for line in plane["lines"]:
            events = b"".join(
                _field(4, _field(1, m) + _field(2, off) + _field(3, dur)
                       # an event's own stat, in the way as on the chip
                       + _field(4, _field(1, 99) + _field(3, 5)))
                for m, off, dur in line["events"])
            body += _field(3, _field(1, 1) + _field(2, line["name"])
                           + _field(3, line["timestamp_ns"]) + events
                           + _field(9, 123456))
        for k, (text, stats) in plane["event_metadata"].items():
            md = _field(1, int(k)) + _field(2, text) + _field(4, "shown")
            for stat, value in stats.items():
                md += _field(5, _field(1, ids[stat]) + _field(5, value))
            # a double-valued stat (fixed 64) must be stepped over
            md += _field(5, _field(1, 98)
                         + _varint(2 << 3 | 1) + struct.pack("<d", 1.5))
            body += _field(4, _field(1, int(k)) + _field(2, md))
        for k, name in plane["stat_metadata"].items():
            body += _field(5, _field(1, int(k))
                           + _field(2, _field(1, int(k)) + _field(2, name)))
        out += _field(1, body)
    return out + _field(4, "hostname")


@pytest.fixture
def trace_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "2026_09_28"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(encode_xspace(DATA["xplane"]))
    return str(tmp_path)


def test_scoped_ops_joins_each_event_to_its_own_metadata(trace_dir):
    import trace as xt
    ops = xplane.scoped_ops(xt.find_xplane(trace_dir))
    assert [(s, d) for _, _, s, d in ops] == [
        (1000, 400), (1400, 100), (2500, 500), (3200, 300), (4000, 200)]
    # two instructions of one name, told apart by the scope of their program
    assert ops[0][0] == ops[2][0]
    assert [o[1].split("/")[1] if o[1] else "" for o in ops] == [
        "kv_write", "attention", "ffn", "kv_write", ""]
    assert xplane.scoped_ops(xt.find_xplane(trace_dir), device=1) == []
    mods = xplane.scoped_ops(xt.find_xplane(trace_dir), xt.MODULES_LINE)
    assert [(s, d) for _, _, s, d in mods] == [(1000, 3200)]


# the stepper thread as it ran those five ops (data "xplane_steps"): a
# decode step whose program ran [1000, 1500) around the first two, a
# chunk step [2500, 3500) around the next two, a decode step
# [4000, 4200) around the last, and a dispatch whose program the
# trace's edge cuts
def test_step_windows_are_the_programs_of_the_dispatches_in_order():
    assert annotations.step_windows(DATA["xplane_steps"]) == [
        (1000, 1500, 1), (2500, 3500, 8), (4000, 4200, 1)]
    # chat's own stepper line opens in the middle of a step: the program
    # at 1000 was dispatched before the trace began and is stepped over
    assert annotations.step_windows(DATA["chat"]) == [
        (2500, 3500, 1), (5000, 5500, 8)]
    assert annotations.step_windows({"planes": []}) == []
    # a trace without the device's modules line has no step to give
    host_only = {"planes": [p for p in DATA["xplane_steps"]["planes"]
                            if p["name"].startswith("/host:")]}
    assert annotations.step_windows(host_only) == []


with open(os.path.join(HERE, "data", "lookahead_trace.json")) as _f:
    AHEAD = json.load(_f)["lookahead"]


def test_step_windows_follow_a_scheduler_that_looks_one_step_ahead():
    """Since PR 35 a turn dispatches step n+1 and then fetches step n:
    the fetch that follows `serve.dispatch w8c128` is `serve.fetch w8c1`,
    the step before. Joined in order, each dispatch gets its own
    program: the one in flight when the trace began is stepped over, the
    `jit__feed_tokens` programs between the steps are no steps, and the
    last dispatch, whose program the trace's end cuts, gets none."""
    got = annotations.step_windows(AHEAD)
    assert got == [(1510, 2500, 1), (2510, 4500, 128), (4510, 5500, 1),
                   (5510, 6500, 1)]
    # whole steps: every window is one program's own interval, so the
    # decode windows hold no part of the 128-wide step (a join of each
    # dispatch to the next fetch of its bucket gave (750, 1500), the step
    # BEFORE the first dispatch, and (2750, 5500), the chunk step's tail
    # with a decode step)
    decode = [(a, b) for a, b, slab in got if slab == 1]
    assert sum(b - a for a, b in decode) == 3 * 990
    ops = AHEAD["planes"][0]["lines"][1]["events"]
    inside = [n for n, s, _ in ops if any(a <= s < b for a, b in decode)]
    assert len(inside) == 3 and all("fusion.1" in n for n in inside)


@pytest.mark.parametrize("within,want", [
    (None, 100.0 * 700 / 1500),     # 400 + 300 of all five ops
    ([(900, 1600), (3900, 4350)], 100.0 * 400 / 700),
    ([(2400, 3600)], 100.0 * 300 / 800),
    ([(3900, 4350)], None),         # the copy alone: nothing under kv_write
    ([], None),
])
def test_scope_share_keeps_the_ops_that_start_inside(trace_dir, within, want):
    got = xplane.scope_share_pct(trace_dir, "/kv_write/", within=within)
    assert got == (want if want is None else pytest.approx(want))


def test_kv_write_share_is_taken_over_the_decode_steps(trace_dir):
    # 400 under kv_write of the 400 + 100 + 200 busy in the two c1 steps;
    # over all steps it would read 46.7 and move with the chunk steps
    ctx = {"trace_dir": trace_dir, "trace": DATA["xplane_steps"]}
    assert reader("kv_write_share_pct.chat")(ctx) \
        == pytest.approx(100.0 * 400 / 700)
    # a slice that holds chunk steps only has no decode share
    host, device = DATA["xplane_steps"]["planes"]
    chunks = {"planes": [device, {"name": "/host:CPU", "lines": [{
        "name": "python3", "events": [
            e for e in host["lines"][0]["events"]
            if not e[0].endswith("c1")]}]}]}
    assert annotations.step_windows(chunks) == [(2500, 3500, 8)]
    assert reader("kv_write_share_pct.chat")(
        {"trace_dir": trace_dir, "trace": chunks}) is None


# -- a program that says nothing ------------------------------------------------------------

@pytest.fixture
def silent(tmp_path):
    """The context of a run of the parent commit: no family of this PR
    in the registry, no annotation and no scope in the trace."""
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    space = json.loads(json.dumps(DATA["xplane"]))
    for plane in space["planes"]:
        for text_stats in plane["event_metadata"].values():
            text_stats[1].pop("tf_op", None)
            text_stats[0] = text_stats[0].replace("_ragged_attn", "")
    (d / "p.xplane.pb").write_bytes(encode_xspace(space))
    trace = {"planes": [
        DATA["chat"]["planes"][0],
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["$selectors.py:452 select", 1000, 4000]]}]}]}
    old = registry(serve_host_phase_seconds={"commit": (1.0, 10)})
    return window(old, old, trace=trace, trace_dir=str(tmp_path))


@pytest.mark.parametrize("name", NEW)
def test_a_silent_program_reads_as_nothing(silent, name):
    assert reader(name)(silent) is None


def test_no_trace_file_reads_as_nothing(tmp_path):
    assert xplane.scope_share_pct(str(tmp_path), "/kv_write/") is None
    assert annotations.idle_by_state({"planes": []}) is None


# -- the manifest --------------------------------------------------------------------------------

def test_the_nine_metrics_are_in_the_manifest_and_it_is_sound(real):
    """Asked for by name, wherever a later PR's metrics leave them: on
    the committed manifest and on the copy with a metric appended."""
    assert mf.validate(real) == []
    names = [m["name"] for m in real.per_layer]
    assert set(NEW) <= set(names)
    chat = {m["name"] for m in real.per_layer_of(
        "mistral7b-serve-1chip.chat")}
    train = {m["name"] for m in real.per_layer_of(
        "mistral7b-train-1chip.seq4k")}
    assert set(NEW[:8]) <= chat and NEW[8] in train
    assert not set(NEW[:8]) & train and NEW[8] not in chat
