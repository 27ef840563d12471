"""A serving step on the device by kind of step and by region
(ISSUE 42: `perfbench/lib/step_regions.py` and the seven readers on it),
against hand-made steps (data/step_regions_steps.json, in us) tiled into
an xplane in the profiler's wire format and read back through the
benchmark's own readers; every answer is computed by hand from the data
file's rows.

One chunk step of the data (4000 us of program, every other one 4200):
  embed 100 | the first row-tile loop 1200, holding qkv_proj 290, rope
  50, an index fusion without metadata 20, the writer's scatter without
  metadata 500, q_pack 30 and an in-place row-tile update without
  metadata 100: its own 210 | the kernel 500 | the second loop 1900,
  holding attention 100, out_proj 200, ffn 600, the slab loop 800 (own
  160) around a gather of 40 and a grouped product of 600, and a
  scatter-shaped fusion
  without metadata 100: its own 100 | head 100 | sampler 100 | a copy
  at the top level 50 | nothing for 50 (250).
"""
import json
import os

import pytest

from perfbench_fixtures import HERE, REPO, real  # noqa: F401
from test_perfbench_annotations import encode_xspace

import manifest as mf
import step_regions
import trace as xtrace

with open(os.path.join(HERE, "data", "step_regions_steps.json")) as _f:
    DATA = json.load(_f)

CELLS = ["mistral7b-serve-1chip.chat", "mimo-v2-flash-serve-1chip.mixedlen"]
NAMES = ["chunk_step_device_ms", "decode_step_device_ms", "chunk_proj_ms",
         "chunk_kv_write_ms", "chunk_attn_ms", "chunk_ffn_ms",
         "chunk_unnamed_pct"]
MANIFEST = mf.Manifest(os.path.join(REPO, "BENCHMARK.json"))
PS = 1000 * 1000        # an event's offset and duration: ps in a us

# ms a chunk step, by hand (the docstring's rows)
REGIONS = dict(
    embed=0.100, rows_before=0.210 + 0.020 + 0.100, qkv_proj=0.290,
    rope=0.050, kv_write=0.500, q_pack=0.030, attention=0.500 + 0.100,
    rows_after=0.100 + 0.100, out_proj=0.200, ffn=0.600,
    moe_experts=0.600, moe_slabs=0.200, head=0.100, sampler=0.100,
    unnamed=0.050)
GROUPS = dict(proj=0.100 + 0.290 + 0.050 + 0.030 + 0.200, kv_write=0.500,
              attn=0.600, ffn=0.600 + 0.600 + 0.200, head=0.100,
              sampler=0.100, rest=0.050 + 0.330 + 0.200)


def reader(name):
    entry = [m for m in MANIFEST.per_layer if m["name"] == name]
    assert len(entry) == 1, f"{name} is not in BENCHMARK.json"
    return MANIFEST.reader(entry[0])


OLD_NAMES = ("kv_write", "attention", "ffn", "moe_route", "moe_experts",
             "head", "sampler")


LOOPS = ("11", "21", "25")      # the data's three `%while` texts


def xspace(chunks, decodes, names=step_regions.REGIONS, loops_say=True):
    """`chunks` chunk steps (c128, every third c16; every other one's
    program 200 us longer) and `decodes` decode steps (c1), a chunk step
    after every decode step while there are any, each dispatched 10 us
    before its program starts. Of the regions only `names` are said;
    without `loops_say` a `%while` event has no `tf_op`, as on the chip."""
    def said(scope):
        return "/".join(p for p in scope.split("/")
                        if p not in step_regions.REGIONS or p in names)
    meta = {k: [text, {"tf_op": said(scope)} if scope and names
                and (loops_say or k not in LOOPS) else {}]
            for k, (text, scope) in DATA["texts"].items()}
    meta["1"] = ["jit_paged_step(42)", {}]
    host = {"1": ["serve.schedule", {}], "2": ["serve.dispatch w32c1", {}],
            "3": ["serve.dispatch w32c128", {}],
            "4": ["serve.dispatch w32c16", {}]}
    modules, ops, stepper, t = [], [], [], 100
    left = dict(chunk=chunks, decode=decodes)
    while left["chunk"] or left["decode"]:
        for kind in ("decode", "chunk"):
            if not left[kind]:
                continue
            left[kind] -= 1
            step = DATA[kind]
            program = step["program"] + (
                200 if kind == "chunk" and left[kind] % 2 else 0)
            width = 2 if kind == "decode" else 4 if left[kind] % 3 == 0 \
                else 3
            stepper += [[1, (t - 30) * PS, 10 * PS],
                        [width, (t - 10) * PS, 5 * PS]]
            modules.append([1, t * PS, program * PS])
            ops += [[m, (t + a) * PS, d * PS] for m, a, d in step["ops"]]
            t += program + 100
    return {"planes": [
        {"name": "/host:CPU", "stat_metadata": {}, "event_metadata": host,
         "lines": [{"name": "python3", "timestamp_ns": 0,
                    "events": stepper}]},
        {"name": "/device:TPU:0",
         "stat_metadata": {"1": "hlo_category", "2": "tf_op"},
         "event_metadata": meta,
         "lines": [{"name": "XLA Modules", "timestamp_ns": 0,
                    "events": modules},
                   {"name": "XLA Ops", "timestamp_ns": 0, "events": ops}]}]}


def context(tmp_path, *args, **kw):
    """A run's context over the tiled steps: the trace as `run.py` loads
    it and the directory its readers find the xplane in."""
    d = tmp_path / "plugins" / "profile" / "2026_10_04"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(encode_xspace(xspace(*args, **kw)))
    path = xtrace.find_xplane(str(tmp_path))
    return {"trace": xtrace.load(path), "trace_dir": str(tmp_path)}


@pytest.mark.parametrize("loops_say", [True, False])
def test_a_step_is_taken_apart_by_kind_region_and_self_time(
        tmp_path, loops_say):
    """The same table whether a `%while` event carries its `tf_op` (the
    compiled program's instruction does) or not (the profiler's event
    does not: its name is read back from its body's ops)."""
    table = step_regions.table(context(tmp_path, 12, 14,
                                       loops_say=loops_say))
    chunk, decode = table["chunk"], table["decode"]
    # c128 and c16 are chunk steps alike, c1 the decode steps
    assert (chunk["steps"], decode["steps"]) == (12, 14)
    assert chunk["program_ms"] == pytest.approx(4.1)
    assert decode["program_ms"] == pytest.approx(1.0)
    # inheritance: the index fusion and the row-tile update take the
    # first loop's name, the scatter-shaped fusion of the second loop
    # the second's; the scatter rule: the first loop's goes to kv_write;
    # self time: a loop keeps what its body's ops leave of it; the
    # grouped product goes to moe_experts by its name
    assert chunk["regions"] == {k: pytest.approx(v)
                                for k, v in REGIONS.items()}
    assert chunk["groups"] == {k: pytest.approx(v)
                               for k, v in GROUPS.items()}
    assert chunk["sum_ms"] == pytest.approx(3.95)
    assert chunk["idle_ms"] == pytest.approx(0.15)
    assert chunk["named"] and decode["named"]
    assert decode["regions"] == {
        "qkv_proj": pytest.approx(0.3), "ffn": pytest.approx(0.3),
        "attention": pytest.approx(0.2), "kv_write": pytest.approx(0.1),
        "sampler": pytest.approx(0.05)}
    assert decode["idle_ms"] == pytest.approx(0.05)


@pytest.mark.parametrize("name,want", [
    ("chunk_step_device_ms", 4.1),
    ("decode_step_device_ms", 1.0),
    ("chunk_proj_ms", GROUPS["proj"]),
    ("chunk_kv_write_ms", 0.5),
    ("chunk_attn_ms", 0.6),
    ("chunk_ffn_ms", 1.4),
    ("chunk_unnamed_pct", 100.0 * 0.58 / 3.95),
])
def test_the_seven_readers(tmp_path, name, want):
    assert reader(name)(context(tmp_path, 12, 14)) == pytest.approx(want)


def test_the_regions_and_the_idle_time_add_up_to_the_program(tmp_path):
    ctx = context(tmp_path, 10, 10)
    parts = sum(reader(n)(ctx) for n in NAMES[2:6])
    row = step_regions.table(ctx)["chunk"]
    rest = reader("chunk_unnamed_pct")(ctx) / 100.0 * row["sum_ms"]
    assert parts + rest + row["groups"]["head"] + row["groups"]["sampler"] \
        == pytest.approx(row["sum_ms"])
    assert row["sum_ms"] + row["idle_ms"] \
        == pytest.approx(reader("chunk_step_device_ms")(ctx))


@pytest.mark.parametrize("name", NAMES)
def test_nine_steps_of_a_kind_are_too_few_for_a_mean(tmp_path, name):
    ctx = context(tmp_path, 9, 9)
    assert step_regions.table(ctx)["chunk"]["steps"] == 9
    assert reader(name)(ctx) is None
    # ten decode steps beside nine chunk steps: the decode reader reads
    other = context(tmp_path / "more", 9, 10)
    assert reader(name)(other) == (
        pytest.approx(1.0) if name == "decode_step_device_ms" else None)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_that_names_nothing_gives_the_step_times_alone(
        tmp_path, name):
    """No `tf_op` at all: what a program without regions leaves in the
    trace. The two step times are the modules' and still read; of the
    regions there is nothing to say, and None is not 100% unnamed."""
    ctx = context(tmp_path, 12, 12, names=())
    want = {"chunk_step_device_ms": 4.1, "decode_step_device_ms": 1.0}
    if name in want:
        assert reader(name)(ctx) == pytest.approx(want[name])
    else:
        assert reader(name)(ctx) is None
    assert not step_regions.table(ctx)["chunk"]["named"]


def test_a_run_without_a_trace_or_without_steps_reads_nothing(tmp_path):
    assert step_regions.table({"trace_dir": str(tmp_path)}) == {}
    ctx = context(tmp_path, 0, 0)
    assert step_regions.table(ctx) == {}
    assert [reader(n)(ctx) for n in NAMES] == [None] * 7


@pytest.mark.parametrize("name,want", [
    ("chunk_step_device_ms", 4.1),
    ("chunk_proj_ms", None),        # no op under any of its regions
    # the loops say nothing there, so the two scatter-shaped fusions
    # inherit nothing and the rule takes both (0.5 + 0.1)
    ("chunk_kv_write_ms", 0.6),
    ("chunk_attn_ms", 0.6),
    ("chunk_ffn_ms", 1.4),          # the slab loop's own under moe_experts
    # embed 0.1, the first loop but its scatter 0.7, the second's own
    # 0.1 with out_proj 0.2, the copy 0.05
    ("chunk_unnamed_pct", 100.0 * 1.15 / 3.95),
])
def test_the_names_of_the_program_before_read_as_what_they_were(
        tmp_path, name, want):
    """This file's readers over a program that has the seven names of
    PRs 24 and 40 and none of ISSUE 42's (the benchmark laid over the
    parent's checkout): a group none of whose regions holds an op is
    left out of the line, and the loops carry no name."""
    got = reader(name)(context(tmp_path, 12, 12, names=OLD_NAMES))
    assert got == (None if want is None else pytest.approx(want))


def test_the_trace_is_parsed_once_a_run(tmp_path, monkeypatch):
    import xplane
    calls, real_ops = [], xplane.scoped_ops

    def counting(path, *a, **kw):
        calls.append(path)
        return real_ops(path, *a, **kw)

    monkeypatch.setattr(xplane, "scoped_ops", counting)
    ctx = context(tmp_path, 10, 10)
    assert all(reader(n)(ctx) is not None for n in NAMES)
    assert len(calls) == 1
    # another run's trace is another parse, and takes the first's place
    other = context(tmp_path / "next", 10, 11)
    assert step_regions.table(other)["decode"]["steps"] == 11
    assert len(calls) == 2 and len(step_regions._TABLES) == 1


WRITER = DATA["texts"]["15"][0]


@pytest.mark.parametrize("text,want", [
    (WRITER, True),
    # PR 29's reading of the chat cell's writer, as the tool printed it
    ("%fusion.9 = bf16[657408,128]{1,0} fusion(bf16[657408,128]{1,0} %c, "
     "s32[4096]{0} %i, bf16[4096,128]{1,0} %r), kind=kCustom", True),
    (DATA["texts"]["27"][0], True),     # shaped so; its loop decides
    (DATA["texts"]["14"][0], False),    # the indices: no buffer among them
    (DATA["texts"]["17"][0], False),    # a row tile put back: no index rows
    (DATA["texts"]["12"][0], False),    # a product
    (DATA["texts"]["11"][0], False),    # the loop itself
    (DATA["texts"]["26"][0], False),    # a custom call
    # rows of another element type than the buffer's
    ("%f = bf16[64,128]{1,0} fusion(bf16[64,128]{1,0} %c, s32[8]{0} %i, "
     "f32[8,128]{1,0} %r), kind=kCustom", False),
    # as many new rows as the buffer has: not an update of some of it
    ("%f = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %c, s32[8]{0} %i, "
     "bf16[8,128]{1,0} %r), kind=kLoop", False),
])
def test_the_scatter_rule(text, want):
    assert step_regions.is_cache_scatter(text) is want


def test_a_loops_name_is_what_its_bodys_ops_share():
    us = 1000
    P = "jit(paged_step)/jit(packed_paged_layer)/"
    ops = [("%while.1 = () while(%t)", "", 0, 5000 * us),
           ("%fusion.1 = x", P + "rows_after/while/body/attention/gather",
            10 * us, 100 * us),
           ("%fusion.2 = s32[2]{0} fusion()", "", 200 * us, 100 * us),
           ("%while.2 = () while(%t)", "", 1000 * us, 2000 * us),
           ("%fusion.3 = x", P + "rows_after/while/body/ffn/moe_experts/"
            "moe_slabs/while/body/gather", 1100 * us, 100 * us),
           ("%copy.1 = x", "", 1300 * us, 100 * us),
           # XLA's own kernel names itself, and a fusion may carry the
           # loop's own `tf_op`: neither says what the body's ops share
           ("%ragged-dot-none.1 = x", "ragged-dot-none", 1500 * us, 100 * us),
           ("%copy.2 = x", P + "rows_after/while:", 3300 * us, 100 * us),
           ("%fusion.4 = x", P + "rows_after/while/body/ffn/add",
            3500 * us, 100 * us),
           # the program before: a loop under no region stays unnamed
           ("%while.3 = () while(%t)", "", 6000 * us, 1000 * us),
           ("%fusion.5 = x", "jit(paged_step)/while/body/add",
            6100 * us, 100 * us),
           # an op that is no loop takes nothing from what it covers
           ("%call.1 = x", "", 8000 * us, 500 * us),
           ("%fusion.6 = x", "jit(paged_step)/head/while/body/dot",
            8100 * us, 100 * us)]
    rows = step_regions.nest([(0, 10000 * us, 128)], ops)
    assert step_regions.loop_scopes(rows) == {
        0: P + "rows_after/while",
        3: P + "rows_after/while/body/ffn/moe_experts/moe_slabs/while",
        9: "jit(paged_step)/while"}
    assert step_regions.regions_of(rows) == [
        "rows_after", "attention", "rows_after", "moe_slabs", "moe_slabs",
        "moe_slabs", "moe_experts", "rows_after", "ffn", "unnamed",
        "unnamed", "unnamed", "head"]


def test_the_rule_names_an_op_only_under_the_first_loop_or_under_nothing():
    us = 1000
    steps = [(0, 10000 * us, 128)]
    loop = lambda name: (f"%while.1 = (s32[]) while(%t)",
                         f"jit(paged_step)/{name}/while")
    for name, want in (("rows_before", "kv_write"),
                       ("rows_after", "rows_after"), ("embed", "embed"),
                       ("moe_slabs", "moe_slabs")):
        ops = [(*loop(name), 0, 1000 * us), (WRITER, "", 100 * us, 500 * us)]
        rows = step_regions.nest(steps, ops)
        assert step_regions.regions_of(rows) == [name, want]
    # with a region of its own an op is where it says, whatever its shape
    ops = [(*loop("rows_before"), 0, 1000 * us),
           (WRITER, "jit(paged_step)/rows_before/while/body/q_pack/dus",
            100 * us, 500 * us)]
    assert step_regions.regions_of(step_regions.nest(steps, ops)) == [
        "rows_before", "q_pack"]
    # one of a chat step's sixteen writers carries the LOOP's own
    # `tf_op` (my chip run, PR 42): nothing names it more closely
    ops[1] = (WRITER, "jit(paged_step)/rows_before/while:", 100 * us,
              500 * us)
    assert step_regions.regions_of(step_regions.nest(steps, ops)) == [
        "rows_before", "kv_write"]
    # at the top level, under no op, there is nothing to inherit: a
    # narrow chunk step's writer lies there, and a copy stays unnamed
    rows = step_regions.nest(steps, [
        (WRITER, "", 0, 500 * us),
        (DATA["texts"]["32"][0], "", 600 * us, 50 * us)])
    assert step_regions.regions_of(rows) == ["kv_write", "unnamed"]


def test_what_was_last_lies_unchanged_before_the_seven(real):
    # what `test_perfbench_moe_slab.py` holds of PR 41's entry with
    # `per_layer[-1]`, in the form an appended manifest can keep (that
    # test is skipped in conftest.py): the seven follow the entry that
    # was last, in ISSUE 42's order, whatever a later PR appends after
    # them (`real`'s second manifest does)
    names = [m["name"] for m in real.per_layer]
    at = names.index(NAMES[0])
    assert names[at:at + len(NAMES)] == NAMES
    assert real.per_layer[at - 1] == dict(
        name="moe_slab_fill_pct.mixedlen", unit="%", better="higher",
        source="program_counter", layer="engine programs",
        moves="itl_ms.p95", workloads=[CELLS[1]])
    assert "moe_slab_fill_pct.mixedlen" not in [
        m["name"] for m in real.per_layer_of(CELLS[0])]


def test_the_seven_metrics_are_in_the_manifest_with_both_cells(real):
    by = {m["name"]: m for m in real.per_layer}
    for name in NAMES:
        m = by[name]
        assert m["workloads"] == CELLS
        assert (m["moves"], m["source"], m["better"]) == (
            "itl_ms.p95", "device_trace", "lower")
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert callable(real.reader(m))
    assert by["chunk_kv_write_ms"]["layer"] == "KV manager"
    assert by["chunk_attn_ms"]["layer"] == "kernels"
    assert by["chunk_unnamed_pct"]["layer"] == "device"
    for cell in CELLS:
        assert set(NAMES) <= {m["name"] for m in real.per_layer_of(cell)}
    assert mf.validate(real) == []
