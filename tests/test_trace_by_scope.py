"""tools/trace_by_scope.py: a traced run's device time by kind of step.
A hand-made trace of one decode step (c1) and one wide chunk step (c128)
whose expert layer's row-tile loop holds a gather, a slab loop and, in
that, XLA's grouped product: self time by region (the benchmark's
`lib/step_regions.py`), depth and the op above, first on plain data,
then through the benchmark's readers from an encoded xplane."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "perfbench"))

from tools import trace_by_scope  # noqa: E402

US = 1000           # ns
STEPS = [(300 * US, 1300 * US, 1), (5300 * US, 9300 * US, 128)]
WRITE = ("%fusion.1 = bf16[8] fusion(bf16[8] %p)",
         "jit(paged_step)/kv_write/slice:")
LOOP = ("%while.141 = (s32[], bf16[2048,4096]) while(%t)",
        "jit(paged_step)/while")
GATHER = ("%fusion.7 = bf16[8] fusion(bf16[8] %p)",
          "jit(paged_step)/while/body/ffn/moe_experts/gather:")
SLABS = ("%while.9 = (s32[]) while(%t)",
         "jit(paged_step)/while/body/ffn/moe_experts/while")
PRODUCT = ("%ragged-dot-none.3 = bf16[8] custom-call(bf16[8] %x)",
           "ragged-dot-none")
OPS = [(*WRITE, 300 * US, 500 * US),
       (*WRITE, 100 * US, 50 * US),             # before any step: dropped
       (*WRITE, 5300 * US, 500 * US),
       (*LOOP, 6000 * US, 3000 * US),
       (*GATHER, 6100 * US, 400 * US),
       (*SLABS, 6600 * US, 2000 * US),
       (*PRODUCT, 6700 * US, 1500 * US)]


def test_self_time_goes_to_the_scope_and_the_step_by_its_width():
    out = trace_by_scope.reduce(STEPS, OPS)
    decode, chunk = out["widths"]
    assert (decode["c"], decode["steps"], chunk["c"]) == (1, 1, 128)
    assert decode["program_mean"] == pytest.approx(1.0)
    assert decode["by_region"] == {"kv_write": pytest.approx(0.5)}
    assert chunk["program_median"] == pytest.approx(4.0)
    # the row-tile loop, under no region here, keeps 3.0 - 0.4 - 2.0;
    # the slab loop (under moe_experts) 2.0 - 1.5 beside the gather's
    # 0.4 and the grouped product's 1.5, which goes there by its name
    assert chunk["by_region"] == {
        "moe_experts": pytest.approx(2.4),
        "unnamed": pytest.approx(0.6),
        "kv_write": pytest.approx(0.5)}
    assert sum(chunk["by_region"].values()) == pytest.approx(3.5)
    assert out["kinds"]["chunk"]["groups"]["ffn"] == pytest.approx(2.4)
    assert out["kinds"]["chunk"]["idle_ms"] == pytest.approx(0.5)
    assert [o["op"] for o in out["rest"]] == ["%while.141"]
    ops = {o["op"]: o for o in out["ops"][128]}
    assert [o["op"] for o in out["ops"][128]][0] == "%while.141"
    assert (ops["%while.9"]["depth"], ops["%while.9"]["within"]) \
        == (1, "%while.141")
    assert (ops["%ragged-dot-none.3"]["depth"],
            ops["%ragged-dot-none.3"]["within"]) == (2, "%while.9")
    assert ops["%ragged-dot-none.3"]["mean"] == pytest.approx(1.5)
    assert ops["%fusion.1"]["calls_a_step"] == 1.0
    assert [o["op"] for o in out["ops"][1]] == ["%fusion.1"]
    assert trace_by_scope.reduce([], OPS) == {
        "kinds": {}, "widths": [], "ops": {}, "rest": []}


def test_the_steps_counters_are_priced_as_a_share_of_the_commit():
    ms = 1000 * US
    turn = trace_by_scope.host_turn([
        (0, 1 * ms, "serve.dispatch w8c1"), (1 * ms, 3 * ms, "serve.fetch w8c1"),
        (3 * ms, 5 * ms, "serve.commit"), (4 * ms, 4.5 * ms, "serve.telemetry"),
        (5 * ms, 6 * ms, "serve.dispatch w8c128"),
        (6 * ms, 8 * ms, "serve.commit"), (7 * ms, 7.5 * ms, "serve.telemetry")])
    assert turn["steps"] == 2
    assert turn["ms_a_step"]["serve.telemetry"] == pytest.approx(0.5)
    assert turn["ms_a_step"]["serve.commit"] == pytest.approx(2.0)
    assert turn["telemetry_pct_of_commit"] == pytest.approx(25.0)
    assert trace_by_scope.host_turn([]) == {}


def test_the_command_reads_an_xplane_through_the_benchmarks_readers(
        tmp_path, capsys):
    from test_perfbench_annotations import encode_xspace
    ps = 1000 * US      # an event's offset and duration are picoseconds
    texts = {10: WRITE, 11: LOOP, 12: GATHER, 13: PRODUCT, 14: SLABS}
    ids = {v: k for k, v in texts.items()}
    space = {"planes": [
        {"name": "/host:CPU", "stat_metadata": {}, "event_metadata": {
            "1": ["serve.schedule", {}], "2": ["serve.dispatch w8c1", {}],
            "3": ["serve.dispatch w8c128", {}]},
         "lines": [{"name": "python3", "timestamp_ns": 0, "events": [
             [1, 0, 100 * ps], [2, 100 * ps, 100 * ps],
             [1, 5000 * ps, 100 * ps], [3, 5100 * ps, 100 * ps]]}]},
        {"name": "/device:TPU:0",
         "stat_metadata": {"1": "hlo_category", "2": "tf_op"},
         "event_metadata": dict(
             {"9": ["jit_paged_step(123)", {}]},
             **{str(k): [t, {"tf_op": s}] for k, (t, s) in texts.items()}),
         "lines": [
             {"name": "XLA Modules", "timestamp_ns": 0, "events": [
                 [9, a // US * ps, (b - a) // US * ps]
                 for a, b, _ in STEPS]},
             {"name": "XLA Ops", "timestamp_ns": 0, "events": [
                 [ids[(t, s)], a // US * ps, d // US * ps]
                 for t, s, a, d in OPS]}]}]}
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "p.xplane.pb").write_bytes(encode_xspace(space))
    assert trace_by_scope.main([str(tmp_path)]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["kind"] for l in lines[:2]] == ["decode", "chunk"]
    assert lines[1]["regions"]["moe_experts"] == pytest.approx(2.4)
    assert [l["c"] for l in lines[2:4]] == [1, 128]
    assert lines[3]["by_region"]["moe_experts"] == pytest.approx(2.4)
    assert lines[-2]["rest"] and lines[-2]["op"] == "%while.141"
    # the stepper's line: two dispatches, 0.1 ms each under each name
    assert lines[-1]["host"]["steps"] == 2
    assert lines[-1]["host"]["ms_a_step"] == {
        "serve.dispatch": pytest.approx(0.1),
        "serve.schedule": pytest.approx(0.1)}
    assert {l["op"] for l in lines[4:-2] if l["c"] == 128} == {
        "%while.141", "%while.9", "%ragged-dot-none.3", "%fusion.7",
        "%fusion.1"}
