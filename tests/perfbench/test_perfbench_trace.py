"""The reduction from a trace to numbers: on hand-made traces whose
answers are known, and on a slice of a trace recorded on the v5e
(data/trace_slice.json.gz) whose numbers were read off by hand."""
import os

import pytest

from perfbench_fixtures import HERE

import trace as xt

RECORDED = os.path.join(HERE, "data", "trace_slice.json.gz")


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "),
                                     "events": v} for k, v in lines.items()]}


def tiny_trace():
    ops = [["fusion.1", 0, 100], ["custom-call.7", 50, 100],   # overlap
           ["all-reduce.3", 200, 50], ["fusion.2", 400, 100]]
    mods = [["jit_paged_step(123)", 0, 250], ["jit_paged_step(123)", 400, 100],
            ["jit_other(9)", 300, 10]]
    host = [["PjitFunction(paged_step)", 240, 170], ["sleep", 0, 20]]
    return {"planes": [plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods),
                       plane("/host:CPU", python=host)]}


def test_union_and_gaps():
    assert xt.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert xt.union_ns([]) == 0
    assert xt.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30),
                                                                (40, 50)]
    assert xt.gaps_ns([(10, 20)], 0, 15) == [(0, 10)]


def test_busy_is_the_union_of_the_ops_over_their_span():
    b = xt.busy(tiny_trace())
    assert b["busy_s"] == pytest.approx(300e-9)        # 150 + 50 + 100
    assert b["window_s"] == pytest.approx(500e-9)
    assert xt.busy({"planes": [plane("/host:CPU", python=[])]}) is None


def test_planes_and_lines_are_found_by_name_not_position():
    t = tiny_trace()
    t["planes"].reverse()
    t["planes"].insert(0, plane("/device:CUSTOM:0", XLA_Ops=[["x", 0, 9]]))
    assert xt.busy(t)["busy_s"] == pytest.approx(300e-9)
    d = xt.event_durations(t, xt.MODULES_LINE, "paged_step")
    assert sorted(d) == pytest.approx([100e-9, 250e-9])
    assert xt.event_durations(t, "no such line", "x") == []


def test_top_ops_idle_gaps_and_exposed_collectives():
    t = tiny_trace()
    assert xt.top_ops(t, 2) == [["fusion.1", pytest.approx(100e-9)],
                                ["custom-call.7", pytest.approx(100e-9)]]
    gaps = dict(xt.idle_gaps(t, 5))
    # the gaps 150-200 and 250-400: the long one lies under the dispatch
    assert gaps["PjitFunction(paged_step)"] == pytest.approx(150e-9)
    assert sum(gaps.values()) == pytest.approx(200e-9)
    assert xt.exposed_seconds(t, "all-reduce") == pytest.approx(50e-9)
    t["planes"][0]["lines"][0]["events"].append(["fusion.9", 200, 30])
    assert xt.exposed_seconds(t, "all-reduce") == pytest.approx(20e-9)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_recorded_v5e_trace():
    t = xt.load(RECORDED)
    import json
    with open(os.path.join(HERE, "data", "trace_slice.expected.json")) as f:
        want = json.load(f)
    b = xt.busy(t)
    assert b["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert b["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    d = xt.event_durations(t, xt.MODULES_LINE, want["module"])
    assert len(d) == want["module_events"]
    assert sum(d) == pytest.approx(want["module_s"], rel=1e-9)
    assert xt.top_ops(t, 1)[0][0] == want["top_op"]
