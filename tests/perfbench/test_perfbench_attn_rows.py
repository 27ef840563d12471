"""`attn_rows_live_pct.chat` (ISSUE 31): the reader of the ragged
kernel's live-row counter against a hand-made registry window, against a
program that has no such counter (the parent of the PR that brought it:
None, and no raise), and its entry in the manifest, committed and with a
foreign configuration appended: asked for by name, wherever later PRs'
appended entries leave it."""
import pytest

from perfbench_fixtures import real  # noqa: F401

NAME = "attn_rows_live_pct.chat"


def reader(man):
    entry = [m for m in man.per_layer if m["name"] == NAME]
    assert len(entry) == 1, f"{NAME} is not in the manifest"
    return entry[0], man.reader(entry[0])


def counters(**kinds):
    return {"serve_attn_rows_total": {"children": {
        k: {"value": float(v)} for k, v in kinds.items()}}}


def window(reg0, reg1):
    return {"out": {"facts": {"reg0": reg0, "reg1": reg1}}}


def test_the_entry_is_the_kernels_layers_wherever_it_stands(real):
    entry, _ = reader(real)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "itl_ms.p95",
        "workloads": ["mistral7b-serve-1chip.chat"]}
    assert entry in real.per_layer_of("mistral7b-serve-1chip.chat")


@pytest.mark.parametrize("reg0,reg1,want", [
    # 2 000 live rows of 3 712 visited, a 128-wide step's work list
    (counters(live=0, visited=0), counters(live=2000, visited=3712),
     100.0 * 2000 / 3712),
    # the window's gain, not the totals
    (counters(live=1000, visited=64000),
     counters(live=1000 + 540, visited=64000 + 1000), 54.0),
    # every visited row live
    (counters(live=5, visited=10), counters(live=133, visited=138), 100.0),
])
def test_live_over_visited_in_the_window(real, reg0, reg1, want):
    _, read = reader(real)
    assert read(window(reg0, reg1)) == pytest.approx(want)


@pytest.mark.parametrize("reg0,reg1", [
    ({}, {}),                                   # a program without the counter
    (counters(live=7, visited=64),
     counters(live=7, visited=64)),             # a window of decode steps
    ({"serve_slab_tokens_total": {"children": {"live": {"value": 1.0}}}},
     {"serve_slab_tokens_total": {"children": {"live": {"value": 9.0}}}}),
])
def test_nothing_to_read_is_none(real, reg0, reg1):
    _, read = reader(real)
    assert read(window(reg0, reg1)) is None
