"""What PR 41 appended to the benchmark: `moe_slab_fill_pct.mixedlen`
reads a registry made by hand (a share; None from a program that counts
no such rows, as the parent's), and its entry sits last in `per_layer`,
on the engine programs' layer, for the one cell that has experts. (A file
of its own: `test_perfbench_mimo.py` is the benchmark's, and a PR that
claims a gain edits no file the benchmark has.)"""
import os

import pytest

from perfbench_fixtures import REPO

import manifest as mf
from test_perfbench_annotations import registry, window

NAME = "moe_slab_fill_pct.mixedlen"
CELL = "mimo-v2-flash-serve-1chip.mixedlen"
MANIFEST = mf.Manifest(os.path.join(REPO, "BENCHMARK.json"))


def entry():
    found = [m for m in MANIFEST.per_layer if m["name"] == NAME]
    assert len(found) == 1, f"{NAME} is not in BENCHMARK.json"
    return found[0]


@pytest.mark.parametrize("here, handed, want", [
    # decode steps only: a slab of 16 x 8 rows an expert layer that got
    # an assignment, about two each
    ((100.0, 180.0), (1000.0, 6120.0), 1.5625),
    # chunk tiles of one slab each, a quarter full
    ((0.0, 5200.0), (0.0, 20800.0), 25.0),
    # every row handed held an assignment
    ((7.0, 263.0), (7.0, 263.0), 100.0),
])
def test_the_reader_divides_the_held_assignments_by_the_rows_handed(
        here, handed, want):
    reg0 = registry(
        serve_moe_assignments_total={"here": here[0], "elsewhere": 0.0},
        serve_moe_slab_rows_total={"": handed[0]})
    reg1 = registry(
        serve_moe_assignments_total={"here": here[1], "elsewhere": 9e3},
        serve_moe_slab_rows_total={"": handed[1]})
    assert MANIFEST.reader(entry())(window(reg0, reg1)) \
        == pytest.approx(want)


def test_a_program_without_the_counter_gives_nothing_to_read():
    read = MANIFEST.reader(entry())
    assert read(window(registry(), registry())) is None
    # nor does a window in which no expert layer ran
    same = registry(serve_moe_assignments_total={"here": 5.0},
                    serve_moe_slab_rows_total={"": 80.0})
    assert read(window(same, same)) is None
    # the parent's registry: assignments counted, no rows
    assert read(window(registry(), registry(
        serve_moe_assignments_total={"here": 50.0, "elsewhere": 750.0}))) \
        is None


def test_the_entry_is_appended_for_the_cell_that_has_experts():
    assert mf.validate(MANIFEST) == []
    assert MANIFEST.per_layer[-1] is entry()
    assert entry() == dict(
        name=NAME, unit="%", better="higher", source="program_counter",
        layer="engine programs", moves="itl_ms.p95", workloads=[CELL])
    assert NAME in [m["name"] for m in MANIFEST.per_layer_of(CELL)]
    assert NAME not in [m["name"] for m in MANIFEST.per_layer_of(
        "mistral7b-serve-1chip.chat")]
