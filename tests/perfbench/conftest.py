"""One test of the benchmark that an append-only manifest cannot keep.

`test_perfbench_moe_slab.py` (PR 41's, the benchmark's: not this PR's to
edit) holds `MANIFEST.per_layer[-1]` to PR 41's entry, so it fails as
soon as any later PR appends a metric, which is the only way a PR may add
one. It is skipped here by name, with the reason in the report; what it
holds besides (the entry's keys, its one cell, `validate` clean) is held
by `test_perfbench_step_regions.py::
test_what_was_last_lies_unchanged_before_the_seven`. A `benchmark` PR
should put `names.index(...)` in the place of `[-1]` there and take this
file away (PERF.md section 7).
"""
import pytest

LAST_NO_MORE = (
    "test_perfbench_moe_slab.py::"
    "test_the_entry_is_appended_for_the_cell_that_has_experts")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(LAST_NO_MORE):
            item.add_marker(pytest.mark.skip(
                reason="holds per_layer[-1] to PR 41's entry; PR 42 "
                       "appended seven after it (see this conftest)"))
