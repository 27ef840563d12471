"""The two readers that show which regime `chat` runs in (ISSUE 38):
`chunk_step_share_pct.chat`, chunk steps over all steps of the window
(how far `itl_ms.p95` sits from the edge between decode-step gaps and
chunk-step gaps), and `steps_ahead_pct.chat`, steps dispatched ahead over
all steps dispatched. Each against hand-made registry windows (the
window's gain and not the totals, nothing under ten steps, nothing from
a program without the counter), and its entry in the manifest, committed
and with a later PR's metric appended after it."""
import pytest

from perfbench_fixtures import real  # noqa: F401

CHAT = "mistral7b-serve-1chip.chat"
SHARE, AHEAD = "chunk_step_share_pct.chat", "steps_ahead_pct.chat"


def reader(man, name):
    entry = [m for m in man.per_layer if m["name"] == name]
    assert len(entry) == 1, f"{name} is not in the manifest"
    return entry[0], man.reader(entry[0])


def kinds(**counts):
    """serve_step_kind_seconds with (seconds, steps) per kind."""
    return {"serve_step_kind_seconds": {"children": {
        k: {"sum": v[0], "count": v[1], "bucket_counts": []}
        for k, v in counts.items()}}}


def modes(**steps):
    return {"serve_steps_dispatched_total": {"children": {
        k: {"value": float(v)} for k, v in steps.items()}}}


def window(reg0, reg1):
    return {"out": {"facts": {"reg0": reg0, "reg1": reg1}}}


@pytest.mark.parametrize("name,reg0,reg1,want", [
    # 900 chunk steps of 4 000: the loaded cell
    (SHARE, kinds(decode=(0.0, 0), chunk=(0.0, 0)),
     kinds(decode=(35.0, 3100), chunk=(18.0, 900)), 22.5),
    # the window's gain, not the totals: the walk's 500 chunk steps and
    # the lead-in's steps came before it
    (SHARE, kinds(decode=(9.0, 800), chunk=(12.0, 500)),
     kinds(decode=(9.0 + 40.0, 800 + 3800), chunk=(12.0 + 4.0, 500 + 200)),
     5.0),
    # the seconds play no part: ten long chunk steps beside ninety short
    (SHARE, kinds(decode=(1.0, 10), chunk=(1.0, 10)),
     kinds(decode=(1.9, 100), chunk=(9.0, 20)), 10.0),
    # a window of decode steps reads 0: a count, not a share of a peak
    (SHARE, kinds(decode=(1.0, 10)), kinds(decode=(2.0, 60)), 0.0),
    # a kind that first appears inside the window
    (SHARE, kinds(decode=(1.0, 10)),
     kinds(decode=(2.0, 40), chunk=(1.0, 10)), 25.0),
    (AHEAD, modes(ahead=0, drained=0), modes(ahead=4389, drained=20),
     100.0 * 4389 / 4409),
    (AHEAD, modes(ahead=700, drained=90), modes(ahead=700 + 990,
                                                drained=90 + 10), 99.0),
    # a scheduler that reads every step before it builds the next
    (AHEAD, modes(drained=5), modes(drained=105), 0.0),
    (AHEAD, modes(drained=5), modes(ahead=45, drained=10), 90.0),
])
def test_the_windows_gain_is_read(real, name, reg0, reg1, want):
    _, read = reader(real, name)
    assert read(window(reg0, reg1)) == pytest.approx(want)


@pytest.mark.parametrize("name,reg0,reg1", [
    (SHARE, {}, {}),                            # a program without the family
    (AHEAD, {}, {}),
    (SHARE, kinds(decode=(5.0, 400), chunk=(3.0, 90)),
     kinds(decode=(5.0, 400), chunk=(3.0, 90))),        # an empty window
    (AHEAD, modes(ahead=400, drained=9), modes(ahead=400, drained=9)),
    (SHARE, kinds(decode=(5.0, 400), chunk=(3.0, 90)),
     kinds(decode=(5.1, 406), chunk=(3.1, 93))),        # nine steps
    (AHEAD, modes(ahead=400, drained=9), modes(ahead=408, drained=10)),
    # the other reader's family alone is nothing to read
    (SHARE, modes(ahead=0), modes(ahead=500)),
    (AHEAD, kinds(decode=(0.0, 0)), kinds(decode=(9.0, 500))),
])
def test_nothing_to_read_is_none(real, name, reg0, reg1):
    _, read = reader(real, name)
    assert read(window(reg0, reg1)) is None


@pytest.mark.parametrize("name,better", [(SHARE, "lower"),
                                         (AHEAD, "higher")])
def test_the_entries_are_the_schedulers_and_chats(real, name, better):
    entry, _ = reader(real, name)
    assert entry == {
        "name": name, "unit": "%", "better": better,
        "source": "program_counter", "layer": "scheduler",
        "moves": "itl_ms.p95", "workloads": [CHAT]}
    assert entry in real.per_layer_of(CHAT)
    assert entry not in real.per_layer_of("mistral7b-train-1chip.seq4k")
