"""The manifest, the traffic generator, the result line, the peaks table,
and that a family, a mix and a metric are added by files alone. Every
test that takes `real` runs on the committed BENCHMARK.json and on a copy
of it to which another family's configuration, cell and metric have been
appended, as a later PR appends them (perfbench_fixtures.foreign_manifest).
"""
import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

from perfbench_fixtures import (BENCH, FOREIGN_CELL, foreign_manifest,
                                real, rehearse, tiny_manifest)  # noqa: F401

import manifest as mf
import measure
import traffic


def test_the_committed_manifest_keeps_the_contract(real):
    assert mf.validate(real) == []
    assert set(real.data) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(real.data)) < 64 * 1024


def test_names_and_units_use_only_the_allowed_characters(real):
    for m in real.end_to_end + real.per_layer:
        assert mf.NAME_RE.match(m["name"]), m["name"]
        assert mf.UNIT_RE.match(m["unit"]) and len(m["unit"]) <= 16
    bad = dict(real.data)
    bad["end_to_end"] = [dict(real.end_to_end[0], unit="tokens per second")]
    probe = mf.Manifest.__new__(mf.Manifest)
    probe.__dict__.update(real.__dict__)
    probe.data, probe.end_to_end = bad, bad["end_to_end"]
    assert any("unit" in c for c in mf.validate(probe))


def test_every_per_layer_metric_moves_a_metric_its_cells_report(real):
    for m in real.per_layer:
        cells = m.get("workloads") or [
            c for c in real.cells
            if m["moves"] in {x["name"] for x in real.end_to_end_of(c)}]
        assert cells, m["name"]
        for c in cells:
            assert m["moves"] in {x["name"]
                                  for x in real.end_to_end_of(c)}, m["name"]
        real.reader(m)        # its reader is found by its name


def test_every_cell_resolves_by_name(real):
    for name, cell in real.cells.items():
        cfg = real.config(cell)
        assert {"source", "reduced", "assumed", "deployment"} <= set(cfg)
        assert real.traffic(cell)["loop"] in ("open", "closed", "steps")
        assert hasattr(real.family(cfg), "dims")
        assert hasattr(real.driver(cfg), "run")


def is_width(key):
    """A key that `reduced` may never name (the contract's widths): a
    hidden, intermediate, latent, state or head size, a rank, and the
    number of experts per token."""
    return (key.endswith(("hidden_size", "intermediate_size", "_dim",
                          "_rank")) or key == "num_experts_per_tok")


def is_number(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def width_faults(cfg, entry):
    """What holds a configuration file to ITS OWN source: the file states
    under `published` what the source's config.json gives for the keys it
    carries, and under `reduced` why each key it changed is changed. The
    complaints, none for a sound file; `entry` is the manifest's."""
    pub, red = cfg.get("published"), cfg.get("reduced")
    if not isinstance(pub, dict) or not pub or not isinstance(red, dict):
        return ["the file needs a `published` and a `reduced` group"]
    bad = []
    if sorted(entry["reduced"]) != sorted(red):
        bad.append(f"the manifest lists {sorted(entry['reduced'])} as "
                   f"reduced, the file {sorted(red)}")
    for key, value in pub.items():
        if key not in red and (key not in cfg or cfg[key] != value):
            bad.append(f"{key} is {cfg.get(key)!r}, published {value!r}, "
                       "and not listed in `reduced`")
    for key, why in red.items():
        if is_width(key):
            bad.append(f"{key} is a width: it may never be reduced")
        if not str(why).strip():
            bad.append(f"`reduced` does not say why {key} is changed")
        if key not in pub or key not in cfg:
            bad.append(f"reduced {key} is not in `published` and the file")
            continue
        here, there = cfg[key], pub[key]
        if here == there:
            bad.append(f"{key} is listed in `reduced` and is unchanged")
        elif is_number(here) and is_number(there) and not here < there:
            bad.append(f"reduced {key} is {here}, above the published "
                       f"{there}")
        elif isinstance(here, dict) and isinstance(there, dict):
            bad += [f"{key}.{k} is a width: it may never be reduced"
                    for k in there if is_width(k) and here.get(k) != there[k]]
    # a number the family can read comes from the source or is owned up to
    bad += [f"{key} = {value} is neither in `published` nor in `assumed`"
            for key, value in cfg.items()
            if is_number(value) and key not in pub
            and key not in cfg.get("assumed", {})]
    return bad


def test_widths_are_the_published_ones(real):
    for cell in real.cells.values():
        cfg = real.config(cell)
        assert width_faults(cfg, real.configs[cell["config"]]) == [], \
            cell["name"]


def _set(key, value, group=None):
    def edit(cfg, entry):
        (cfg if group is None else cfg[group])[key] = value
    return edit


def _list_as_reduced(key):
    def edit(cfg, entry):
        cfg["reduced"][key] = "for room"
        entry["reduced"].append(key)
    return edit


def _shrink_nested_width(cfg, entry):
    _list_as_reduced("linear_attn_config")(cfg, entry)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], head_dim=8)


@pytest.mark.parametrize("edit, complaint", [
    (_set("hidden_size", 40), "hidden_size is 40, published 48"),
    (_set("moe_intermediate_size", 16), "not listed in `reduced`"),
    (_set("linear_attn_config", {"short_conv_kernel_size": 2}),
     "linear_attn_config is"),
    (_list_as_reduced("head_dim"), "head_dim is a width"),
    (_list_as_reduced("moe_intermediate_size"), "is a width"),
    (_list_as_reduced("num_experts_per_tok"), "is a width"),
    (_shrink_nested_width, "linear_attn_config.head_dim is a width"),
    (_list_as_reduced("n_shared_experts"), "is unchanged"),
    (_set("n_routed_experts", 128), "above the published 64"),
    (_set("vocab_size", "", "reduced"), "does not say why vocab_size"),
    (_set("state_size", 16), "neither in `published` nor in `assumed`"),
    (lambda cfg, entry: entry["reduced"].remove("vocab_size"),
     "the manifest lists"),
    (lambda cfg, entry: cfg.pop("published"), "needs a `published`"),
])
def test_a_configuration_that_leaves_its_source_unsaid_is_caught(
        tmp_path, edit, complaint):
    """The foreign file is sound; each edit is one way a later PR could
    cut a configuration without saying so. The widths test fails on the
    manifest that holds the spoilt file, and the fault is named."""
    m = mf.Manifest(foreign_manifest(str(tmp_path), edit=edit))
    cell = m.cell(FOREIGN_CELL)
    faults = width_faults(m.config(cell), m.configs[cell["config"]])
    assert any(complaint in f for f in faults), faults
    with pytest.raises(AssertionError):
        test_widths_are_the_published_ones(m)


def test_the_foreign_cell_rehearses_through_the_committed_manifest(
        tmp_path, capsys):
    """The appended cell resolves by name beside the committed cells
    (family, mix, driver, its per-layer metric and no other's) and its
    CPU rehearsal comes out correct."""
    path = foreign_manifest(str(tmp_path))
    m = mf.Manifest(path)
    assert mf.validate(m) == []
    assert m.family(m.config(m.cell(FOREIGN_CELL))).FAMILY_NAME == "foreign"
    assert [x["name"] for x in m.end_to_end_of(FOREIGN_CELL)] \
        == ["setup_s", "itl_ms.p95"]
    assert [x["name"] for x in m.per_layer_of(FOREIGN_CELL)] \
        == ["foreign_steps"]
    assert m.per_layer[-1]["name"] == "foreign_steps"
    rc, line, out = rehearse(capsys, path, FOREIGN_CELL)
    assert rc == 0 and line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traffic_is_reproducible_and_every_seed_gets_the_same_work():
    mix = dict(loop="open", rate_per_s=6.4, lead_in_s=6, grace_s=10,
               lead_in_burst=12,
               prompt=dict(dist="pareto", median=200, alpha=1.5, min=16,
                           max=1024),
               output=dict(dist="lognormal", median=64, mean=96, min=4,
                           max=384))
    big = 2 ** 31 + 12345
    a = traffic.open_loop_plan(mix, big, 30)
    b = traffic.open_loop_plan(mix, big, 30)
    c = traffic.open_loop_plan(mix, big + 1, 30)
    assert a == b and a != c
    win = lambda p: [r for r in p if r["phase"] == "window"]
    assert len(win(a)) == round(6.4 * 30)
    for key in ("prompt_len", "max_new_tokens"):
        assert sorted(r[key] for r in win(a)) == sorted(r[key]
                                                        for r in win(c))
    # the same gaps in another order: same first-to-last span
    due = [r["due_s"] for r in win(a)]
    assert 0 <= min(due) and max(due) < 30
    assert all(r["due_s"] < 0 for r in a if r["phase"] == "lead_in")
    med = np.median([r["prompt_len"] for r in win(a)])
    assert 180 <= med <= 220
    assert (traffic.prompt_tokens(big, 3, 50, 1000)
            == traffic.prompt_tokens(big, 3, 50, 1000)).all()
    assert traffic.prompt_tokens(big, 3, 50, 1000).min() >= 1


@pytest.mark.parametrize("n,block", [(272, 16), (160, 8), (48, 4)])
def test_every_block_of_the_order_is_a_stratified_sample(n, block):
    """`dealt`, where the blocks come out even: each block of the order
    holds exactly one value of every run of b consecutive order
    statistics."""
    vals = traffic.exp_gaps(5.6, n)
    out = traffic.dealt(vals, block, np.random.default_rng(n))
    b = n // block
    ranks = np.searchsorted(vals, out)
    for lo in range(0, n, block):
        assert sorted(r // b for r in ranks[lo:lo + block]) \
            == list(range(block))
    assert traffic.dealt(vals, block, np.random.default_rng(n + 1)).tolist() \
        != out.tolist()


@pytest.mark.parametrize("n,block", [(269, 16), (45, 16), (168, 16),
                                     (100, 8), (33, 4)])
def test_a_block_wise_order_keeps_the_multiset(n, block):
    """Any n, where the last run of order statistics is short and some
    blocks hold one value more: the same values, the same order from
    the same draw, and sums over whole blocks nearer their share than a
    free order's (over a dozen draws, at the blocks' mean length)."""
    vals = traffic.exp_gaps(5.6, n)
    m = n // round(n / block)
    stray = lambda order: np.abs(
        np.cumsum(order)[m - 1::m] - np.arange(m, n + 1, m) / n
        * vals.sum()).mean()
    blocked, free = [], []
    for k in range(12):
        out = traffic.dealt(vals, block, np.random.default_rng([n, k]))
        assert sorted(out) == sorted(vals)
        assert (out == traffic.dealt(vals, block,
                                     np.random.default_rng([n, k]))).all()
        blocked.append(stray(out))
        free.append(stray(np.random.default_rng([n, k, 1])
                          .permutation(vals)))
    assert np.mean(blocked) < np.mean(free)


def test_without_a_block_or_with_too_few_values_any_order_is_drawn():
    vals = traffic.lengths(dict(dist="uniform", min=1, max=100), 20)
    for block in (None, 0, 16, 40):        # 20 / 16 rounds to one block
        a = traffic.dealt(vals, block, np.random.default_rng(3))
        b = np.random.default_rng(3).permutation(vals)
        assert (a == b).all()


def test_chats_plan_is_one_multiset_in_a_block_wise_order():
    """The committed mix, whatever its rate: every seed the same sizes
    and gaps, rate x seconds of them, and every sixth of the window
    brings about a sixth of the requests and of the tokens, whichever
    seed."""
    with open(os.path.join(BENCH, "traffic", "chat.json")) as f:
        mix = json.load(f)
    assert mix["order_block"] >= 4 and len(mix["order_block_why"]) > 100
    n = round(mix["rate_per_s"] * 48)
    plans = [[r for r in traffic.open_loop_plan(mix, s, 48)
              if r["phase"] == "window"]
             for s in (2 ** 31 + 3, 3000002011, 7)]
    for key in ("prompt_len", "max_new_tokens"):
        assert len({tuple(sorted(r[key] for r in p)) for p in plans}) == 1
    assert plans[0] != plans[1]
    total = sum(r["prompt_len"] + r["max_new_tokens"] for r in plans[0])
    for p in plans:
        assert len(p) == n
        for k in range(6):
            part = [r for r in p if 8 * k <= r["due_s"] < 8 * (k + 1)]
            assert 0.75 * n / 6 < len(part) < 1.25 * n / 6
            work = sum(r["prompt_len"] + r["max_new_tokens"] for r in part)
            assert 0.7 * total / 6 < work < 1.3 * total / 6


def _serve_driver():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_driver", os.path.join(BENCH, "drivers", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    return serve


def test_open_loop_times_count_from_when_a_request_was_due():
    serve = _serve_driver()
    rec = lambda due, sent, first: dict(
        phase="window", due=due, sent=sent, status="finished",
        max_new_tokens=2, tokens=[1, 2], prompt_len=5, index=0,
        events=[(first, 1), (first + 0.010, 1)])
    late = [rec(1.0, 1.5, 2.0)] * 20        # sent half a second late
    m, counts = serve.end_to_end(late, dict(loop="open", grace_s=5), 10.0)
    assert m["ttft_ms.p95"] == pytest.approx(1000.0)     # from due, not sent
    assert counts["lateness_ms_p95"] == pytest.approx(500.0)
    assert m["itl_ms.p95"] == pytest.approx(10.0)
    failed = [dict(rec(1.0, 1.0, 2.0), status="truncated", events=[])]
    m, counts = serve.end_to_end(late + failed, dict(loop="open",
                                                     grace_s=5), 10.0)
    assert counts["failed"] == 1 and counts["attempted"] == 21


def _stream(due, first, n, gap_ms, end=None, phase="window", late=0.0):
    """One finished request: first token at `first`, then one every
    `gap_ms`."""
    events = [(first + i * gap_ms / 1e3, 1) for i in range(n)]
    return dict(phase=phase, due=due, sent=due + late, status="finished",
                max_new_tokens=n, tokens=list(range(n)), prompt_len=5,
                index=0, events=events,
                end=events[-1][0] if end is None else end)


def test_the_percentiles_beside_the_p95_show_the_two_populations():
    """800 gaps of 11 ms and 200 of 20: the p95 sits among the chunk
    steps' gaps, and p50 / p90 / p99, printed beside it, say so."""
    serve = _serve_driver()
    recs = [_stream(0.5, 1.0, 801, 11.0), _stream(0.5, 1.0, 201, 20.0)]
    m, counts = serve.end_to_end(recs, dict(loop="open", grace_s=5), 10.0)
    assert counts["itl_samples"] == 1000
    assert m["itl_ms.p50"] == pytest.approx(11.0)
    assert m["itl_ms.p90"] == pytest.approx(20.0)
    assert m["itl_ms.p95"] == pytest.approx(20.0)
    assert m["itl_ms.p99"] == pytest.approx(20.0)


@pytest.mark.parametrize("lo,hi,want", [
    (0.0, 10.0, (4.0 + 2.0 + 5.0) / 10.0),
    (0.0, 5.0, (4.0 + 0.0 + 0.0) / 5.0),    # the window's opening
    (5.0, 10.0, (0.0 + 2.0 + 5.0) / 5.0),
])
def test_streams_in_flight_is_a_mean_over_the_clients_clock(lo, hi, want):
    """Sent to ended, clipped to the interval: one stream over [-1, 4),
    one over [6, 8), one sent at 5 that never ended (held to the
    horizon), one never sent."""
    serve = _serve_driver()
    recs = [dict(sent=-1.0, end=4.0), dict(sent=6.0, end=8.0),
            dict(sent=5.0, end=None), dict(sent=None, end=None)]
    assert serve.streams_in_flight(recs, lo, hi, 40.0) \
        == pytest.approx(want)


def _sweep_facts(decode, chunk):
    kind = lambda d, c: {"serve_step_kind_seconds": {"children": {
        "decode": {"sum": 0.0, "count": d}, "chunk": {"sum": 0.0,
                                                      "count": c}}}}
    return {"reg0": kind(100, 50), "reg1": kind(100 + decode, 50 + chunk),
            "blocks_high_water": 57}


@pytest.mark.parametrize("why,ttft_first,ttft_second,late,failed,want", [
    ("steady", 0.30, 0.30, 0.0, 0, True),
    ("a backlog that grows: the second half waits longer", 0.30, 0.45,
     0.0, 0, False),
    ("the noise of two halves: 60 ms more on 300", 0.30, 0.36, 0.0, 0,
     True),
    ("a fifth more is noise where a tenth of the limit is not", 0.70, 0.83,
     0.0, 0, True),
    ("a backlog that shrinks", 0.45, 0.30, 0.0, 0, True),
    ("time to first token over the limit of a second", 1.2, 1.1, 0.0, 0,
     False),
    ("the client did not keep up", 0.30, 0.30, 0.080, 0, False),
    ("a request failed", 0.30, 0.30, 0.0, 1, False),
])
def test_a_rate_is_sustained_by_the_knees_own_definition(
        why, ttft_first, ttft_second, late, failed, want):
    serve = _serve_driver()
    recs = [_stream(t, t + late + (ttft_first if t < 5.0 else ttft_second),
                    4, 11.0, late=late) for t in np.arange(0.0, 10.0, 0.25)]
    recs += [dict(_stream(9.0, 9.3, 4, 11.0), status="truncated",
                  events=[])] * failed
    mix = dict(loop="open", grace_s=5)
    m, counts = serve.end_to_end(recs, mix, 10.0)
    with open(os.path.join(BENCH, "traffic", "chat.json")) as f:
        limits = json.load(f)["knee_limits"]
    row = serve.sweep_row(4.0, recs, _sweep_facts(3000, 900), m, counts,
                          10.0, limits)
    assert row["sustained"] is want, why
    assert row["steps"] == {"decode": 3000, "chunk": 900}
    assert row["blocks_high_water"] == 57
    assert row["attempted"] == 40 + failed and row["failed"] == failed
    assert {"itl_p50", "itl_p90", "itl_p95", "itl_p99", "ttft_p50",
            "ttft_p95", "lateness_ms_p95", "tokens_per_s",
            "streams_window", "streams_first_5s"} <= set(row)


KNEE_LIMITS = {"lateness_ms_p95_max", "ttft_ms_p95_max",
               "ttft_growth_share", "ttft_growth_ms"}


def rate_faults(mix):
    """What a test can hold of the rules a serving cell is proven by
    (PERF.md section 4): an open-loop mix states the knee a sweep found,
    the limits that defined it, and offers four fifths of it."""
    if mix["loop"] != "open":
        return []
    bad = []
    if not is_number(mix.get("knee_per_s")):
        return ["no knee_per_s"]
    if set(mix.get("knee_limits", ())) - {"why"} != KNEE_LIMITS:
        bad.append(f"knee_limits is not {sorted(KNEE_LIMITS)}")
    if mix["rate_per_s"] != round(0.8 * mix["knee_per_s"], 1):
        bad.append(f"rate_per_s {mix['rate_per_s']} is not 0.8 of the knee "
                   f"{mix['knee_per_s']}")
    return bad


def test_every_open_loop_cell_offers_four_fifths_of_its_swept_knee(real):
    for name, cell in real.cells.items():
        assert rate_faults(real.traffic(cell)) == [], name


@pytest.mark.parametrize("edit,complaint", [
    (lambda m: m.pop("knee_per_s"), "no knee_per_s"),
    (lambda m: m.update(rate_per_s=1.7), "is not 0.8 of the knee"),
    (lambda m: m.update(knee_per_s=2.1), "is not 0.8 of the knee"),
    (lambda m: m.pop("knee_limits"), "knee_limits is not"),
    (lambda m: m["knee_limits"].pop("ttft_growth_ms"), "knee_limits is not"),
])
def test_a_stale_rate_or_knee_is_told(real, edit, complaint):
    mix = json.loads(json.dumps(
        real.traffic(real.cell("mistral7b-serve-1chip.chat"))))
    edit(mix)
    assert any(complaint in c for c in rate_faults(mix)), rate_faults(mix)


def test_chats_walk_stops_where_the_mix_says(real):
    """The lattice follows the rate: the more requests a window holds,
    the further into the size tails its `max_batch` largest reach, and
    the hard bound on the blocks in flight passes a power of two that
    the machines' compile cache has no room for (it holds 64 of these
    bucket programs). The mix stops the walk at `warm_t_hi` and says
    why; without the key the hard bound decides, and a bound under the
    mix's stop is kept."""
    serve = _serve_driver()
    cell = real.cell("mistral7b-serve-1chip.chat")
    cfg, mix = real.config(cell), real.traffic(cell)
    e = cfg["engine"]
    levels, widths = serve.lattice_of(cfg, mix, real.run_seconds)
    assert levels[0] == mix["warm_t_lo"] == 1
    assert levels == [1 << i for i in range(len(levels))]
    assert widths[-1] == e["prefill_chunk"]
    assert levels[-1] == serve.next_pow2(mix["warm_t_hi"])
    assert len(levels) * len(widths) <= 64
    assert len(mix["warm_t_hi_why"]) > 100
    n = round(mix["rate_per_s"] * real.run_seconds)
    hard_bound = traffic.footprint_bound(mix, n, e["max_batch"],
                                         e["block_size"])
    assert hard_bound > mix["warm_t_hi"]        # or the key has no use
    free = {k: v for k, v in mix.items() if k != "warm_t_hi"}
    hard, _ = serve.lattice_of(cfg, free, real.run_seconds)
    assert hard[:len(levels)] == levels
    assert hard[-1] == serve.next_pow2(hard_bound) > levels[-1]
    low = dict(mix, warm_t_hi=40)
    assert serve.lattice_of(cfg, low, real.run_seconds)[0][-1] == 64
    few = dict(mix, rate_per_s=0.4)     # 19 requests pair to few blocks
    assert traffic.footprint_bound(few, 19, e["max_batch"],
                                   e["block_size"]) < mix["warm_t_hi"]
    assert serve.lattice_of(cfg, few, real.run_seconds)[0][-1] \
        <= levels[-1]


def test_the_result_line_has_exactly_the_contract_keys():
    buf = io.StringIO()
    with redirect_stdout(buf):
        measure.emit({"correct": True, "attempted": 3, "failed": 0,
                      "metrics": {"setup_s": measure.metric(1.25, "s")},
                      "device": {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1, "memory_peak_bytes": 1}})
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"]["setup_s"] == {"value": 1.25, "unit": "s"}
    with pytest.raises(KeyError):
        measure.emit({"correct": True})


def test_an_unknown_device_has_no_peaks():
    assert measure.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        measure.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        measure.peaks_for("_source")


def test_spread_is_the_contracts_quartile_distance():
    vals = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert measure.spread(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))


FAMILY = '''
"""A throw-away second family: the Mistral equations under another name."""
import importlib.util, os
_p = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))), "perfbench", "models", "mistral.py")
_s = importlib.util.spec_from_file_location("toy_base", _p)
_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)
globals().update({k: v for k, v in vars(_m).items() if not k.startswith("__")})
FAMILY_NAME = "toy"
'''


def test_a_family_a_mix_and_a_metric_are_added_by_files_alone(tmp_path,
                                                              capsys):
    tmp = str(tmp_path)
    extra = os.path.join(tmp, "later_pr")
    for d in ("models", "traffic", "metrics", "configs"):
        os.makedirs(os.path.join(extra, d))
    with open(os.path.join(extra, "models", "toy.py"), "w") as f:
        f.write(FAMILY)
    with open(os.path.join(BENCH, "..", "tests", "perfbench", "data", "tiny",
                           "configs", "tiny-serve.json")) as f:
        cfg = json.load(f)
    cfg["family"] = "toy"
    with open(os.path.join(extra, "configs", "toy-serve.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(extra, "traffic", "tiny-burst.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 9.0, "lead_in_s": 0.5,
                   "lead_in_burst": 3, "grace_s": 20.0, "warm_t_lo": 4,
                   "prompt": {"dist": "uniform", "min": 4, "max": 30},
                   "output": {"dist": "const", "value": 4, "min": 4,
                              "max": 4}}, f)
    with open(os.path.join(extra, "metrics", "toy_steps.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['out']['facts']"
                "['engine']['steps'])\n")
    path = tiny_manifest(
        tmp, extra_paths=["later_pr"],
        configs=[{"name": "toy-serve", "source": "tests", "reduced": [],
                  "file": "later_pr/configs/toy-serve.json", "why": "t"}],
        cells=[{"name": "toy-serve.tiny-burst", "config": "toy-serve",
                "traffic": "tiny-burst", "chips": 1, "why": "t"}],
        per_layer=[{"name": "toy_steps", "unit": "steps", "better": "higher",
                    "source": "program_counter", "layer": "scheduler",
                    "moves": "ttft_ms.p95",
                    "workloads": ["toy-serve.tiny-burst"]}])
    with open(path) as f:
        man = json.load(f)
    man["end_to_end"][1]["workloads"].append("toy-serve.tiny-burst")
    with open(path, "w") as f:
        json.dump(man, f)
    m = mf.Manifest(path)
    assert mf.validate(m) == []
    cell = m.cell("toy-serve.tiny-burst")
    assert m.family(m.config(cell)).FAMILY_NAME == "toy"
    assert m.traffic(cell)["rate_per_s"] == 9.0
    toy = [x for x in m.per_layer_of(cell["name"]) if x["name"] == "toy_steps"]
    assert m.reader(toy[0])({"out": {"facts": {"engine": {"steps": 7}}}}) == 7
    rc, line, out = rehearse(capsys, path, "toy-serve.tiny-burst")
    assert rc == 0 and line["correct"] is True, out
