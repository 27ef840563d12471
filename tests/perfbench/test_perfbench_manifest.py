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


def test_open_loop_times_count_from_when_a_request_was_due():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_driver", os.path.join(BENCH, "drivers", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
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
