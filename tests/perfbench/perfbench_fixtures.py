"""Shared by the harness's tests: a throw-away checkout-shaped directory
whose BENCHMARK.json names tiny configurations (tests/perfbench/data/tiny)
beside the real benchmark directory, a copy of the committed
BENCHMARK.json with a foreign configuration appended as a later PR would
(tests/perfbench/data/foreign), and the CPU rehearsal of one cell."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "perfbench")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import manifest as mf  # noqa: E402


def load_run():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


E2E = [
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
    {"name": "ttft_ms.p95", "unit": "ms", "better": "lower", "bound": 0.05,
     "source": "host_clock", "workloads": ["tiny-serve.tiny-chat"]},
    {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.05, "source": "host_clock",
     "workloads": ["tiny-serve.tiny-docs"]},
    {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.05, "source": "host_clock",
     "workloads": ["tiny-train.tiny-seq"]},
]


def _link(tmp_path, **targets):
    """The directories a throw-away manifest's `paths` name, as links."""
    for name, target in targets.items():
        link = os.path.join(tmp_path, name)
        if not os.path.exists(link):
            os.symlink(target, link)


def tiny_manifest(tmp_path, extra_paths=(), configs=(), cells=(),
                  per_layer=(), e2e=()):
    """Write a BENCHMARK.json into tmp_path whose paths are the real
    benchmark directory, the tiny data, and `extra_paths` (directories
    under tmp_path)."""
    _link(tmp_path, perfbench=BENCH, tiny=os.path.join(HERE, "data", "tiny"))
    man = {
        "command": ["python3", "perfbench/run.py"],
        "paths": ["perfbench", "tiny", *extra_paths],
        "run_seconds": 2,
        "configs": [
            {"name": "tiny-serve", "source": "tests",
             "file": "tiny/configs/tiny-serve.json", "reduced": [],
             "why": "CPU rehearsal"},
            {"name": "tiny-train", "source": "tests",
             "file": "tiny/configs/tiny-train.json", "reduced": [],
             "why": "CPU rehearsal"}, *configs],
        "workloads": [
            {"name": "tiny-serve.tiny-chat", "config": "tiny-serve",
             "traffic": "tiny-chat", "chips": 1, "why": "open loop"},
            {"name": "tiny-serve.tiny-docs", "config": "tiny-serve",
             "traffic": "tiny-docs", "chips": 1, "why": "closed loop"},
            {"name": "tiny-train.tiny-seq", "config": "tiny-train",
             "traffic": "tiny-seq", "chips": 1, "why": "steps"}, *cells],
        "end_to_end": [*E2E, *e2e],
        "per_layer": [
            {"name": "compile_s", "unit": "s", "better": "lower",
             "source": "program_counter", "layer": "platform",
             "moves": "setup_s"}, *per_layer],
    }
    path = os.path.join(tmp_path, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(man, f)
    return path


FOREIGN_CELL = "foreign-serve.foreign-chat"


def foreign_manifest(tmp_path, edit=None):
    """Write into tmp_path a copy of the COMMITTED BENCHMARK.json with
    what a `model_config` PR adds and nothing edited: a directory of its
    own among `paths`, a configuration of another family (none of the
    committed widths, a layer pattern, experts held beside the published
    count, a sliced vocabulary), one cell of it, the cell's name on
    `itl_ms.p95`, and one per-layer metric at the END of `per_layer`.
    `edit(cfg, entry)` spoils the configuration and its manifest entry
    first; the spoilt file then lies in tmp_path."""
    foreign = os.path.join(HERE, "data", "foreign")
    _link(tmp_path, perfbench=BENCH, foreign=foreign)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    with open(os.path.join(foreign, "configs", "foreign-serve.json")) as f:
        cfg = json.load(f)
    entry = {"name": "foreign-serve", "source": "tests",
             "file": "foreign/configs/foreign-serve.json",
             "reduced": list(cfg["reduced"]),
             "why": "another family's shapes, at a width the CPU holds"}
    man["paths"].append("foreign")
    if edit is not None:
        edit(cfg, entry)
        man["paths"].append("spoilt")
        entry["file"] = "spoilt/foreign-serve.json"
        os.makedirs(os.path.join(tmp_path, "spoilt"))
        with open(os.path.join(tmp_path, entry["file"]), "w") as f:
            json.dump(cfg, f)
    man["configs"].append(entry)
    man["workloads"].append(
        {"name": FOREIGN_CELL, "config": "foreign-serve",
         "traffic": "foreign-chat", "chips": 1, "why": "open loop"})
    itl = [m for m in man["end_to_end"] if m["name"] == "itl_ms.p95"]
    itl[0]["workloads"].append(FOREIGN_CELL)
    man["per_layer"].append(
        {"name": "foreign_steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "itl_ms.p95", "workloads": [FOREIGN_CELL]})
    path = os.path.join(tmp_path, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(man, f)
    return path


@pytest.fixture(scope="module", params=["committed", "with_a_foreign_cell"])
def real(request, tmp_path_factory):
    """The committed manifest, and the same with a later PR's additions:
    what holds for the one has to hold for the other."""
    if request.param == "committed":
        return mf.Manifest(os.path.join(REPO, "BENCHMARK.json"))
    return mf.Manifest(foreign_manifest(
        str(tmp_path_factory.mktemp("foreign"))))


def rehearse(capsys, manifest, workload, seed=3000000019, seconds=2,
             extra=()):
    """Run one cell on the CPU through run.main and return (exit code,
    the parsed result line, everything printed)."""
    run = load_run()
    rc = run.main(["--manifest", manifest, "--workload", workload,
                   "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    return rc, json.loads(last), out
