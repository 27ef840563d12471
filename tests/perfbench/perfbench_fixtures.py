"""Shared by the harness's tests: a throw-away checkout-shaped directory
whose BENCHMARK.json names tiny configurations (tests/perfbench/data/tiny)
beside the real benchmark directory, and the CPU rehearsal of one cell."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "perfbench")
sys.path.insert(0, os.path.join(BENCH, "lib"))


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


def tiny_manifest(tmp_path, extra_paths=(), configs=(), cells=(),
                  per_layer=(), e2e=()):
    """Write a BENCHMARK.json into tmp_path whose paths are the real
    benchmark directory, the tiny data, and `extra_paths` (directories
    under tmp_path)."""
    for name, target in (("perfbench", BENCH),
                         ("tiny", os.path.join(HERE, "data", "tiny"))):
        link = os.path.join(tmp_path, name)
        if not os.path.exists(link):
            os.symlink(target, link)
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
