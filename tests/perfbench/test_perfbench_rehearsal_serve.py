"""CPU rehearsal of the serving driver end to end at a tiny width:
client process -> gateway -> stepper -> engine -> paged step, kernels
interpreted. The line says `platform: cpu` and carries no metric."""
import pytest

from perfbench_fixtures import rehearse, tiny_manifest

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload", ["tiny-serve.tiny-chat",
                                      "tiny-serve.tiny-docs"])
def test_rehearsal(tmp_path, capsys, workload):
    rc, line, out = rehearse(capsys, tiny_manifest(str(tmp_path)), workload)
    assert rc == 0
    assert CONTRACT_KEYS <= set(line)
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert line["not_a_measured_run"] == "rehearsal"
    assert line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {c["name"] for c in line["checks"]}
    assert {"mean_gap", "worst_gap", "window_compiles"} <= names
    # every number compared is printed beside its limit
    assert "[check] mean_gap:" in out and "(limit" in out


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, capsys, monkeypatch):
    """The timed path broken underneath: every token the scheduler
    commits is shifted by one. The rest of the run is as ever, and
    `correct` comes out false."""
    from paddle_tpu.incubate.nn import continuous_batching as cbm
    real = cbm.ContinuousBatchingEngine._append_token

    def shifted(self, req, tok, now):
        return real(self, req, (int(tok) + 1) % 128, now)

    monkeypatch.setattr(cbm.ContinuousBatchingEngine, "_append_token",
                        shifted)
    rc, line, out = rehearse(capsys, tiny_manifest(str(tmp_path)),
                             "tiny-serve.tiny-chat")
    assert rc == 0 and line["correct"] is False
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "mean_gap" in bad


def test_a_lower_precision_engine_is_not_correct(tmp_path, capsys):
    """The control, at a size a test can hold: the tiny configuration
    states float32, so the engine built in bfloat16 (the nearest
    precision below) must fail the limits float32 passes. The mix is an
    open loop, whose plan is a fixed number of requests each run to its
    end (the grace is a minute): how many tokens are compared follows
    from the plan, not from how fast this machine steps."""
    import json
    import os

    import traffic
    path = tiny_manifest(str(tmp_path))
    low = os.path.join(str(tmp_path), "low")
    os.makedirs(os.path.join(low, "configs"))
    with open(os.path.join(str(tmp_path), "tiny", "configs",
                           "tiny-serve.json")) as f:
        cfg = json.load(f)
    cfg["dtype"] = "bfloat16"
    cfg["check"]["sample_requests"] = 200    # every finished request
    with open(os.path.join(low, "configs", "tiny-serve-bf16.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(path) as f:
        man = json.load(f)
    man["paths"].append("low")
    man["configs"].append({"name": "tiny-serve-bf16", "source": "tests",
                           "file": "low/configs/tiny-serve-bf16.json",
                           "reduced": [], "why": "control"})
    cell = "tiny-serve-bf16.tiny-control"
    man["workloads"].append({"name": cell, "config": "tiny-serve-bf16",
                             "traffic": "tiny-control", "chips": 1,
                             "why": "control"})
    man["end_to_end"][1]["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(man, f)
    seed, seconds = 3000000019, 3
    with open(os.path.join(str(tmp_path), "tiny", "traffic",
                           "tiny-control.json")) as f:
        plan = traffic.open_loop_plan(json.load(f), seed, seconds)
    due = [r for r in plan if r["phase"] != "lead_out"]
    want = sum(r["max_new_tokens"] for r in due)
    assert len(due) == 42 and want >= 300   # some hundreds of tokens
    rc, line, out = rehearse(capsys, path, cell, seed=seed, seconds=seconds)
    assert rc == 0 and line["correct"] is False, out
    assert line["attempted"] == 30 and line["failed"] == 0, out
    # it is a number of the comparison that fails it, nothing beside
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert bad and bad <= {"mean_gap", "worst_gap", "nonargmax_share"}
    compared = [c for c in line["checks"] if c["name"] == "tokens_compared"]
    assert compared[0]["value"] == want
