"""CPU rehearsal of the training driver end to end at a tiny width, the
control (the reference with fp8 matmuls in the program's place) and a
step that returns its state unchanged: both must come out not correct."""
from perfbench_fixtures import rehearse, tiny_manifest


def test_rehearsal(tmp_path, capsys):
    rc, line, out = rehearse(capsys, tiny_manifest(str(tmp_path)),
                             "tiny-train.tiny-seq")
    assert rc == 0 and line["correct"] is True, out
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    names = {c["name"] for c in line["checks"]}
    assert {"loss_step0", "loss_step2", "grad_norm_worst_leaf",
            "update_norm_worst_leaf"} <= names
    assert line["attempted"] > 0


def test_the_loss_sequence_of_a_seed_reproduces(tmp_path, capsys):
    path = tiny_manifest(str(tmp_path))
    a = rehearse(capsys, path, "tiny-train.tiny-seq", seed=11)[1]
    b = rehearse(capsys, path, "tiny-train.tiny-seq", seed=11)[1]
    c = rehearse(capsys, path, "tiny-train.tiny-seq", seed=12)[1]
    loss = lambda line: [r["program"] for r in line["checks"]
                         if r["name"].startswith("loss_step")]
    assert loss(a) == loss(b) and loss(a) != loss(c)


def test_the_fp8_control_is_not_correct(tmp_path, capsys):
    rc, line, out = rehearse(capsys, tiny_manifest(str(tmp_path)),
                             "tiny-train.tiny-seq", extra=("--control",
                                                           "fp8"))
    assert rc == 0 and line["correct"] is False, out
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "grad_norm_worst_leaf" in bad


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, capsys, monkeypatch):
    from paddle_tpu.models import pretrain
    real = pretrain.make_train_step

    def broken(model, mesh, meta, **kw):
        step = real(model, mesh, meta, donate=False, **kw)

        def run(params, opt_state, batch):
            _, _, loss, gnorm = step(params, opt_state, batch)
            return params, opt_state, loss, gnorm
        return run

    monkeypatch.setattr(pretrain, "make_train_step", broken)
    rc, line, out = rehearse(capsys, tiny_manifest(str(tmp_path)),
                             "tiny-train.tiny-seq")
    assert rc == 0 and line["correct"] is False
    bad = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "update_norm_worst_leaf" in bad
