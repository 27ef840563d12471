"""What PR 40 appended to the benchmark: the MiMo-V2-Flash configuration
and its cell resolve by name and are held to their source, the family's
cost functions count what the shapes say, each new reader reads a small
registry or trace whose answer is computed by hand (and None from a
program that has nothing to read), and the cell rehearses on the CPU at a
toy's widths through the same driver."""
import json
import os

import pytest

from perfbench_fixtures import HERE, REPO, rehearse, tiny_manifest

import manifest as mf
from test_perfbench_annotations import DATA, encode_xspace, registry, window
from test_perfbench_manifest import rate_faults, width_faults

CELL = "mimo-v2-flash-serve-1chip.mixedlen"
MANIFEST = mf.Manifest(os.path.join(REPO, "BENCHMARK.json"))


def reader(name):
    entry = [m for m in MANIFEST.per_layer if m["name"] == name]
    assert len(entry) == 1, f"{name} is not in BENCHMARK.json"
    return MANIFEST.reader(entry[0])


def test_the_cell_resolves_and_is_held_to_its_source():
    assert mf.validate(MANIFEST) == []
    cell = MANIFEST.cell(CELL)
    cfg, mix = MANIFEST.config(cell), MANIFEST.traffic(cell)
    assert width_faults(cfg, MANIFEST.configs[cell["config"]]) == []
    assert rate_faults(mix) == []
    assert "block-stratified" in cell["why"] and "expert" in cell["why"]
    assert "block-stratified" in mix["why"] and mix["order_block"] == 16
    assert [m["name"] for m in MANIFEST.end_to_end_of(CELL)] \
        == ["setup_s", "itl_ms.p95"]
    fam = MANIFEST.family(cfg)
    d = fam.dims(cfg)
    # no width differs from the source's; the cut is depth, experts held
    # and the vocabulary's slice
    pub = cfg["published"]
    assert (d.E, d.H, d.Dk, d.Dv, d.F, d.Fe, d.top_k, d.routed) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["head_dim"],
        pub["v_head_dim"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["num_experts_per_tok"],
        pub["n_routed_experts"])
    assert d.rot == 64 and d.held == 16 and d.V * 8 == pub["vocab_size"]
    assert d.kvh == (4, 8, 8, 8, 8, 4, 8, 8, 8, 8, 8)
    assert d.window == tuple(0 if k == 4 else 128 for k in d.kvh)
    assert d.moe == (False,) + (True,) * 10
    # the walk is 64 buckets: 8 levels x 8 widths
    driver = MANIFEST.driver(cfg)
    levels, widths = driver.lattice_of(cfg, mix, MANIFEST.run_seconds)
    assert len(levels) * len(widths) <= 64 and levels[-1] == 128
    e = cfg["engine"]
    assert e["num_blocks"] == 1 + e["max_batch"] * (
        e["max_seq_len"] // e["block_size"])
    assert mix["prompt"]["max"] + mix["output"]["max"] <= e["max_seq_len"]


def test_every_seed_gives_every_chips_share_the_same_router_biases():
    """The refused check of PR 40: the router's correction bias decides
    the selection, so drawn freely the seed decided how many held experts
    a step reaches, and with that the cell's step time. Each share's
    biases are one multiset in a seeded order: none zero, and the share
    of a token's assignments that fall on the held experts, router and
    biases at the cell's widths, stays within a few per cent of 1/16 over
    seeds (free draws of the same spread read 0.41-0.59 of a token's 8)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = MANIFEST.config(MANIFEST.cell(CELL))
    fam = MANIFEST.family(cfg)
    d = fam.dims(cfg)
    assert d.shares == cfg["deployment_chips"] == d.routed // d.held
    here = []
    for seed in (3, 2 ** 31 + 5, 2 ** 32 + 7, 11):
        kr, kb, kz = jax.random.split(fam._key(seed, 9), 3)
        b = np.asarray(fam.share_biases(kb, d), np.float32)
        shares = np.sort(b.reshape(d.shares, -1), axis=1)
        assert (shares == shares[0]).all() and (b != 0).all()
        assert b.std() == pytest.approx(fam.BIAS_STD, rel=0.1)
        assert len({tuple(np.argsort(r)) for r in b.reshape(d.shares, -1)}) \
            > 1                         # each share in an order of its own
        router = fam.INIT_STD * jax.random.normal(kr, (d.E, d.routed))
        z = jax.random.normal(kz, (2048, d.E))
        w = fam.routing(z, router, jnp.asarray(b), d.top_k)
        assert float((w > 0).sum(1).mean()) == d.top_k
        here.append(float((w[:, d.lo:d.lo + d.held] > 0).sum(1).mean()))
    assert max(here) - min(here) < 0.08 * d.top_k / d.shares, here
    # a share's biases do not change with what this chip holds
    whole = dict(cfg, n_routed_experts=d.routed)
    np.testing.assert_array_equal(
        np.asarray(fam.share_biases(kb, fam.dims(whole))), b)


def test_the_cost_functions_count_what_the_shapes_say():
    cfg = MANIFEST.config(MANIFEST.cell(CELL))
    fam = MANIFEST.family(cfg)
    # ISSUE 40's count: 5.42 B parameters, 10.10 GiB in bfloat16
    assert fam.weight_bytes(cfg) / 2 ** 30 == pytest.approx(10.10, abs=0.01)
    full = (64 + 4) * 192 + 4 * 128 + 64 * 128      # x 4096: 89.1 M
    swa = (64 + 8) * 192 + 8 * 128 + 64 * 128       # 94.4 M
    assert fam.token_matmul_flops(cfg) == 2.0 * 4096 * (
        2 * full + 9 * swa + 3 * 16384 + 10 * 256)
    assert fam.expert_flops(cfg) == 2.0 * 3 * 2048 * 4096
    assert fam.head_flops(cfg) == 2.0 * 4096 * 19072
    assert fam.attention_pair_flops(cfg) == (2.0 * 64 * 320 * 2,
                                             2.0 * 64 * 320 * 9)


def test_the_counter_readers_on_a_registry_computed_by_hand():
    reg0 = registry(
        serve_kv_block_steps_total={"full": 100.0, "window": 50.0},
        serve_attn_entries_total={"full": 40.0, "window": 30.0})
    reg1 = registry(
        serve_kv_block_steps_total={"full": 1100.0, "window": 300.0},
        serve_attn_entries_total={"full": 440.0, "window": 130.0})
    ctx = window(reg0, reg1)
    assert reader("window_blocks_held_pct.mixedlen")(ctx) \
        == pytest.approx(25.0)
    assert reader("window_attn_entries_pct.mixedlen")(ctx) \
        == pytest.approx(25.0)
    # a program that counts none of it: nothing to read
    silent = window(registry(), registry())
    for name in ("window_blocks_held_pct.mixedlen",
                 "window_attn_entries_pct.mixedlen"):
        assert reader(name)(silent) is None


def test_the_steps_share_of_the_peak_from_counts():
    cfg = MANIFEST.config(MANIFEST.cell(CELL))
    fam = MANIFEST.family(cfg)
    # 1000 tokens stepped: 80 assignments each over the ten expert
    # layers, a sixteenth of them here; 100 sampled; pairs by kind
    reg1 = registry(
        serve_tokens_stepped_total={"": 1000.0},
        serve_moe_assignments_total={"here": 5000.0, "elsewhere": 75000.0},
        serve_tokens_total={"": 100.0},
        serve_attn_pairs_total={"full": 1e6, "window": 1e5})
    ctx = window(registry(), reg1, family=fam, config=cfg, seconds=2.0,
                 devices=[0], peaks={"flops_per_s": {"bfloat16": 197e12}})
    ops = (1000 * fam.token_matmul_flops(cfg) + 5000 * fam.expert_flops(cfg)
           + 100 * fam.head_flops(cfg) + 1e6 * 2 * 64 * 320 * 2
           + 1e5 * 2 * 64 * 320 * 9)
    assert reader("step_mfu_pct.mixedlen")(ctx) \
        == pytest.approx(100.0 * ops / (2.0 * 197e12))
    assert reader("step_mfu_pct.mixedlen")(
        window(registry(), registry(), family=fam, config=cfg, seconds=2.0,
               devices=[0], peaks=ctx["peaks"])) is None
    other = MANIFEST.family(MANIFEST.config(
        MANIFEST.cell("mistral7b-serve-1chip.chat")))
    assert reader("step_mfu_pct.mixedlen")(dict(ctx, family=other)) is None


def test_the_expert_share_of_the_steps_from_scopes_and_kernel_names(
        tmp_path):
    """The hand-made trace with its feed-forward op under `moe_experts`
    and its last fusion renamed as XLA's grouped product, which carries
    no scope: 500 + 300 of the 1500 busy inside the three steps."""
    space = json.loads(json.dumps(DATA["xplane"]))
    md = space["planes"][1]["event_metadata"]
    md["11"][1]["tf_op"] = "jit(paged_step)/ffn/moe_experts/gather:"
    md["300"] = ["%ragged-dot-none.3 = bf16[8] custom-call(bf16[8] %x)",
                 {"tf_op": "ragged-dot-none"}]
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "p.xplane.pb").write_bytes(encode_xspace(space))
    ctx = {"trace_dir": str(tmp_path), "trace": DATA["xplane_steps"]}
    assert reader("moe_share_pct.mixedlen")(ctx) \
        == pytest.approx(100.0 * 800 / 1500)
    # the parent's program: neither scope nor kernel
    (d / "p.xplane.pb").write_bytes(encode_xspace(DATA["xplane"]))
    assert reader("moe_share_pct.mixedlen")(ctx) is None
    assert reader("moe_share_pct.mixedlen")(
        {"trace_dir": str(tmp_path / "none"),
         "trace": DATA["xplane_steps"]}) is None


def test_the_cell_rehearses_on_the_cpu_at_a_toys_widths(tmp_path, capsys):
    """Client process -> gateway -> stepper -> scheduler with two block
    tables -> the paged step with both kinds of layer and a share of the
    experts, kernels interpreted; held to the family's own reference."""
    os.symlink(os.path.join(HERE, "data", "tiny-mimo"),
               os.path.join(str(tmp_path), "tiny-mimo"))
    cell = "tiny-mimo-serve.tiny-chat"
    path = tiny_manifest(
        str(tmp_path), extra_paths=("tiny-mimo",),
        configs=[{"name": "tiny-mimo-serve", "source": "tests",
                  "file": "tiny-mimo/configs/tiny-mimo-serve.json",
                  "reduced": [], "why": "CPU rehearsal"}],
        cells=[{"name": cell, "config": "tiny-mimo-serve",
                "traffic": "tiny-chat", "chips": 1, "why": "open loop"}],
        e2e=[{"name": "itl_ms.p95", "unit": "ms", "better": "lower",
              "bound": 0.05, "source": "host_clock", "workloads": [cell]}])
    rc, line, out = rehearse(capsys, path, cell)
    assert rc == 0 and line["correct"] is True, out
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["not_a_measured_run"] == "rehearsal"
    got = {c["name"]: c for c in line["checks"]}
    assert got["tokens_compared"]["value"] > 20
    assert got["mean_gap"]["value"] <= 1e-5
