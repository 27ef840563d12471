"""Every op of the serving step lies in a region, and the region names
reach the compile cache's key.

`tracing.device_scope(name)` opens a `jax.named_scope` (what a device
trace shows, a path component of the op's `tf_op`) and the frontend
attribute `scope` (IR: jax strips MLIR locations, and with them every
named scope, from the persistent cache's key). Held here on the CPU, on
one decode bucket and one wide bucket (more than ROW_TILE slab rows) of
a tiny dense engine and of a tiny engine with expert layers and window
layers: (a) parameters, constants and the compiler's own copies aside,
at most 2% of the compiled step's instructions lie under no name of
`STEP_REGIONS`, read as the benchmark's reader reads a trace (an
instruction's own `op_name`, else the instruction it runs inside); (b)
the text the key is made from differs when a region is renamed and does
NOT differ when a bare named scope is (the trap); (c) the names today's
readers match are still path components of the `op_name`s of their ops.
"""
import collections
import contextlib
import os
import re
import sys

import numpy as np
import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "perfbench", "lib"))

from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,  # noqa: E402
                                    GenerationRequest)
from paddle_tpu.inference import FusedMultiTransformerEngine  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

import test_paged_live_rows as dense_case  # noqa: E402
import test_serve_block_description as mixed_case  # noqa: E402

OLD_NAMES = ("kv_write", "attention", "ffn", "moe_route", "moe_experts",
             "head", "sampler")
MAX_BATCH, CHUNK = 8, 64
assert MAX_BATCH * CHUNK > pa.ROW_TILE      # the wide bucket packs its rows
ENGINES = ("dense", "experts_and_windows")
BUCKETS = {"decode": 1, "wide": CHUNK}


@pytest.fixture(autouse=True)
def _interpret():
    old, fa._INTERPRET = fa._INTERPRET, True
    yield
    fa._INTERPRET = old


def _serving(kind):
    """A fresh engine and its scheduler: the step's jitted function is
    the engine's own, so a fresh one traces again under whatever
    `tracing.device_scope` is at the time."""
    if kind == "dense":
        c = dense_case
        engine = FusedMultiTransformerEngine(
            c._weights(), num_heads=c.H, head_dim=c.D, max_seq_len=c.CAP,
            dtype="float32", norm_type="rmsnorm", activation="swiglu",
            gqa_group_size=c.G, use_neox_rotary_style=True)
        return engine, ContinuousBatchingEngine(
            engine, num_blocks=c.NB, block_size=c.BS, max_batch=MAX_BATCH,
            prefill_chunk=CHUNK)
    fam = mixed_case._family()
    engine = FusedMultiTransformerEngine(
        fam.serve_weights(mixed_case.SEED, mixed_case.CFG),
        **fam.serve_engine_kwargs(mixed_case.CFG))
    return engine, ContinuousBatchingEngine(
        engine, num_blocks=40, block_size=mixed_case.BLOCK,
        max_batch=MAX_BATCH, prefill_chunk=CHUNK)


def lowered(kind, width):
    """One bucket of the paged step, lowered from the arguments of a
    real step with the slab `width` columns wide, as the benchmark's
    `compile_ahead` lowers its buckets."""
    engine, cb = _serving(kind)
    real, seen = engine._paged_step, {}

    def record(*args):
        seen["args"] = args
        return real(*args)

    engine._paged_step = record
    cb.submit(GenerationRequest(np.ones(1, np.int64), 1, request_id="w"))
    while cb.step():
        pass
    w, _, slab, q, sel, tables, lens, work, pack, temp, topp, key = \
        seen["args"]
    return real.__wrapped__.lower(
        w, cb.caches, np.zeros((slab.shape[0], width), slab.dtype), q,
        np.zeros((sel.shape[0], 1), sel.dtype), tables, lens,
        tuple(np.zeros_like(a) for a in work), pack, temp, topp, key)


# -- the compiled step, read as the trace's reader reads it -------------------

ASIDE = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "copy")    # copies are the compiler's own (layouts, loop carries)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([\w\-]+)\(")
_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def instructions(hlo):
    """[(computation, opcode, `op_name`, the `scope` attribute or None,
    the computations it calls)] of a compiled module's text."""
    out, comp = [], None
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY ")) and line.rstrip().endswith("{"):
            comp = line.split()[line.startswith("ENTRY")].lstrip("%")
            continue
        m = _INSTRUCTION.match(line)
        if comp is None or not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        attr = re.search(r'frontend_attributes=\{[^}]*scope="(\w+)"', line)
        called = []
        for one, many in _CALLED.findall(line):
            called += [one] if one else [
                c.strip().lstrip("%") for c in many.split(",")]
        out.append((comp, m.group(1), name.group(1) if name else "",
                    attr.group(1) if attr else None, called))
    return out


def innermost(op_name):
    parts = [p for p in op_name.split("/") if p in tracing.STEP_REGIONS]
    return parts[-1] if parts else None


def regions(hlo):
    """Counter of regions over the instructions that are not ASIDE: an
    instruction's own (its `op_name`'s innermost region, else its
    attribute), else that of the instruction whose computation it lies
    in (a loop's body, a fusion's), else `unnamed`."""
    rows = instructions(hlo)
    above = {}
    for comp, _, name, attr, called in rows:
        for c in called:
            above[c] = (comp, innermost(name) or attr)

    def inherited(comp):
        seen = set()
        while comp in above and comp not in seen:
            seen.add(comp)
            comp, own = above[comp]
            if own:
                return own
        return "unnamed"

    return collections.Counter(
        innermost(name) or attr or inherited(comp)
        for comp, opcode, name, attr, _ in rows if opcode not in ASIDE)


_COMPILED = {}


def compiled(kind, bucket):
    if (kind, bucket) not in _COMPILED:
        _COMPILED[kind, bucket] = lowered(
            kind, BUCKETS[bucket]).compile().as_text()
    return _COMPILED[kind, bucket]


@pytest.mark.parametrize("bucket", list(BUCKETS))
@pytest.mark.parametrize("kind", ENGINES)
def test_at_most_a_fiftieth_of_the_steps_ops_lie_under_no_region(
        kind, bucket):
    by = regions(compiled(kind, bucket))
    assert by["unnamed"] <= 0.02 * sum(by.values()), by
    want = {"embed", "qkv_proj", "rope", "kv_write", "attention",
            "out_proj", "ffn", "head", "sampler"}
    if bucket == "wide":
        want |= {"rows_before", "rows_after", "q_pack"}
    if kind != "dense":
        want |= {"moe_route", "moe_experts", "moe_slabs"}
    assert want <= set(by), (want - set(by), by)
    # each interpreted kernel is one region: the ragged kernel's ops
    # under `attention`, the writer's under `kv_write`
    rest = max(n for r, n in by.items() if r not in ("attention", "kv_write"))
    assert min(by["attention"], by["kv_write"]) > rest, by


@pytest.mark.parametrize("bucket", list(BUCKETS))
@pytest.mark.parametrize("kind", ENGINES)
def test_the_old_names_are_still_path_components_of_their_ops(
        kind, bucket):
    """An instruction's attribute is the innermost scope it was traced
    under; its `op_name` (where XLA kept the whole path, `jit(...)/...`)
    holds that name as its innermost region, so the readers that match
    `/kv_write/`, `/moe_` and the like find what they found."""
    seen, agree = collections.Counter(), collections.Counter()
    for _, _, name, attr, _ in instructions(compiled(kind, bucket)):
        if attr is None or not name.startswith("jit("):
            continue
        seen[attr] += 1
        agree[attr] += innermost(name) == attr
    old = set(OLD_NAMES) - ({"moe_route", "moe_experts"}
                            if kind == "dense" else set())
    assert old <= set(seen), (old - set(seen), seen)
    # not every one: where XLA merges two instructions (the residual
    # add of `out_proj` into `ffn`'s) the survivor keeps one's attribute
    # and the other's `op_name`
    for name in old:
        assert agree[name] >= 0.7 * seen[name], (name, agree, seen)
    if bucket == "wide":    # a loop hands its name down to its body's ops
        names = [n for _, _, n, _, _ in instructions(compiled(kind, bucket))]
        assert any("/rows_before/while/body/kv_write/" in n for n in names)
        assert any("/rows_after/while/body/ffn/" in n for n in names)
        assert any(n.endswith("/rows_before/while") for n in names)
        if kind != "dense":
            assert any("/moe_experts/moe_slabs/while/body/" in n
                       for n in names)


# -- the compile cache's key --------------------------------------------------

@contextlib.contextmanager
def bare_scope(name):
    """What the step had until PR 42: a named scope and nothing else."""
    with jax.named_scope(name):
        yield


@pytest.mark.parametrize("bucket", list(BUCKETS))
@pytest.mark.parametrize("kind", ENGINES)
def test_the_key_follows_a_regions_name_and_not_a_bare_named_scope(
        kind, bucket, monkeypatch):
    """`lowered.as_text()` prints attributes and no locations, which is
    what `jax._src.cache_key` hashes (`strip-debuginfo`)."""
    width = BUCKETS[bucket]
    text = lowered(kind, width).as_text()
    assert 'mhlo.frontend_attributes = {scope = "kv_write"}' in text
    assert text == lowered(kind, width).as_text()     # a fresh engine's

    real = tracing.device_scope
    renamed = tuple("rotary" if n == "rope" else n
                    for n in tracing.STEP_REGIONS)
    monkeypatch.setattr(tracing, "STEP_REGIONS", renamed)
    with pytest.raises(ValueError, match="STEP_REGIONS"):
        with real("rope"):          # the vocabulary is the tuple
            pass
    monkeypatch.setattr(
        tracing, "device_scope",
        lambda name: real("rotary" if name == "rope" else name))
    other = lowered(kind, width).as_text()
    assert other != text
    assert 'scope = "rotary"' in other and 'scope = "rope"' not in other

    # the trap: two programs that differ in a named scope only are one
    # program to the cache
    monkeypatch.setattr(tracing, "device_scope", bare_scope)
    plain = lowered(kind, width).as_text()
    monkeypatch.setattr(
        tracing, "device_scope",
        lambda name: bare_scope("rotary" if name == "rope" else name))
    assert lowered(kind, width).as_text() == plain
    assert "frontend_attributes" not in plain.replace(
        "mhlo.frontend_attributes = {}", "")
    assert plain != text


def test_the_benchmarks_reader_knows_the_same_regions():
    import step_regions
    assert step_regions.REGIONS == tracing.STEP_REGIONS
    covered = {r for parts in step_regions.GROUPS.values() for r in parts}
    assert covered == set(tracing.STEP_REGIONS) | {step_regions.UNNAMED}
