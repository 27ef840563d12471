"""The scheduler's look-ahead of one step (ISSUE 35): step n+1 is built
from counts, its decode tokens fed on the device from step n's samples,
and dispatched before step n's tokens are read.

Held here, on the CPU, where the client aliases an aligned numpy
argument (so a step input written while its step is in flight shows as a
wrong token, and under `host_debug_check` as a failed assertion): in
every scheduler mode, at tp 1 and tp 2, a run that looks ahead serves
the tokens, statuses and compile buckets of a run that reads each step
before it builds the next (`_depth` forced to 0, today's order), gives
every KV block back, and counts its steps `ahead` exactly where the rule
allows it. Then the hazards one by one: an input set written in flight,
a request scheduled past its length, a cancel with a step in flight,
what `step()` returns while a last token is on its way.
"""
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                    GenerationRequest)
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _engine(tp):
    """The tiny engines the tp tests share (kv heads that split over the
    8-device CPU mesh), cached per tp: (engine, vocabulary)."""
    import test_serve_tp
    return test_serve_tp._engine(tp), test_serve_tp.V


def _ahead_count():
    kids = obs.get_registry().snapshot().get(
        "serve_steps_dispatched_total", {}).get("children", {})
    return {m: kids.get(m, {}).get("value", 0.0)
            for m in ("ahead", "drained")}


# mode -> (engine options, the schedule a run is driven through)
def _queue_three(cb, rng, V):
    """Three requests through two slots: the third waits for a slot that
    goes back by count, while its last token is still on its way."""
    reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n)
            for p, n in ((4, 3), (6, 2), (3, 3))]
    for r in reqs:
        cb.submit(r)
    cb.run()
    return reqs


def _long_prompts(cb, rng, V):
    reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n)
            for p, n in ((5, 4), (11, 3), (7, 2))]
    for r in reqs:
        cb.submit(r)
    cb.run()
    return reqs


def _shared_prefix(cb, rng, V):
    """A leader, a follower submitted with it (it stalls on the block the
    leader computes) and a late comer that maps the published blocks."""
    pre = rng.integers(1, V, 16).astype(np.int32)
    mk = lambda: GenerationRequest(np.concatenate(
        [pre, rng.integers(1, V, 2).astype(np.int32)]), 4)
    reqs = [mk(), mk()]
    for r in reqs:
        cb.submit(r)
    for _ in range(3):
        cb.step()
    reqs.append(mk())
    cb.submit(reqs[-1])
    cb.run()
    return reqs


def _preempt(cb, rng, V):
    low = [GenerationRequest(rng.integers(1, V, 14).astype(np.int32), 8,
                             priority=2) for _ in range(2)]
    high = GenerationRequest(rng.integers(1, V, 12).astype(np.int32), 6,
                             priority=0)
    for r in low:
        cb.submit(r)
    for _ in range(4):
        cb.step()
    cb.submit(high)
    cb.run()
    assert sum(r.preemptions for r in low) >= 1
    return low + [high]


def _cancel(cb, rng, V):
    reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), 8)
            for p in (6, 9, 4)]
    for r in reqs:
        cb.submit(r)
    for _ in range(4):
        cb.step()
    cb.cancel(reqs[1].request_id)
    cb.run()
    return reqs


def _repetitive(cb, rng, V):
    pat = [7, 23, 41, 11]
    reqs = [GenerationRequest(np.asarray(pat * 4, np.int32), 8),
            GenerationRequest(np.asarray(pat * 2, np.int32), 8)]
    for r in reqs:
        cb.submit(r)
    cb.run()
    return reqs


MODES = {
    "plain": (dict(), _queue_three),
    "chunked": (dict(prefill_chunk=4), _long_prompts),
    "budgeted": (dict(prefill_chunk=4, token_budget=6), _long_prompts),
    "prefix": (dict(prefill_chunk=8, prefix_cache=True, num_blocks=16,
                    max_batch=3), _shared_prefix),
    "preempt": (dict(num_blocks=7), _preempt),
    "cancel": (dict(), _cancel),
    "spec": (dict(prefill_chunk=8, spec_k=4), _repetitive),
}


def _run(tp, mode, drained):
    eng, V = _engine(tp)
    kw, drive = MODES[mode]
    kw = dict(dict(num_blocks=9, block_size=8, max_batch=2), **kw)
    cb = ContinuousBatchingEngine(eng, host_debug_check=True, **kw)
    if drained:
        cb._depth = lambda: 0       # read every step before the next
    before = _ahead_count()
    reqs = drive(cb, np.random.default_rng(5), V)
    after = _ahead_count()
    return cb, reqs, {m: after[m] - before[m] for m in after}


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_run_that_looks_ahead_serves_what_a_drained_run_serves(mode, tp):
    cb, reqs, steps = _run(tp, mode, drained=False)
    ref_cb, ref, ref_steps = _run(tp, mode, drained=True)
    assert [r.status for r in reqs] == [r.status for r in ref]
    for r, want in zip(reqs, ref):
        got, want = list(r.generated), list(want.generated)
        if r.status == "cancelled":
            # the token of the step in flight at the cancel is discarded
            assert got == want[:len(got)] and len(want) - len(got) <= 1
        else:
            assert got == want, r.request_id
            assert list(cb.finished[r.request_id]) == got
    assert [r.preemptions for r in reqs] == [r.preemptions for r in ref]
    if mode != "preempt":
        # the same steps over the same slots: slot and blocks of a request
        # go back when its last token is DISPATCHED, which is where a
        # drained run retires it. (A preemption discards the victim's
        # token in flight, so the victim resumes one token earlier.)
        assert cb._seen_buckets == ref_cb._seen_buckets
        assert cb._step_count == ref_cb._step_count
    assert all(t & (t - 1) == 0 and c & (c - 1) == 0
               for t, c in cb._seen_buckets)
    assert cb.cache_stats == ref_cb.cache_stats
    for engine in (cb, ref_cb):
        alloc = engine.allocator
        assert alloc.num_free + alloc.num_pooled \
            == alloc.num_blocks - alloc.reserved
        assert engine._flight is None and not engine._finishing
        assert engine.num_active == 0 and not engine.queue
    # the look-ahead engages wherever the next step can be built from
    # counts, and nowhere else
    assert ref_steps["ahead"] == 0
    assert steps["ahead"] + steps["drained"] == cb._step_count
    if mode == "spec":
        assert steps["ahead"] == 0
    else:
        assert steps["ahead"] >= cb._step_count - 2 > 0


def test_the_served_tokens_are_the_dense_engines():
    eng, V = _engine(1)
    cb, reqs, _ = _run(1, "chunked", drained=False)
    for r in reqs:
        ref = eng.generate(np.asarray(r.prompt)[None, :],
                           max_new_tokens=r.max_new_tokens)
        assert r.generated == ref[0, :r.max_new_tokens].tolist()


def test_an_input_set_written_in_flight_fails_the_debug_check():
    """Both directions of the hazard: with ONE set of step inputs the
    build of step n+1 writes what step n was given, which
    `host_debug_check` catches at step n's fetch; with the engine's two
    sets every dispatched set is bit-equal at its fetch (every run above
    is made under the check)."""
    eng, V = _engine(1)
    rng = np.random.default_rng(2)
    cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                  max_batch=2, host_debug_check=True)
    cb._inputs = (cb._inputs[0], cb._inputs[0])
    cb.submit(GenerationRequest(rng.integers(1, V, 5).astype(np.int32), 4))
    with pytest.raises(AssertionError, match="in flight"):
        cb.run()
    # and a write from outside, into the set a step in flight holds
    cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                  max_batch=2, host_debug_check=True)
    cb.submit(GenerationRequest(rng.integers(1, V, 5).astype(np.int32), 4))
    cb.step()
    assert cb._flight is not None
    cb._flight.snapshot[0][0][0, 0] += 1
    with pytest.raises(AssertionError, match="in flight"):
        cb.step()


def test_a_request_that_ends_by_length_is_never_scheduled_past_it():
    """Which slots a step carries is decided before the tokens of the
    step before it are read, from `len(generated) + _pending`: every
    request is granted its prompt and one position per token but the
    last, and not one more."""
    eng, V = _engine(1)
    rng = np.random.default_rng(9)
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=3, prefill_chunk=4)
    real, granted = eng._paged_step, {}

    def record(*args):
        q_arr = args[3]
        for i, req in enumerate(cb.slots):
            if req is not None and q_arr[i]:
                granted[req.request_id] = granted.get(req.request_id, 0) \
                    + int(q_arr[i])
        assert not [i for i, req in enumerate(cb.slots)
                    if req is None and q_arr[i]]
        return real(*args)

    eng._paged_step = record
    try:
        reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n)
                for p, n in ((5, 1), (9, 4), (3, 2), (6, 5))]
        for r in reqs:
            cb.submit(r)
        out = cb.run()
    finally:
        eng._paged_step = real
    for r in reqs:
        assert len(out[r.request_id]) == r.max_new_tokens
        assert granted[r.request_id] \
            == len(r.prompt) + r.max_new_tokens - 1


def test_a_cancel_with_a_step_in_flight_discards_its_token():
    eng, V = _engine(1)
    rng = np.random.default_rng(4)
    cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                  max_batch=2, host_debug_check=True)
    events = []
    cb.on_token = lambda rid, toks, step: events.append((rid, list(toks)))
    cb.on_terminal = lambda rid, res: events.append((rid, res.status))
    gone = GenerationRequest(rng.integers(1, V, 6).astype(np.int32), 8,
                             request_id="gone")
    stays = GenerationRequest(rng.integers(1, V, 4).astype(np.int32), 5,
                              request_id="stays")
    cb.submit(gone)
    cb.submit(stays)
    for _ in range(3):
        cb.step()
    assert cb._flight is not None and gone._pending == 1
    had = list(gone.generated)
    assert cb.cancel("gone") is True
    out = cb.run()
    assert out["gone"].status == "cancelled" and list(out["gone"]) == had
    assert gone._pending == 0
    # nothing of it after the cancel but its one terminal event
    mine = [e for e in events if e[0] == "gone"]
    assert mine == [("gone", [t]) for t in had] + [("gone", "cancelled")]
    ref = eng.generate(np.asarray(gone.prompt)[None, :], max_new_tokens=8)
    assert had == ref[0, :len(had)].tolist() and 0 < len(had) < 8
    # the other stream never noticed
    ref = eng.generate(np.asarray(stays.prompt)[None, :], max_new_tokens=5)
    assert list(out["stays"]) == ref[0, :5].tolist()
    assert cb.allocator.num_free \
        == cb.allocator.num_blocks - cb.allocator.reserved


def test_step_counts_a_last_token_on_its_way_as_in_flight():
    """`step()` returns 0 only when every token is on the host: a
    request whose last step is dispatched and not read has given its
    slot back and still counts, so `run()`, the stepper's park test and
    a loop on `step()`'s return drain by themselves."""
    eng, V = _engine(1)
    cb = ContinuousBatchingEngine(eng, num_blocks=9, block_size=8,
                                  max_batch=2)
    req = GenerationRequest(np.arange(1, 6, dtype=np.int32), 2)
    cb.submit(req)
    assert cb.step() == 1 and req.generated == []      # the prompt's step
    assert cb.step() == 1 and len(req.generated) == 1  # decode; read it
    # nothing left to dispatch: the slot goes back by count, the last
    # token is read, the record is made
    assert cb.slots[0] is req and cb.num_active == 1
    assert cb.step() == 0
    assert cb.slots[0] is None and len(req.generated) == 2
    assert cb.finished[req.request_id].status == "finished"
    assert cb.step() == 0 and cb._flight is None
