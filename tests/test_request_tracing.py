"""Per-request lifecycle tracing + flight recorder (ISSUE 6).

Covers the span ring itself (bounded wraparound, thread safety, the
tracer->float guard — the runtime half of the GL105 contract), the
continuous-batching engine's lifecycle instrumentation (span counts are
host math: one queue_wait, ceil(P/chunk) prefill chunks, N-1 decode
spans), and the anomaly triggers: an injected KV alloc failure and a
forced post-warmup bucket recompile must each produce a flight dump
that reconstructs the offending request's timeline and loads through
tools/request_trace.py AND the stdlib-only schema validator."""
import json
import os
import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing


def _tiny_engine(seed=0):
    # the CACHED serving engine (identical weights/config per seed):
    # one compile bill for every serving test file in the tier-1 window
    from test_chunked_prefill import _tiny_engine as _cached
    return _cached(seed=seed, max_seq_len=32)


@pytest.fixture(autouse=True)
def _interpret():
    from paddle_tpu.ops.pallas import flash_attention as fa
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test sees a fresh process-wide ring and a disarmed flight
    recorder (other test files' serving runs record spans too)."""
    obs.get_tracer().clear()
    obs.get_flight_recorder().disarm()
    yield
    obs.get_flight_recorder().disarm()


# -- span ring core --------------------------------------------------------

def test_ring_wraparound_bounded():
    rec = tracing.SpanRecorder(capacity=16)
    for i in range(100):
        rec.event("e", request=i % 3, i=i)
    assert len(rec) == 16
    assert rec.recorded_total == 100
    # the ring keeps the NEWEST spans
    kept = [s["args"]["i"] for s in rec.spans()]
    assert kept == list(range(84, 100))


def test_concurrent_recording_thread_safe():
    rec = tracing.SpanRecorder(capacity=100000)

    def work(tid):
        for i in range(1000):
            rec.event("t", request=tid, i=i)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 8000 and rec.recorded_total == 8000
    for tid in range(8):
        assert len(rec.spans(request=tid)) == 1000


def test_window_keeps_overlapping_spans():
    """The flight-recorder window keeps spans that OVERLAP it: a long
    queue_wait STARTING before the window but ending inside it is
    exactly the outlier evidence a dump must carry."""
    rec = tracing.SpanRecorder()
    rec.record_span("old_done", 0.0, 10.0)            # ends at 10us
    rec.record_span("queue_wait", 50.0, 100.0)        # spans 50..150us
    rec.record_span("recent", 140.0, 5.0)
    names = [s["name"] for s in rec.spans(since_us=120.0)]
    assert names == ["queue_wait", "recent"]
    # until_us still windows on start (profiler export scoping)
    names = [s["name"] for s in rec.spans(until_us=60.0)]
    assert names == ["old_done", "queue_wait"]


def test_span_context_manager_measures():
    rec = tracing.SpanRecorder()
    with rec.span("outer", request="r", width=4):
        rec.event("inner", request="r")
    spans = rec.spans(request="r")
    names = [s["name"] for s in spans]
    assert names == ["inner", "outer"]     # outer closes (records) last
    outer = spans[1]
    assert outer["dur_us"] >= 0 and outer["args"]["width"] == 4
    # disabled ring records nothing but stays reusable
    rec.enabled = False
    rec.event("dropped")
    assert len(rec) == 2
    rec.enabled = True
    rec.event("kept")
    assert len(rec) == 3


def test_record_rejects_tracers_at_trace_time():
    """Recording a span (or a span ARG) under jit must raise — same
    host-side-only contract as the metrics registry; graftlint GL105
    now covers tracing.* statically."""
    import jax
    import jax.numpy as jnp

    rec = tracing.SpanRecorder()

    def f(x):
        rec.event("bad", val=x)
        return x

    with pytest.raises(TypeError, match="host"):
        jax.jit(f)(jnp.float32(1.0))
    assert len(rec) == 0


# -- engine lifecycle spans ------------------------------------------------

def _serve(workload, seed=7, ids=None, **engine_kw):
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    eng, V = _tiny_engine()
    rng = np.random.default_rng(seed)
    kw = dict(num_blocks=12, block_size=8, max_batch=2, prefill_chunk=4)
    kw.update(engine_kw)
    cb = ContinuousBatchingEngine(eng, **kw)
    reqs = [GenerationRequest(rng.integers(1, V, p).astype(np.int32), n,
                              request_id=None if ids is None else ids[j])
            for j, (p, n) in enumerate(workload)]
    for r in reqs:
        cb.submit(r)
    out = cb.run()
    return cb, reqs, out


def test_lifecycle_span_counts_are_host_math():
    """ceil(P/chunk) prefill_chunk spans, exactly one queue_wait /
    first_token / retire, N-1 decode spans — per request."""
    workload = [(5, 3), (11, 4)]
    cb, reqs, out = _serve(workload)
    tr = obs.get_tracer()
    for r, (p, n) in zip(reqs, workload):
        spans = tr.spans(request=r.request_id)
        counts = {}
        for s in spans:
            counts[s["name"]] = counts.get(s["name"], 0) + 1
        assert counts == {"submit": 1, "queue_wait": 1,
                          "prefill_chunk": -(-p // 4),
                          "first_token": 1, "decode": n - 1,
                          "retire": 1}, (r.request_id, counts)
        # chunk grants reconstruct the prompt exactly
        widths = [s["args"]["granted"] for s in spans
                  if s["name"] == "prefill_chunk"]
        assert sum(widths) == p
    # engine lane: one serve_step + one paged_step dispatch per step
    eng_spans = [s for s in tr.spans() if s["request"] is None]
    steps = [s for s in eng_spans if s["name"] == "serve_step"]
    assert len(steps) == cb._step_count
    assert len([s for s in eng_spans if s["name"] == "paged_step"]) == \
        cb._step_count


def test_dispatch_seconds_histogram_mirrors_spans():
    """_dispatch_span lands every dispatch in dispatch_seconds{program}
    too (ISSUE 8): the windowed time-series layer needs a HISTOGRAM to
    answer "did dispatch get slower over the last N seconds" — span
    count and histogram count must agree per program."""
    obs.get_registry().reset()
    workload = [(5, 3), (11, 4)]
    cb, reqs, out = _serve(workload)
    tr = obs.get_tracer()
    snap = obs.get_registry().snapshot()
    kids = snap["dispatch_seconds"]["children"]
    spans_for = lambda name: len([s for s in tr.spans()
                                  if s["request"] is None
                                  and s["name"] == name])
    assert kids["paged_step"]["count"] == cb._step_count == \
        spans_for("paged_step")
    # every dispatch program the histogram saw agrees with its span lane
    for program, child in kids.items():
        assert child["count"] == spans_for(program), (program, kids)
        assert child["sum"] > 0


def test_explain_digest():
    workload = [(11, 4)]
    cb, reqs, out = _serve(workload)
    ex = cb.explain(reqs[0].request_id)
    assert ex["retired"] is True
    assert ex["prompt_tokens"] == 11 and ex["generated_tokens"] == 4
    assert ex["queue_wait_s"] >= 0 and ex["ttft_s"] > 0
    assert [c["granted"] for c in ex["prefill_chunks"]] == [4, 4, 3]
    assert ex["decode_steps"] == 3 and ex["tpot_s"] > 0
    assert ex["stalls"] == {"budget": 0, "alloc": 0, "admit_blocked": 0,
                            "cache_pending": 0}


def test_budget_starvation_records_stall_spans():
    """token_budget=4 with two 8-token prompts: while one slot eats its
    chunk the other stalls at zero work entries — span-visible."""
    workload = [(8, 2), (8, 2)]
    cb, reqs, out = _serve(workload, token_budget=4)
    tr = obs.get_tracer()
    stalls = [s for s in tr.spans() if s["name"] == "stall_budget"]
    assert stalls, "budget starvation left no stall spans"
    starved = {s["request"] for s in stalls}
    assert starved <= {r.request_id for r in reqs}
    # the digest rolls them up
    ex = cb.explain(sorted(starved)[0])
    assert ex["stalls"]["budget"] >= 1
    # granted < requested on at least one starved chunk
    grants = [(s["args"]["granted"], s["args"]["requested"])
              for s in tr.spans() if s["name"] == "prefill_chunk"]
    assert any(g < r for g, r in grants)


def test_speculative_decode_spans_carry_accounting():
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    eng, V = _tiny_engine()
    pattern = [7, 23, 41, 11]
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=1, prefill_chunk=8, spec_k=4)
    req = GenerationRequest(np.asarray(pattern * 4, np.int32), 12)
    cb.submit(req)
    out = cb.run()
    assert req.spec_drafted > 0
    tr = obs.get_tracer()
    decodes = [s for s in tr.spans(request=req.request_id)
               if s["name"] == "decode"]
    assert sum(s["args"]["drafted"] for s in decodes) == req.spec_drafted
    assert sum(s["args"]["accepted"] for s in decodes) == req.spec_accepted
    assert sum(s["args"]["emitted"] for s in decodes) == 12 - 1
    ex = cb.explain(req.request_id)
    assert ex["spec"]["drafted"] == req.spec_drafted
    assert ex["spec"]["accept_rate"] == pytest.approx(
        req.spec_accepted / req.spec_drafted)


# -- flight recorder triggers ----------------------------------------------

def _load_with_cli(path):
    """The dump must load through tools/request_trace.py too."""
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        from tools import request_trace
    finally:
        sys.path.remove(repo)
    dump = tracing.load_dump(path)
    import io
    buf = io.StringIO()
    request_trace.render_dump(dump, out=buf)
    return dump, buf.getvalue()


def test_render_rolls_up_serve_step_host_phases(tmp_path):
    """The engine lane of a rendered dump ends with one host-phase
    rollup line summing the serve_step spans' host_*_us args — the
    CLI answer to "is the host the bottleneck" (ISSUE 20)."""
    _serve([(5, 3), (11, 4)])
    path = tmp_path / "dump.json"
    tracing.write_dump(str(path), reason="manual")
    _, text = _load_with_cli(str(path))
    lines = [ln for ln in text.splitlines()
             if ln.startswith("host phases over ")]
    assert len(lines) == 1
    for phase in ("sched=", "build=", "dispatch=", "fetch="):
        assert phase in lines[0], (phase, lines[0])


def test_injected_alloc_failure_dumps_flight_record(tmp_path):
    """An injected KV alloc failure mid-step with NO preemptible victim
    is a PER-REQUEST failure (ISSUE 11 demoted the old engine crash):
    the step survives, the request lands in `finished` with a
    structured `failed` status, and the dump's spans still reconstruct
    the whole timeline: queue wait, granted chunks, the stall, the
    failure."""
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    eng, V = _tiny_engine()
    rng = np.random.default_rng(3)
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=2, prefill_chunk=4)
    req = GenerationRequest(rng.integers(1, V, 9).astype(np.int32), 3,
                            request_id="victim")
    cb.submit(req)
    cb.step()                       # admit + chunk 1 (tokens 1..4)
    cb.step()                       # chunk 2 (tokens 5..8, block full)
    obs.get_flight_recorder().arm(tmp_path)
    cb.allocator._free.clear()      # inject: pool suddenly empty
    cb.allocator._free_set.clear()
    cb.step()                   # final token crosses the block edge:
    #                             no victim exists -> request fails,
    #                             the engine does NOT raise
    assert cb.finished["victim"].status == "failed"
    assert cb.finished["victim"].reason == "kv_alloc_failure"
    # the failed request gave back every block it held (num_used is
    # free-list-derived and meaningless here: the test emptied the
    # free list by hand — the refcount table is the truth)
    assert cb.num_active == 0 and not cb.allocator._ref
    dumps = list(tmp_path.glob("flightrec_kv_alloc_failure_*.json"))
    assert len(dumps) == 1
    dump, rendered = _load_with_cli(str(dumps[0]))
    assert dump["reason"] == "kv_alloc_failure"
    assert dump["request"] == "victim"
    names = [s["name"] for s in dump["spans"]
             if s["request"] == "victim"]
    # the timeline tells the whole story: submitted, waited, got one
    # chunk granted, then stalled on allocation and failed
    for expected in ("submit", "queue_wait", "prefill_chunk",
                     "stall_alloc", "request_failed"):
        assert expected in names, (expected, names)
    digest = tracing.request_summary("victim", spans=dump["spans"])
    assert digest["stalls"]["alloc"] == 1
    assert digest["status"] == "failed"
    # the dump is written where the schedule fails the request, and the
    # second chunk's step was dispatched and not read then (the engine
    # looks one step ahead): the dump holds the chunk committed so far,
    # the ring holds both once that step has landed
    assert digest["prefill_chunks"] == [{"granted": 4, "requested": 4}]
    assert tracing.request_summary("victim")["prefill_chunks"] == [
        {"granted": 4, "requested": 4}, {"granted": 4, "requested": 4}]
    assert "victim" in rendered and "stall_alloc" in rendered
    # metrics snapshot rode along, including the alloc-failure counter
    fails = dump["metrics"]["kv_alloc_failures_total"]["children"]
    assert sum(c["value"] for c in fails.values()) >= 1


def test_forced_post_warmup_recompile_dumps(tmp_path):
    """declare_warm() then a workload that keys a fresh (work-list,
    chunk) bucket: the recompile must produce a dump naming the bucket
    and containing the offending request's spans."""
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    eng, V = _tiny_engine()
    rng = np.random.default_rng(5)
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=2, prefill_chunk=4)
    cb.submit(GenerationRequest(rng.integers(1, V, 5).astype(np.int32),
                                2, request_id="warm"))
    cb.run()
    cb.declare_warm()
    obs.get_flight_recorder().arm(tmp_path)
    # two concurrent long prompts -> work list far past anything warmed
    cb.submit(GenerationRequest(rng.integers(1, V, 23).astype(np.int32),
                                2, request_id="cold1"))
    cb.submit(GenerationRequest(rng.integers(1, V, 21).astype(np.int32),
                                2, request_id="cold2"))
    cb.run()
    dumps = list(tmp_path.glob("flightrec_post_warmup_recompile_*.json"))
    assert dumps, "post-warmup recompile fired no dump"
    dump = tracing.load_dump(str(dumps[0]))
    assert dump["context"]["bucket"]      # names the offending bucket
    assert "cold1" in dump["requests"]
    counter = obs.get_registry().get("flight_recorder_dumps_total")
    assert counter.labels(
        reason="post_warmup_recompile").value >= 1


def test_warm_engine_same_workload_never_dumps(tmp_path):
    """The inverse gate: replaying an already-warmed workload after
    declare_warm() must write NOTHING (tracing is anomaly-silent in
    steady state)."""
    from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                        GenerationRequest)

    eng, V = _tiny_engine()
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, V, 9).astype(np.int32)
    cb = ContinuousBatchingEngine(eng, num_blocks=12, block_size=8,
                                  max_batch=2, prefill_chunk=4)
    cb.submit(GenerationRequest(prompt.copy(), 3))
    cb.run()
    cb.declare_warm()
    obs.get_flight_recorder().arm(tmp_path)
    cb.submit(GenerationRequest(prompt.copy(), 3))
    cb.run()
    assert list(tmp_path.glob("flightrec_*.json")) == []


def test_tpot_slo_breach_dumps(tmp_path):
    """An absurdly tight TPOT SLO breaches on real decode intervals and
    fires the flight recorder (rate-limited to one dump)."""
    workload = [(5, 12)]
    obs.get_flight_recorder().arm(tmp_path)
    cb, reqs, out = _serve(workload, tpot_slo=1e-9)
    dumps = list(tmp_path.glob("flightrec_tpot_slo_breach_*.json"))
    assert len(dumps) == 1           # cooldown collapses the storm
    dump = tracing.load_dump(str(dumps[0]))
    assert dump["context"]["slo_s"] == pytest.approx(1e-9)
    assert dump["context"]["tpot_mean_s"] > 0


def test_trigger_write_failure_does_not_raise(tmp_path):
    """A dump-write failure (full disk / unwritable dir) must never
    propagate into the serving step or the watchdog thread: trigger()
    swallows the OSError, leaves a flight_dump_failed event on the
    timeline, counts it, and gives the cooldown back so the next
    anomaly retries instead of being silently suppressed."""
    rec = tracing.SpanRecorder()
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where the dump dir should be")
    fr = tracing.FlightRecorder(recorder=rec)
    fr.arm(blocker / "dumps")            # makedirs hits NotADirectoryError
    assert fr.trigger("kv_alloc_failure", request="victim") is None
    assert fr.dumps == []
    names = [s["name"] for s in rec.spans()]
    assert "flight_dump_failed" in names
    fails = obs.get_registry().get("flight_recorder_dump_failures_total")
    assert fails.labels(reason="kv_alloc_failure").value >= 1
    # the failed attempt must NOT consume the per-reason cooldown
    fr.arm(tmp_path)
    path = fr.trigger("kv_alloc_failure", request="victim")
    assert path is not None and fr.dumps == [path]
    assert tracing.load_dump(path)["reason"] == "kv_alloc_failure"


def test_manual_dump_records_path(tmp_path):
    """dump_to/write_dump participate in the `dumps` bookkeeping the
    attribute promises ("paths written this process"), not just
    trigger()."""
    rec = tracing.SpanRecorder()
    rec.event("tick", request="r")
    fr = tracing.FlightRecorder(recorder=rec)
    out = str(tmp_path / "manual.json")
    assert fr.dump_to(out) == out
    assert fr.dumps == [out]
    assert tracing.load_dump(out)["reason"] == "manual"


# -- flight-recorder retention (ISSUE 8) -----------------------------------

def _dump_names(d):
    return sorted(f.name for f in d.glob("flightrec_*.json")
                  if f.name != tracing.MANIFEST_NAME)


def test_retention_rotates_oldest_first_with_manifest(tmp_path):
    """max_dumps=3: five triggers keep exactly the NEWEST three on
    disk, the manifest lists them oldest-first and stays consistent
    with the dir, and every retained dump still loads."""
    rec = tracing.SpanRecorder()
    rec.event("tick", request="r")
    fr = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    fr.arm(tmp_path, max_dumps=3)
    paths = [fr.trigger(f"reason{i}") for i in range(5)]
    assert all(p is not None for p in paths)
    kept = _dump_names(tmp_path)
    assert len(kept) == 3
    # the two OLDEST rotated out, the newest three survived
    assert sorted(os.path.basename(p) for p in paths[2:]) == kept
    assert not os.path.exists(paths[0]) and not os.path.exists(paths[1])
    assert fr.evicted_total == 2
    man = tracing.load_manifest(tmp_path)
    entries = man["dumps"]
    assert [e["file"] for e in entries] == \
        [os.path.basename(p) for p in paths[2:]]     # oldest-first
    assert [e["reason"] for e in entries] == \
        ["reason2", "reason3", "reason4"]
    assert man["evicted_total"] == 2
    for e in entries:
        loaded = tracing.load_dump(str(tmp_path / e["file"]))
        assert loaded["reason"] == e["reason"]
        assert e["bytes"] == os.path.getsize(tmp_path / e["file"])
    # `dumps` stays the full process history; `retained()` the survivors
    assert len(fr.dumps) == 5
    assert [e["file"] for e in fr.retained()] == kept and \
        sorted(e["file"] for e in fr.retained()) == kept


def test_retention_max_bytes_under_large_dumps(tmp_path):
    """max_bytes with injected LARGE dumps: the dir's total stays under
    the cap (the newest dump always survives, even alone over-budget),
    and the manifest byte accounting matches the files."""
    rec = tracing.SpanRecorder(capacity=4096)
    for i in range(300):                # inflate every dump to ~40KB+
        rec.event("pad", request="r", note="x" * 120, i=i)
    fr = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0,
                                window_s=1e9)
    fr.arm(tmp_path)
    one = fr.trigger("probe")
    size = os.path.getsize(one)
    os.remove(one)
    fr.disarm()
    fr.arm(tmp_path, max_bytes=int(size * 2.5))
    for i in range(4):
        fr.trigger(f"big{i}")
    kept = _dump_names(tmp_path)
    assert len(kept) == 2, kept         # 2 fit under 2.5x, 3 would not
    total = sum(os.path.getsize(tmp_path / f) for f in kept)
    assert total <= size * 2.5
    assert fr.evicted_total == 2
    man = tracing.load_manifest(tmp_path)
    assert sum(e["bytes"] for e in man["dumps"]) == total
    # a single dump larger than the whole budget still survives (the
    # newest is never evicted — evidence beats the quota)
    fr.disarm()
    fr.arm(tmp_path, max_bytes=1)
    p = fr.trigger("oversized")
    assert p is not None and os.path.exists(p)
    assert _dump_names(tmp_path) == [os.path.basename(p)]


def test_retention_rearm_adopts_manifest(tmp_path):
    """A restarted server re-arming the same dir continues the SAME
    rotation window instead of orphaning the previous process's dumps."""
    rec = tracing.SpanRecorder()
    rec.event("tick")
    fr1 = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    fr1.arm(tmp_path, max_dumps=2)
    first = [fr1.trigger(f"gen1_{i}") for i in range(2)]
    # "new process": a fresh recorder adopts the manifest on arm()
    fr2 = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    fr2.arm(tmp_path, max_dumps=2)
    assert [e["file"] for e in fr2.retained()] == \
        [os.path.basename(p) for p in first]
    p3 = fr2.trigger("gen2_0")
    kept = _dump_names(tmp_path)
    assert len(kept) == 2
    assert os.path.basename(p3) in kept
    assert not os.path.exists(first[0])     # gen-1's oldest rotated out
    man = tracing.load_manifest(tmp_path)
    assert [e["reason"] for e in man["dumps"]] == ["gen1_1", "gen2_0"]


def test_rearm_same_dir_keeps_inmemory_manifest(tmp_path):
    """Re-arming the dir a LIVE recorder is already rotating (e.g. to
    adjust quotas) must keep the in-memory manifest, not re-read disk:
    the adoption read runs outside the lock (GL115), so a dump retained
    between that read and the state flip would otherwise be orphaned
    from rotation by the stale disk copy."""
    rec = tracing.SpanRecorder()
    rec.event("tick")
    fr = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    fr.arm(tmp_path, max_dumps=4)
    p = fr.trigger("live")
    # simulate the worst-case stale read: the on-disk manifest vanishes
    # entirely between the re-arm's read and its lock acquisition
    os.remove(os.path.join(tmp_path, tracing.MANIFEST_NAME))
    fr.arm(tmp_path, max_dumps=2)           # quota tweak, same dir
    assert [e["file"] for e in fr.retained()] == [os.path.basename(p)]
    assert fr.max_dumps == 2                # the quota change applied
    # a fresh recorder (new process) still adopts from disk
    fr2 = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    fr2.arm(tmp_path, max_dumps=2)
    assert fr2.retained() == []             # disk manifest was removed


def test_retention_ignores_explicit_paths_outside_dir(tmp_path):
    """dump_to() to an explicit path OUTSIDE the armed dir is the
    caller's file: never rotated, never in the manifest."""
    rec = tracing.SpanRecorder()
    rec.event("tick")
    fr = tracing.FlightRecorder(recorder=rec, min_interval_s=0.0)
    armed = tmp_path / "armed"
    fr.arm(armed, max_dumps=1)
    keepme = str(tmp_path / "elsewhere" / "keep.json")
    fr.dump_to(keepme)
    fr.trigger("a")
    fr.trigger("b")                     # rotates "a" out
    assert os.path.exists(keepme)
    assert len(_dump_names(armed)) == 1
    assert all(e["file"] != "keep.json" for e in fr.retained())
    # a manual dump INSIDE the armed dir participates like any trigger
    fr.dump_to(str(armed / "flightrec_manual_x.json"))
    assert [e["reason"] for e in fr.retained()] == ["manual"]
    assert len(_dump_names(armed)) == 1


# -- exporters / profiler merge --------------------------------------------

def test_chrome_span_events_per_request_lanes():
    workload = [(5, 2), (3, 2)]
    cb, reqs, out = _serve(workload)
    ev = obs.chrome_span_events(pid=42)
    xs = [e for e in ev if e["ph"] == "X"]
    metas = [e for e in ev if e["ph"] == "M"]
    assert xs and metas
    # each request got its own lane, engine spans a lane of their own
    lanes = {e["tid"] for e in xs}
    assert len(lanes) >= 3
    lane_names = {e["args"]["name"] for e in metas}
    assert "serve engine" in lane_names
    for r in reqs:
        assert f"request {r.request_id}" in lane_names
    # profiler export contract: uniform key shape
    assert all({"name", "ph", "ts", "dur", "pid", "tid", "args"}
               <= set(e) for e in ev)


def test_profiler_export_merges_request_lanes(tmp_path):
    """One chrome file carries host ranges AND request-lifecycle spans,
    window-scoped: pre-profiler spans stay out."""
    import paddle_tpu as paddle
    from paddle_tpu.profiler import Profiler

    obs.get_tracer().event("before_window", request="outside")
    path = str(tmp_path / "trace.json")
    with Profiler() as prof:
        x = paddle.randn([4, 4])
        paddle.matmul(x, x)
        _serve([(5, 2)], ids=["profiled"])
    prof.export(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    cats = {e.get("cat") for e in events}
    assert "request" in cats          # span lanes made it in
    names = {e["name"] for e in events if e.get("cat") == "request"}
    assert "serve_step" in names and "prefill_chunk" in names
    assert "before_window" not in names   # window scoping
    lanes = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("cat") == "request"}
    assert "request profiled" in lanes


def test_flight_dump_counts_into_registry_exports():
    """flight_recorder_dumps_total shows up in the Prometheus export
    like any other family (dashboardable anomaly rate)."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        obs.get_flight_recorder().arm(d)
        assert obs.get_flight_recorder().trigger("test_reason") is not None
    obs.get_flight_recorder().disarm()
    assert 'flight_recorder_dumps_total{reason="test_reason"}' \
        in obs.to_prometheus()
