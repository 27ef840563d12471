"""The host on the chip's clock (ISSUE 24): the one seam into the jax
profiler (`tracing.annotation` / `PhaseMarks`), the states it
marks on the stepper thread and in `step()`, the gateway's two hand-offs
(`handoff`, `emit_to_wire`), and the registry's step-kind split and slab
fill. Annotations are recorded by patching the seam, never by timing.
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.incubate.nn import (ContinuousBatchingEngine,
                                    GenerationRequest)
from paddle_tpu.observability import tracing
from paddle_tpu.serving import sse


@pytest.fixture(autouse=True)
def _interpret():
    from paddle_tpu.ops.pallas import flash_attention as fa
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


@pytest.fixture(scope="module")
def eng():
    from test_chunked_prefill import _tiny_engine
    engine, _v = _tiny_engine(seed=0, max_seq_len=64)
    return engine


def _cb(eng, **kw):
    kw = dict(dict(num_blocks=40, block_size=8, max_batch=4,
                   prefill_chunk=8), **kw)
    return ContinuousBatchingEngine(eng, **kw)


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation behind the seam: counts
    the objects made and logs enter/exit per thread."""

    enabled = True
    made = 0
    log = []        # (thread name, "enter" | "exit", annotation name)

    def __init__(self, name):
        type(self).made += 1
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append((threading.current_thread().name, "enter",
                         self.name))
        return self

    def __exit__(self, *exc):
        self.log.append((threading.current_thread().name, "exit",
                         self.name))
        return False


@pytest.fixture
def fake(monkeypatch):
    FakeAnnotation.enabled, FakeAnnotation.made = True, 0
    FakeAnnotation.log = []
    monkeypatch.setattr(tracing, "_trace_annotation", FakeAnnotation)
    return FakeAnnotation


def _serve_one(cb, prompt_len=8, new_tokens=2, rid="a1"):
    """One request through a stepper, to its end; returns the stepper's
    step count."""
    stepper = serving.EngineStepper(cb, name="stepper-under-test").start()
    done = threading.Event()
    try:
        fut = stepper.submit(
            GenerationRequest(np.arange(1, prompt_len + 1, dtype=np.int32),
                              new_tokens, request_id=rid),
            on_event=lambda ev: ev["type"] == "end" and done.set())
        assert fut.result(60) == "queued"
        assert done.wait(120), "the request did not finish"
    finally:
        stepper.stop()
    assert not stepper._thread.is_alive()
    return stepper.steps


# -- the seam ------------------------------------------------------------------

def test_no_session_means_no_object_and_one_shared_noop(fake):
    fake.enabled = False
    a, b = tracing.annotation("x"), tracing.annotation("y")
    assert a is b is tracing._NO_ANNOTATION
    with a:
        pass
    marks = tracing.PhaseMarks()
    marks.mark("serve.schedule")
    marks.mark("serve.build")
    marks.end()
    assert fake.made == 0 and fake.log == []


def test_a_served_request_with_no_session_makes_no_annotation(fake, eng):
    fake.enabled = False
    steps = _serve_one(_cb(eng))
    assert steps >= 2
    assert fake.made == 0       # the fixed number: none, however many steps


def test_phase_marks_share_their_boundaries(fake):
    marks = tracing.PhaseMarks()
    t0 = time.perf_counter()
    a = marks.mark("one")
    b = marks.mark("two w4c1")
    marks.end()
    marks.end()                 # closing twice closes nothing twice
    assert t0 <= a <= b <= time.perf_counter()
    me = threading.current_thread().name
    assert fake.log == [(me, "enter", "one"), (me, "exit", "one"),
                        (me, "enter", "two w4c1"), (me, "exit", "two w4c1")]


def test_the_steppers_states_are_annotations_and_leave_the_ring_alone(
        fake, eng):
    """Only `idle_*` reads `stepper.idle` / `stepper.commands`, and reads
    the annotation: a ring copy would have no reader."""
    ring = tracing.get_tracer()
    n0 = ring.recorded_total
    _serve_one(_cb(eng), rid="ringless")
    names = {name for _, _, name in fake.log}
    assert {"stepper.idle", "stepper.commands"} <= names
    assert ring.recorded_total > n0         # the engine's own spans
    assert not [s for s in ring.spans()
                if s["name"].startswith(("stepper.", "serve."))]


def test_the_seam_never_imports_jax_for_a_process_without_it(monkeypatch):
    import sys
    monkeypatch.setattr(tracing, "_trace_annotation", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing.annotation("x") is tracing._NO_ANNOTATION
    assert "jax" not in sys.modules and tracing._trace_annotation is None


# -- the stepper's states, in order and nesting ----------------------------------

# One request of two tokens through an engine that looks one step ahead:
# the first turn dispatches the prompt's step and has nothing to read yet,
# the second dispatches the decode step and then reads the first, the
# third has nothing left to dispatch and reads the second.
TURNS = [["serve.schedule", "serve.build", "serve.dispatch", "serve.commit"],
         ["serve.schedule", "serve.build", "serve.dispatch", "serve.fetch",
          "serve.commit"],
         ["serve.schedule", "serve.fetch", "serve.commit"]]


def test_one_request_through_a_stepper_emits_the_states_in_order(fake, eng):
    steps = _serve_one(_cb(eng), prompt_len=8, new_tokens=2)
    log = [(kind, name) for thread, kind, name in fake.log
           if thread == "stepper-under-test"]
    assert len(log) == len(fake.log), "an annotation from another thread"
    # properly nested on the thread, and serve.telemetry the only child
    stack, order, parents = [], [], {}
    for kind, name in log:
        if kind == "enter":
            parents[name.split(" ")[0]] = stack[-1] if stack else None
            stack.append(name)
            order.append(name)
        else:
            assert stack.pop() == name
    assert parents.pop("serve.telemetry") == "serve.commit"
    assert set(parents.values()) == {None}
    # the submit, then whole steps, each in the stated order
    heads = [n.split(" ")[0] for n in order if n != "serve.telemetry"]
    # parked until the submit came, and again after the last step
    assert heads[0] == heads[-1] == "stepper.idle"
    assert heads[1] == "stepper.commands"
    serve = [h for h in heads if h.startswith("serve.")]
    assert steps == len(TURNS)
    assert serve == [name for turn in TURNS for name in turn]
    # one for each step read, one for each turn's close-out
    assert order.count("serve.telemetry") == 2 + len(TURNS)
    # a step is waited for under the bucket it was dispatched under, and
    # in the order of the dispatches
    dispatched = [n.split(" ")[1] for n in order
                  if n.startswith("serve.dispatch ")]
    fetched = [n.split(" ")[1] for n in order
               if n.startswith("serve.fetch ")]
    assert dispatched == fetched and len(fetched) == 2
    assert all(b[0] == "w" and "c" in b for b in dispatched)
    # the first step prefills 8 tokens, the second decodes one
    assert dispatched[0].endswith("c8") and dispatched[1].endswith("c1")


def test_an_engine_that_reads_each_step_keeps_the_five_in_a_row(fake, eng):
    """A speculative engine's next step needs the last one's values: every
    turn dispatches and reads its own step, as every turn once did."""
    cb = _cb(eng, spec_k=2)
    cb.submit(GenerationRequest(np.arange(1, 9, dtype=np.int32), 2))
    while cb.step():
        pass
    heads = [name.split(" ")[0] for _, kind, name in fake.log
             if kind == "enter" and name != "serve.telemetry"]
    turn = ["serve.schedule", "serve.build", "serve.dispatch", "serve.fetch",
            "serve.commit"]
    assert heads[:-1] == turn * ((len(heads) - 1) // 5) and len(heads) > 5
    assert heads[-1] == "serve.schedule"    # the idle tick that retired it


def test_an_idle_tick_closes_what_it_opened(fake, eng):
    cb = _cb(eng)
    assert cb.step() == 0       # nothing queued: returns from the schedule
    me = threading.current_thread().name
    assert fake.log == [(me, "enter", "serve.schedule"),
                        (me, "exit", "serve.schedule")]


# -- the registry: step kinds and slab fill ----------------------------------------

def _hist(snap, family, child):
    c = snap.get(family, {}).get("children", {}).get(child)
    return (c["count"], c["sum"]) if c else (0, 0.0)


def _value(snap, family, child):
    c = snap.get(family, {}).get("children", {}).get(child)
    return c["value"] if c else 0.0


# The ragged kernel's query rows on those chunk steps (G = 2 rows a query
# position, 8-token blocks, 4 slots packed into one tile): per work entry
# the live rows are the span's positions that see the entry's block, the
# visited ones the 64-row sub-tiles (or the whole tile, where it is no
# taller) that hold them.
@pytest.mark.parametrize(
    "prompt_len,chunk,chunk_steps,live,capacity,attn_live,attn_visited", [
        # the whole prompt in one 8-wide slab: one block, a 64-row tile
        (8, 8, 1, 8, 4 * 8, 8 * 2, 64),
        # 8 then 4: slabs 8 and 4 wide; the second span sees two blocks
        # through a 32-row tile
        (12, 8, 2, 12, 4 * 8 + 4 * 4, 8 * 2 + 2 * 4 * 2, 64 + 2 * 32),
        # 3 tokens in a 4-wide slab
        (3, 8, 1, 3, 4 * 4, 3 * 2, 32),
        # 32 then 8: the 32-wide slab's tile is 256 rows, of which each
        # of the span's four blocks visits the slot's one sub-tile, and
        # block k is seen by the 32 - 8k positions at or after it; then
        # five blocks through a 64-row tile
        (40, 32, 2, 40, 4 * 32 + 4 * 8,
         (32 + 24 + 16 + 8) * 2 + 5 * 8 * 2, 4 * 64 + 5 * 64),
    ])
def test_step_kinds_and_slab_fill_match_a_hand_count(
        eng, prompt_len, chunk, chunk_steps, live, capacity, attn_live,
        attn_visited):
    """`chunk_steps` chunk steps, the last of which emits the first
    token, then two decode steps for tokens two and three (which leave
    the chunk steps' counters as they are)."""
    cb = _cb(eng, prefill_chunk=chunk)
    rid = f"kinds-{prompt_len}-{chunk}"
    cb.submit(GenerationRequest(
        np.arange(1, prompt_len + 1, dtype=np.int32), 3, request_id=rid))
    snap0 = obs.get_registry().snapshot()
    steps = 0
    while cb.step():
        steps += 1
        assert steps < 20
    snap1 = obs.get_registry().snapshot()
    # and one turn that had nothing to dispatch and read the last step
    assert steps == chunk_steps + 2

    def gained(kind):
        n0, s0 = _hist(snap0, "serve_step_kind_seconds", kind)
        n1, s1 = _hist(snap1, "serve_step_kind_seconds", kind)
        return n1 - n0, s1 - s0
    (n_chunk, s_chunk), (n_dec, s_dec) = gained("chunk"), gained("decode")
    assert (n_chunk, n_dec) == (chunk_steps, 2)
    assert s_chunk > 0 and s_dec > 0
    # a step's share of the cadence runs from the later of its own
    # dispatch and the tokens of the step before it to its own tokens:
    # the request's spans carry both stamps. Each step after the first
    # was dispatched before the one before it was read, so the shares
    # tile the time from the first dispatch to the last tokens
    mine = sorted((s for s in tracing.get_tracer().spans()
                   if s["request"] == rid
                   and s["name"] in ("prefill_chunk", "decode")),
                  key=lambda s: s["ts_us"])
    assert len(mine) == chunk_steps + 2
    done = [s["ts_us"] + s["dur_us"] for s in mine]
    assert all(s["ts_us"] < d for s, d in zip(mine[1:], done))
    assert s_chunk + s_dec == pytest.approx(
        (done[-1] - mine[0]["ts_us"]) / 1e6, rel=1e-4)
    # the host's five phases are the turns' own: every one was entered
    phases = {p: _hist(snap1, "serve_host_phase_seconds", p)
              for p in ("schedule", "build", "dispatch", "fetch", "commit")}
    assert all(n > 0 and sec > 0 for n, sec in phases.values())
    for kind, want in (("live", live), ("capacity", capacity)):
        got = (_value(snap1, "serve_slab_tokens_total", kind)
               - _value(snap0, "serve_slab_tokens_total", kind))
        assert got == want, kind
    for kind, want in (("live", attn_live), ("visited", attn_visited)):
        got = (_value(snap1, "serve_attn_rows_total", kind)
               - _value(snap0, "serve_attn_rows_total", kind))
        assert got == want, kind


def _dispatched(snap):
    return tuple(_value(snap, "serve_steps_dispatched_total", mode)
                 for mode in ("ahead", "drained"))


def test_steps_dispatched_ahead_and_drained_match_a_hand_count(eng):
    """A step is `ahead` when the step before it was dispatched and not
    read, `drained` when every earlier step's tokens were on the host:
    the first step of a busy engine, the first after an empty tick, and
    every step of an engine whose next input needs values."""
    def gained(fn):
        a0, d0 = _dispatched(obs.get_registry().snapshot())
        fn()
        a1, d1 = _dispatched(obs.get_registry().snapshot())
        return a1 - a0, d1 - d0

    def serve(cb, new_tokens):
        cb.submit(GenerationRequest(np.arange(1, 9, dtype=np.int32),
                                    new_tokens))
        while cb.step():
            pass

    cb = _cb(eng)
    # the prompt's step, then three decode steps dispatched ahead
    assert gained(lambda: serve(cb, 4)) == (3, 1)
    # an empty tick dispatches nothing
    assert gained(cb.step) == (0, 0)
    # and the engine starts over: nothing is in flight
    assert gained(lambda: serve(cb, 2)) == (1, 1)
    # a speculative engine reads every step before it builds the next
    spec = _cb(eng, spec_k=2)
    ahead, drained = gained(lambda: serve(spec, 4))
    assert ahead == 0 and 1 <= drained <= 4


def test_request_spans_name_the_step_that_caused_them(eng):
    cb = _cb(eng)
    cb.submit(GenerationRequest(np.arange(1, 9, dtype=np.int32), 3,
                                request_id="s1"))
    while cb.step():
        pass
    ring = tracing.get_tracer().spans()
    steps = {s["args"]["step"]: s for s in ring if s["name"] == "serve_step"}
    mine = [s for s in ring if s["request"] == "s1"
            and s["name"] in ("prefill_chunk", "decode")]
    assert [s["name"] for s in mine] == ["prefill_chunk", "decode", "decode"]
    for s in mine:
        cause = steps[s["args"]["step"]]
        assert cause["ts_us"] <= s["ts_us"]
        assert (s["ts_us"] + s["dur_us"]
                == pytest.approx(cause["ts_us"] + cause["dur_us"]))
    assert len({s["args"]["step"] for s in mine}) == 3


# -- the gateway's two hand-offs -------------------------------------------------------

@pytest.fixture(scope="module")
def gw(eng):
    from test_serve_gateway import Harness
    h = Harness(_cb(eng))
    yield h
    h.close()


def _raw_stream(port, body):
    """POST /v1/generate and return the response's bytes after the HTTP
    head, exactly as they came off the socket."""
    payload = json.dumps(body).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=120) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\nContent-Length: "
                  + str(len(payload)).encode() + b"\r\n\r\n" + payload)
        buf = b""
        while True:
            got = s.recv(65536)
            if not got:
                break
            buf += got
    head, _, rest = buf.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200"), head
    return rest


def test_the_wire_carries_the_events_and_not_a_byte_more(gw):
    """The stamp that rides beside an event never reaches the payload:
    the stream is the events' own frames, key for key, and nothing
    else."""
    raw = _raw_stream(gw.gw.port, {"prompt": [5, 6, 7, 8, 9],
                                   "max_new_tokens": 4,
                                   "request_id": "wire1"})
    events = list(sse.iter_events(raw.decode().split("\n")))
    assert [e for e, _ in events] == ["accepted"] + ["token"] * 4 + ["end"]
    assert b"".join(sse.format_event(e, p) for e, p in events) == raw
    for etype, payload in events:
        want = {"accepted": {"request"},
                "token": {"request", "tokens", "step", "index"},
                "end": {"request", "status", "reason", "preemptions",
                        "tokens"}}[etype]
        assert set(payload) == want, etype


def test_handoff_and_emit_to_wire_carry_the_gateways_request_id(gw):
    snap0 = obs.get_registry().snapshot()
    code, events = gw.stream({"prompt": [3, 4, 5, 6, 7, 8],
                              "max_new_tokens": 3})
    assert code == 200
    rid = events[0][1]["request"]           # the id the gateway assigned
    n_tokens = sum(1 for e, _ in events if e == "token")
    time.sleep(0.05)    # the last frame's record follows its drain
    mine = tracing.get_tracer().spans(request=rid)
    handoff = [s for s in mine if s["name"] == "handoff"]
    emits = [s for s in mine if s["name"] == "emit_to_wire"]
    # every token event is observed (below); the ring keeps the first
    assert len(handoff) == 1 and len(emits) == 1 and n_tokens == 3
    decodes = [s for s in mine if s["name"] == "decode"]
    # ... stamped before the step that made the second token was fetched
    assert emits[0]["ts_us"] < decodes[0]["ts_us"] + decodes[0]["dur_us"]
    submit = [s for s in mine if s["name"] == "submit"][0]
    # the hand-off ends where the engine's own record of the request begins
    assert handoff[0]["ts_us"] + handoff[0]["dur_us"] <= submit["ts_us"]
    assert all(s["dur_us"] > 0 for s in handoff + emits)
    snap1 = obs.get_registry().snapshot()
    for family, n in (("gateway_handoff_seconds", 1),
                      ("gateway_emit_to_wire_seconds", 3)):
        n0, s0 = _hist(snap0, family, "")
        n1, s1 = _hist(snap1, family, "")
        assert n1 - n0 == n and s1 > s0, family
    # and the operator's view of it
    code, d = gw.get_json(f"/requests/{rid}")
    assert code == 200
    assert d["handoff_s"] == pytest.approx(handoff[0]["dur_us"] / 1e6)
    assert d["first_byte_s"] == pytest.approx(emits[0]["dur_us"] / 1e6)


@pytest.mark.parametrize("spans,handoff_s,first_byte_s", [
    ([], None, None),
    ([("handoff", 2500.0, {})], 0.0025, None),
    ([("handoff", 2500.0, {}), ("emit_to_wire", 800.0, {})],
     0.0025, 0.0008),
])
def test_request_summary_reads_the_two_waits(spans, handoff_s, first_byte_s):
    rows = [{"name": n, "ts_us": 10.0 * i, "dur_us": d, "request": "q",
             "args": a} for i, (n, d, a) in enumerate(spans)]
    out = tracing.request_summary("q", spans=rows)
    assert out["handoff_s"] == handoff_s
    assert out["first_byte_s"] == first_byte_s
