"""The serving driver: client process -> HTTP gateway -> EngineStepper ->
ContinuousBatchingEngine -> the engine's paged step, never `generate()`.

Set-up makes the weights on the device from the seed, builds the engine
the configuration file describes, compiles the step program's buckets
(`compile_ahead`) and walks them (`warm_lattice`), brings the system to
its working load with the mix's lead-in, and only then opens the
measured window. After the window the engine is freed and the family's
plain reference is run over a seeded sample of what was served.
"""
import asyncio
import gc
import itertools
import json
import math
import os
import sys
import threading
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "lib"))
import measure  # noqa: E402
import readers  # noqa: E402
import traffic  # noqa: E402

TRACE_SECONDS = 4.0      # the profiler records this much of a traced window
TRACE_START = 0.35       # ... starting at this share of it


def next_pow2(n):
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# -- the compile lattice -----------------------------------------------------------

def lattice_of(cfg, mix, seconds):
    """(levels, widths): the step program compiles once per (work-list
    length, slab width), both rounded up to powers of two. Widths are
    every power of two up to `prefill_chunk`, since a prompt's last chunk
    has any length. Levels run from the mix's `warm_t_lo` to a hard bound
    on the blocks in flight: the cache, and for an open loop the
    `max_batch` largest requests the mix's size multiset can pair. A mix
    may stop the walk lower, at `warm_t_hi`, and says why: a bucket
    beyond it that the window did reach is lowered there, and the run
    reports `correct: false`, not a wrong number."""
    e = cfg["engine"]
    bs, mb = e["block_size"], e["max_batch"]
    cap = e["num_blocks"] - 1
    if mix["loop"] == "open":
        n = int(round(mix["rate_per_s"] * seconds))
        cap = min(cap, traffic.footprint_bound(mix, n, mb, bs))
    else:
        per = -(-(mix["prompt"]["max"] + mix["output"]["max"]) // bs)
        cap = min(cap, mb * per)
    levels, t = [], next_pow2(int(mix.get("warm_t_lo", 64)))
    cap = min(cap, int(mix.get("warm_t_hi", cap)))
    while t < 2 * cap and t <= next_pow2(cap):
        levels.append(t)
        t *= 2
    widths = [1 << i for i in range(int(math.log2(e["prefill_chunk"])) + 1)]
    return levels, widths


def compile_ahead(cb, buckets, threads):
    """Lower and compile every bucket's step program before the walk, the
    compiling on `threads` threads at once. The walk steps the buckets
    one after another, and from an empty cache that is one backend
    compile after another (~14 s each on the v5e's host: 64 buckets do
    not fit the 1200 s a first run may take). Here one real step shows
    the step's arguments; the main thread lowers each bucket through the
    step's own `jax.jit` object with those arguments reshaped to the
    bucket, and a pool calls `compile()` on the lowered modules, which
    leaves the interpreter lock. Nothing is executed and no cache is
    donated. The walk's real calls then find jax's lowerings and
    executables in this process, and a later process finds them in the
    persistent cache. The pool starts only when the lowering is done: a
    compile thread holding the cache directory's file lock (jax takes
    one where a largest cache size is set) would wait for the
    interpreter at every call while the main thread traces, and the
    other threads' locks time out. Returns the seconds spent lowering
    and compiling."""
    from concurrent.futures import ThreadPoolExecutor
    from paddle_tpu.incubate.nn import GenerationRequest
    eng, seen = cb.engine, {}
    real = eng._paged_step

    def record(*args):
        seen["args"] = args
        return real(*args)

    eng._paged_step = record        # for one real step, to see its arguments
    try:
        cb.submit(GenerationRequest(np.ones(1, np.int64), 1,
                                    request_id="warm-0"))
        for _ in range(4):
            if not cb.step():
                break
    finally:
        eng._paged_step = real
    (w, _, slab, q_arr, sel, tables, lens, work, pack, temp, topp,
     key) = seen["args"]
    jitted = real.__wrapped__
    t0 = time.perf_counter()
    lowered = [jitted.lower(
        w, cb.caches, np.zeros((slab.shape[0], c), slab.dtype), q_arr,
        np.zeros((sel.shape[0], min(c, 1 + cb.spec_k)), sel.dtype),
        tables, lens, tuple(np.zeros(t, a.dtype) for a in work),
        pack, temp, topp, key) for t, c in buckets]
    t1 = time.perf_counter()
    with ThreadPoolExecutor(threads, thread_name_prefix="compile") as pool:
        # the executables are dropped at once: jax and its cache keep them
        for _ in pool.map(lambda low: low.compile() and None, lowered):
            pass
    return t1 - t0, time.perf_counter() - t1


def warm_lattice(cb, cfg, levels, widths, vocab):
    """Visit every (level, width) bucket through the engine's own
    `submit` and `step`. Ballast requests hold a known number of cache
    blocks in flight while one-token probes of each width pass through:
    a probe of width c lands in bucket (t, c) when the ballast's blocks B
    satisfy t/2 < B + ceil(c / block) <= t. The harness counts B itself
    (prompt + generated so far, rounded up to blocks), so which bucket a
    step lands in is arithmetic, not luck. Each level builds its own
    ballast and, inside a level, widths descend, so B only ever has to
    grow. Returns the set of buckets it aimed at and could reach."""
    from paddle_tpu.incubate.nn import GenerationRequest
    e = cfg["engine"]
    bs, chunk, mb = e["block_size"], e["prefill_chunk"], e["max_batch"]
    pool = e["num_blocks"] - 1
    rng = np.random.default_rng(0)
    ballast, ids = [], itertools.count(1)
    blocks = lambda tokens: -(-tokens // bs)

    def new_request(n_prompt, n_out):
        return GenerationRequest(rng.integers(1, vocab, n_prompt), n_out,
                                 request_id=f"warm-{next(ids)}")

    def alive():
        return [r for r in ballast if not r.done]

    def inflight():     # blocks the ballast holds in the NEXT step
        return sum(blocks(len(r.prompt) + max(1, len(r.generated)))
                   for r in alive())

    def reserved():     # blocks admission holds back for the ballast
        return sum(blocks(len(r.prompt) + r.max_new_tokens)
                   for r in alive())

    def top_up(to_blocks):
        """More ballast, prefilled, so inflight() reaches `to_blocks` (as
        far as a sequence's length and the pool allow). A step costs the
        same however many slots prefill in it, so the blocks are spread
        over as many requests as there are slots."""
        n_out = len(widths) + 8         # outlives its level, little more
        free = mb - 1 - len(alive())
        before = inflight()
        need = to_blocks - before
        if need < 1 or free < 1:
            return False
        room = pool - reserved() - blocks(chunk + 1) - 1
        per = min(-(-need // free), (e["max_seq_len"] - n_out) // bs - 1,
                  room // free - blocks(n_out) - 1)
        k = min(free, -(-need // max(per, 1)))  # may overshoot by < per
        if per < 1:
            return False
        # a few tokens short of a whole block, so that the token a step
        # appends does not tip a request into its next block mid-level
        tokens = per * bs - min(bs // 2, n_out)
        for _ in range(k):
            r = new_request(tokens, n_out)
            cb.submit(r)
            ballast.append(r)
        for _ in range(-(-tokens // chunk)):
            cb.step()
        return inflight() > before

    reached = set()
    for t in levels:
        t_level = time.perf_counter()
        for r in alive():               # each level builds its own ballast
            cb.cancel(r.request_id)
        ballast.clear()
        for c in reversed(widths):
            pb = 0 if c == 1 else blocks(c)
            lo, hi = t // 2 - pb, t - pb        # lo < B <= hi
            while inflight() <= lo:
                if not top_up(max(lo + 1, min(hi, t // 2 + 2))):
                    break
            if not lo < inflight() <= hi:
                continue        # e.g. a wide probe under a small level
            if c > 1:
                cb.submit(new_request(c, 1))
            t_step = time.perf_counter()
            cb.step()
            reached.add((t, c))
            if time.perf_counter() - t_step > 5.0:
                print(f"[setup]   bucket ({t}, {c}) took "
                      f"{time.perf_counter() - t_step:.1f} s", flush=True)
        print(f"[setup]  level {t}: {time.perf_counter() - t_level:.1f} s, "
              f"{len(alive())} ballast requests hold {inflight()} blocks",
              flush=True)
    for r in alive():
        cb.cancel(r.request_id)
    cb.step()
    want = {(t, c) for t in levels for c in widths}
    seen = getattr(cb, "_seen_buckets", None)
    print(f"[setup] lattice: reached {len(reached)} of {len(want)} buckets, "
          f"not reachable {sorted(want - reached)}"
          + ("" if seen is None else
             f"; the engine saw {len(seen)}, beyond the lattice "
             f"{sorted(set(seen) - want)}, aimed at and not seen "
             f"{sorted(reached - set(seen))}"), flush=True)
    return reached


# -- spans out of the program's ring ----------------------------------------------

class SpanTap(threading.Thread):
    """The program's span ring holds 8192 spans and a serving window
    makes more: this copies the per-request ones (`first_token`,
    `queue_wait`) out as they come, in traced runs only."""

    NAMES = ("first_token", "queue_wait")

    def __init__(self, period=0.25):
        super().__init__(daemon=True, name="span-tap")
        self.period = period
        self.rows = {}
        self._halt = threading.Event()

    def run(self):
        from paddle_tpu.observability import tracing
        tracer = tracing.get_tracer()
        since = None
        while not self._halt.is_set():
            now_us = time.perf_counter() * 1e6
            for s in tracer.spans(since_us=since):
                if s["name"] in self.NAMES and s["request"] is not None:
                    self.rows[(s["name"], s["request"])] = s
            since = now_us - 2e6 * self.period
            self._halt.wait(self.period)

    def stop(self):
        self._halt.set()
        self.join(5.0)
        return list(self.rows.values())


# -- the window ----------------------------------------------------------------------

async def _drive(ctx, cb, stepper, plan, mix, seconds, tag):
    """Gateway up, client process through its plan, gateway down.
    Returns (client records, window facts)."""
    import jax
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import ServingGateway

    loop = asyncio.get_running_loop()
    gw = await ServingGateway(stepper, port=0).start()
    facts = {}
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(os.path.dirname(_HERE), "lib",
                                     "client.py"),
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
        limit=1 << 28)
    try:
        plan = dict(plan, port=gw.port, tag=tag)
        proc.stdin.write((json.dumps(plan) + "\n").encode())
        await proc.stdin.drain()
        ready = await asyncio.wait_for(proc.stdout.readline(), 60)
        if ready.strip() != b"ready":
            raise RuntimeError(f"client said {ready!r}")
        lead = float(mix.get("lead_in_s", 0.0))
        t0 = time.monotonic() + lead + 0.3
        proc.stdin.write(f"go {t0!r}\n".encode())
        await proc.stdin.drain()
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        # ---- the window opens: set-up ends here
        facts["t0"] = t0
        facts["setup_s"] = t0 - ctx["t_start"]
        facts["reg0"] = obs.get_registry().snapshot()
        facts["compile0"] = ctx["watch"].mark()
        jax.config.update("jax_log_compiles", True)   # names a stray one
        # the allocator's high-water mark counts from here, so that it
        # reads the window's own and not the walk's: the most blocks held
        # at once, which no work list of the window was longer than
        cb.allocator.high_water = cb.allocator.num_used
        tap = None
        if ctx["trace"]:
            tap = SpanTap()
            tap.start()
            t_on = t0 + TRACE_START * seconds
            await asyncio.sleep(max(0.0, t_on - time.monotonic()))
            await loop.run_in_executor(
                None, jax.profiler.start_trace, ctx["trace_dir"])
            facts["trace_on"] = time.monotonic() - t0
            await asyncio.sleep(min(TRACE_SECONDS, 0.5 * seconds))
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            facts["trace_off"] = time.monotonic() - t0
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        # ---- the window closes
        jax.config.update("jax_log_compiles", False)
        facts["reg1"] = obs.get_registry().snapshot()
        facts["compile1"] = ctx["watch"].mark()
        facts["blocks_high_water"] = int(cb.allocator.high_water)
        facts["device"] = measure.device_facts(ctx["devices"])
        out = await asyncio.wait_for(
            proc.stdout.readline(),
            float(mix.get("grace_s", 10.0)) + 30.0)
        await proc.wait()
        facts["spans"] = tap.stop() if tap is not None else []
        return json.loads(out)["records"], facts
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        await gw.close()


def build_plan(cfg, mix, seed, seconds):
    vocab = cfg["vocab_size"]
    base = dict(seed=int(seed), vocab=vocab, seconds=float(seconds),
                loop=mix["loop"], grace_s=float(mix.get("grace_s", 10.0)),
                lead_in_s=float(mix.get("lead_in_s", 0.0)))
    if mix["loop"] == "open":
        base["requests"] = traffic.open_loop_plan(mix, seed, seconds)
    elif mix["loop"] == "closed":
        base["requests"] = traffic.closed_loop_pool(mix, seed)
        base["clients"] = int(mix["clients"])
    else:
        raise ValueError(f"the serving driver has no loop {mix['loop']!r}")
    return base


# -- end-to-end numbers from the client's records ---------------------------------------

def end_to_end(records, mix, seconds):
    """Every end-to-end candidate the records support, with the counts.
    Times are the client's, from when a request was DUE."""
    grace = float(mix.get("grace_s", 10.0))
    counted = [r for r in records if r["phase"] == "window"] \
        if mix["loop"] == "open" else \
        [r for r in records if r["phase"] in ("window", "lead_in")]
    ok_status = ("finished",) if mix["loop"] == "open" else ("finished",
                                                             "cut")
    failed = [r for r in counted if r["status"] not in ok_status
              or (r["status"] == "finished"
                  and len(r["tokens"] or ()) != r["max_new_tokens"])]
    ttft, late = [], []
    for r in counted:
        if r.get("sent") is not None and r.get("due") is not None:
            late.append(r["sent"] - r["due"])
        if r["events"]:
            ttft.append((r["events"][0][0] - r["due"]) * 1e3)
        elif r in failed:
            ttft.append((seconds + grace) * 1e3)   # misses every limit
    gaps, out_tokens, prompt_tokens = [], 0, 0
    for r in records:
        ev = r["events"]
        for (ta, _), (tb, nb) in zip(ev, ev[1:]):
            if 0.0 <= tb <= seconds:
                gaps.extend([(tb - ta) * 1e3 / nb] * nb)
        out_tokens += sum(n for t, n in ev if 0.0 <= t <= seconds)
        if ev and 0.0 <= ev[0][0] <= seconds:
            prompt_tokens += r["prompt_len"]
    m = {}
    if ttft:
        m["ttft_ms.p95"] = measure.percentile(ttft, 95)
        m["ttft_ms.p50"] = measure.percentile(ttft, 50)
    if gaps:
        # p95 is the metric; p50, p90 and p99 are printed beside it to
        # show which population of gaps (decode steps, chunk steps) the
        # p95 sits in or between
        for q in (50, 90, 95, 99):
            m[f"itl_ms.p{q}"] = measure.percentile(gaps, q)
    m["serve_tokens_per_s"] = (out_tokens + prompt_tokens) / seconds
    counts = dict(attempted=len(counted), failed=len(failed),
                  finished=sum(r["status"] == "finished" for r in counted),
                  cut=sum(r["status"] == "cut" for r in counted),
                  ttft_samples=len(ttft), itl_samples=len(gaps),
                  out_tokens=out_tokens, prompt_tokens=prompt_tokens,
                  lateness_ms_p95=(measure.percentile(late, 95) or 0) * 1e3,
                  lateness_ms_max=(max(late) if late else 0) * 1e3,
                  failed_statuses=sorted({r["status"] for r in failed}),
                  streams_first_5s=streams_in_flight(
                      records, 0.0, min(5.0, seconds), seconds + grace),
                  streams_window=streams_in_flight(
                      records, 0.0, seconds, seconds + grace))
    return m, counts


def streams_in_flight(records, lo, hi, horizon):
    """Mean number of requests between sent and ended over [lo, hi), by
    the client's clock: how the lead-in is sized (the window has to open
    on its own load). One that never ended counts up to `horizon`."""
    held = 0.0
    for r in records:
        if r.get("sent") is None:
            continue
        end = r.get("end")
        held += max(0.0, min(hi, horizon if end is None else end)
                    - max(lo, r["sent"]))
    return held / (hi - lo)


# -- correctness ---------------------------------------------------------------------------

def sample_streams(records, cfg, seed, n):
    # `seed` is the traffic's: it keys the prompts
    """A seeded sample of the finished requests, the longest among them:
    [(prompt ids, served ids)]."""
    done = [r for r in records if r["status"] == "finished" and r["tokens"]
            and r["phase"] != "lead_out"]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + len(r["tokens"]), r["index"]))
    picked = [done.pop()]
    rng = traffic.rng_for(seed, 11)
    for i in rng.permutation(len(done))[:max(0, n - 1)]:
        picked.append(done[int(i)])
    return [(traffic.prompt_tokens(seed, r["doc"], r["prompt_len"],
                                   cfg["vocab_size"]),
             np.asarray(r["tokens"], np.int64)) for r in picked]


def check_served(family, cfg, seed, streams, mix):
    """The numbers compared, each beside its limit. The served token's
    reference logit lies `gap` below the reference's best at that
    position: 0 for the argmax."""
    limits = cfg["check"]
    if not streams:
        return False, [dict(name="streams_compared", value=0, limit=">=1",
                            ok=False)]
    t0 = time.perf_counter()
    res = family.served_token_gaps(
        seed, cfg, streams,
        length=mix["prompt"]["max"] + mix["output"]["max"])
    gaps = np.concatenate([g for g, _ in res])
    best = np.concatenate([b for _, b in res])
    finite = bool(np.isfinite(gaps).all())
    rows = [dict(name="mean_gap", value=float(gaps.mean())),
            dict(name="worst_gap", value=float(gaps.max())),
            dict(name="nonargmax_share", value=float(1.0 - best.mean()))]
    for r in rows:      # a number without a limit in the file is printed only
        r["limit"] = limits.get(r["name"] + "_max", "-")
        r["ok"] = bool(finite and (r["limit"] == "-"
                                   or r["value"] <= r["limit"]))
    rows.append(dict(name="tokens_compared", value=int(gaps.size),
                     limit=">=1", ok=bool(gaps.size >= 1)))
    rows.append(dict(name="reference_s", value=time.perf_counter() - t0,
                     limit="-", ok=True))
    return all(r["ok"] for r in rows), rows


# -- the run ---------------------------------------------------------------------------------

def build_engine(ctx, cfg, family, weight_quant=None):
    from paddle_tpu.incubate.nn import ContinuousBatchingEngine
    from paddle_tpu.inference import FusedMultiTransformerEngine
    e = cfg["engine"]
    weights = family.serve_weights(ctx["seed"], cfg)
    engine = FusedMultiTransformerEngine(
        weights, tp=int(cfg.get("tp", 1)), weight_quant=weight_quant,
        **family.serve_engine_kwargs(cfg))
    del weights
    cb = ContinuousBatchingEngine(
        engine, num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], temperature=e["temperature"],
        prefill_chunk=e["prefill_chunk"])
    return engine, cb


def sweep_row(rate, records, facts, metrics, counts, seconds, limits):
    """One offered rate's readings, and whether the system sustained it
    by the mix's own `knee_limits`: nothing failed, the client kept up
    (`lateness_ms_p95_max`), `ttft_ms.p95` under `ttft_ms_p95_max` and
    not growing from the window's first half to its second. A backlog
    that grows lifts the second half's by seconds; two halves of a steady
    window differ by the noise of a 95th percentile over a hundred
    requests each, so "no higher" has the room of `ttft_growth_share` of
    the first half's or `ttft_growth_ms`, whichever is more."""
    win = [r for r in records if r["phase"] == "window" and r["events"]]
    half = lambda lo, hi: measure.percentile(
        [(r["events"][0][0] - r["due"]) * 1e3 for r in win
         if lo <= r["due"] < hi] or [0.0], 95)
    first, second = half(0, seconds / 2), half(seconds / 2, seconds)
    row = dict(rate=rate, attempted=counts["attempted"],
               failed=counts["failed"],
               lateness_ms_p95=counts["lateness_ms_p95"],
               ttft_p50=metrics.get("ttft_ms.p50"),
               ttft_p95=metrics.get("ttft_ms.p95"),
               ttft_p95_first_half=first, ttft_p95_second_half=second,
               **{f"itl_p{q}": metrics.get(f"itl_ms.p{q}")
                  for q in (50, 90, 95, 99)},
               itl_samples=counts["itl_samples"],
               tokens_per_s=metrics["serve_tokens_per_s"],
               **readers.window_steps(facts["reg0"], facts["reg1"]),
               blocks_high_water=facts["blocks_high_water"],
               streams_window=counts["streams_window"],
               streams_first_5s=counts["streams_first_5s"])
    row["sustained"] = bool(
        row["failed"] == 0 and row["ttft_p95"] is not None
        and row["lateness_ms_p95"] < limits["lateness_ms_p95_max"]
        and row["ttft_p95"] < limits["ttft_ms_p95_max"]
        and second - first <= max(limits["ttft_growth_share"] * first,
                                  limits["ttft_growth_ms"]))
    return row


def sweep(ctx, cb, stepper, rates):
    """Not a measured run: one window per offered rate in one process,
    to find the knee once: the highest rate `sweep_row` calls sustained."""
    cfg, mix, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    rows = []
    try:
        for i, rate in enumerate(rates):
            m2 = dict(mix, rate_per_s=float(rate), grace_s=8.0)
            plan = build_plan(cfg, m2, ctx["seed"] + i, seconds)
            records, facts = asyncio.run(_drive(
                ctx, cb, stepper, plan, m2, seconds, tag=f"r{i}"))
            metrics, counts = end_to_end(records, m2, seconds)
            row = sweep_row(rate, records, facts, metrics, counts, seconds,
                            mix["knee_limits"])
            row["window_compiles"] = ctx["watch"].diff(
                facts["compile0"], facts["compile1"])["lowers"]
            if ctx.get("control") or ctx.get("sweep_check"):
                streams = sample_streams(records, cfg, ctx["seed"] + i,
                                         int(cfg["check"]["sample_requests"]))
                _, checks = check_served(ctx["family"], cfg, ctx["seed"],
                                         streams, m2)
                row.update({c["name"]: c["value"] for c in checks})
            rows.append(row)
            print(f"[sweep] {json.dumps(row)}", flush=True)
    finally:
        stepper.stop()
    return dict(metrics={"setup_s": 0.0}, counts=dict(attempted=0, failed=0),
                facts=dict(device=measure.device_facts(ctx["devices"])),
                records=[], checks=[], correct=False, sweep=rows)


def run(ctx):
    import jax
    from paddle_tpu.serving import EngineStepper

    cfg, mix, family = ctx["config"], ctx["traffic"], ctx["family"]
    seconds = ctx["seconds"]
    watch = ctx["watch"]
    engine, cb = build_engine(ctx, cfg, family, ctx.get("weight_quant"))
    jax.block_until_ready(cb.caches)
    print(f"[setup] engine built at {time.monotonic() - ctx['t_start']:.1f} s",
          flush=True)
    # a sweep walks the lattice of its highest rate
    levels, widths = lattice_of(
        cfg, dict(mix, rate_per_s=max(ctx["sweep"])) if ctx.get("sweep")
        else mix, seconds)
    m0 = watch.mark()
    lower_s, compile_s = compile_ahead(
        cb, [(t, c) for t in levels for c in widths],
        threads=max(1, min(12, (os.cpu_count() or 2) - 1)))
    m1 = watch.mark()
    ad = watch.diff(m0, m1)
    print(f"[setup] compiled ahead: {lower_s:.1f} s lowering, then "
          f"{compile_s:.1f} s in the pool; {ad['lowers']} lowered, "
          f"{ad['compiles']} compiled, {ad['cache_hits']} cache hits, "
          f"{ad['cache_misses']} misses", flush=True)
    t_w = time.perf_counter()
    lattice = warm_lattice(cb, cfg, levels, widths, cfg["vocab_size"])
    wd = watch.diff(m1, watch.mark())
    print(f"[setup] lattice levels {levels} x widths {widths}: "
          f"{time.perf_counter() - t_w:.1f} s, {wd['traces']} traced, "
          f"{wd['lowers']} lowered, {wd['compiles']} compiled, "
          f"{wd['cache_hits']} cache hits, {wd['cache_misses']} misses",
          flush=True)
    stepper = EngineStepper(cb).start()
    if ctx.get("sweep"):
        return sweep(ctx, cb, stepper, ctx["sweep"])
    plan = build_plan(cfg, mix, ctx["seed"], seconds)
    try:
        records, facts = asyncio.run(_drive(
            ctx, cb, stepper, plan, mix, seconds, tag=f"s{ctx['seed']}"))
    finally:
        stepper.stop()
    if stepper.error is not None:
        raise RuntimeError(f"the stepper died: {stepper.error!r}")
    alloc = cb.allocator
    facts["engine"] = dict(
        num_blocks=alloc.num_blocks,
        kv_high_water=int(getattr(alloc, "high_water", 0)),
        steps=stepper.steps, lattice=len(lattice),
        buckets_seen=len(getattr(cb, "_seen_buckets", ())))
    window = watch.diff(facts["compile0"], facts["compile1"])
    facts["window_compiles"] = window["compiles"] + window["lowers"]
    metrics, counts = end_to_end(records, mix, seconds)
    counts.update(readers.window_steps(facts["reg0"], facts["reg1"]),
                  blocks_high_water=facts["blocks_high_water"],
                  lattice_top=levels[-1])
    metrics["setup_s"] = facts["setup_s"]
    streams = sample_streams(records, cfg, ctx["seed"],
                             int(cfg["check"]["sample_requests"]))
    # free the program before the reference takes the chip
    del engine, cb, stepper
    gc.collect()
    jax.clear_caches()
    ok, rows = check_served(family, cfg, ctx["seed"], streams, mix)
    # a first sighting inside the window is a stall in a tail: the run
    # does not count (the CPU rehearsal times nothing, so there it only
    # reports)
    rows.append(dict(name="window_compiles", value=facts["window_compiles"],
                     limit=0, ok=facts["window_compiles"] == 0
                     or ctx["rehearse"]))
    return dict(metrics=metrics, counts=counts, facts=facts, records=records,
                checks=rows, correct=all(r["ok"] for r in rows))
