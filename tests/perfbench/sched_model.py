"""A model of the serving scheduler on the CPU, for COUNTS and for
comparing orders of one multiset of work; never a time. It plays an
open-loop plan (`traffic.open_loop_plan`) through what the engine does
with it: up to `slots` requests in flight, a waiting request admitted as
soon as a slot is free, every step feeding each prefilling slot its next
`chunk` prompt tokens and each decoding slot one token, a step a chunk
step when some slot prefills. What a step holds is arithmetic and exact
(its work-list entries are the cache blocks its slots hold); how long it
takes is a line fitted once to PERF.md section 5's step times on the
chip (PRs 31 and 35: a decode step 10.67 / 10.91 / 11.32 ms at 4 / 8 /
16 entries, a 128-wide step 18.5 / 19.1 / 20.0 / 23.3 at 4 / 8 / 16 /
32, ~9.5 ms for every row tile of 256 live rows after the first), and is
only good for telling one ORDER of the same work from another: PR 38
found the model to put six seeds in the chip's own order and to count
their gaps to within 0.3%.

PERF.md section 6 (PR 38) quotes it for two things, which
test_perfbench_sched_model.py holds: the most blocks in flight a window
of `chat` reaches (against the mix's `warm_t_hi`), and the seed-to-seed
spread of the modelled `itl_ms.p95` with and without `order_block`."""
import math
import statistics

WIDE_EXTRA_MS = {1: 6.0, 2: 6.0, 4: 6.0, 8: 6.0, 16: 6.1, 32: 6.5, 64: 7.0,
                 128: 7.6}
ROW_TILE = 256


def next_pow2(n):
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def step_ms(widths, blocks, decoding, slots):
    """A step over `blocks` work-list entries: `widths` are the chunks
    its prefilling slots take, `decoding` the slots that decode."""
    t = 10.45 + 0.062 * blocks
    if not widths:
        return t
    c = next_pow2(max(widths))
    live = sum(widths) + decoding
    tiles = 1 if slots * c <= ROW_TILE else max(1, -(-live // ROW_TILE))
    return (t + WIDE_EXTRA_MS[c] + (c / 128.0) * (0.4 + 0.12 * blocks)
            + (tiles - 1) * 9.5)


def simulate(plan, seconds, slots=16, chunk=128, block_size=128, grace=30.0):
    """Returns dict(gaps_ms: the gaps between tokens that end inside
    [0, seconds], ttft_ms: of the window's requests, steps: (decode,
    chunk) counts inside the window, blocks_max: the most work-list
    entries of a step dispatched inside the window)."""
    reqs = sorted(plan, key=lambda r: r["due_s"])
    t, i, queue, active = reqs[0]["due_s"], 0, [], []
    gaps, ttft, steps, blocks_max = [], [], [0, 0], 0
    end = seconds + grace
    while t < end and (i < len(reqs) or queue or active):
        while i < len(reqs) and reqs[i]["due_s"] <= t:
            queue.append(reqs[i])
            i += 1
        while queue and len(active) < slots:
            r = queue.pop(0)
            active.append(dict(r=r, rem=r["prompt_len"], ctx=0,
                               left=r["max_new_tokens"], last=None))
        if not active:
            t = reqs[i]["due_s"] if i < len(reqs) else end
            continue
        widths, decoding = [], 0
        for a in active:
            a["take"] = min(chunk, a["rem"])
            if a["take"]:
                widths.append(a["take"])
            else:
                decoding += 1
        blocks = sum(-(-(a["ctx"] + max(a["take"], 1)) // block_size)
                     for a in active)
        if 0.0 <= t <= seconds:
            steps[1 if widths else 0] += 1
            blocks_max = max(blocks_max, blocks)
        t += step_ms(widths, blocks, decoding, slots) / 1e3
        for a in list(active):
            if a["take"]:
                a["rem"] -= a["take"]
                a["ctx"] += a["take"]
                emits = a["rem"] == 0   # the last chunk samples a token
            else:
                a["ctx"] += 1
                emits = True
            if not emits:
                continue
            if a["last"] is None:
                if a["r"]["phase"] == "window":
                    ttft.append((t - a["r"]["due_s"]) * 1e3)
            elif 0.0 <= t <= seconds:
                gaps.append((t - a["last"]) * 1e3)
            a["last"] = t
            a["left"] -= 1
            if a["left"] <= 0:
                active.remove(a)
    return dict(gaps_ms=gaps, ttft_ms=ttft, steps=tuple(steps),
                blocks_max=blocks_max)


def percentile(values, q):
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def spread(values):
    """The quartiles' distance over the median, as the driver takes it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
