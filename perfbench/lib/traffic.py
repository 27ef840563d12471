"""The one traffic generator: a mix is a data file, this reads it.

Every seed gets the SAME multiset of sizes and of gaps between arrivals
— the distribution's quantiles at (i + 0.5) / n — in an order drawn from
the seed. So two seeds do the same amount of work and differ only in
which request meets which; a run-to-run difference is then the system's,
not the draw's. Where a mix names `order_block`, the order is drawn
block by block (`dealt`), so that every stretch of that many requests
also brings the same work at the same mean rate: which seed draws a
long burst of long answers no longer decides a tail percentile. Token
ids come from (seed, request index) alone, so the client process and
the reference check make the same prompt without passing it around.

numpy only: the client process imports this and must stay off jax.
"""
import math

import numpy as np


def quantile(spec, u):
    """Inverse CDF of a length distribution at u in (0, 1), before
    clipping. `spec["dist"]` is one of const, uniform, pareto, lognormal."""
    kind = spec["dist"]
    if kind == "const":
        return float(spec["value"])
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if kind == "pareto":
        # median m, tail index a: x_m = m / 2**(1/a)
        a = float(spec["alpha"])
        x_m = spec["median"] / 2.0 ** (1.0 / a)
        return x_m / (1.0 - u) ** (1.0 / a)
    if kind == "lognormal":
        # median m and mean mu fix sigma: mu = m * exp(sigma**2 / 2)
        sigma = math.sqrt(2.0 * math.log(spec["mean"] / spec["median"]))
        z = math.sqrt(2.0) * _erfinv(2.0 * u - 1.0)
        return spec["median"] * math.exp(sigma * z)
    raise ValueError(f"unknown distribution {kind!r}")


def _erfinv(y):
    # Winitzki's approximation refined by two Newton steps: exact to 1e-9
    a = 0.147
    ln = math.log(1.0 - y * y)
    t = 2.0 / (math.pi * a) + ln / 2.0
    x = math.copysign(math.sqrt(math.sqrt(t * t - ln / a) - t), y)
    for _ in range(2):
        x -= (math.erf(x) - y) / (2.0 / math.sqrt(math.pi)
                                  * math.exp(-x * x))
    return x


def lengths(spec, n):
    """The n stratified lengths of a distribution, clipped, ascending."""
    out = [int(round(quantile(spec, (i + 0.5) / n))) for i in range(n)]
    return np.clip(out, spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(rate, n):
    """The n stratified gaps of a Poisson process of `rate` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(rate)


def rng_for(seed, *stream):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *stream])


def prompt_tokens(seed, index, n, vocab):
    """Token ids of request `index` under `seed` (never id 0)."""
    return rng_for(seed, 7, int(index)).integers(1, vocab, int(n))


def dealt(values, block, rng):
    """The values in an order drawn from `rng`. Without `block` (or with
    fewer than two blocks' worth) any order. With it, the n values fall
    into b = round(n / block) consecutive blocks, and each run of b
    consecutive order statistics gives one value to each block (which
    to which is drawn), then each block's own order is drawn: every
    block is a stratified sample of the whole multiset, about `block`
    strata, and the multiset is what it was."""
    values = np.sort(np.asarray(values))
    n = len(values)
    b = int(round(n / block)) if block else 1
    if b < 2:
        return rng.permutation(values)
    blocks = [[] for _ in range(b)]
    for lo in range(0, n, b):
        run = values[lo:lo + b]
        for v, k in zip(run, rng.permutation(b)[:len(run)]):
            blocks[k].append(v)
    return np.concatenate([rng.permutation(np.asarray(x)) for x in blocks])


def _sized(mix, n, rng, block=None):
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)
    return dealt(p, block, rng), dealt(o, block, rng)


def open_loop_plan(mix, seed, seconds):
    """Requests of an open-loop mix: a lead-in (a burst at -lead_in_s,
    then arrivals at the mix's rate, so the window opens on a system in
    steady state), the window's arrivals in [0, seconds), and a lead-out
    that keeps the load up while the window's last requests finish.
    Each request: dict(index, phase, due_s, prompt_len, max_new_tokens).
    """
    rate = float(mix["rate_per_s"])
    lead = float(mix.get("lead_in_s", 0.0))
    grace = float(mix.get("grace_s", 10.0))
    burst = int(mix.get("lead_in_burst", 0))
    block = mix.get("order_block")
    plan = []

    def phase(name, n, t0, stream):
        if n <= 0:
            return
        rng = rng_for(seed, stream)
        p, o = _sized(mix, n, rng, block)
        cum = np.cumsum(dealt(exp_gaps(rate, n), block, rng))
        # n arrivals inside [t0, t0 + n / rate), the last strictly inside
        due = t0 + cum * ((n / rate) * n / (n + 1.0) / cum[-1])
        for i in range(n):
            plan.append(dict(phase=name, due_s=float(due[i]),
                             prompt_len=int(p[i]), max_new_tokens=int(o[i])))

    n_lead = int(round(rate * lead))
    if lead > 0:
        # the burst's requests are due together at -lead_in_s
        rng = rng_for(seed, 1)
        p, o = _sized(mix, max(burst, 1), rng)
        for i in range(burst):
            plan.append(dict(phase="lead_in", due_s=-lead,
                             prompt_len=int(p[i]),
                             max_new_tokens=int(o[i])))
        phase("lead_in", n_lead, -lead, 2)
    phase("window", int(round(rate * seconds)), 0.0, 3)
    phase("lead_out", int(round(rate * grace)), float(seconds), 4)
    plan.sort(key=lambda r: r["due_s"])
    for i, r in enumerate(plan):
        r["index"] = i
    return plan


def closed_loop_pool(mix, seed):
    """The documents of a closed-loop mix, in the order clients take
    them: `pool` stratified (prompt, output) sizes, shuffled by the seed.
    """
    n = int(mix["pool"])
    p, o = _sized(mix, n, rng_for(seed, 5))
    return [dict(index=i, phase="pool", prompt_len=int(p[i]),
                 max_new_tokens=int(o[i])) for i in range(n)]


def footprint_bound(mix, n_window, slots, block_size):
    """Hard upper bound on the cache blocks `slots` concurrent requests of
    this mix can hold: the `slots` largest (prompt + output) footprints
    the size multisets can pair."""
    n = max(int(n_window), slots)
    p = np.sort(lengths(mix["prompt"], n))[::-1][:slots]
    o = np.sort(lengths(mix["output"], n))[::-1][:slots]
    return int(sum(-(-int(a + b) // block_size) for a, b in zip(p, o)))
