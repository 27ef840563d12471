"""Paged KV-cache serving attention as a Pallas TPU kernel.

Reference: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
(paged/block KV cache) and masked_multihead_attention_kernel.cu (decode
attention) behind python/paddle/incubate/nn/functional
block_multihead_attention (SURVEY.md §2.9).

TPU-native shape: the KV cache lives in HBM as fixed-size blocks; each
sequence owns a list of block ids (block_tables [B, max_blocks]). A
layer's K and V halves are ONE buffer [2, KVH, num_blocks, block_size,
Dc] that the engine's step program donates: the writers at the end of
this file lay new rows into it (a small kernel that moves the 8-row
groups its live tokens fall in) and the ragged kernel DMAs blocks out of
it, so no program slices a half out or stacks two back. Dc is the
head dim rounded up to the 128-lane tile (`paged_head_dim`; the pad
lanes hold zeros): Mosaic DMAs whole (sublane, 128) tiles, so a 64-wide
row cannot be sliced out of HBM
("Slice shape along dimension 3 must be aligned to tiling (128)"), and
XLA lays a sub-128 minor dim out transposed, which would put a relayout
copy of the whole cache around every kernel call.

The kernel, `ragged_paged_attention` ("Ragged Paged Attention",
PAPERS.md): the grid is flattened over a scalar-prefetched work list with
one entry per ACTUAL cache block (length = sum of per-sequence block
counts — no padding-block steps), the GQA query groups of `pack`
co-scheduled sequences ride one [pack*G, D] VMEM tile so the MXU
multiplies real sublanes, and consecutive KV-block loads are
double-buffered by hand (two VMEM slots + DMA semaphores; step t waits
slot t%2 after kicking off t+1's copy) so the next block streams from HBM
while the current one is in the MXU.

Each work entry carries its sequence's QUERY SPAN (q_start, q_len):
decode sequences span one token, prefill sequences a chunk of up to C
prompt tokens — so one kernel invocation serves a MIXED prefill+decode
batch, the Sarathi-style chunked-prefill step. Speculative decode rides
the same span: a decode sequence verifying K prompt-lookup drafts asks
for a 1+K span (its last real token plus the drafts), pays ONE kernel
invocation for all K+1 positions, and the host rolls rejected suffixes
back with `truncate_paged_kv`. The packed tile grows to [pack*C*G, D]
(C query positions per sequence) and each query row is causally masked
to its own absolute position, so a 512-token prompt costs ceil(512/C)
steps at C-row MXU intensity instead of 512 steps at one row.

A grid step does NOT multiply that whole tile. Its entry is one cache
block of one slot, of whose span q_len positions are live this step, so
it visits only the SUB_ROWS-row sub-tiles of the tile that hold those
q_len*G rows (a `lax.fori_loop` over `pl.ds` slices of the query block,
the running max / sum / accumulator scratch and the output block, its
trip count read from the scalar-prefetched q_len, its first trip from
the causal bound: a sub-tile whose last query sits before the block's
first position is skipped too). A decode slot in a 128-wide chunk step
costs one sub-tile where it cost the 1024-row tile, a prefilling slot
what its span holds, and a padding entry of the bucketed list waits for
its DMA and returns. The accumulators start over at a slot's first block
and its live rows are written out at its last, so rows no slot had live
are never written (the wrapper masks them). A tile of at most SUB_ROWS
rows (every decode bucket) is its own single sub-tile, with no loop.
`attn_rows` counts the same trips on the host for the scheduler's
`serve_attn_rows_total`.

The work list is built host-side (`build_ragged_work`) because the block
allocator that owns the tables is host code anyway; under `jax.jit` the
caller passes the arrays in (`work=`) and the list length stays static
per compile (bucket it — `bucket_to=next_pow2` — so mixed-progress
serving batches reuse a handful of programs).

Tensor-parallel serving shards this kernel over KV HEADS (the grid's
first axis): each device of a `tp` mesh holds a [KVH/tp, NB, BS, D]
cache shard plus the query heads of its kv groups, and runs the SAME
work list over its local heads (`kv_head_shard` spells the ownership
contract). Nothing in the kernel changes — the per-device call is just
a smaller-KVH instance — which is exactly the property that makes the
work-list design shard cleanly: work items are (sequence, block) pairs,
head-blind by construction, so one host-built list drives every shard
of one compiled mesh step (inference/tp_layout.py + the engine's
shard_map'd paged programs).
"""
import functools
import math
import typing

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES, NEG_INF, _interpret_mode


# ---------------------------------------------------------------------------
# ragged paged attention
# ---------------------------------------------------------------------------

def next_pow2(n):
    """Work-list bucketing for serving: compile one program per power of
    two instead of one per distinct total block count."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def paged_head_dim(head_dim):
    """Minor dim of a paged KV cache serving `head_dim`-wide heads: the
    next multiple of the 128-lane tile (see the module docstring)."""
    return -(-int(head_dim) // LANES) * LANES


def _lane_pad(x, width):
    """Zero-pad x's minor dim up to `width` (a cache's Dc)."""
    pad = width - x.shape[-1]
    if pad < 0:
        raise ValueError(
            f"head dim {x.shape[-1]} wider than the cache rows ({width})")
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def kv_head_shard(num_kv_heads, tp, rank=None):
    """Kv-head ownership under tensor-parallel serving: the ragged
    kernel's grid is (kv_head, work item), so the natural multi-chip
    split hands each of `tp` devices a contiguous `num_kv_heads/tp`
    head slice of the paged cache — the WORK LIST itself is head-blind
    (one entry per (sequence, cache block)) and replicates verbatim,
    which is what lets the host build it once for the whole mesh.

    Returns (start, count) for `rank`, or just `count` when rank is
    None (the per-device head budget). Raises when the heads don't
    split evenly: a ragged head split would give devices different
    grid shapes and break the shared (work-list length, chunk width)
    compile keys."""
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if num_kv_heads % tp != 0:
        raise ValueError(
            f"kv heads ({num_kv_heads}) must divide evenly over tp "
            f"({tp}): every device must run the same (kvh, work) grid")
    count = num_kv_heads // tp
    if rank is None:
        return count
    if not 0 <= int(rank) < tp:
        raise ValueError(f"rank {rank} outside [0, {tp})")
    return int(rank) * count, count


def build_ragged_work(block_tables, context_lens, block_size, pack,
                      bucket_to=None, q_lens=None):
    """Flatten (sequence, block) pairs into the ragged kernel's work list.

    Host-side on purpose: the block tables live on the host in the serving
    allocator, and the list length must be static under jit. Entries are
    group-major (all blocks of the `pack` co-scheduled sequences of group
    0, then group 1, ...) so the kernel's accumulators live across exactly
    one contiguous span per group.

    Each entry carries its sequence's QUERY SPAN (q_start, q_len): the
    chunk of trailing context positions that act as queries this step.
    Decode is q_len == 1 (the default when `q_lens` is omitted: span =
    the last token); chunked prefill passes `q_lens` [B] with up to
    `chunk` new tokens per sequence. `context_lens` always counts the
    TOTAL context including the span, so q_start = len - q_len. A
    sequence whose q_len is 0 is skipped outright — zero work entries,
    zero grid steps (its output rows are masked off by the caller).

    Returns (arrays, t_real, t_total, pack): nine int32 [t_total] arrays
    (seq id, group id, row-in-group, cache block id, block position,
    group-first flag, group-last flag, query start, query len), the
    number of real entries, the padded length (== t_real unless
    bucket_to is given), and the (clamped) pack factor the list was
    built with — the kernel's query packing MUST use the same pack, so
    pass this whole tuple as `ragged_paged_attention(..., work=...)` and
    it travels together. Padding entries point their block position past
    every valid token (and carry q_len 0) so the kernel masks them to a
    no-op.

    A length past the table capacity (max_blocks * block_size) walks only
    the blocks that exist: this pairs with `append_paged_kv`
    dropping the write a full row has no slot for — the row attends over
    its capacity tokens instead of indexing past its table row.
    """
    tables = np.asarray(block_tables)
    lens = np.asarray(context_lens)
    b = lens.shape[0]
    pack = max(1, min(int(pack), b))
    max_nb = tables.shape[1]
    if q_lens is None:
        ql_arr = np.ones(b, np.int64)
    else:
        ql_arr = np.asarray(q_lens).astype(np.int64).reshape(-1)
        if ql_arr.shape[0] != b:
            raise ValueError(
                f"q_lens must be shape [{b}], got {ql_arr.shape}")
    ws, wg, wr, wblk, wpos, wfirst, wlast, wqs, wql = (
        [] for _ in range(9))
    for grp in range(-(-b // pack)):
        start_t = len(ws)
        for s in range(grp * pack, min((grp + 1) * pack, b)):
            if q_lens is not None and ql_arr[s] <= 0:
                continue    # no queries this step: costs zero grid steps
            q_len = int(ql_arr[s])
            q_start = max(int(lens[s]) - q_len, 0)
            for j in range(min(-(-int(lens[s]) // block_size), max_nb)):
                ws.append(s)
                wg.append(grp)
                wr.append(s % pack)
                wblk.append(int(tables[s, j]))
                wpos.append(j)
                wfirst.append(0)
                wlast.append(0)
                wqs.append(q_start)
                wql.append(q_len)
        if len(ws) > start_t:
            wfirst[start_t] = 1
            wlast[-1] = 1
    t_real = len(ws)
    t_total = t_real
    if bucket_to is not None and t_real > 0:
        t_total = max(t_real, int(bucket_to(t_real)))
        last_grp = wg[-1]
        # sentinel block position far past any representable cache length
        # (NOT max_nb: an over-capacity len could still reach past that),
        # int32-safe in the kernel's pos = wpos*block_size + iota
        pad_pos = (1 << 30) // block_size
        for _ in range(t_total - t_real):
            ws.append(0)
            wg.append(last_grp)  # same q/out block: no pipeline flush
            wr.append(0)
            wblk.append(0)
            wpos.append(pad_pos)  # position >= every len: fully masked
            wfirst.append(0)
            wlast.append(0)
            wqs.append(0)
            wql.append(0)        # zero-length span: every row masked
    arrs = tuple(np.asarray(a, np.int32)
                 for a in (ws, wg, wr, wblk, wpos, wfirst, wlast, wqs, wql))
    return arrs, t_real, t_total, pack


class RaggedWorkBuilder:
    """Incremental `build_ragged_work`: same nine arrays, same padding,
    same bucket math — assembled into persistent per-bucket buffers
    instead of per-step Python lists.

    The serving invariant this exploits: a steady-state decode slot's
    (seq, block) entries are STRUCTURALLY constant step to step — its
    seq/group/row/position columns never change, and its block-id column
    only changes when the allocator touches the slot's table row
    (admit, grow, COW, rewind, preempt, retire). The engine marks
    exactly those sites dirty; everything else reuses the segment
    already sitting in the buffer. Only the per-entry query span
    (q_start, q_len) is refreshed every step — q_start advances with
    every committed token, so it can never be cached — as one scalar
    slice-fill per active slot.

    Two assembly modes, chosen per step:
      * incremental — the per-slot segment layout AND the padded bucket
        match the previous step: only dirtied slots' block columns are
        rewritten (at unchanged offsets), flags and padding stand.
      * full — layout or bucket changed: every active slot's segment is
        re-laid out (vectorized row-slice copies, still no Python entry
        lists), flags recomputed, the pad tail refreshed.

    Counters (`segments_reused` / `segments_rebuilt` / `assemblies_*`)
    count ACTIVE slots only, so a steady-state decode step scores 100%
    reuse — the number `tests/test_host_fastpath.py` pins.

    The returned arrays are views of the persistent bucket buffer. jit
    does not copy a numpy argument at dispatch (an aligned buffer is
    aliased on the CPU, copied asynchronously on an accelerator), so
    the NEXT build may run only once the previous step's result has
    been fetched — which is where the engine's step() ends."""

    def __init__(self, batch, max_blocks, block_size, pack,
                 bucket_to=next_pow2):
        self.batch = int(batch)
        self.max_blocks = int(max_blocks)
        self.block_size = int(block_size)
        self.pack = max(1, min(int(pack), self.batch))
        self.bucket_to = bucket_to
        b = self.batch
        # per-slot cached state: block-column validity (dirty flag) and
        # the segment length the buffer currently holds for the slot
        self._dirty = np.ones(b, bool)      # nothing cached yet
        self._seg_n = np.full(b, -1, np.int64)
        # scratch (size-b host math, reused every step)
        self._ncov = np.zeros(b, np.int64)
        self._seglen = np.zeros(b, np.int64)
        self._off = np.zeros(b + 1, np.int64)
        self._arange = np.arange(self.max_blocks, dtype=np.int32)
        self._pad_pos = (1 << 30) // self.block_size
        # bucket buffers: t_total -> (nine arrays, state dict). `state`
        # remembers the layout the buffer holds so a return to the same
        # bucket after a detour still re-lays out correctly.
        self._bufs = {}
        self._last_total = None     # bucket used by the previous build
        self._empty = tuple(np.zeros(0, np.int32) for _ in range(9))
        # counters — monotonic, read by the engine's host_stats
        self.segments_reused = 0
        self.segments_rebuilt = 0
        self.assemblies_full = 0
        self.assemblies_incremental = 0

    def mark_dirty(self, slot):
        """Invalidate slot's cached block column. Call from every site
        that writes the slot's block-table row."""
        self._dirty[slot] = True

    def mark_all_dirty(self):
        self._dirty[:] = True

    def _bucket_buf(self, t_total):
        ent = self._bufs.get(t_total)
        if ent is None:
            arrs = [np.zeros(t_total, np.int32) for _ in range(9)]
            arrs[4][:] = self._pad_pos     # wpos: fully-masked sentinel
            ent = (tuple(arrs), {"seglen": None, "t_real": 0,
                                 "last_grp": -1})
            self._bufs[t_total] = ent
        return ent

    def build(self, block_tables, context_lens, q_lens):
        """Drop-in for `build_ragged_work(tables, lens, block_size,
        pack, bucket_to=..., q_lens=...)` over the persistent engine
        arrays. `context_lens` counts the TOTAL span (len + q) exactly
        like the from-scratch builder."""
        b = self.batch
        bs = self.block_size
        ql = q_lens
        # n_cov per slot: blocks the attention span touches, clipped to
        # the table width (over-capacity lens walk only real blocks)
        np.floor_divide(
            np.asarray(context_lens, np.int64) + (bs - 1), bs,
            out=self._ncov)
        np.minimum(self._ncov, self.max_blocks, out=self._ncov)
        np.multiply(self._ncov, ql > 0, out=self._seglen)
        np.cumsum(self._seglen, out=self._off[1:])
        t_real = int(self._off[b])
        if t_real == 0:
            # no work entries at all (every active slot budget-starved):
            # the from-scratch builder skips bucketing and returns nine
            # empty arrays — reproduce that, and force a full re-layout
            # on the next nonempty step
            self._last_total = None
            return self._empty, 0, 0, self.pack
        t_total = t_real
        if self.bucket_to is not None:
            t_total = max(t_real, int(self.bucket_to(t_real)))
        arrs, state = self._bucket_buf(t_total)
        ws, wg, wr, wblk, wpos, wfirst, wlast, wqs, wql = arrs
        # incremental only when this very buffer was written by the
        # PREVIOUS build (dirty flags are global, not per-bucket: after
        # a detour through another bucket they no longer describe this
        # buffer's staleness) and the slot layout is unchanged
        incremental = (
            t_total == self._last_total
            and state["seglen"] is not None
            and np.array_equal(state["seglen"], self._seglen))
        reused = rebuilt = 0
        active = np.nonzero(self._seglen)[0]
        for s in active:
            off = int(self._off[s])
            n = int(self._seglen[s])
            fresh = bool(self._dirty[s]) or int(self._seg_n[s]) != n
            if fresh:
                rebuilt += 1
            else:
                reused += 1
            if not incremental or fresh:
                end = off + n
                if not incremental:
                    ws[off:end] = s
                    wg[off:end] = s // self.pack
                    wr[off:end] = s % self.pack
                    wpos[off:end] = self._arange[:n]
                wblk[off:end] = block_tables[s, :n]
                self._seg_n[s] = n
                self._dirty[s] = False
            # the query span changes every step a token commits: always
            # refreshed, never part of the cached segment
            q = int(ql[s])
            wqs[off:off + n] = max(int(context_lens[s]) - q, 0)
            wql[off:off + n] = q
        if not incremental:
            # group flags: one first/last pair per nonempty group, over
            # the contiguous span its packed slots occupy
            wfirst[:t_real] = 0
            wlast[:t_real] = 0
            for g in range(-(-b // self.pack)):
                lo = int(self._off[g * self.pack])
                hi = int(self._off[min((g + 1) * self.pack, b)])
                if hi > lo:
                    wfirst[lo] = 1
                    wlast[hi - 1] = 1
            # pad maintenance: entries the previous layout filled past
            # this one's t_real revert to the masked sentinel, and the
            # pad tail's group id tracks the last REAL group (same
            # q/out block: no pipeline flush)
            old_real = state["t_real"]
            if t_real < old_real:
                ws[t_real:old_real] = 0
                wr[t_real:old_real] = 0
                wblk[t_real:old_real] = 0
                wpos[t_real:old_real] = self._pad_pos
                wfirst[t_real:old_real] = 0
                wlast[t_real:old_real] = 0
                wqs[t_real:old_real] = 0
                wql[t_real:old_real] = 0
            last_grp = int(wg[t_real - 1])
            if t_real != old_real or last_grp != state["last_grp"]:
                wg[t_real:t_total] = last_grp
            state["t_real"] = t_real
            state["last_grp"] = last_grp
            if state["seglen"] is None:
                state["seglen"] = self._seglen.copy()
            else:
                np.copyto(state["seglen"], self._seglen)
            self.assemblies_full += 1
        else:
            self.assemblies_incremental += 1
        self.segments_reused += reused
        self.segments_rebuilt += rebuilt
        self._last_total = t_total
        return arrs, t_real, t_total, self.pack


def window_span(xp, lens, q_lens, window, block_size):
    """(lo, hi): the first and the last block position a window layer's
    step touches for each slot, whose span of q_lens queries starts at
    position lens: the block of the first key its first query sees
    (lens - window + 1) to the block its last query is written to. Blocks
    before `lo` are never read again, by this step or a later one. One
    arithmetic for the device's work list (`xp` = jnp) and the host's
    block table (`xp` = numpy)."""
    lo = xp.maximum(lens - (window - 1), 0) // block_size
    hi = (lens + xp.maximum(q_lens, 1) - 1) // block_size
    return lo, hi


def window_entries(chunk, window, block_size):
    """Work entries a slot can need in a window layer's list at slab
    width `chunk`: the blocks chunk + window - 1 positions can straddle."""
    return -(-(int(chunk) + int(window) - 1) // int(block_size)) + 1


def window_work(block_tables, lens, q_lens, *, window, block_size, chunk,
                pack):
    """A window layer's work list, built on the device from the window
    layers' block table [B, max_blocks] and the step's lens / q_lens: the
    same nine arrays as `build_ragged_work`, at the fixed length
    B x window_entries(chunk, window, block_size), so that a step's compile
    key knows nothing of it. Slot-major (so group-major); a slot lists
    the blocks `window_span` gives and pads the rest of its entries
    (q_len 0, a block position past every length). Block positions and
    the query start count from the slot's FIRST LISTED block, not from
    the sequence's: the kernel starts a slot over at position 0 and its
    masks only ever compare positions of one slot, so the shift changes
    nothing but lets a list begin anywhere."""
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32).reshape(-1)
    ql = jnp.minimum(jnp.asarray(q_lens, jnp.int32).reshape(-1), chunk)
    b, max_nb = tables.shape
    nw = window_entries(chunk, window, block_size)
    lo, hi = window_span(jnp, lens, ql, window, block_size)
    j = jnp.arange(nw, dtype=jnp.int32)[None, :]
    at = lo[:, None] + j                                      # [B, nw]
    valid = (ql[:, None] > 0) & (at <= hi[:, None]) & (at < max_nb)
    blk = jnp.take_along_axis(tables, jnp.minimum(at, max_nb - 1), axis=1)
    slot = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                            (b, nw))
    zero = jnp.zeros((b, nw), jnp.int32)
    pad_pos = (1 << 30) // block_size
    cols = (slot, slot // pack, slot % pack,
            jnp.where(valid, blk, 0),
            jnp.where(valid, j, pad_pos),
            zero, zero,
            jnp.where(valid, (lens - lo * block_size)[:, None], 0),
            jnp.where(valid, ql[:, None], 0))
    return tuple(c.reshape(-1).astype(jnp.int32) for c in cols)


# Rows of one query sub-tile. A grid step multiplies only the sub-tiles
# that hold live query rows of its own entry's slot, so the height trades
# the rows a decode entry pays for beyond its G live ones against the
# number of trips a prefilling slot's entry makes (each trip loads the
# block into the MXU again). 64 is a whole number of sublane tiles of
# every dtype the kernel takes (8 rows of f32, 16 of bf16, 32 of int8),
# so a sub-tile's first row is always tile-aligned.
SUB_ROWS = 64


def query_subtile(pack, chunk, group_q):
    """(sub, rows): the height of the kernel's query sub-tiles for a
    packed tile of pack*chunk*group_q rows, and the tile's rows padded to
    whole sub-tiles. A tile no taller than SUB_ROWS is its own single
    sub-tile (every decode bucket: 8 rows)."""
    rows = pack * chunk * group_q
    if rows <= SUB_ROWS:
        return rows, rows
    return SUB_ROWS, -(-rows // SUB_ROWS) * SUB_ROWS


class _Trips(typing.NamedTuple):
    """What one work entry visits of its group's packed query tile."""
    lo: typing.Any      # first sub-tile holding a row that sees the block
    hi: typing.Any      # one past the last sub-tile holding a live row
    start: typing.Any   # the sub-tile the slot's first row lies in
    first: typing.Any   # the slot's first row
    rows: typing.Any    # live rows of the slot's span (q_len x G)
    seen: typing.Any    # of them, rows whose query sees the block


def _subtile_trips(xp, wr, wpos, wqs, wql, *, chunk, group_q, sub, rows,
                   block_size):
    """`_Trips` of one work entry (or, on the host, of a whole list's
    arrays). The slot's span starts at row wr*chunk*group_q of the
    packed tile and wql query positions of it are live; the first
    `unseen` of those sit before the block's first cache position, and
    the sub-tiles that hold nothing else are skipped. A padding entry
    (wql 0) visits none: hi == lo. `xp` is jnp inside the kernel
    (scalars read from SMEM: shifts, no division) and numpy on the host
    (`attn_rows`): one arithmetic."""
    ql = xp.minimum(wql, chunk)
    unseen = xp.clip(wpos * block_size - wqs, 0, ql)
    first = wr * (chunk * group_q)
    if sub == rows:                     # the tile is its one sub-tile
        lo = start = first * 0
        hi = xp.where(ql > unseen, 1, 0)
    else:
        shift = sub.bit_length() - 1
        assert sub == 1 << shift, sub
        lo = (first + unseen * group_q) >> shift
        start = first >> shift
        hi = xp.where(ql > unseen,
                      (first + ql * group_q + sub - 1) >> shift, lo)
    return _Trips(lo, hi, start, first, ql * group_q,
                  (ql - unseen) * group_q)


def attn_rows(work, pack, chunk, group_q, block_size):
    """(live, visited) query rows of one ragged call over `work` (the
    nine arrays), per kv head: the rows whose query sees some of its
    entry's cache block, and the rows of the sub-tiles the kernel
    multiplies for them. Host arithmetic for the scheduler's counter,
    from the function the kernel takes its trip counts from."""
    wr, wpos, wqs, wql = (
        np.asarray(work[i], np.int64) for i in (2, 4, 7, 8))
    sub, rows = query_subtile(pack, chunk, group_q)
    trips = _subtile_trips(
        np, wr, wpos, wqs, wql, chunk=chunk, group_q=group_q, sub=sub,
        rows=rows, block_size=block_size)
    return int(trips.seen.sum()), int((trips.hi - trips.lo).sum()) * sub


def _ragged_kernel(ws, wg, wr, wblk, wpos, wfirst, wlast, wqs, wql,
                   q_ref, kv_hbm, *refs,
                   block_size, scale, group_q, chunk, sub, depth=2,
                   window=None):
    # told by its arguments: a layer with a learned sink logit per query
    # head hands `sink_ref` [1, rows, LANES] (the row's head's logit), a
    # window layer the static `window`; without either this is the plain
    # causal kernel, op for op
    sink_ref = refs[0] if len(refs) == 9 else None
    o_ref, kbuf, vbuf, ksem, vsem, m_scr, l_scr, acc = refs[-8:]
    hh = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    # kv_hbm is the layer's whole [2, KVH, NB, BS, Dc] cache, left in
    # HBM: the K and the V half are told apart in the DMA's index, so no
    # half is ever sliced out (a custom call cannot read a view, and a
    # materialised half is a copy of half the cache)
    def dma(half, buf, sem, slot, idx):
        # a valid work list only holds live block ids, but the list is
        # host-built data: clamp both ends before the HBM DMA — an OOB id
        # (including a -1 free-slot sentinel) doesn't fault on TPU, it
        # reads whatever block aliases (graftlint GL301)
        blk = jnp.clip(wblk[idx], 0, kv_hbm.shape[2] - 1)
        src = kv_hbm.at[half, hh, blk]
        if buf.shape[-1] != kv_hbm.shape[-1]:
            # values narrower than the cache's rows (keys wider than
            # values, one Dc for both): only their lane tiles move
            src = src.at[:, pl.ds(0, buf.shape[-1])]  # graftlint: disable=GL301 - a static lane slice of the clamped block
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[slot])

    kdma = functools.partial(dma, 0, kbuf, ksem)
    vdma = functools.partial(dma, 1, vbuf, vsem)

    # multi-buffering, `depth` slots (depth=2 is classic double
    # buffering): t == 0 warms entries 0..depth-2, then every step
    # starts entry t+depth-1's copy before waiting on t's — up to
    # depth-1 KV blocks are in flight over HBM while this one
    # multiplies. depth=1 degenerates to a serial start-then-wait
    # pipeline (the autotuner's lower bound). The grid length is
    # static, so the warmup loop unrolls at trace time.
    @pl.when(t == 0)
    def _warmup():
        for i in range(min(depth - 1, nt)):
            kdma(i % depth, i).start()
            vdma(i % depth, i).start()

    @pl.when(t + depth - 1 < nt)
    def _prefetch_next():
        kdma((t + depth - 1) % depth, t + depth - 1).start()
        vdma((t + depth - 1) % depth, t + depth - 1).start()

    # The packed tile holds `pack` slots' query spans (chunk positions x
    # G group rows each, row = (slot*chunk + j)*G + gr) and this entry
    # is one cache block of ONE of them, of whose span wql[t] positions
    # are live this step: a decode slot's one, a prefilling slot's up to
    # `chunk`, a padding entry's none. Only the sub-tiles that hold
    # those rows are touched, here and in `_init` and `_final`; a
    # sub-tile may also hold rows of the slot's neighbours, which the
    # row mask leaves alone. (wfirst / wlast mark a GROUP's first and
    # last entry for the whole-tile reference; the kernel starts and
    # finishes per slot, which wpos tells it.)
    trips = _subtile_trips(
        jnp, wr[t], wpos[t], wqs[t], wql[t], chunk=chunk, group_q=group_q,
        sub=sub, rows=m_scr.shape[0], block_size=block_size)

    def over_subtiles(lo, body):
        """body(rows, r0) for the sub-tiles lo .. trips.hi - 1: `rows`
        indexes the sub-tile's rows in a ref, r0 is its first row."""
        if sub == m_scr.shape[0]:   # one sub-tile: the caller's pl.when
            return body(slice(None), 0)

        def trip(i, carry):
            # the trip range is host-built data (wr, wql): a row past
            # the tile would not fault, it would alias other scratch
            r0 = pl.multiple_of(
                jnp.minimum(i, m_scr.shape[0] // sub - 1) * sub, sub)
            body(pl.ds(r0, sub), r0)
            return carry

        jax.lax.fori_loop(lo, trips.hi, trip, 0)

    def live_rows_of(r0, shape):
        """[*shape] bool: row r0 + i is a live query row of this slot;
        and the row's position in the slot's span."""
        rel = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
            - trips.first
        g_shift = group_q.bit_length() - 1      # a shift where G allows
        j = rel >> g_shift if group_q == 1 << g_shift else rel // group_q
        return (rel >= 0) & (rel < trips.rows), j

    # the slot's first block: its rows' running max, sum and accumulator
    # start over. The sub-tiles are set whole: a neighbour whose rows
    # share one is either done (its `_final` has run) or yet to start
    @pl.when(wpos[t] == 0)
    def _init():
        def body(rows, _):
            m_scr[rows, :] = jnp.full((sub, LANES), NEG_INF, jnp.float32)
            l_scr[rows, :] = jnp.zeros((sub, LANES), jnp.float32)
            acc[rows, :] = jnp.zeros((sub, acc.shape[1]), jnp.float32)
        over_subtiles(trips.start, body)

    kdma(t % depth, t).wait()
    vdma(t % depth, t).wait()

    @pl.when(trips.hi > trips.lo)
    def _attend():
        k = kbuf[t % depth].astype(jnp.float32)          # [BS, D]
        v = vbuf[t % depth].astype(jnp.float32)          # [BS, D]

        def body(rows, r0):
            q = q_ref[0, 0, rows, :].astype(jnp.float32)  # [sub, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [sub, BS]
            # query position j of the span sits at absolute position
            # q_start + j and sees the cache up to itself (the
            # intra-chunk causal boundary, which also caps at
            # q_start + q_len - 1 == ctx - 1); rows that are not this
            # slot's live ones are a numerical no-op (p == 0, m/l/acc
            # carried through)
            live, j = live_rows_of(r0, s.shape)
            pos = wpos[t] * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = live & (pos <= wqs[t] + j)
            if window is not None:
                # a window layer: the query at q_start + j sees the
                # `window` positions that end with its own
                mask = mask & (pos > wqs[t] + j - window)
            m_prev = m_scr[rows, :1]
            m_new = jnp.maximum(
                m_prev,
                jnp.max(jnp.where(mask, s, NEG_INF), axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)    # masked rows: exp(0) == 1
            l_scr[rows, :] = jnp.broadcast_to(
                corr * l_scr[rows, :1] + jnp.sum(p, axis=1, keepdims=True),
                (sub, LANES))
            acc[rows, :] = acc[rows, :] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows, :] = jnp.broadcast_to(m_new, (sub, LANES))

        over_subtiles(trips.lo, body)

    # the slot's last block (the next entry does not continue it): its
    # live rows go to the output tile, the rows around them stay as they
    # are. Rows no slot ever writes are masked off after the call
    nxt = jnp.minimum(t + 1, nt - 1)

    @pl.when((wql[t] > 0)
             & ((t == nt - 1) | (wpos[nxt] != wpos[t] + 1)))
    def _final():
        def body(rows, r0):
            l = l_scr[rows, :1]
            if sink_ref is not None:
                # the sink logit joins the softmax's denominator and
                # nothing else: exp(sink - m) beside the running sum
                m = m_scr[rows, :1]
                l = l + jnp.where(
                    m > 0.5 * NEG_INF,
                    jnp.exp(sink_ref[0, rows, :1] - m), 0.0)
            l = jnp.where(l == 0.0, 1.0, l)
            live, _ = live_rows_of(r0, (sub, acc.shape[1]))
            o_ref[0, 0, rows, :] = jnp.where(
                live, (acc[rows, :] / l).astype(o_ref.dtype),
                o_ref[0, 0, rows, :])
        over_subtiles(trips.start, body)


def _pack_queries(q, kvh, g, pack, rows):
    """[B, C, H, D] -> [ngroups, KVH, rows, D] (+zero rows past B and
    past pack*C*G, up to the tile's whole sub-tiles).

    Row order within a group is sequence-major, then chunk position,
    then GQA group row — row = (slot*C + j)*G + gr — matching the
    kernel's row arithmetic."""
    b, c, h, d = q.shape
    ngroups = -(-b // pack)
    qg = q.reshape(b, c, kvh, g, d)
    pad = ngroups * pack - b
    if pad:
        qg = jnp.concatenate(
            [qg, jnp.zeros((pad,) + qg.shape[1:], qg.dtype)], 0)
    qp = qg.reshape(ngroups, pack, c, kvh, g, d) \
        .transpose(0, 3, 1, 2, 4, 5) \
        .reshape(ngroups, kvh, pack * c * g, d)
    if rows > pack * c * g:
        qp = jnp.pad(qp, [(0, 0), (0, 0), (0, rows - pack * c * g), (0, 0)])
    return qp


def _unpack_outputs(out, b, c, h, g, pack):
    ngroups = out.shape[0]
    kvh = out.shape[1]
    d = out.shape[-1]
    return out[:, :, :pack * c * g] \
        .reshape(ngroups, kvh, pack, c, g, d) \
        .transpose(0, 2, 3, 1, 4, 5) \
        .reshape(ngroups * pack, c, h, d)[:b]


def default_pack(batch, group_q):
    """Co-schedule enough sequences that the packed query tile fills at
    least one f32 sublane tile (8 rows) — the MXU minimum."""
    return max(1, min(batch, -(-8 // group_q)))


def ragged_paged_attention(q, kv_cache, block_tables, context_lens,
                           scale=None, pack=None, work=None, q_lens=None,
                           buffer_depth=2, window=None, sink=None,
                           v_dim=None):
    """Mixed decode/prefill attention over a paged KV cache, ragged grid.

    q:            [B, H, D] — one query token per sequence (decode), or
                  [B, C, H, D] — a chunk of up to C query tokens per
                  sequence (chunked prefill; rows past q_lens[b] ignored)
    kv_cache:     [2, KVH, num_blocks, block_size, Dc] — one layer's K
                  and V halves in the one buffer the engine allocates
                  and the writers append into (`jnp.stack([k, v])` of
                  separate halves); the kernel reads blocks out of it
                  where it lies. Dc >= D (the engine allocates
                  Dc = paged_head_dim(D); q is zero-padded to Dc for
                  the kernel and the output sliced back to D — zeros
                  add nothing to q.k, and the pad lanes of p.v are
                  dropped)
    block_tables: [B, max_blocks_per_seq] int32 cache-block ids
    context_lens: [B] int32 valid cache length per sequence INCLUDING
                  this call's query span (0 allowed: the row costs zero
                  grid steps and returns zeros)
    q_lens:       [B] int32 valid query count per sequence ([B, C, H, D]
                  mode; None means one query per sequence). Sequence b's
                  queries sit at positions context_lens[b]-q_lens[b] ..
                  context_lens[b]-1, each causally masked to its own
                  prefix. q_len 0 skips the sequence (zero grid steps,
                  zero output).
    pack:         co-scheduled sequences per query tile (default: enough
                  that pack*G >= 8)
    work:         optional prebuilt `build_ragged_work(...)` result —
                  required under jit where context_lens is traced;
                  arrays may be traced values, lengths (and the carried
                  pack) must be static. The work list's group/row
                  encoding and the kernel's query packing must agree, so
                  a pack carried by `work` wins; passing a CONFLICTING
                  explicit pack raises. The list's q spans must fit the
                  slab (q_len <= C) — under jit this cannot be checked.
    buffer_depth: KV DMA pipeline slots (static; autotunable). 2 is the
                  classic double buffer; 1 serializes copy/compute;
                  deeper keeps more blocks in flight at depth x
                  2 x block_size x D x itemsize VMEM. Pure scheduling —
                  results are bit-identical across depths.
    window:       static int or None. A window layer's query at position
                  p sees positions p - window + 1 .. p; its work list
                  may then hold only the blocks that window touches,
                  with block positions and query starts counted from the
                  slot's first listed block (`window_work`)
    sink:         [H] learned logit per query head, or None: it joins
                  the softmax's denominator and weighs no value
    v_dim:        width of the values where it is not the queries' (keys
                  wider than values in one Dc-wide cache): the output's
                  minor dim, and the V lane tiles the kernel moves
    returns       [B, H, D] or [B, C, H, D], matching q (minor dim v_dim
                  where given)
    """
    buffer_depth = int(buffer_depth)
    if not 1 <= buffer_depth <= 8:
        raise ValueError(
            f"buffer_depth must be in [1, 8], got {buffer_depth}")
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, c, h, d_q = q.shape
    if kv_cache.ndim != 5 or kv_cache.shape[0] != 2:
        raise ValueError(
            "ragged_paged_attention takes one [2, KVH, NB, BS, Dc] cache "
            f"(K and V stacked), got shape {tuple(kv_cache.shape)}")
    _, kvh, _, block_size, _ = kv_cache.shape
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d_q)
    if work is not None:
        work_arrs, t_total = work[0], work[2]
        work_pack = work[3] if len(work) > 3 else None
        if work_pack is not None:
            if pack is not None and pack != work_pack:
                raise ValueError(
                    f"pack={pack} conflicts with the work list (built "
                    f"with pack={work_pack})")
            pack = work_pack
        elif pack is None:
            # bare work arrays with no pack anywhere: guessing a default
            # could silently disagree with the list's group encoding
            raise ValueError(
                "a prebuilt work list needs its pack factor — pass the "
                "full build_ragged_work(...) 4-tuple, or pack= explicitly")
    if pack is None:
        pack = default_pack(b, g)
    pack = max(1, min(pack, b))
    if work is None:
        work_arrs, _, t_total, pack = build_ragged_work(
            block_tables, context_lens, block_size, pack, q_lens=q_lens)
    if t_total == 0:
        out = jnp.zeros((b, c, h, v_dim or d_q), q.dtype)
        return out[:, 0] if squeeze else out
    # only a slot's live rows are ever written: every other row (a
    # len 0 / q_len 0 slot's, the columns past q_len) carries
    # uninitialised VMEM, and is masked off by its slot's count of
    # valid columns
    if q_lens is None:
        n_valid = jnp.asarray(context_lens).reshape(-1) > 0
    else:
        n_valid = jnp.asarray(q_lens).reshape(-1)
    out = _ragged_call(
        tuple(jnp.asarray(a, jnp.int32) for a in work_arrs), q, kv_cache,
        n_valid.astype(jnp.int32), sink, scale=float(scale), pack=pack,
        depth=buffer_depth, interpret=_interpret_mode(), window=window,
        v_dim=v_dim)
    return out[:, 0] if squeeze else out


_KERNEL_STATICS = ("scale", "pack", "depth", "interpret", "window", "v_dim")


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _ragged_call(work, q, kv_cache, n_valid, sink=None, *, scale, pack,
                 depth, interpret, window=None, v_dim=None):
    """The kernel over q [B, C, H, D], its packing and its output laid
    back into the slab, as ONE jitted function: the layers of a step
    call it with the same shapes, so one trace of the kernel's body and
    one lowering to Mosaic serve them all (a step that unrolls 16 layers
    would trace and lower it 16 times; the set-up of a serving process
    is mostly that)."""
    b, c, h, d_q = q.shape
    out = _ragged_tiles(work, q, kv_cache, sink, scale=scale, pack=pack,
                        depth=depth, interpret=interpret, window=window,
                        v_dim=v_dim)
    out = _unpack_outputs(
        out, b, c, h, h // kv_cache.shape[1], pack)[..., :v_dim or d_q]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]            # [B, C]
    return jnp.where(valid[:, :, None, None], out, 0.0)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _ragged_tiles(work, q, kv_cache, sink=None, *, scale, pack, depth,
                  interpret, window=None, v_dim=None):
    """q [B, C, H, D] packed into the kernel's query tiles and the
    kernel's output tiles [ngroups, KVH, rows, Dv] as it leaves them, in
    the same row order (`_pack_queries`): only the rows some slot had
    live were written. Dv is the cache's Dc, or the values' own lane
    tiles where `v_dim` says they are narrower than the keys."""
    b, c, h, _ = q.shape
    _, kvh, _, block_size, d = kv_cache.shape
    g = h // kvh
    ngroups = -(-b // pack)
    sub, pg = query_subtile(pack, c, g)
    qp = _pack_queries(_lane_pad(q, d), kvh, g, pack, pg)
    dv = d if v_dim is None else paged_head_dim(v_dim)
    ins, specs = [qp, kv_cache], []
    if sink is not None:
        # row (slot*C + j)*G + gr of kv head hh is query head hh*G + gr
        per = jnp.tile(sink.astype(jnp.float32).reshape(kvh, 1, g),
                       (1, pack * c, 1)).reshape(kvh, pack * c * g)
        per = jnp.pad(per, [(0, 0), (0, pg - pack * c * g)])
        ins.append(jnp.broadcast_to(per[:, :, None], (kvh, pg, LANES)))
        specs.append(pl.BlockSpec(
            (1, pg, LANES), lambda hh, t, *_: (hh, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(kvh, work[0].shape[0]),
        in_specs=[
            pl.BlockSpec((1, 1, pg, d),
                         lambda hh, t, ws, wg, *_: (wg[t], hh, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the cache stays in
        ] + specs,                               # HBM; blocks DMA'd by hand
        out_specs=pl.BlockSpec(
            (1, 1, pg, dv), lambda hh, t, ws, wg, *_: (wg[t], hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, block_size, d), kv_cache.dtype),
            pltpu.VMEM((depth, block_size, dv), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.VMEM((pg, LANES), jnp.float32),
            pltpu.VMEM((pg, LANES), jnp.float32),
            pltpu.VMEM((pg, dv), jnp.float32),
        ],
    )
    statics = {} if window is None else {"window": int(window)}
    return pl.pallas_call(
        functools.partial(_ragged_kernel, block_size=block_size,
                          scale=scale, group_q=g, chunk=c, sub=sub,
                          depth=depth, **statics),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ngroups, kvh, pg, dv), q.dtype),
        # the HLO instruction takes this name (`%paged_step_ragged_attn.N
        # = ... custom-call`): it begins `paged_step` because that is
        # what the instruction was called after the enclosing jit before
        # it had a name, and what the benchmark's first reader matches
        name="paged_step_ragged_attn",
        interpret=interpret,
    )(*work, *ins)


def ragged_attention_tiles(q, kv_cache, work, scale=None, buffer_depth=2,
                           window=None, sink=None, v_dim=None):
    """`ragged_paged_attention` over a prebuilt `work` 4-tuple, stopping
    at the kernel's own output tiles: for a caller that wants a few of
    the slab's rows back (`tile_rows`) and not the [B, C, H, D] slab,
    most of which a wide step never had live."""
    work_arrs, _, t_total, pack = work
    b, c, h, d_q = q.shape
    kvh, d = kv_cache.shape[1], kv_cache.shape[-1]
    if t_total == 0:
        return jnp.zeros((-(-b // pack), kvh,
                          query_subtile(pack, c, h // kvh)[1],
                          d if v_dim is None else paged_head_dim(v_dim)),
                         q.dtype)
    return _ragged_tiles(
        tuple(jnp.asarray(a, jnp.int32) for a in work_arrs), q, kv_cache,
        sink, scale=float(1.0 / math.sqrt(d_q) if scale is None else scale),
        pack=pack, depth=int(buffer_depth), interpret=_interpret_mode(),
        window=window, v_dim=v_dim)


def tile_rows(tiles, slot, col, live, pack, chunk, heads, head_dim):
    """The attention output rows [n, H, D] of the slab cells
    (slot[i], col[i]), gathered out of the kernel's tiles where they lie
    (row (slot % pack * chunk + col) * G + gr of group slot // pack,
    under each kv head); rows that are not `live` come back zero."""
    ngroups, kvh, _, d = tiles.shape
    g = heads // kvh
    cells = tiles[:, :, :pack * chunk * g].reshape(
        ngroups, kvh, pack, chunk, g, d)
    out = cells[slot // pack, :, slot % pack, col]       # [n, KVH, G, Dc]
    out = jnp.where(live[:, None, None, None], out, 0.0)
    return out.reshape(-1, heads, d)[..., :head_dim]


def ragged_paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     context_lens, scale=None, pack=None,
                                     q_lens=None):
    """Plain-JAX (no Pallas) execution of the ragged algorithm: same work
    list, same packed tiles, same online-softmax update, same query-span
    masking — each update jitted as one program so XLA applies the same
    FMA contraction as inside the kernel. It multiplies every entry's
    WHOLE packed tile, the algorithm the kernel visits the live
    sub-tiles of: a row's running max, sum and accumulator see the same
    values either way. On the CPU interpret grid the kernel must match
    this BIT-EXACTLY; it is also the validation oracle the serving tests
    diff against."""
    q = jnp.asarray(q)
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, c, h, d_q = q.shape
    kc = jnp.asarray(k_cache)
    vc = jnp.asarray(v_cache)
    kvh, _, bs, d = kc.shape
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d_q)
    q = _lane_pad(q, d)        # same padded tiles as the kernel
    if pack is None:
        pack = default_pack(b, g)
    lens = np.asarray(context_lens)
    (ws, wg, wr, wblk, wpos, wfirst, wlast, wqs, wql), _, t_total, pack = \
        build_ragged_work(block_tables, lens, bs, pack, q_lens=q_lens)
    span = c * g
    pg = pack * span
    qp = _pack_queries(q, kvh, g, pack, pg)
    ngroups = qp.shape[0]

    @jax.jit
    def upd(qt, k, v, m, l, acc, wr_t, wpos_t, wqs_t, wql_t):
        s = jax.lax.dot_general(
            qt, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * float(scale)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        rel = row - wr_t * span
        j = rel // g
        pos = wpos_t * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = ((rel >= 0) & (rel < span) & (j < wql_t)
                & (pos <= wqs_t + j))
        m_new = jnp.maximum(m, jnp.max(jnp.where(mask, s, NEG_INF),
                                       axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l2 = corr * l + jnp.sum(p, axis=1, keepdims=True)
        acc2 = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l2, acc2

    fin = jax.jit(
        lambda acc, l: (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype))
    out = np.zeros((ngroups, kvh, pg, d), q.dtype)
    for hh in range(kvh):
        m = l = acc = None
        for t in range(t_total):
            if wfirst[t]:
                m = jnp.full((pg, 1), NEG_INF, jnp.float32)
                l = jnp.zeros((pg, 1), jnp.float32)
                acc = jnp.zeros((pg, d), jnp.float32)
            m, l, acc = upd(qp[wg[t], hh].astype(jnp.float32),
                            kc[hh, wblk[t]].astype(jnp.float32),
                            vc[hh, wblk[t]].astype(jnp.float32),
                            m, l, acc, int(wr[t]), int(wpos[t]),
                            int(wqs[t]), int(wql[t]))
            if wlast[t]:
                out[wg[t], hh] = np.asarray(fin(acc, l))
    out = _unpack_outputs(jnp.asarray(out), b, c, h, g, pack)[..., :d_q]
    if q_lens is None:
        valid = jnp.asarray(lens).reshape(-1, 1) > 0
    else:
        valid = (jnp.arange(c)[None, :]
                 < jnp.asarray(q_lens).reshape(-1, 1))
    out = jnp.where(valid[:, :, None, None], out, 0.0)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# the live rows of a wide step
# ---------------------------------------------------------------------------
#
# A chunk step's slab is [B, C] tokens of which qlens[b] columns per slot
# are live: one or two slots prefill a chunk while the others decode one
# token, so a 16 x 128 slab carries ~160 live tokens. Everything a step
# does per token (embedding, norms, projections, rope, the cache append,
# the feed-forward) is row-wise, so a wide step packs its live tokens to
# the front of a [B * C]-row buffer and walks ROW_TILE rows at a time,
# ceil(n_live / ROW_TILE) times: a trip count read on the device, so the
# step's arguments, shapes and compile buckets do not know about it. Only
# the attention kernel's query side keeps the slab's [B, C] geometry
# (`LiveRows.back` lays the packed q rows into it); its output is read
# back a row tile at a time from the kernel's own tiles (`tile_rows` by
# `slot`/`col`), so no [B, C, H, D] slab of ctx is ever written.

# Rows per tile: the v5e's ridge. A bf16 weight streamed once from HBM at
# 819 GB/s pays for ~240 rows of matmul at 197 TFLOP/s, so a 256-row tile
# costs about what one row costs, and more tiles cost no more than the
# padded matmul did. A slab of at most ROW_TILE rows (every decode bucket,
# every speculative span) is one tile whatever is live in it: such a step
# is straight-line code with no packing and no loop.
ROW_TILE = 256


class LiveRows(typing.NamedTuple):
    """The packed order of a [B, C] slab's live tokens: slot-major,
    column-ascending, padded to whole tiles (R = ceil(B*C / ROW_TILE)
    tiles' rows). Indices of dead rows are clamped into range: they read
    some other row's data and their results are never used."""
    n_tiles: jax.Array   # [] tiles that hold a live row
    slot: jax.Array      # [R] the slab row of packed row r
    col: jax.Array       # [R] its slab column
    live: jax.Array      # [R] bool, r < n_live
    back: jax.Array      # [B, C] the packed row of slab cell (b, j)


def live_rows(q_lens, width):
    """`LiveRows` of a [B, width] slab whose slot b holds q_lens[b] live
    columns (0 parks the slot)."""
    ql = jnp.asarray(q_lens, jnp.int32).reshape(-1)
    b = ql.shape[0]
    r = jnp.arange(-(-b * width // ROW_TILE) * ROW_TILE)
    ends = jnp.cumsum(ql)
    offset = ends - ql
    slot = jnp.minimum(jnp.sum(r[:, None] >= ends[None, :], axis=1), b - 1)
    return LiveRows(
        n_tiles=-(-ends[-1] // ROW_TILE), slot=slot,
        col=jnp.clip(r - offset[slot], 0, width - 1), live=r < ends[-1],
        back=jnp.minimum(offset[:, None] + jnp.arange(width)[None, :],
                         r.shape[0] - 1))


def step_rows(batch, width, n_live=None):
    """Rows the row-wise layers of one paged step compute for a
    [batch, width] slab holding n_live live tokens (None: every cell):
    the whole slab where it is one tile, else the tiles `live_rows`
    finds a live row in. Host arithmetic, for the scheduler's counters."""
    rows = batch * width
    if rows <= ROW_TILE:
        return rows
    return -(-(rows if n_live is None else n_live) // ROW_TILE) * ROW_TILE


def over_row_tiles(n_tiles, body, carry):
    """carry = body(r0, carry) for each tile that holds a live row, r0
    the tile's first packed row. Tiles past n_tiles are never computed."""
    return jax.lax.fori_loop(
        0, n_tiles, lambda i, c: body(i * ROW_TILE, c), carry)


def row_tile(a, r0):
    """The ROW_TILE rows of a packed [R, ...] array from row r0."""
    return jax.lax.dynamic_slice_in_dim(a, r0, ROW_TILE)


def put_row_tile(buf, new, r0):
    """`buf` with the tile from row r0 replaced by `new` (in place where
    `buf` is a loop carry)."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, new.astype(buf.dtype), r0, 0)


# ---------------------------------------------------------------------------
# the cache writers
# ---------------------------------------------------------------------------
#
# One operand form, one contract: a layer's stacked cache
# [2, KVH, NB, BS, Dc] (`append_paged_kv`, `append_paged_kv_chunk`,
# `append_paged_kv_rows` for a wide step's packed tiles,
# `truncate_paged_kv`, `copy_paged_kv`). Rows are written into that
# buffer itself and the buffer is the result, so a jitted program that
# donates it never reads or writes a whole cache to append a row —
# `tests/test_attention_ragged_paged.py` `TestStepNeverCopiesTheCache`
# pins that for the engine's paged step (no slice, stack or pad of a
# cache or a half in its jaxpr, its cache results aliased to its donated
# arguments). All take their cells from `_span_cells`, where the index
# arithmetic and the boundary contract are written once:
#   * a position at or past its row's `stop`, or at/after the table's
#     capacity (max_blocks * block_size), is DROPPED — its block id is
#     set to NB, past the pool, and the writer (`_kv_rows_kernel`, or
#     the rewind's scatter with mode="drop") discards it — never aliased
#     onto whatever block a clamped gather would hand back;
#   * the block-table column read is clamped into the table.
#
# New rows have ONE writer, `_write_rows`, whatever the slab's width (a
# wide tile, a narrow chunk slab, a decode step): a small kernel that
# costs what it writes. A DMA into the cache can address no less than
# the KV_GROUP = 8 rows of one HBM tile (Mosaic refuses a one-row slice
# of the tiled [BS, Dc] plane), so the kernel moves a block's aligned
# 8-row GROUP: read into VMEM, the live rows laid over it, written back.
# Rows that share a group are merged into one such move, so a
# prefilling slot's span costs one move per 8 tokens, a decode row one,
# and a dead row nothing. (A scatter walks one index row per (half, kv
# head, token), live or dead: 2 x 256 x KVH a wide tile, 68 ns each on
# the v5e; with the token as its index, [2, KVH, Dc] windows, the v5e
# compiler puts two cache-sized transposes around it.)

KV_GROUP = 8    # cache rows of one HBM tile: the least a DMA addresses
_KV_MOVES = 4   # a group's read starts this many turns before its write


def _span_cells(block_tables, start, stop, span, nb, bs):
    """([B, span] block id, in-block offset) of positions start[b] + j
    of sequence b, j < span (a static int): the id is `nb`, past the
    pool, where the position is at/after stop[b] or the table's
    capacity (DROPPED, see above)."""
    max_nb = block_tables.shape[1]
    pos = jnp.reshape(start, (-1, 1)) + jnp.arange(span)[None, :]  # [B, S]
    valid = (pos < jnp.reshape(stop, (-1, 1))) & (pos < max_nb * bs)
    blk_col = jnp.minimum(pos // bs, max_nb - 1)    # clamp the table read
    blk_ids = jnp.take_along_axis(block_tables, blk_col, axis=1)
    return jnp.where(valid, blk_ids, nb), pos % bs


def _kv_rows_kernel(blk, off, new_ref, _, cache, first, buf, rsem, wsem):
    """Rows r of new_ref [2, R, KVH, Dc] (f32, in VMEM) into
    cache[:, :, blk[r], off[r]] (HBM, aliased to the result), dropped
    where blk[r] is outside the pool. blk, off: scalar-prefetched [R],
    off in 0 .. BS - 1. The cells are distinct, as a scatter's are.

    Pass 1 (scalars only) finds the groups: first[g] is the first row of
    the g-th run of live rows that share (block, offset // group); dead
    rows split no run. Pass 2 walks them over a ring of buffers, one
    loop whose turn t starts group t's read and merges group
    t - `_KV_MOVES` + 1: wait for its rows, lay its live rows over them,
    start the write. A buffer's last write is waited for before the next
    read lands in it, a wait a turn, the last writes in the loop's last
    turns. Should two groups within a ring's length be ONE group (two
    sequences in one block: no engine appends so, a scatter allows it),
    a read ahead could pass the write it must see: pass 1 sees that
    (`clash`), and the walk then reads nothing ahead, each turn waiting
    for the write before it.

    Every step program lowers this body once and holds a copy a layer,
    so it is kept small: `lax` primitives on its scalars (a `jnp.where`
    or a `//` is a nested jit the lowering traces again), one site each
    for a move's start and its wait, three loops."""
    _, _, nb, bs, _ = cache.shape
    nbuf, group = buf.shape[0], buf.shape[3]
    i32, lax = jnp.int32, jax.lax
    pick = lambda c, a, b: lax.select(c, i32(a), i32(b))

    def cell(r):
        """Row r's (block, offset, whether the block is in the pool)."""
        b = blk[r]
        return b, off[r], (b >= 0) & (b < nb)

    def scan(r, carry):
        n, end, clash, prev, *ring = carry      # ring: the keys before prev
        b, o, live = cell(r)
        key = pick(live, b * (bs // group) + lax.div(o, i32(group)), prev)
        head = key != prev
        first[n] = r        # slot n is nobody's until a head row takes it
        seen = functools.reduce(lax.bitwise_or, [key == k for k in ring])
        ring = [pick(head, k, old)
                for k, old in zip([prev] + ring[:-1], ring)]
        return (n + head.astype(i32), pick(live, r + 1, end),
                clash | (head & seen), key, *ring)

    n, end, clash, *_ = lax.fori_loop(
        0, blk.shape[0], scan,
        (i32(0), i32(0), False) + (i32(-1),) * (nbuf - 1))
    first[n] = end          # the last group stops after the last live row

    def move(g, write=False):
        # a group's head row is live, so its cell is in the pool; the
        # ids are still data: clamp both before the HBM DMA (GL301)
        b, o, _ = cell(first[g])
        rows = cache.at[:, :, jnp.minimum(lax.max(b, 0), nb - 1), pl.ds(
            pl.multiple_of(jnp.minimum(
                lax.max(lax.div(o, i32(group)), 0), bs // group - 1)
                * group, group), group)]
        s = lax.rem(g, i32(nbuf))
        if write:
            return pltpu.make_async_copy(buf.at[s], rows, wsem.at[s])
        return pltpu.make_async_copy(rows, buf.at[s], rsem.at[s])

    def put(r, s):
        _, o, live = cell(r)
        shape = (2, group, buf.shape[-1])
        at = (lax.broadcasted_iota(i32, shape, 1)
              == lax.rem(o, i32(group))) & live
        for h in range(buf.shape[2]):
            buf[s, :, h] = lax.select(
                at, jnp.broadcast_to(new_ref[:, pl.ds(r, 1), h, :], shape),
                buf[s, :, h].astype(jnp.float32)).astype(buf.dtype)
        return s

    def turn(t, carry):
        # group t's buffer is free once the write before it there has
        # landed; after a clash its rows are final only once every
        # earlier write has, and the turn before this one waited for
        # all but the last
        w = pick(clash, t - 1, t - nbuf)

        @pl.when((w >= 0) & (w < n))
        def _():
            move(w, write=True).wait()

        @pl.when(t < n)
        def _():
            move(t).start()
        g = pick(clash, t, t - (_KV_MOVES - 1))

        @pl.when((g >= 0) & (g < n))
        def _():
            move(g).wait()
            lax.fori_loop(first[g], first[g + 1], put, lax.rem(g, i32(nbuf)))
            move(g, write=True).start()
        return carry

    # the last turns start nothing: they wait for the last writes
    lax.fori_loop(0, pick(n > 0, n + nbuf, 0), turn, 0)


@functools.partial(jax.jit, static_argnames="interpret")
def _write_rows(cache, k_new, v_new, block_tables, start, stop, *,
                interpret):
    """THE writer of new rows: k_new / v_new [B, S, KVH, D], row (b, j)
    at position start[b] + j of the sequence whose blocks
    block_tables[b] lists, into the stacked cache [2, KVH, NB, BS, Dc];
    dropped at/after stop[b] or the table's capacity (`_span_cells`).
    Rows narrower than Dc are zero-padded (`paged_head_dim`). One jitted
    function, cells and all: a step's layers of one shape share one
    trace of it and one lowering of the kernel."""
    _, kvh, nb, bs, d = cache.shape
    b, s = k_new.shape[:2]
    blk, off = _span_cells(block_tables, start, stop, s, nb, bs)
    # a block of fewer rows than a tile, or not of whole tiles, moves in
    # the largest groups that divide it (the CPU tests' blocks; Mosaic
    # takes whole tiles or a whole plane)
    group = math.gcd(bs, KV_GROUP)
    # rows in the cache's type (the values the scatter stored), widened:
    # a packed type's single row cannot be read out of VMEM at a row the
    # data chooses, a 32-bit one can, and the way back is exact. They
    # stay token major, as the projection leaves them: handed over kv
    # head major (the cache's order) the compiler lays the projection's
    # and the rope's outputs out head major for it, q's with them, and
    # copies a wide step's packed q buffer back once a layer
    new = jnp.stack([_lane_pad(k_new, d), _lane_pad(v_new, d)]) \
        .reshape(2, b * s, kvh, d).astype(cache.dtype).astype(jnp.float32)
    return pl.pallas_call(
        _kv_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SMEM((b * s + 1,), jnp.int32),
                pltpu.VMEM((_KV_MOVES + 2, 2, kvh, group, d), cache.dtype),
                pltpu.SemaphoreType.DMA((_KV_MOVES + 2,)),
                pltpu.SemaphoreType.DMA((_KV_MOVES + 2,))]),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={3: 0},
        # NOT `paged_step*`: the benchmark's reader of the ragged kernel
        # counts every custom call so named
        name="kv_rows_write",
        interpret=interpret,
    )(blk.reshape(-1).astype(jnp.int32), off.reshape(-1).astype(jnp.int32),
      new, cache)


def append_paged_kv_chunk(cache, k_new, v_new, block_tables, context_lens,
                          valid_counts):
    """Append a CHUNK of new K/V rows ([B, C, KVH, D]) into one layer's
    stacked cache [2, KVH, NB, BS, Dc]: sequence b's row j lands at
    position context_lens[b] + j for j < valid_counts[b]. The chunk may
    span block boundaries (the caller grew the block table first).
    Returns the updated cache (`_write_rows`).

    Boundary contract: rows past valid_counts[b] and rows whose position
    falls at/after the table capacity are DROPPED (see above)."""
    return _write_rows(
        cache, k_new, v_new, block_tables, context_lens,
        context_lens + valid_counts, interpret=_interpret_mode())


def append_paged_kv_rows(cache, k_rows, v_rows, block_tables, slot, pos,
                         live):
    """Append one tile of a wide step's PACKED rows ([R, KVH, D], in
    `live_rows` order) into one layer's stacked cache: row r lands at
    position pos[r] of sequence slot[r], a one-column span per row. The
    writer costs the tile's live rows, however wide the slab is
    (`_write_rows`).

    Boundary contract: a row that is not `live`, or whose position falls
    at/after the table capacity, is DROPPED (see above)."""
    return _write_rows(
        cache, k_rows[:, None], v_rows[:, None],
        jnp.asarray(block_tables)[slot], pos, pos + live,
        interpret=_interpret_mode())


def append_paged_kv(cache, k_new, v_new, block_tables, context_lens):
    """Append one decode step's K/V ([B, KVH, D]) into one layer's
    stacked cache at position context_lens (the slot the new token
    occupies): a one-column chunk. A row whose context_lens already
    equals the table capacity has nowhere to append — its write is
    DROPPED."""
    return append_paged_kv_chunk(
        cache, k_new[:, None], v_new[:, None], block_tables, context_lens,
        jnp.ones_like(context_lens))


def truncate_paged_kv(cache, block_tables, new_lens, old_lens, max_span):
    """Rewind one layer's stacked cache: ZERO positions new_lens[b] ..
    old_lens[b]-1 of every sequence — the KV a rejected speculative
    draft span left behind. `max_span` (static python int) bounds
    old_lens - new_lens, so the scatter keeps a jit-compatible static
    shape; rows where new_lens == old_lens are a no-op.

    Zeroing (rather than just rolling the host length back) keeps the
    strong invariant the serving tests lean on: a speculated-then-rewound
    cache is BIT-IDENTICAL to one that never speculated, so token-exact
    claims never rest on overwrite-before-attend reasoning.

    Boundary contract: positions past the span, past old_lens, or
    at/after the table capacity are DROPPED (see above)."""
    _, kvh, nb, bs, _ = cache.shape
    blk, off = _span_cells(
        block_tables, new_lens, old_lens, int(max_span), nb, bs)
    # one scatter of a scalar, the half's and the head's index in it:
    # dropped cells aim past the pool and vanish
    idx = (jnp.arange(2)[:, None, None, None], jnp.arange(kvh),
           blk[:, :, None], off[:, :, None])
    return cache.at[idx].set(jnp.zeros((), cache.dtype), mode="drop")


def copy_paged_kv(cache, src_block, dst_block):
    """Duplicate ONE physical block of one layer's stacked cache: copy
    every (half, kv_head, slot, d) row of `src_block` into `dst_block` —
    the device half of the serving engine's copy-on-write. A request
    that must append into a block other requests still read gets a
    private copy first; the shared original stays byte-identical for its
    remaining readers, so prefix sharing never rests on
    overwrite-ordering reasoning.

    Boundary contract: both block ids are data from the host allocator,
    so the gather side is CLAMPED into the pool and the scatter side
    uses mode="drop" — an out-of-pool id copies garbage nowhere instead
    of aliasing another sequence's KV."""
    src = jnp.minimum(src_block, cache.shape[2] - 1)    # clamp the gather
    row = jax.lax.dynamic_index_in_dim(cache, src, axis=2, keepdims=False)
    return cache.at[:, :, dst_block].set(row, mode="drop")
