"""Pallas TPU kernels: fused pipeline front end (§4, Fig. 3 steps 1-3).

Fuses the memory-intensive front end of `map_pairs` — Partitioned Seeding
(2-bit packing + xxHash32, §4.3), the SeedMap padded-row lookup (§4.4) and
Paired-Adjacency Filtering (§4.5) — so the per-read `(B, S*K)` sorted
start lists and the `(B, S, K)` location tensor never round-trip through
HBM.  This is the TPU analogue of the paper's NMSL memory subsystem: the
Location Table stays in HBM, each grid step DMAs only the `2*S*BLK` rows
it is about to merge into VMEM, and only the `(B, C)` candidate set plus
the per-read hit counts are written back.

Two kernels, one op
-------------------
The row-gather DMAs are aimed by scalar-prefetch tables of *flattened row
starts* (``bucket * K`` in padded rows, ``offsets[bucket]`` in CSR
lines, whose row counts ride along as VMEM blocks), and scalar-prefetch
operands must exist before the launch, so the fused op runs as two
back-to-back kernels:

  1. `seed_buckets_pallas` — in-VMEM seed extraction + 2-bit packing +
     xxHash32 (reusing `kernels/xxhash`'s `xxhash32_lanes` hashing unit,
     the paper's 6-way Partitioned Seeding module) -> `(B, S)` bucket ids.
  2. `pair_frontend_pallas` — scalar-prefetch row-gather (the
     `kernels/seed_gather` NMSL idiom, but S rows per read and fused with
     the consumer), location->read-start conversion, in-VMEM sorted merge,
     Δ-adjacency filter and front-compaction -> `CandidateSet` arrays.

Only the tiny `(B, S)` int32 bucket tensor (4 B/seed — exactly the
paper's centralized-buffer traffic, §5.2) crosses HBM between the two
(with a CSR index, plus one gather of each bucket's two Seed-Table
offsets, `ops.py`).

Reads on lanes, starts on sublanes
----------------------------------
`jnp.sort` has no Mosaic lowering, and a per-read compare matrix costs
M^2 work on the cross-lane unit, so the merge/filter works on a whole
block of reads at once (`merge_filter_block`): each mate's (BLK, M =
S*K) locations become read starts, are filled with INVALID_LOC up to Mp
(`_sort_width`, a power of two >= max(M, 8)) and transposed once to
(Mp, BLK), so every lane holds one read's starts down its sublanes.
Everything after that is elementwise over lanes:

- a bitonic network sorts each lane's column: log2(Mp) * (log2(Mp) +
  1) / 2 stages of min/max, partners 8 or more sublanes apart taken as
  whole vregs, nearer ones through two sublane rolls and a select;
- the Δ filter mirrors `pair_filter._row_filter` exactly: ``lo`` (the
  `searchsorted` count) and the partner select fold over the M rows of
  the sorted read-2 starts, each row broadcast down the sublanes; the
  occurrence index of a duplicated read-1 start is ``i`` minus the start
  of its run (a running max down the sublanes); the probe clips at the
  oracle's M - 1, not at Mp - 1; equal (start1, start2) pairs drop by
  an adjacent compare;
- front compaction: a running count of the kept starts down the
  sublanes gives each its slot, and each of the C slots is one masked
  sum down the sublanes.

The outputs come out lane-major, (C, BLK) and (1, BLK), and the op
transposes them back.  A block is 128 reads (`DEFAULT_BLOCK`); on the
chip it is a multiple of 128.

Double-buffered row DMA (ping-pong protocol)
--------------------------------------------
The row-gather kernel reuses the `candidate_align` cross-grid-step
protocol: the `(B*S,)` DMA start tables are scalar-prefetch operands
(SMEM, visible to every step), so step ``g`` issues step ``g+1``'s
2*S*BLK row fetches into the *other* of two VMEM location banks while its
own merge/filter compute runs, then waits only on its own bank's
semaphores (one per bank and mate; each wait consumes one row's
bytes).  The refill of the bank step ``g`` computed on is issued during
step ``g+1``, after step ``g``'s compute has fully completed (grid steps
run sequentially), so no write-after-read hazard exists: the
Location-Table HBM traffic of step g+1 hides behind the sort/filter
compute of step g.  The same starts also ride as (BLK, S) VMEM blocks,
which give the per-read lane shifts of the row cuts as vectors.

Mosaic DMAs a slice of an HBM table only at tile boundaries, so the
table is dense 128-lane lines (`kernels/_util.py`): each seed's DMA
fetches the lines holding its row — one for a padded row, which starts
at a multiple of K; two for a CSR row, which starts at any lane — and
the kernel cuts the row at its lane offset.  A CSR row's lanes at or
past its count belong to the next bucket and are set to INVALID_LOC.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.seedmap import INVALID_LOC
from repro.kernels._util import LANES, cut_lanes, gather_lines, lines_spanned
from repro.kernels.xxhash.kernel import xxhash32_lanes

#: Name of every launch of this family: its HLO instruction name
#: (``pair_frontend.N``) and its op name in a device profile.
NAME = "pair_frontend"

DEFAULT_BLOCK = 128      # reads per grid step, one a lane (2*S row DMAs each)
SUBLANES = 8             # sublanes of a vreg
HASH_BLOCK = 128         # rows per seed_buckets grid step
MAX_SEED_WORDS = 4       # 16-byte hash input: seed_len <= 64
N_BANKS = 2              # ping-pong VMEM location banks

# Rows per pallas launch (ops.py chunks bigger batches): the two (rows, S)
# scalar-prefetch DMA tables are SMEM-resident, so bound them the same way
# candidate_align bounds its tables — 2048 rows * S=3 is 48 KB.
LAUNCH_ROWS = 2048


# --------------------------------------------------------------- hashing --
def _seed_bucket_kernel(reads_ref, out_ref, *, offs, seed_len: int,
                        hash_seed: int, mask: int):
    """(R, BLK) int32 base codes, read-major -> (S, BLK) int32 SeedMap
    bucket ids.

    Reads lie along lanes and read positions along sublanes, so every
    base of a seed is one static row load of the block and every hash
    lane is a read.  (The (BLK, R) layout needs a width-1 lane column per
    base, which Mosaic miscompiles on the chip.)
    """
    n_full, rem = divmod(seed_len, 16)
    for s, off in enumerate(offs):
        words = []
        for w in range(MAX_SEED_WORDS):
            # 2-bit pack bases [off+16w, off+16w+cnt) little-endian; words
            # past the seed are zero (pack_seed_words' zero padding).
            # int32 bits of the uint32 words (see `xxhash32_lanes`).
            cnt = 16 if w < n_full else (rem if w == n_full else 0)
            acc = jnp.zeros((1, reads_ref.shape[1]), jnp.int32)
            for i in range(cnt):
                p = off + 16 * w + i
                acc = acc | (reads_ref[p:p + 1, :] << (2 * i))
            words.append(acc)
        out_ref[s:s + 1, :] = xxhash32_lanes(*words, seed=hash_seed) & mask


def seed_buckets_pallas(
    reads: jnp.ndarray,      # (N, R) int32, N a multiple of `block`
    offs: tuple,             # static per-seed offsets within the read
    seed_len: int,
    hash_seed: int,
    table_size: int,
    block: int = HASH_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, R) reads -> (N, S) bucket ids (ops.py pads N)."""
    n, R = reads.shape
    assert n % block == 0, (n, block)
    assert seed_len <= 16 * MAX_SEED_WORDS, seed_len
    S = len(offs)
    return pl.pallas_call(
        functools.partial(_seed_bucket_kernel, offs=offs, seed_len=seed_len,
                          hash_seed=hash_seed, mask=table_size - 1),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((R, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((S, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((S, n), jnp.int32),
        name=NAME,
        interpret=interpret,
    )(reads.T).T


# ---------------------------------------------------------- merge+filter --
def _sort_width(M: int) -> int:
    """Sublanes a read's ``M`` starts occupy: a power of two >= max(M, 8)
    (the bitonic network's width; the starts past M are INVALID_LOC)."""
    return max(SUBLANES, 1 << (M - 1).bit_length())


def _shift_down(x: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """``out[i] = x[i - d]`` along sublanes, ``fill`` for i < d."""
    i = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(i >= d, pltpu.roll(x, d, 0), fill)


def _scan_sublanes(x: jnp.ndarray, op, fill) -> jnp.ndarray:
    """Inclusive running ``op`` down axis 0 (Hillis–Steele over sublane
    rolls; Mosaic lowers no `cumsum`/`cummax`)."""
    d = 1
    while d < x.shape[0]:
        x = op(x, _shift_down(x, d, fill))
        d *= 2
    return x


def _compare_exchange(x: jnp.ndarray, d: int, k: int) -> jnp.ndarray:
    """One bitonic stage: element i meets i ^ d, ascending where
    ``i & k == 0``.  Partners 8 or more sublanes apart are whole vregs
    (static slices, static direction); nearer ones come from two rolls."""
    Mp = x.shape[0]
    if d >= SUBLANES:
        parts = []
        for g0 in range(0, Mp, 2 * d):
            lo, hi = x[g0:g0 + d], x[g0 + d:g0 + 2 * d]
            a, b = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
            parts += [b, a] if g0 & k else [a, b]
        return jnp.concatenate(parts, axis=0)
    i = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    upper = (i & d) != 0
    partner = jnp.where(upper, pltpu.roll(x, d, 0), pltpu.roll(x, Mp - d, 0))
    # min: the lower of an ascending pair or the upper of a descending one
    take_min = upper == ((i & k) != 0)
    return jnp.where(take_min, jnp.minimum(x, partner),
                     jnp.maximum(x, partner))


def _sort_sublanes(x: jnp.ndarray) -> jnp.ndarray:
    """(Mp, BLK) int32 -> every lane's column ascending: a bitonic
    network of log2(Mp) * (log2(Mp) + 1) / 2 min/max stages."""
    k = 2
    while k <= x.shape[0]:
        d = k // 2
        while d:
            x = _compare_exchange(x, d, k)
            d //= 2
        k *= 2
    return x


def _over_rows(row_buf, M: int, body) -> jnp.ndarray:
    """Fold ``body(row, acc, j)`` over rows j < M of ``row_buf`` (each a
    (1, BLK) row broadcast down the sublanes) from a zero (Mp, BLK) acc.
    Eight rows per loop step, unrolled; the rows past M that the last
    step reaches hold the sentinel padding."""
    def step(t, acc):
        for u in range(SUBLANES):
            j = t * SUBLANES + u
            acc = body(row_buf[pl.ds(j, 1), :], acc, j)
        return acc
    return jax.lax.fori_loop(0, -(-M // SUBLANES), step,
                             jnp.zeros(row_buf.shape, jnp.int32))


def merge_filter_block(l1, l2, row_buf, *, seed_offs, K: int, delta: int,
                       cap: int):
    """The fused front-end math on one resident block of reads.

    l1, l2: (BLK, M = S*K) int32 raw per-seed locations, seed-major
    (element s*K + k is location k of seed s), INVALID_LOC padded.
    ``row_buf`` is a (Mp, BLK) int32 VMEM scratch (`_sort_width`).
    Mirrors `merge_read_starts` + `pair_filter._row_filter` bit-for-bit,
    with reads on lanes and each read's starts on sublanes.  Returns
    (pos1, pos2) (cap, BLK) and (n, nh1, nh2) (1, BLK) int32.
    """
    BLK, M = l1.shape
    Mp = row_buf.shape[0]
    # Per-element seed offset, built from iota + static scalars (Pallas
    # kernels cannot capture constant arrays).
    seed_of = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1) // K
    offv = jnp.zeros((1, M), jnp.int32)
    for s, off in enumerate(seed_offs):
        offv = jnp.where(seed_of == s, jnp.int32(off), offv)

    def starts_of(locs):
        starts = jnp.where(locs != INVALID_LOC, locs - offv, INVALID_LOC)
        if Mp > M:
            starts = jnp.concatenate(
                [starts, jnp.full((BLK, Mp - M), INVALID_LOC, jnp.int32)],
                axis=1)
        col = _sort_sublanes(starts.T)                        # (Mp, BLK)
        # Seed offsets are >= 0, so no valid start equals the sentinel.
        return col, jnp.sum(jnp.where(col != INVALID_LOC, 1, 0), axis=0,
                            keepdims=True)

    s1, nh1 = starts_of(l1)
    s2, nh2 = starts_of(l2)
    row_buf[...] = s2
    i = jax.lax.broadcasted_iota(jnp.int32, (Mp, BLK), 0)

    # searchsorted(side="left") == #{j < M : s2_j < v - Δ}: one row of s2
    # against every sublane at a time.
    target = s1 - delta
    lo = _over_rows(row_buf, M, lambda row, acc, j: acc + jnp.where(
        row < target, 1, 0))
    # Occurrence k of a duplicated read-1 start probes partner lo + k
    # (pair_filter semantics): k = i - the start of i's run of equals.
    prev1 = _shift_down(s1, 1, INVALID_LOC)
    run_start = _scan_sublanes(jnp.where((i == 0) | (s1 != prev1), i, 0),
                               jnp.maximum, 0)
    # The oracle's list is M long: the probe clips at M - 1, not Mp - 1.
    idx = jnp.clip(lo + i - run_start, 0, M - 1)
    p2 = _over_rows(row_buf, M, lambda row, acc, j: jnp.where(
        idx == j, row, acc))

    within = ((p2 != INVALID_LOC) & (jnp.abs(p2 - s1) <= delta)
              & (s1 != INVALID_LOC))
    # (start1, start2) pair dedup: equal pairs are adjacent.
    prev_same = ((s1 == prev1) & (p2 == _shift_down(p2, 1, INVALID_LOC))
                 & (i != 0))
    keep = jnp.where(within & ~prev_same, 1, 0)

    # Front compaction: kept element i lands at slot #{j < i : keep_j}.
    kept = _scan_sublanes(keep, jnp.add, 0)
    slot = jnp.where(keep == 1, kept - keep, -1)              # -1: dropped
    nkeep = kept[Mp - 1:Mp, :]

    def compact(x):
        return jnp.concatenate(
            [jnp.sum(jnp.where(slot == c, x, 0), axis=0, keepdims=True)
             for c in range(cap)], axis=0)                    # (cap, BLK)

    filled = jax.lax.broadcasted_iota(jnp.int32, (cap, BLK), 0) < nkeep
    pos1 = jnp.where(filled, compact(s1), INVALID_LOC)
    pos2 = jnp.where(filled, compact(p2), INVALID_LOC)
    return pos1, pos2, jnp.minimum(nkeep, cap), nh1, nh2


def _write(outs, vals):
    for ref, val in zip(outs, vals):
        ref[...] = val


def _outputs(block: int, B: int, C: int):
    """Lane-major outputs — (pos1, pos2) (C, B), (n, nh1, nh2) (1, B) —
    as (out_specs, out_shape)."""
    spec = lambda rows: pl.BlockSpec((rows, block), lambda i, *_: (0, i))
    return ([spec(C), spec(C), spec(1), spec(1), spec(1)],
            [jax.ShapeDtypeStruct((C, B), jnp.int32)] * 2
            + [jax.ShapeDtypeStruct((1, B), jnp.int32)] * 3)


def _row_major(outs):
    """Lane-major kernel outputs -> (pos1, pos2) (B, C), (n, nh1, nh2) (B,)."""
    pos1, pos2, n, nh1, nh2 = outs
    return pos1.T, pos2.T, n[0], nh1[0], nh2[0]


def _check_block(block: int, interpret: bool) -> None:
    if not interpret and block % LANES:
        raise ValueError(
            f"pair_frontend: block={block} rows per grid step must be a "
            f"multiple of {LANES} on the chip (each read is a lane)")


# ------------------------------------------------- fused gather + filter --
def _frontend_kernel(*refs, S: int, K: int, nl: int, counted: bool,
                     seed_offs: tuple, delta: int, cap: int):
    """Refs, in order: the two scalar-prefetch (B*S,) int32 tables of each
    mate's flattened row starts in SMEM; the (n, 128) int32 row-table
    lines in HBM (ANY); the same starts as (BLK, S) VMEM blocks and,
    when ``counted``, each mate's (BLK, S) row counts; the outputs
    (pos1, pos2 (C, BLK); n, nh1, nh2 (1, BLK)); the scratch location
    banks (N_BANKS, 2, S, BLK*nl, 128) int32 VMEM, their (N_BANKS, 2)
    DMA semaphores and the (Mp, BLK) row buffer."""
    starts = refs[0:2]
    table_any = refs[2]
    n_blocked = 4 if counted else 2
    shifts = refs[3:5]
    counts = refs[5:7] if counted else None
    outs = refs[3 + n_blocked:8 + n_blocked]
    loc, sems, row_buf = refs[8 + n_blocked:]
    BLK = shifts[0].shape[0]
    g = pl.program_id(0)
    nsteps = pl.num_programs(0)
    bank = jax.lax.rem(g, N_BANKS)

    # ---- ping-pong row streaming HBM -> VMEM (candidate_align protocol) --
    # Row (r, s)'s `nl` covering lines land in rows [r*nl, r*nl + nl).
    def _dma(bnk, mate, step, i):
        r, s = i // S, i % S
        st = starts[mate][(step * BLK + r) * S + s]
        return pltpu.make_async_copy(
            table_any.at[pl.ds(st // LANES, nl), :],
            loc.at[bnk, mate, s, pl.ds(r * nl, nl), :],
            sems.at[bnk, mate])

    def _start_step(step, bnk):
        def issue(i, _):
            _dma(bnk, 0, step, i).start()
            _dma(bnk, 1, step, i).start()
            return 0
        jax.lax.fori_loop(0, BLK * S, issue, 0)

    def _wait_step(step, bnk):
        def drain(i, _):
            _dma(bnk, 0, step, i).wait()
            _dma(bnk, 1, step, i).wait()
            return 0
        jax.lax.fori_loop(0, BLK * S, drain, 0)

    @pl.when(g == 0)
    def _():                     # warm-up: first step fetches its own bank
        _start_step(0, 0)

    @pl.when(g + 1 < nsteps)
    def _():                     # prefetch next step into the other bank
        _start_step(g + 1, jax.lax.rem(g + 1, N_BANKS))

    _wait_step(g, bank)          # this step's rows are now resident

    # A padded row starts at a multiple of K; a CSR row at any lane.
    align = 1 if counted else math.gcd(K, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (BLK, K), 1)

    def block_locs(mate):
        """(BLK, S*K) seed-major locations: each seed's row cut at its
        lane offset out of its covering lines and, counted, the lanes
        past its count (the next bucket's) set to INVALID_LOC."""
        cuts = []
        for s in range(S):
            lines = gather_lines(loc.at[bank, mate, s], BLK, nl)
            row = cut_lanes(lines, shifts[mate][:, s:s + 1] & (LANES - 1),
                            K, align)
            if counted:
                row = jnp.where(lane < counts[mate][:, s:s + 1], row,
                                INVALID_LOC)
            cuts.append(row)
        return jnp.concatenate(cuts, axis=1)

    _write(outs, merge_filter_block(
        block_locs(0), block_locs(1), row_buf, seed_offs=seed_offs, K=K,
        delta=delta, cap=cap))


def pair_frontend_pallas(
    table: jnp.ndarray,          # (n, 128) int32 row-table lines
    sdma1: jnp.ndarray,          # (B*S,) int32 flattened row starts
    sdma2: jnp.ndarray,
    seed_offs: tuple,            # static per-seed read offsets
    K: int,
    delta: int,
    max_candidates: int,
    counts: tuple | None = None,  # CSR: ((B*S,), (B*S,)) int32 row counts
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """B must be a multiple of `block` (ops.py pads and chunks launches to
    <= LAUNCH_ROWS rows so the SMEM DMA tables stay bounded); on the chip
    `block` is a multiple of 128.

    Padded lines (``counts`` None): row starts are ``bucket * K`` and
    rows are INVALID_LOC padded.  CSR lines: row starts are
    ``offsets[bucket]``, and the lanes at or past each row's count are
    masked.

    Returns (pos1, pos2) (B, C) and (n, n_hits1, n_hits2) (B,) int32.
    """
    _check_block(block, interpret)
    S = len(seed_offs)
    B = sdma1.shape[0] // S
    assert B % block == 0, (B, block)
    C = max_candidates
    counted = counts is not None
    nl = lines_spanned(K, 1 if counted else K)
    # The starts ride twice: scalars aim the DMAs, a (BLK, S) block gives
    # the per-read lane shifts (and, CSR, the count masks).
    blocked = [x.reshape(B, S) for x in
               (sdma1, sdma2) + (tuple(counts) if counted else ())]
    out_specs, out_shape = _outputs(block, B, C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec((block, S), lambda i, *_: (i, 0))] * len(blocked),
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((N_BANKS, 2, S, block * nl, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((N_BANKS, 2)),
            pltpu.VMEM((_sort_width(S * K), block), jnp.int32),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_frontend_kernel, S=S, K=K, nl=nl,
                          counted=counted, seed_offs=tuple(seed_offs),
                          delta=delta, cap=C),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=NAME,
        interpret=interpret,
    )(sdma1, sdma2, table, *blocked)
    return _row_major(outs)


# ------------------------------------------------- merge+filter only -----
def _merge_filter_kernel(l1_ref, l2_ref, *refs, seed_offs: tuple, K: int,
                         delta: int, cap: int):
    *outs, row_buf = refs
    _write(outs, merge_filter_block(
        l1_ref[...], l2_ref[...], row_buf, seed_offs=seed_offs, K=K,
        delta=delta, cap=cap))


def merge_filter_pallas(
    locs1: jnp.ndarray,          # (B, S*K) int32 seed-major locations
    locs2: jnp.ndarray,
    seed_offs: tuple,
    K: int,
    delta: int,
    max_candidates: int,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """Post-query entry: merge+filter for locations already gathered (the
    sharded serve step).  B must be a multiple of `block` (ops.py pads);
    on the chip `block` is a multiple of 128."""
    _check_block(block, interpret)
    B, M = locs1.shape
    assert B % block == 0, (B, block)
    assert M == len(seed_offs) * K, (M, len(seed_offs), K)
    C = max_candidates
    row_spec = pl.BlockSpec((block, M), lambda i: (i, 0))
    out_specs, out_shape = _outputs(block, B, C)
    outs = pl.pallas_call(
        functools.partial(_merge_filter_kernel, seed_offs=tuple(seed_offs),
                          K=K, delta=delta, cap=C),
        grid=(B // block,),
        in_specs=[row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((_sort_width(M), block), jnp.int32)],
        name=NAME,
        interpret=interpret,
    )(locs1, locs2)
    return _row_major(outs)
