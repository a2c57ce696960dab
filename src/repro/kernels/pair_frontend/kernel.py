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
lines, where a count table rides along), and scalar-prefetch operands
must exist before the launch, so the fused op runs as two back-to-back
kernels:

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

In-VMEM sorted merge
--------------------
`jnp.sort` has no Mosaic lowering, so the merge uses the same
stable-rank one-hot idiom as the candidate_align prescreen: rank every
element by `#{j : x_j < x_i or (x_j == x_i and j < i)}` with one
`(M, M)` compare, then scatter values to their rank with a one-hot
sum.  M = S*K (96 at the paper's S=3, K=32); the kernel runs the merge
one read at a time, since a whole block's `(BLK, M, M)` tensors would
overflow VMEM.

The Δ filter mirrors `pair_filter._row_filter` exactly: a broadcast-
compare `searchsorted`, per-occurrence partner probing (duplicate
read-1 starts probe successive read-2 starts), `(start1, start2)` pair
dedup via adjacent-compare, and cumulative-sum front compaction.

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
compute of step g.

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
from repro.kernels._util import LANES, cut_lanes, lines_spanned
from repro.kernels.xxhash.kernel import xxhash32_lanes

#: Name of every launch of this family: its HLO instruction name
#: (``pair_frontend.N``) and its op name in a device profile.
NAME = "pair_frontend"

DEFAULT_BLOCK = 8        # batch rows per grid step (2*S row DMAs each)
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
def _sort_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(BLK, M) int32 -> ascending per row (stable-rank one-hot scatter)."""
    BLK, M = x.shape
    xi = x[:, :, None]
    xj = x[:, None, :]
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, M, M), 1)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, M, M), 2)
    ahead = (xj < xi) | ((xj == xi) & (j_idx < i_idx))
    rank = jnp.sum(ahead.astype(jnp.int32), axis=2)          # (BLK, M)
    # scatter: sorted[m] = x[i] where rank[i] == m (ranks are a permutation)
    hot = rank[:, :, None] == j_idx
    return jnp.sum(jnp.where(hot, xi, 0), axis=1)


def merge_filter_block(l1, l2, *, seed_offs, K: int, delta: int, cap: int):
    """The fused front-end math on one resident block.

    l1, l2: (BLK, M = S*K) int32 raw per-seed locations, seed-major
    (element s*K + k is location k of seed s), INVALID_LOC padded.
    Mirrors `merge_read_starts` + `pair_filter._row_filter` bit-for-bit.
    Returns (pos1, pos2) (BLK, cap) and (n, nh1, nh2) (BLK, 1) int32.
    """
    BLK, M = l1.shape
    # Per-element seed offset, built from iota + static scalars (Pallas
    # kernels cannot capture constant arrays).
    seed_of = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1) // K
    offv = jnp.zeros((1, M), jnp.int32)
    for s, off in enumerate(seed_offs):
        offv = jnp.where(seed_of == s, jnp.int32(off), offv)

    def starts_of(locs):
        valid = locs != INVALID_LOC
        starts = jnp.where(valid, locs - offv, INVALID_LOC)
        return (_sort_rows(starts),
                jnp.sum(valid.astype(jnp.int32), axis=1, keepdims=True))

    s1, nh1 = starts_of(l1)
    s2, nh2 = starts_of(l2)

    i_idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, M, M), 1)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, M, M), 2)
    v1 = s1[:, :, None]
    # searchsorted(side="left") == #{j : s2_j < v - Δ}; occurrence k of a
    # duplicated read-1 start probes partner lo+k (pair_filter semantics).
    lo = jnp.sum((s2[:, None, :] < v1 - delta).astype(jnp.int32), axis=2)
    occ = jnp.sum(((s1[:, None, :] == v1) & (j_idx < i_idx)).astype(jnp.int32),
                  axis=2)
    idx = jnp.clip(lo + occ, 0, M - 1)
    hot = idx[:, :, None] == j_idx
    p2 = jnp.sum(jnp.where(hot, s2[:, None, :], 0), axis=2)  # (BLK, M)

    within = ((p2 != INVALID_LOC) & (jnp.abs(p2 - s1) <= delta)
              & (s1 != INVALID_LOC))
    # Mosaic neither concatenates nor lane->sublane reshapes i1 vectors,
    # so the adjacent-compare and the compaction scatter work on int32.
    def prev(x):
        return jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)
    first = jax.lax.broadcasted_iota(jnp.int32, (BLK, M), 1) == 0
    prev_same = (s1 == prev(s1)) & (p2 == prev(p2)) & ~first
    keep = within & ~prev_same

    # Front compaction: kept element i lands at slot #{j < i : keep_j}.
    cpos = jnp.sum((keep[:, None, :] & (j_idx < i_idx)).astype(jnp.int32),
                   axis=2)
    cpos = jnp.where(keep, cpos, -1)                          # -1: dropped
    c_idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, M, cap), 2)
    sel = cpos[:, :, None] == c_idx                           # (BLK, M, cap)
    pos1 = jnp.sum(jnp.where(sel, s1[:, :, None], 0), axis=1)
    pos2 = jnp.sum(jnp.where(sel, p2[:, :, None], 0), axis=1)
    nkeep = jnp.sum(keep.astype(jnp.int32), axis=1, keepdims=True)
    filled = jax.lax.broadcasted_iota(jnp.int32, (BLK, cap), 1) < nkeep
    pos1 = jnp.where(filled, pos1, INVALID_LOC)
    pos2 = jnp.where(filled, pos2, INVALID_LOC)
    return pos1, pos2, jnp.minimum(nkeep, cap), nh1, nh2


def _merge_filter_rows(n_rows: int, locs_of, outs, **kw):
    """`merge_filter_block` one row at a time, writing row r of each
    output ref: a whole block's (BLK, M, M) compare tensors would
    overflow VMEM.  ``locs_of(r)`` returns row r's (1, M) locations."""
    def body(r, carry):
        res = merge_filter_block(*locs_of(r), **kw)
        for ref, val in zip(outs, res):
            ref[pl.ds(r, 1), :] = val
        return carry
    jax.lax.fori_loop(0, n_rows, body, 0)


# ------------------------------------------------- fused gather + filter --
def _frontend_kernel(*refs, S: int, K: int, nl: int, counted: bool,
                     seed_offs: tuple, delta: int, cap: int):
    """Refs, in order: the scalar-prefetch (B*S,) int32 tables in SMEM —
    each mate's flattened row starts and, when ``counted``, its row
    counts —; the (n, 128) int32 row-table lines in HBM (ANY); the
    outputs (pos1, pos2 (BLK, C); n, nh1, nh2 (BLK, 1)); the scratch
    location banks (N_BANKS, 2, S, BLK*nl, 128) int32 VMEM and their
    (N_BANKS, 2) DMA semaphores."""
    n_tables = 4 if counted else 2
    starts = refs[0:2]
    counts = refs[2:4] if counted else None
    table_any = refs[n_tables]
    pos1_ref, pos2_ref, n_ref, nh1_ref, nh2_ref = refs[n_tables + 1:
                                                      n_tables + 6]
    loc, sems = refs[n_tables + 6:]
    BLK = pos1_ref.shape[0]
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
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def row_locs(mate, r):
        """(1, S*K) seed-major locations of row r: each seed's row cut at
        its lane offset out of its covering lines, and, counted, the
        lanes past its count (the next bucket's) set to INVALID_LOC."""
        cuts = []
        for s in range(S):
            lines = loc[bank, mate, s, pl.ds(r * nl, nl), :]   # (nl, 128)
            line = jnp.concatenate([lines[q:q + 1] for q in range(nl)],
                                   axis=1) if nl > 1 else lines
            i = (g * BLK + r) * S + s
            row = cut_lanes(line, starts[mate][i] % LANES, K, align)
            if counted:
                row = jnp.where(lane < counts[mate][i], row, INVALID_LOC)
            cuts.append(row)
        return jnp.concatenate(cuts, axis=1)

    _merge_filter_rows(
        BLK, lambda r: (row_locs(0, r), row_locs(1, r)),
        (pos1_ref, pos2_ref, n_ref, nh1_ref, nh2_ref),
        seed_offs=seed_offs, K=K, delta=delta, cap=cap)


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
    <= LAUNCH_ROWS rows so the SMEM DMA tables stay bounded).

    Padded lines (``counts`` None): row starts are ``bucket * K`` and
    rows are INVALID_LOC padded.  CSR lines: row starts are
    ``offsets[bucket]``, and the lanes at or past each row's count are
    masked.

    Returns (pos1, pos2) (B, C) and (n, n_hits1, n_hits2) (B,) int32.
    """
    S = len(seed_offs)
    B = sdma1.shape[0] // S
    assert B % block == 0, (B, block)
    C = max_candidates
    counted = counts is not None
    nl = lines_spanned(K, 1 if counted else K)
    tables = (sdma1, sdma2) + (tuple(counts) if counted else ())
    row_spec = lambda cols: pl.BlockSpec((block, cols), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables),
        grid=(B // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row_spec(C), row_spec(C),
                   row_spec(1), row_spec(1), row_spec(1)],
        scratch_shapes=[
            pltpu.VMEM((N_BANKS, 2, S, block * nl, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((N_BANKS, 2)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_frontend_kernel, S=S, K=K, nl=nl,
                          counted=counted, seed_offs=tuple(seed_offs),
                          delta=delta, cap=C),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, C), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3,
        name=NAME,
        interpret=interpret,
    )(*tables, table)
    pos1, pos2, n, nh1, nh2 = outs
    return pos1, pos2, n[:, 0], nh1[:, 0], nh2[:, 0]


# ------------------------------------------------- merge+filter only -----
def _merge_filter_kernel(l1_ref, l2_ref, pos1_ref, pos2_ref,
                         n_ref, nh1_ref, nh2_ref, *,
                         seed_offs: tuple, K: int, delta: int, cap: int):
    _merge_filter_rows(
        pos1_ref.shape[0],
        lambda r: (l1_ref[pl.ds(r, 1), :], l2_ref[pl.ds(r, 1), :]),
        (pos1_ref, pos2_ref, n_ref, nh1_ref, nh2_ref),
        seed_offs=seed_offs, K=K, delta=delta, cap=cap)


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
    sharded serve step).  B must be a multiple of `block` (ops.py pads)."""
    B, M = locs1.shape
    assert B % block == 0, (B, block)
    assert M == len(seed_offs) * K, (M, len(seed_offs), K)
    C = max_candidates
    row_spec = lambda cols: pl.BlockSpec((block, cols), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_merge_filter_kernel, seed_offs=tuple(seed_offs),
                          K=K, delta=delta, cap=C),
        grid=(B // block,),
        in_specs=[row_spec(M), row_spec(M)],
        out_specs=[row_spec(C), row_spec(C),
                   row_spec(1), row_spec(1), row_spec(1)],
        out_shape=[jax.ShapeDtypeStruct((B, C), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 3,
        name=NAME,
        interpret=interpret,
    )(locs1, locs2)
    pos1, pos2, n, nh1, nh2 = outs
    return pos1, pos2, n[:, 0], nh1[:, 0], nh2[:, 0]
