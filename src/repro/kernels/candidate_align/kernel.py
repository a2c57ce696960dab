"""Pallas TPU kernel: fused candidate light-alignment (§4, Fig. 3 step 4).

Fuses the step-4 hot path — per-candidate reference-window gather, the
shifted-mask Light Alignment of both mates, the optional zero-shift Hamming
prescreen (§Perf G2), and the argmax-over-candidates pair reduction — into
one kernel.  The reference stays in HBM (`pl.ANY`); each grid step DMAs
only the `2*C*BLK` candidate windows it is about to align into VMEM
scratch, so the `(B, C, R+2E)` window tensor and the `B*C` row reshape of
the unfused path never exist in HBM.  This is the TPU analogue of the
paper's bounded candidate FIFO between the Paired-Adjacency filter and the
Light Alignment array: windows stream through on-chip memory and only the
per-row winner is written back.

Double-buffered DMA (ping-pong protocol)
----------------------------------------
The window DMA start indices are scalar-prefetch operands (the full (B, C)
tables live in SMEM for every grid step), so step ``g`` can issue step
``g+1``'s fetches while its own compute runs.  Two VMEM banks per mate
alternate between "being computed on" and "being filled":

    grid step g          bank g%2                 bank (g+1)%2
    -----------          --------                 ------------
    g == 0               start own DMAs           -
    all g                |                        start step g+1's DMAs
                         wait 2*C*BLK sems        |   (in flight during
                         prescreen + align        |    this step's compute)
    g+1                  start step g+2's DMAs    wait, compute ...

Each (bank, mate, candidate, row) DMA has its own semaphore; a bank is
reused only two steps later, after its windows were consumed by the
previous compute, so no write-after-read hazard exists.  This replaces the
seed kernel's start-all/wait-all burst, overlapping the HBM window traffic
of step g+1 with the `align_block` compute of step g — the near-memory
pipelining argument of GateSeeder, on a TPU.

In-kernel prescreen skip (§Perf G2)
-----------------------------------
With ``0 < prescreen_top < C`` the kernel first runs the cheap zero-shift
Hamming pass (one vector compare per candidate — the paper's one-cycle XOR
unit) over all C candidates, ranks candidate *pairs* by summed mismatches
(stable sort order, replicating `lax.top_k` tie-breaking), then gathers the
windows of the top ``P = prescreen_top`` candidates with one-hot sublane
selects and runs the full shifted-mask `align_block` on those P only.  The
Pallas backend therefore does P/C of the alignment FLOPs — the compute
saving the oracle realizes with `top_k` + `take_along_axis` — while staying
bit-exact with it.  (The DMA traffic is unchanged: the prescreen itself
must read every window.)

Layout: windows land in a `(2, C, BLK, W)` scratch so each candidate's
block is a contiguous `(BLK, W)` 2D tile; the alignment math (shared with
the light_align kernel via `align_block`) runs per selected candidate in a
static loop, and per-candidate scalars are concatenated to `(BLK, P)` for
the reduction.

With `packed_ref=True` the DMA fetches 2-bit packed uint32 words (4x less
HBM traffic, mirroring the paper's 2-bit SRAM encoding) and the kernel
unpacks + cuts the per-row `[off, off+W)` base window with a 16-way select
on the intra-word offset.

Argmax tie-breaking matches the jnp oracle exactly: the reduction key is
``(score1 + score2) * C - j`` where ``j`` is the candidate's position in
the prescreen ordering (its slot index when the prescreen is off), so
equal pair scores resolve to the earliest candidate in oracle order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import BASES_PER_WORD
from repro.core.scoring import Scoring
from repro.kernels._util import (
    LANES,
    cut_lanes,
    gather_lines,
    lines_spanned,
    unpack_window_block,
)
from repro.kernels.light_align.kernel import align_block

#: Name of every launch of this family: its HLO instruction name
#: (``candidate_pair_align.N``) and its op name in a device profile.
NAME = "candidate_pair_align"

DEFAULT_BLOCK = 16     # batch rows per grid step (C candidates x 2 mates each)
NEG_BIG = -(1 << 20)   # masked-candidate score sentinel
MM_BIG = 1 << 20       # masked-candidate Hamming sentinel
N_BANKS = 2            # ping-pong VMEM window banks

# The reduction key is (sc1 + sc2) * C - j in int32; keep the whole key
# range representable.
MAX_CANDIDATES = 512

# Rows per pallas launch (ops.py chunks bigger batches): the scalar-prefetch
# DMA tables are SMEM-resident at 2 * rows * C * 4 bytes per launch, so the
# footprint must stay bounded no matter how large the serve batch is —
# 1024 rows * C=8 is 64 KB.  Each chunk restarts the ping-pong pipeline
# (one un-overlapped DMA burst per chunk boundary), which is noise across
# the >= LAUNCH_ROWS/BLOCK grid steps in between.
LAUNCH_ROWS = 1024


def _candidate_align_kernel(
    # scalar prefetch: full (B, C) int32 DMA start tables in SMEM, visible
    # to every grid step (required to issue step g+1's fetches from step g)
    sdma1_ref, sdma2_ref,
    # blocked inputs
    off1_ref, off2_ref,          # (BLK, C) int32 VMEM: window offset in line
    valid1_ref, valid2_ref,      # (BLK, C) int32 VMEM: candidate validity
    reads1_ref, reads2_ref,      # (BLK, R) int32 VMEM
    ref_any,                     # (n, 128) int32 ANY/HBM: reference lines
    # outputs, all (BLK, 1) int32
    slot_ref, rank_ref, sc1_ref, sc2_ref, ok1_ref, ok2_ref,
    et1_ref, el1_ref, ep1_ref, et2_ref, el2_ref, ep2_ref,
    # scratch
    win1, win2,                  # (N_BANKS, C, BLK*nl, 128) int32 VMEM
    sems,                        # (N_BANKS, 2) DMA semaphores
    *,
    E: int, R: int, scoring: Scoring, threshold: int, mode: str,
    prescreen_top: int, packed: bool, win_elems: int, nl: int,
):
    BLK, C = off1_ref.shape
    W = R + 2 * E
    g = pl.program_id(0)
    nsteps = pl.num_programs(0)
    bank = jax.lax.rem(g, N_BANKS)

    # ---- ping-pong window streaming HBM -> VMEM -------------------------
    # A window's `nl` covering lines land in rows [r*nl, r*nl + nl).
    def _dma(bnk, mate, step, i):
        r, c = i // C, i % C
        starts = (sdma1_ref, sdma2_ref)[mate]
        win = (win1, win2)[mate]
        s = starts[(step * BLK + r) * C + c]
        return pltpu.make_async_copy(
            ref_any.at[pl.ds(s, nl), :], win.at[bnk, c, pl.ds(r * nl, nl), :],
            sems.at[bnk, mate])

    def _start_step(step, bnk):
        def issue(i, _):
            _dma(bnk, 0, step, i).start()
            _dma(bnk, 1, step, i).start()
            return 0
        jax.lax.fori_loop(0, BLK * C, issue, 0)

    def _wait_step(step, bnk):
        def drain(i, _):
            _dma(bnk, 0, step, i).wait()
            _dma(bnk, 1, step, i).wait()
            return 0
        jax.lax.fori_loop(0, BLK * C, drain, 0)

    @pl.when(g == 0)
    def _():                     # warm-up: first step fetches its own bank
        _start_step(0, 0)

    @pl.when(g + 1 < nsteps)
    def _():                     # prefetch next step into the other bank
        _start_step(g + 1, jax.lax.rem(g + 1, N_BANKS))

    _wait_step(g, bank)          # this step's windows are now resident

    def window(win, off_ref, c):
        """Candidate c's (BLK, W) base window from the active bank."""
        off = off_ref[:, c:c + 1]
        lines = gather_lines(win.at[bank, c], BLK, nl)  # (BLK, nl*128)
        if not packed:
            return cut_lanes(lines, off, W)
        # Packed: `off` = 16 * word lane + base-in-word; the shared 2-bit
        # unpack cuts the per-row base offset (kernels/_util.py).
        raw = cut_lanes(lines, off >> 4, win_elems)    # (BLK, n_words)
        return unpack_window_block(raw, off & (BASES_PER_WORD - 1), W)

    reads1 = reads1_ref[...]
    reads2 = reads2_ref[...]
    valid1 = valid1_ref[...]                           # (BLK, C) int32 0/1
    valid2 = valid2_ref[...]
    w1 = [window(win1, off1_ref, c) for c in range(C)]
    w2 = [window(win2, off2_ref, c) for c in range(C)]
    col = jax.lax.broadcasted_iota(jnp.int32, (BLK, C), 1)

    # Per-candidate selects and reductions stay on int32: Mosaic cannot
    # slice or widen i1 vectors here.
    if 0 < prescreen_top < C:
        P = prescreen_top
        # Zero-shift Hamming pass over all C candidate pairs (one vector
        # compare per candidate — far cheaper than a full alignment).
        mm0 = jnp.concatenate(
            [(jnp.sum(jnp.where(w1[c][:, E:E + R] != reads1, 1, 0), -1)
              + jnp.sum(jnp.where(w2[c][:, E:E + R] != reads2, 1, 0), -1)
              )[:, None]
             for c in range(C)], axis=1)               # (BLK, C)
        mm0 = jnp.where((valid1 & valid2) != 0, mm0, MM_BIG)
        # rank = candidate's position in the mm0-ascending stable sort,
        # replicating lax.top_k's lower-index-first tie-breaking; ranks are
        # a per-row permutation of 0..C-1, so `rank == j` is exactly
        # one-hot per row.
        rank = jnp.zeros((BLK, C), jnp.int32)
        for cp in range(C):
            mcp = mm0[:, cp:cp + 1]
            ahead = (mcp < mm0) | ((mcp == mm0) & (cp < col))
            rank = rank + jnp.where(ahead, 1, 0)

        def gwin(ws, j):                               # -> (BLK, W)
            out = ws[0]
            for c in range(1, C):
                out = jnp.where(rank[:, c:c + 1] == j, ws[c], out)
            return out

        def gcol(mat, j):                              # (BLK, C) -> (BLK,)
            return jnp.sum(jnp.where(rank == j, mat, 0), axis=1)

        # Full shifted-mask alignment only for the P survivors: the Pallas
        # backend now does P/C of the alignment work (DMA is unchanged —
        # the prescreen itself read every window).
        aw1 = [gwin(w1, j) for j in range(P)]
        aw2 = [gwin(w2, j) for j in range(P)]
        slots = jnp.concatenate(
            [gcol(col, j)[:, None] for j in range(P)], axis=1)
        gv1 = jnp.concatenate(
            [gcol(valid1, j)[:, None] for j in range(P)], axis=1) != 0
        gv2 = jnp.concatenate(
            [gcol(valid2, j)[:, None] for j in range(P)], axis=1) != 0
    else:
        P = C
        aw1, aw2 = w1, w2
        slots = col
        gv1, gv2 = valid1 != 0, valid2 != 0

    cols1 = [align_block(reads1, aw1[j], E=E, scoring=scoring, mode=mode)
             for j in range(P)]
    cols2 = [align_block(reads2, aw2[j], E=E, scoring=scoring, mode=mode)
             for j in range(P)]

    def stack(cols, k):                                # -> (BLK, P)
        return jnp.concatenate([x[k][:, None] for x in cols], axis=1)

    sc1_raw, et1, el1, ep1 = (stack(cols1, k) for k in range(4))
    sc2_raw, et2, el2, ep2 = (stack(cols2, k) for k in range(4))
    sc1 = jnp.where(gv1, sc1_raw, NEG_BIG)
    sc2 = jnp.where(gv2, sc2_raw, NEG_BIG)

    # Unique per-row reduction key: pair scores differ by >= 1 and
    # positions j by < C, so key ties are impossible and `hot` is exactly
    # one-hot.  All values stay in int32 because C <= MAX_CANDIDATES.
    idx = jax.lax.broadcasted_iota(jnp.int32, (BLK, P), 1)
    key = (sc1 + sc2) * C - idx
    hot = key == jnp.max(key, axis=-1, keepdims=True)

    def pick(x):                                       # (BLK, P) -> (BLK, 1)
        return jnp.sum(jnp.where(hot, x, 0), axis=-1, keepdims=True)

    slot_ref[...] = pick(slots)
    rank_ref[...] = pick(idx)
    sc1_ref[...] = pick(sc1)
    sc2_ref[...] = pick(sc2)
    ok1_ref[...] = pick(jnp.where((sc1_raw >= threshold) & gv1, 1, 0))
    ok2_ref[...] = pick(jnp.where((sc2_raw >= threshold) & gv2, 1, 0))
    et1_ref[...] = pick(et1)
    el1_ref[...] = pick(el1)
    ep1_ref[...] = pick(ep1)
    et2_ref[...] = pick(et2)
    el2_ref[...] = pick(el2)
    ep2_ref[...] = pick(ep2)


def candidate_align_pallas(
    ref_lines: jnp.ndarray,      # (n, 128) int32 padded ref lines
    reads1: jnp.ndarray,         # (B, R) int32
    reads2: jnp.ndarray,         # (B, R) int32
    sdma1: jnp.ndarray,          # (B*C,) int32 first line of each window
    sdma2: jnp.ndarray,
    off1: jnp.ndarray,           # (B, C) int32 window offset in that line
    off2: jnp.ndarray,
    valid1: jnp.ndarray,         # (B, C) int32 0/1
    valid2: jnp.ndarray,
    max_gap: int,
    scoring: Scoring,
    threshold: int,
    mode: str,
    prescreen_top: int,
    packed: bool,
    win_elems: int,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """B must be a multiple of `block` (ops.py pads and chunks launches
    to <= LAUNCH_ROWS rows).

    The DMA start tables ride in as scalar-prefetch operands (SMEM,
    ``2 * B * C * 4`` bytes per launch — bounded by ops.py's chunking) so
    every grid step can plan the next step's window fetches — the
    double-buffer protocol needs lookahead the per-step BlockSpec
    pipeline cannot provide.

    Returns 12 (B,) int32 arrays: (slot, rank, score1, score2, ok1, ok2,
    edit_type1, edit_len1, edit_pos1, edit_type2, edit_len2, edit_pos2).
    """
    B, R = reads1.shape
    C = off1.shape[1]
    assert B % block == 0, (B, block)
    assert C <= MAX_CANDIDATES, (C, MAX_CANDIDATES)
    nl = lines_spanned(win_elems)
    grid = (B // block,)
    row_spec = lambda cols: pl.BlockSpec((block, cols), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            row_spec(C), row_spec(C), row_spec(C), row_spec(C),
            row_spec(R), row_spec(R),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[row_spec(1)] * 12,
        scratch_shapes=[
            pltpu.VMEM((N_BANKS, C, block * nl, LANES), jnp.int32),
            pltpu.VMEM((N_BANKS, C, block * nl, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((N_BANKS, 2)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _candidate_align_kernel, E=max_gap, R=R, scoring=scoring,
            threshold=threshold, mode=mode, prescreen_top=prescreen_top,
            packed=packed, win_elems=win_elems, nl=nl,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 12,
        name=NAME,
        interpret=interpret,
    )(sdma1, sdma2, off1, off2, valid1, valid2, reads1, reads2, ref_lines)
    return tuple(o[:, 0] for o in outs)
