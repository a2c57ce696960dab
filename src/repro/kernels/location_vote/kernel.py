"""Pallas TPU kernel: the Location Voting reduction (§4.7, [85]).

Each lane is one long read; its (M,) candidate-diagonal row streams from
HBM into VMEM and reduces to the winning vote bin + count without ever
materializing a histogram: an M-step `fori_loop` accumulates each slot's
bin multiplicity with an all-pairs compare (``counts += (vbin ==
vbin[:, j]) & valid[j]``), then ``votes = max`` over the valid counts and
``win_bin = min`` bin among the maxima — the same smallest-bin tie-break
`ref.py` pins.  O(M^2) compares on the VPU beat a VMEM histogram: M is
the per-read candidate budget ((S-1) * max_candidates, ~100), while the
bin range spans the whole reference.

Same double-buffered DMA protocol as `residual_dp`, one DMA per block
(a step's diagonal rows are contiguous in the 2-D row array): two VMEM
banks ping-pong between "being reduced" and "being filled", and both the
issue and the wait are gated on the block being live (``step * BLK <
n_rows``, the launch's live row count riding in as a scalar-prefetch
operand), so the grid steps past the batch's true row count cost neither
HBM traffic nor compute — they just write zero sentinels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.seedmap import INVALID_LOC
from repro.kernels._util import LANES

#: Name of every launch of this family: its HLO instruction name
#: (``location_vote.N``) and its op name in a device profile.
NAME = "location_vote"

DEFAULT_BLOCK = 64     # reads per grid step
N_BANKS = 2            # ping-pong VMEM diagonal-row banks

# Reads per pallas launch (ops.py chunks bigger batches).
LAUNCH_ROWS = 4096


def _location_vote_kernel(
    # scalar prefetch (SMEM, visible to every grid step)
    nrows_ref,                   # (1,) int32 live read count of this launch
    # inputs
    diag_any,                    # (rows, Mp) int32 ANY/HBM: diagonal rows
    # outputs, all (BLK, 1) int32
    bin_ref, votes_ref, did_ref,
    # scratch
    win,                         # (N_BANKS, BLK, Mp) int32 VMEM
    sems,                        # (N_BANKS,) DMA semaphores
    *,
    M: int, vote_bin: int,
):
    BLK = bin_ref.shape[0]
    g = pl.program_id(0)
    nsteps = pl.num_programs(0)
    n = nrows_ref[0]
    bank = jax.lax.rem(g, N_BANKS)

    def live(step):
        return step * BLK < n

    # ---- ping-pong block streaming HBM -> VMEM (live blocks only) -------
    # A step's BLK diagonal rows are contiguous: one DMA per block.
    def _dma(step, bnk):
        return pltpu.make_async_copy(
            diag_any.at[pl.ds(step * BLK, BLK), :], win.at[bnk],
            sems.at[bnk])

    @pl.when((g == 0) & live(0))
    def _():                     # warm-up: first step fetches its own bank
        _dma(0, 0).start()

    @pl.when((g + 1 < nsteps) & live(g + 1))
    def _():                     # prefetch next live step, other bank
        _dma(g + 1, jax.lax.rem(g + 1, N_BANKS)).start()

    @pl.when(live(g))
    def _():                     # this block holds real reads
        _dma(g, bank).wait()
        d = win[bank]                                  # (BLK, Mp)
        valid = d != INVALID_LOC
        # Floored division, matching the oracle: negative near-origin
        # diagonals must round toward -inf, not toward zero.
        vbin = jnp.floor_divide(d, vote_bin)
        valid_i = jnp.where(valid, 1, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

        def count_slot(j, counts):
            # Slot j's bin and validity as (BLK, 1) columns (one-hot lane
            # reductions: Mosaic lowers no dynamic lane slice).
            at_j = lane == j
            bj = jnp.sum(jnp.where(at_j, vbin, 0), axis=1, keepdims=True)
            vj = jnp.sum(jnp.where(at_j, valid_i, 0), axis=1, keepdims=True)
            return counts + jnp.where((vbin == bj) & (vj != 0), 1, 0)

        counts = jax.lax.fori_loop(
            0, M, count_slot, jnp.zeros(d.shape, jnp.int32))
        votes = jnp.max(jnp.where(valid, counts, 0), axis=-1)
        at_max = valid & (counts == votes[:, None])
        win_bin = jnp.min(
            jnp.where(at_max, vbin, jnp.int32(INVALID_LOC)), axis=-1)
        bin_ref[...] = jnp.where(votes > 0, win_bin, 0)[:, None]
        votes_ref[...] = votes[:, None]
        did_ref[...] = jnp.ones((BLK, 1), jnp.int32)

    @pl.when(~live(g))
    def _():                     # dead block: sentinels, no DMA, no vote
        bin_ref[...] = jnp.zeros((BLK, 1), jnp.int32)
        votes_ref[...] = jnp.zeros((BLK, 1), jnp.int32)
        did_ref[...] = jnp.zeros((BLK, 1), jnp.int32)


def location_vote_pallas(
    diag: jnp.ndarray,           # (rows, Mp) int32 diagonal rows
    n_rows: jnp.ndarray,         # (1,) int32 live read count
    vote_bin: int,
    M: int,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """rows must be a multiple of `block` (ops.py pads and chunks), and
    Mp >= M a multiple of 128 (a row DMA moves whole lines): slots past
    the M real ones must hold INVALID_LOC.

    Returns 3 (rows,) int32 arrays: (win_bin, votes, did) — `did` is 1
    exactly on the lanes of grid steps that executed at runtime.
    """
    rows, Mp = diag.shape
    assert rows % block == 0, (rows, block)
    assert Mp % LANES == 0 and Mp >= M, (Mp, M)
    grid = (rows // block,)
    row_spec = lambda cols: pl.BlockSpec((block, cols), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row_spec(1)] * 3,
        scratch_shapes=[
            pltpu.VMEM((N_BANKS, block, Mp), jnp.int32),
            pltpu.SemaphoreType.DMA((N_BANKS,)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_location_vote_kernel, M=M, vote_bin=vote_bin),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 3,
        name=NAME,
        interpret=interpret,
    )(n_rows, diag)
    return tuple(o[:, 0] for o in outs)
