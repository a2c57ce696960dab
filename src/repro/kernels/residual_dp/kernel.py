"""Pallas TPU kernel: fused residual-pair DP fallback (§7.4, the GenDP
analogue — pipeline step 5).

Fuses the step-5 hot path — per-residual reference-window gather and the
banded Gotoh DP — into one kernel, the DP twin of `candidate_align`.  The
reference stays in HBM (`pl.ANY`); each grid step DMAs only the ``BLK``
windows it is about to align into VMEM scratch, so the ``(cap, R +
2*dp_pad)`` window tensors of the staged path never exist in HBM.  The
Gotoh scan itself is the shared `banded_sw.kernel.dp_block` recurrence
(banded moving frame: ``2*band + 1`` columns per row instead of ``W``).

Single-mate-aware item grid
---------------------------
The launch's lanes are *work items* — (residual row, mate) pairs whose
Light Alignment failed — compacted to the front of the item buffer by the
ops wrapper, with the item count riding in as a scalar-prefetch operand.
A grid step whose whole block lies past the item count skips its window
DMAs and the entire DP scan at runtime (`pl.when` on the prefetched
scalar) and just writes sentinels: with the typical one-failed-mate
residual mix, half the provisioned item blocks never execute — the
"halving DP work" the single-mate design buys.  The per-step `did`
output records which blocks really ran (the op's ``dp_lanes``
instrumentation; exact at ``block=1``).

Double-buffered DMA (ping-pong protocol)
----------------------------------------
Same protocol as `candidate_align`: the window DMA start table is a
scalar-prefetch operand visible to every step, two VMEM banks alternate
between "being computed on" and "being filled", and step ``g`` issues
step ``g+1``'s fetches before waiting on its own — but here both the
issue and the wait are gated on the block being live, so dead blocks
cost no HBM traffic either.

With ``packed=True`` the DMA fetches 2-bit packed uint32 words (4x less
HBM traffic, the paper's SRAM encoding) and the kernel unpacks + cuts the
per-item ``[off, off+W)`` base window with a 16-way select on the
intra-word offset, exactly as `candidate_align` does.
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
from repro.kernels.banded_sw.kernel import NEG, dp_block

#: Name of every launch of this family: its HLO instruction name
#: (``residual_pair_dp.N``) and its op name in a device profile.
NAME = "residual_pair_dp"

DEFAULT_BLOCK = 32     # work items (failed mates) per grid step
N_BANKS = 2            # ping-pong VMEM window banks

# Items per pallas launch (ops.py chunks bigger batches): the
# scalar-prefetch DMA start table is SMEM-resident at rows * 4 bytes per
# launch, bounded no matter how large the residual buffer is.
LAUNCH_ROWS = 4096


def _residual_dp_kernel(
    # scalar prefetch (SMEM, visible to every grid step)
    sdma_ref,                    # (rows,) int32 window DMA starts
    nitems_ref,                  # (1,) int32 live item count of this launch
    # blocked inputs
    reads_ref,                   # (BLK, R) int32 item reads
    off_ref,                     # (BLK, 1) int32 window offset in line
    ref_any,                     # (n, 128) int32 ANY/HBM: reference lines
    # outputs, all (BLK, 1) int32
    score_ref, end_ref, did_ref,
    # scratch
    win,                         # (N_BANKS, BLK*nl, 128) int32 VMEM
    sems,                        # (N_BANKS,) DMA semaphores
    *,
    R: int, W: int, band: int | None, scoring: Scoring, packed: bool,
    win_elems: int, nl: int,
):
    BLK = reads_ref.shape[0]
    g = pl.program_id(0)
    nsteps = pl.num_programs(0)
    n = nitems_ref[0]
    bank = jax.lax.rem(g, N_BANKS)

    def live(step):
        return step * BLK < n

    # ---- ping-pong window streaming HBM -> VMEM (live blocks only) ------
    # An item's `nl` covering lines land in rows [r*nl, r*nl + nl).
    def _dma(step, bnk, r):
        s = sdma_ref[step * BLK + r]
        return pltpu.make_async_copy(
            ref_any.at[pl.ds(s, nl), :], win.at[bnk, pl.ds(r * nl, nl), :],
            sems.at[bnk])

    def _start_step(step, bnk):
        def issue(r, _):
            _dma(step, bnk, r).start()
            return 0
        jax.lax.fori_loop(0, BLK, issue, 0)

    def _wait_step(step, bnk):
        def drain(r, _):
            _dma(step, bnk, r).wait()
            return 0
        jax.lax.fori_loop(0, BLK, drain, 0)

    @pl.when((g == 0) & live(0))
    def _():                     # warm-up: first step fetches its own bank
        _start_step(0, 0)

    @pl.when((g + 1 < nsteps) & live(g + 1))
    def _():                     # prefetch next live step, other bank
        _start_step(g + 1, jax.lax.rem(g + 1, N_BANKS))

    @pl.when(live(g))
    def _():                     # this block holds real failed-mate items
        _wait_step(g, bank)
        lines = gather_lines(win.at[bank], BLK, nl)    # (BLK, nl*128)
        off = off_ref[...]
        if packed:
            # `off` = 16 * word lane + base-in-word: the lane cut, then the
            # shared 2-bit unpack (the same one candidate_align uses).
            raw = cut_lanes(lines, off >> 4, win_elems)
            wrow = unpack_window_block(raw, off & (BASES_PER_WORD - 1), W)
        else:
            wrow = cut_lanes(lines, off, W)
        score, end = dp_block(reads_ref[...], wrow,
                              scoring=scoring, band=band)
        score_ref[...] = score[:, None]
        end_ref[...] = end[:, None]
        did_ref[...] = jnp.ones((BLK, 1), jnp.int32)

    @pl.when(~live(g))
    def _():                     # dead block: sentinels, no DMA, no DP
        score_ref[...] = jnp.full((BLK, 1), NEG, jnp.int32)
        end_ref[...] = jnp.zeros((BLK, 1), jnp.int32)
        did_ref[...] = jnp.zeros((BLK, 1), jnp.int32)


def residual_dp_pallas(
    ref_lines: jnp.ndarray,      # (n, 128) int32 padded ref lines
    sdma: jnp.ndarray,           # (rows,) int32 first line of each window
    n_items: jnp.ndarray,        # (1,) int32 live item count
    reads: jnp.ndarray,          # (rows, R) int32 item reads
    off: jnp.ndarray,            # (rows, 1) int32 window offset in line
    dp_pad: int,
    band: int | None,
    scoring: Scoring,
    packed: bool,
    win_elems: int,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """rows must be a multiple of `block` (ops.py pads and chunks).

    Returns 3 (rows,) int32 arrays: (score, ref_end, did) — `did` is 1
    exactly on the lanes of grid steps that executed the DP at runtime.
    """
    rows, R = reads.shape
    W = R + 2 * dp_pad
    assert rows % block == 0, (rows, block)
    nl = lines_spanned(win_elems)
    grid = (rows // block,)
    row_spec = lambda cols: pl.BlockSpec((block, cols), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            row_spec(R), row_spec(1),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[row_spec(1)] * 3,
        scratch_shapes=[
            pltpu.VMEM((N_BANKS, block * nl, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((N_BANKS,)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _residual_dp_kernel, R=R, W=W, band=band, scoring=scoring,
            packed=packed, win_elems=win_elems, nl=nl,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 3,
        name=NAME,
        interpret=interpret,
    )(sdma, n_items, reads, off, ref_lines)
    return tuple(o[:, 0] for o in outs)
