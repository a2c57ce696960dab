"""Pallas TPU kernel: batched affine-gap Gotoh DP (the GenDP fallback).

Residual read-pairs are aligned with a semiglobal Gotoh DP.  The shared
`dp_block` below is the one Gotoh recurrence of the repo (the DP analogue
of `light_align.kernel.align_block`): the standalone `banded_sw` family
and the fused `residual_dp` family both call it, so the row math exists
exactly once.  Two shapes:

- **full** (``band is None`` or ``band >= W``): the whole wavefront in
  registers/VMEM — one grid step owns a block of candidates (lanes) and
  scans read rows with a fori_loop; the in-row horizontal-gap dependency
  is resolved with a Hillis–Steele running max (log2(W) vector steps)
  instead of a sequential sweep — the TPU-native version of GenDP's
  systolic wavefront.  Bit-identical to `core.dp_fallback.
  gotoh_semiglobal`.

- **banded**: only the ``K = 2*band + 1``-wide moving frame around the
  center diagonal (`core.dp_fallback.band_center`) is materialized; the
  frame slides one column right per read row (vertical moves shift the
  carried H/E vectors by one lane, the horizontal prefix max runs over K
  lanes, out-of-window frame cells are masked NEG).  ~W/K x less row work
  and state than the full shape, bit-identical to the masked oracle
  `gotoh_semiglobal_banded` on every in-band cell.

Working set (full): 2 * BLK * (W+1) * 4 B carries + BLK * (R + W) inputs;
BLK=128, R=150, W=182 ≈ 0.4 MB.  Banded at band=24: K=49, ≈ 0.11 MB.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dp_fallback import band_center
from repro.core.scoring import Scoring
from repro.kernels._util import first_index, prefix_scan

#: Name of every launch of this family: its HLO instruction name
#: (``banded_sw.N``) and its op name in a device profile.
NAME = "banded_sw"

DEFAULT_BLOCK = 128
NEG = -(1 << 20)


class DPBlockCounter:
    """Trace-time `dp_block` invocation count (see the context manager)."""

    def __init__(self) -> None:
        self.count = 0


_counter: DPBlockCounter | None = None


@contextlib.contextmanager
def count_dp_block_calls():
    """Count `dp_block` invocations traced while the context is active.

    The DP analogue of `light_align.kernel.count_align_block_calls`: both
    the `banded_sw` and `residual_dp` kernels route every Gotoh scan
    through `dp_block`, so the trace-time call count pins that the two
    families share one recurrence (a Pallas kernel body is traced once
    per launch shape regardless of grid size — per-lane *runtime* skip
    counts are the `residual_dp` op's `dp_lanes` output instead).
    Callers must ensure a fresh trace happens inside the context
    (e.g. `<op>.clear_cache()`); cached executables trace nothing.
    """
    global _counter
    prev, _counter = _counter, DPBlockCounter()
    try:
        yield _counter
    finally:
        _counter = prev


def _rotate_left(x: jnp.ndarray) -> jnp.ndarray:
    """Rotate lanes left by one: the per-row column walk of the DP
    (Mosaic lowers no value-level `dynamic_slice`)."""
    return jnp.concatenate([x[:, 1:], x[:, :1]], axis=1)


def _dp_block_full(read, win, scoring: Scoring):
    """Unbanded semiglobal Gotoh over one block (== gotoh_semiglobal)."""
    BLK, R = read.shape
    W = win.shape[1]
    match = jnp.int32(scoring.match)
    mis = jnp.int32(scoring.mismatch)
    open_ = jnp.int32(scoring.gap_open)
    ext = jnp.int32(scoring.gap_extend)
    first = open_ + ext
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (1, W + 1), 1)

    h0 = jnp.zeros((BLK, W + 1), jnp.int32)
    e0 = jnp.full((BLK, W + 1), NEG, jnp.int32)

    def row(i, carry):
        h_prev, e_prev, read_sh = carry
        read_col = read_sh[:, :1]                                  # (BLK,1)
        e = jnp.maximum(h_prev - first, e_prev - ext)
        sub = jnp.where(read_col == win, match, -mis)  # (BLK, W)
        diag = h_prev[:, :-1] + sub
        h_tmp = jnp.maximum(diag, e[:, 1:])
        col0 = -(open_ + ext * (i + 1))
        h_tmp = jnp.concatenate(
            [jnp.full((BLK, 1), 1, jnp.int32) * col0, h_tmp], -1)
        g = h_tmp + ext * j_idx
        gmax = prefix_scan(g, jnp.maximum, NEG)
        f = jnp.concatenate(
            [jnp.full((BLK, 1), NEG, jnp.int32), gmax[:, :-1]], -1
        ) - open_ - ext * j_idx
        h = jnp.maximum(h_tmp, f)
        return (h, e, _rotate_left(read_sh))

    h_last, _, _ = jax.lax.fori_loop(0, R, row, (h0, e0, read))
    score = jnp.max(h_last, axis=-1)
    ref_end = first_index(h_last == score[:, None])
    return score, ref_end


def _dp_block_banded(read, win, scoring: Scoring, band: int):
    """Moving-frame banded Gotoh: frame slot k of row i is column
    ``j = i + c - band + k`` (c the center diagonal), K = 2*band + 1."""
    BLK, R = read.shape
    W = win.shape[1]
    assert W >= R, (R, W)   # row slices never clamp: 0 <= c, R + c <= W
    c = band_center(R, W)
    K = 2 * band + 1
    match = jnp.int32(scoring.match)
    mis = jnp.int32(scoring.mismatch)
    open_ = jnp.int32(scoring.gap_open)
    ext = jnp.int32(scoring.gap_extend)
    first = open_ + ext
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    neg_col = jnp.full((BLK, 1), NEG, jnp.int32)

    # Window padded so every row's K-wide substring slice is in bounds;
    # the -1 sentinel can never equal a base code (masked cells anyway).
    # Row i compares win_pad[i + c + 1 : i + c + 1 + K]: the carry holds
    # win_pad rotated left by i + c + 1 (and the read by i), so each row
    # takes static leading slices and rotates by one lane.
    pad = jnp.full((BLK, band + 1), -1, jnp.int32)
    win_pad = jnp.concatenate([pad, win, pad], axis=1)
    win_sh = jnp.concatenate([win_pad[:, c + 1:], win_pad[:, :c + 1]], 1)

    # Row 0 frame: H[0, j] = 0 inside the window, dead outside.
    j0 = c - band + k_iota
    h0 = jnp.where((j0 >= 0) & (j0 <= W),
                   jnp.zeros((BLK, K), jnp.int32), NEG)
    e0 = jnp.full((BLK, K), NEG, jnp.int32)

    def row(i, carry):
        h_prev, e_prev, read_sh, win_sh = carry  # frame ends at i+c+band
        read_col = read_sh[:, :1]
        jcol = (i + 1 + c - band) + k_iota          # row i+1 frame columns
        # Vertical moves read the SAME column of the previous row, which
        # sits one frame slot to the left after the slide: shift in NEG
        # at the right edge (that column is out of the previous band).
        h_up = jnp.concatenate([h_prev[:, 1:], neg_col], -1)
        e_up = jnp.concatenate([e_prev[:, 1:], neg_col], -1)
        e = jnp.maximum(h_up - first, e_up - ext)
        # Diagonal moves keep the slot index; sub compares win[j-1].
        wrow = win_sh[:, :K]
        sub = jnp.where(read_col == wrow, match, -mis)
        h_tmp = jnp.maximum(h_prev + sub, e)
        col0 = -(open_ + ext * (i + 1))
        h_tmp = jnp.where(jcol == 0, col0, h_tmp)
        h_tmp = jnp.where((jcol >= 0) & (jcol <= W), h_tmp, NEG)
        # Horizontal prefix inside the frame; the per-row column offset
        # of the oracle's ext*j term is a row constant, so ext*k gives
        # the identical max.
        g = h_tmp + ext * k_iota
        gmax = prefix_scan(g, jnp.maximum, NEG)
        f = jnp.concatenate([neg_col, gmax[:, :-1]], -1) - open_ - ext * k_iota
        h = jnp.maximum(h_tmp, f)
        h = jnp.where((jcol >= 0) & (jcol <= W), h, NEG)
        return (h, e, _rotate_left(read_sh), _rotate_left(win_sh))

    h_last, _, _, _ = jax.lax.fori_loop(0, R, row,
                                        (h0, e0, read, win_sh))
    score = jnp.max(h_last, axis=-1)
    k_best = first_index(h_last == score[:, None])
    ref_end = R + c - band + k_best      # frame slot -> window column
    return score, ref_end


def dp_block(read, win, *, scoring: Scoring, band: int | None = None):
    """Semiglobal Gotoh DP over one block of alignments.

    read (BLK, R) int32, win (BLK, W) int32 -> (score (BLK,), ref_end
    (BLK,)) int32.  ``band`` restricts the DP to cells within ``band`` of
    the center diagonal (None or >= W: exact full DP).  Shared by the
    banded_sw and residual_dp Pallas kernels; bit-identical to
    `gotoh_semiglobal_banded` (and, unbanded, to `gotoh_semiglobal`).
    """
    if _counter is not None:
        _counter.count += 1
    W = win.shape[1]
    if band is None or band >= W:
        return _dp_block_full(read, win, scoring)
    return _dp_block_banded(read, win, scoring, band)


def _banded_sw_kernel(read_ref, win_ref, score_ref, end_ref, *,
                      scoring: Scoring, band: int | None):
    score, end = dp_block(read_ref[...], win_ref[...],
                          scoring=scoring, band=band)
    score_ref[...] = score[:, None]
    end_ref[...] = end[:, None]


def banded_sw_pallas(
    read: jnp.ndarray,
    win: jnp.ndarray,
    scoring: Scoring = Scoring(),
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
    band: int | None = None,
):
    """(B, R), (B, W) int32 -> (score (B,), ref_end (B,)) int32."""
    B, R = read.shape
    W = win.shape[1]
    assert B % block == 0, (B, block)
    grid = (B // block,)
    score, end = pl.pallas_call(
        functools.partial(_banded_sw_kernel, scoring=scoring, band=band),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, R), lambda i: (i, 0)),
            pl.BlockSpec((block, W), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 2,
        name=NAME,
        interpret=interpret,
    )(read, win)
    return score[:, 0], end[:, 0]
