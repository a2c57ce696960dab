"""Pallas TPU kernel: Light Alignment (§4.6 / §5.4), vectorized XOR unit.

One grid step aligns a block of candidates: lanes = candidates, sublanes =
base positions.  All 2E+1 shifted mismatch masks are built with static
slices + vector compares ("all Hamming masks in a single clock cycle"), the
per-shift optimal split is found with two prefix sums (generalized
min-split, DESIGN.md §3), and the winning hypothesis is reduced in-register.
Working set per block: O(BLK * (2E+1) * R * 4 B) — BLK=128, E=8, R=150
≈ 1.3 MB, comfortably inside VMEM.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.scoring import Scoring
from repro.kernels._util import first_index, prefix_scan

DEFAULT_BLOCK = 128
BIG = 1 << 20


class AlignBlockCounter:
    """Trace-time `align_block` invocation count (see the context manager)."""

    def __init__(self) -> None:
        self.count = 0


_counter: AlignBlockCounter | None = None


@contextlib.contextmanager
def count_align_block_calls():
    """Count `align_block` invocations traced while the context is active.

    `align_block` is unrolled statically inside the kernels (one call per
    candidate per mate), so the trace-time call count IS the per-row
    alignment work: with the candidate prescreen enabled the fused
    candidate_align kernel must trace `prescreen_top` calls per mate, not
    `C`.  Interpret-mode tests use this to prove the G2 compute saving is
    real skipped work, not just a masked reduction.  Callers must ensure a
    fresh trace happens inside the context (e.g. `jit.clear_cache()`);
    cached executables trace nothing and count zero.
    """
    global _counter
    prev, _counter = _counter, AlignBlockCounter()
    try:
        yield _counter
    finally:
        _counter = prev


def align_block(read, win, *, E: int, scoring: Scoring, mode: str):
    """Pure shifted-mask Light Alignment over one block of candidates.

    read (BLK, R) int32, win (BLK, R+2E) int32 -> six (BLK,) int32 arrays:
    (score, edit_type, edit_len, edit_pos, n_mismatch, mm_zero_shift).
    The last is the 0-shift Hamming distance, exposed for the candidate
    prescreen (candidate_align kernel); the rest match LightAlignResult.
    Shared by the light_align and candidate_align Pallas kernels.
    """
    if _counter is not None:
        _counter.count += 1
    BLK, R = read.shape
    m2 = scoring.match + scoring.mismatch

    # Hamming masks for every shift, as int32 mismatch indicators, and
    # their exclusive prefix sums (each (BLK, R+1)).
    cum = [prefix_scan(jnp.concatenate(
        [jnp.zeros((BLK, 1), jnp.int32),
         (win[:, s : s + R] != read).astype(jnp.int32)], axis=-1),
        jnp.add, 0) for s in range(2 * E + 1)]
    cum0 = cum[E]
    p_range = jax.lax.broadcasted_iota(jnp.int32, (1, R + 1), 1)

    mm_none = cum0[:, R]
    best_score = scoring.match * R - m2 * mm_none
    best_type = jnp.zeros((BLK,), jnp.int32)       # EDIT_NONE
    best_len = jnp.zeros((BLK,), jnp.int32)
    best_pos = jnp.zeros((BLK,), jnp.int32)
    best_mm = mm_none

    def consider(score, etype, elen, epos, emm):
        nonlocal best_score, best_type, best_len, best_pos, best_mm
        better = score > best_score
        best_type = jnp.where(better, etype, best_type)
        best_len = jnp.where(better, elen, best_len)
        best_pos = jnp.where(better, epos, best_pos)
        best_mm = jnp.where(better, emm, best_mm)
        best_score = jnp.where(better, score, best_score)

    for k in range(1, E + 1):
        # deletion of k: suffix at shift +k
        cum_d = cum[E + k]
        cand = cum0 + (cum_d[:, R:R + 1] - cum_d)
        interior = (p_range >= 1) & (p_range <= R - 1)
        cand = jnp.where(interior, cand, BIG)
        if mode == "paper":
            cand = jnp.where(cand == 0, cand, BIG)
        mm_d = jnp.min(cand, axis=-1)
        p_d = first_index(cand == mm_d[:, None])
        sc_d = scoring.match * R - m2 * mm_d - (
            scoring.gap_open + scoring.gap_extend * k)
        sc_d = jnp.where(mm_d >= BIG, -BIG, sc_d)
        consider(sc_d, jnp.full((BLK,), 2, jnp.int32),
                 jnp.full((BLK,), k, jnp.int32), p_d, mm_d)

        # insertion of k: suffix at shift -k, suffix cut at p + k
        cum_i = cum[E - k]
        shifted = jnp.concatenate(
            [cum_i[:, k:], jnp.zeros((BLK, k), jnp.int32)], axis=-1)
        cand = cum0 + (cum_i[:, R:R + 1] - shifted)
        interior = (p_range >= 1) & (p_range <= R - k - 1)
        cand = jnp.where(interior, cand, BIG)
        if mode == "paper":
            cand = jnp.where(cand == 0, cand, BIG)
        mm_i = jnp.min(cand, axis=-1)
        p_i = first_index(cand == mm_i[:, None])
        sc_i = scoring.match * (R - k) - m2 * mm_i - (
            scoring.gap_open + scoring.gap_extend * k)
        sc_i = jnp.where(mm_i >= BIG, -BIG, sc_i)
        consider(sc_i, jnp.full((BLK,), 1, jnp.int32),
                 jnp.full((BLK,), k, jnp.int32), p_i, mm_i)

    return best_score, best_type, best_len, best_pos, best_mm, mm_none


def _light_align_kernel(
    read_ref, win_ref, score_ref, type_ref, len_ref, pos_ref, mm_ref,
    *, E: int, scoring: Scoring, threshold: int, mode: str,
):
    del threshold  # `ok` is derived outside the kernel
    score, etype, elen, epos, mm, _ = align_block(
        read_ref[...], win_ref[...], E=E, scoring=scoring, mode=mode)
    score_ref[...] = score[:, None]
    type_ref[...] = etype[:, None]
    len_ref[...] = elen[:, None]
    pos_ref[...] = epos[:, None]
    mm_ref[...] = mm[:, None]


def light_align_pallas(
    read: jnp.ndarray,
    refwin: jnp.ndarray,
    max_gap: int,
    scoring: Scoring = Scoring(),
    threshold: int | None = None,
    mode: str = "minsplit",
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """(B, R), (B, R+2E) int32 -> 5 arrays (B,) int32.

    B must be a multiple of `block` (ops.py pads).  Returns
    (score, edit_type, edit_len, edit_pos, n_mismatch).
    """
    B, R = read.shape
    E = max_gap
    assert refwin.shape == (B, R + 2 * E)
    assert B % block == 0, (B, block)
    if threshold is None:
        threshold = scoring.default_threshold(R)
    grid = (B // block,)
    outs = pl.pallas_call(
        functools.partial(
            _light_align_kernel, E=E, scoring=scoring,
            threshold=threshold, mode=mode,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, R), lambda i: (i, 0)),
            pl.BlockSpec((block, R + 2 * E), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0))] * 5,
        out_shape=[jax.ShapeDtypeStruct((B, 1), jnp.int32)] * 5,
        interpret=interpret,
    )(read, refwin)
    return tuple(o[:, 0] for o in outs)
