"""Pure-jnp oracle for the fused candidate light-alignment op.

This is the *unfused* step-4 hot path exactly as `core/pipeline.py` and
`core/genpairx_step.py` wrote it out before the fusion: materialize every
`(B, C, R+2E)` candidate reference window, light-align all `B*C`
(read, window) rows per mate, mask invalid candidates, and argmax the
summed pair score.  The Pallas kernel (`kernel.py`) must match this
bit-for-bit; the pipeline's results are pinned against it.  With
``0 < prescreen_top < C`` both paths align only the top-P candidate
pairs ranked by summed zero-shift Hamming distance: here via
``lax.top_k`` + ``take_along_axis`` over the materialized windows, in
the kernel via a stable-rank one-hot gather in VMEM — the interpret-mode
instrumentation test (`count_align_block_calls`) pins that parity.

Two window-gather flavors, preserved verbatim from the two call sites:

- ``packed_ref=False``: ``ref`` is an unpacked ``(L,)`` uint8 base array;
  invalid starts are replaced by 0 and the gather clamps per element
  (`core.light_align.gather_ref_windows`).
- ``packed_ref=True``: ``ref`` is a 2-bit packed ``(Lw,)`` uint32 word
  array; window starts are ``pos - E`` (clamped as a scalar) and bases are
  unpacked on the fly (`core.encoding.gather_windows_packed`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.encoding import gather_windows_packed
from repro.core.light_align import (
    LightAlignResult,
    cigar_ops,
    gather_ref_windows,
    light_align,
)
from repro.core.scoring import Scoring
from repro.core.seedmap import INVALID_LOC

NEG_BIG = -(1 << 20)   # masked-candidate score sentinel (matches pipeline)
MM_BIG = 1 << 20       # masked-candidate Hamming sentinel (prescreen)


class PairAlignResult(NamedTuple):
    """Best-candidate Light Alignment for a batch of read pairs.

    All fields are per-row reductions over the (B, C) candidate set; the
    `(B, C, R+2E)` window tensor never escapes the op.
    """

    best: jnp.ndarray    # (B,) int32 winner index in post-prescreen order
    slot: jnp.ndarray    # (B,) int32 winner's original candidate slot
    pos1: jnp.ndarray    # (B,) int32 winning candidate start (mate 1)
    pos2: jnp.ndarray    # (B,) int32 winning candidate start (mate 2)
    score1: jnp.ndarray  # (B,) int32 masked score (NEG_BIG if invalid slot)
    score2: jnp.ndarray  # (B,) int32
    ok1: jnp.ndarray     # (B,) bool  score >= threshold and slot valid
    ok2: jnp.ndarray     # (B,) bool
    cigar1: jnp.ndarray  # (B, 3, 2) int32 light-align CIGAR runs
    cigar2: jnp.ndarray  # (B, 3, 2) int32


def _gather(ref, pos, valid, read_len, max_gap, packed_ref):
    if packed_ref:
        safe = jnp.where(valid, pos - max_gap, 0)
        return gather_windows_packed(ref, safe, read_len + 2 * max_gap)
    safe = jnp.where(valid, pos, 0)
    return gather_ref_windows(ref, safe, read_len, max_gap)


def candidate_pair_align_ref(
    ref: jnp.ndarray,
    reads1: jnp.ndarray,     # (B, R) mate 1, reference orientation
    reads2: jnp.ndarray,     # (B, R) mate 2, reference orientation (revcomp'd)
    pos1: jnp.ndarray,       # (B, C) candidate starts, INVALID_LOC padded
    pos2: jnp.ndarray,       # (B, C)
    max_gap: int,
    scoring: Scoring = Scoring(),
    threshold: int | None = None,
    mode: str = "minsplit",
    prescreen_top: int = 0,
    packed_ref: bool = False,
) -> PairAlignResult:
    B, R = reads1.shape
    C = pos1.shape[1]
    E = max_gap
    if threshold is None:
        threshold = scoring.default_threshold(R)

    valid1 = pos1 != INVALID_LOC
    valid2 = pos2 != INVALID_LOC
    wins1 = _gather(ref, pos1, valid1, R, E, packed_ref)  # (B, C, R+2E)
    wins2 = _gather(ref, pos2, valid2, R, E, packed_ref)

    pos1s, pos2s = pos1, pos2
    if 0 < prescreen_top < C:
        # §Perf G2: one zero-shift Hamming count per candidate *pair* (the
        # XOR compare the paper's hardware does in one cycle), then full
        # shifted-mask alignment only on the top P pairs, mates ranked
        # jointly so pairing is preserved.
        mm0 = (jnp.sum(wins1[..., E:E + R] != reads1[:, None, :], -1)
               + jnp.sum(wins2[..., E:E + R]
                         != reads2[:, None, :], -1)).astype(jnp.int32)
        mm0 = jnp.where(valid1 & valid2, mm0, MM_BIG)
        _, top = jax.lax.top_k(-mm0, prescreen_top)      # (B, P)
        wins1 = jnp.take_along_axis(wins1, top[..., None], 1)
        wins2 = jnp.take_along_axis(wins2, top[..., None], 1)
        pos1s = jnp.take_along_axis(pos1, top, 1)
        pos2s = jnp.take_along_axis(pos2, top, 1)
        valid1 = jnp.take_along_axis(valid1, top, 1)
        valid2 = jnp.take_along_axis(valid2, top, 1)
        slots = top.astype(jnp.int32)
    else:
        slots = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None, :],
                                 (B, C))
    P = pos1s.shape[1]

    def run_light(reads, wins, valid):
        res = light_align(
            jnp.broadcast_to(reads[:, None], (B, P, R)).reshape(B * P, R),
            wins.reshape(B * P, -1), E, scoring, threshold, mode)
        sc = jnp.where(valid.reshape(-1), res.score, NEG_BIG).reshape(B, P)
        return res, sc

    res1, sc1 = run_light(reads1, wins1, valid1)
    res2, sc2 = run_light(reads2, wins2, valid2)
    best = jnp.argmax(sc1 + sc2, axis=-1).astype(jnp.int32)  # (B,)

    def take(x):
        x = x.reshape((B, P) + x.shape[1:])
        return jnp.take_along_axis(
            x, best.reshape((B, 1) + (1,) * (x.ndim - 2)), axis=1)[:, 0]

    b_pos1 = jnp.take_along_axis(pos1s, best[:, None], 1)[:, 0]
    b_pos2 = jnp.take_along_axis(pos2s, best[:, None], 1)[:, 0]
    return PairAlignResult(
        best=best,
        slot=jnp.take_along_axis(slots, best[:, None], 1)[:, 0],
        pos1=b_pos1,
        pos2=b_pos2,
        score1=jnp.take_along_axis(sc1, best[:, None], 1)[:, 0],
        score2=jnp.take_along_axis(sc2, best[:, None], 1)[:, 0],
        ok1=take(res1.ok) & (b_pos1 != INVALID_LOC),
        ok2=take(res2.ok) & (b_pos2 != INVALID_LOC),
        cigar1=take(cigar_ops(res1, R)),
        cigar2=take(cigar_ops(res2, R)),
    )


def best_fields_to_cigars(etype, elen, epos, read_len):
    """(B,) edit fields -> (B, 3, 2) CIGAR runs (kernel-path helper)."""
    zeros = jnp.zeros_like(etype)
    res = LightAlignResult(score=zeros, ok=zeros.astype(bool),
                           edit_type=etype, edit_len=elen, edit_pos=epos,
                           n_mismatch=zeros)
    return cigar_ops(res, read_len)
