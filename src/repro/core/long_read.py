"""The long-read lane (§4.7): long reads as interleaved pseudo-pairs.

A long read is partitioned into ``segment_len``-sized segments every
``segment_stride`` bases; consecutive segments form pseudo-pairs (in-read
distance = the stride, < Δ by construction) that reuse the paired-end
front end unchanged — Partitioned Seeding, SeedMap Query, and the
Paired-Adjacency filter with Δ widened by the stride.  Every surviving
candidate proposes a read-start diagonal (candidate position minus the
segment's in-read offset); Location Voting ([85]) bins the diagonals by
``vote_bin`` and the most-voted bin wins.  The anchor segment (segment 0)
is then DP-aligned against a reference window centered on the voted
diagonal — *banded*, with the band covering exactly the residual start
uncertainty (half a vote bin + ``max_gap`` of indel drift), not the full
window width.

The lane is staged-oracle / fused-kernel twinned like the short-read
pipeline, stage by stage:

  stage       jnp oracle (this module + core.*)   kernel family
  ---------   --------------------------------    -----------------------
  front end   seed/query each segment once,       `pair_frontend`
              pair adjacent QueryResults          (`segment_pair_frontend`)
  voting      `location_vote_ref` (sorted         `location_vote`
              multiplicities)
  diagonal    `dp_fallback.gotoh_semiglobal_      `banded_sw` (shared
  DP          banded` (moving frame)              `dp_block` recurrence)

Backends resolve through `kernels/backend.py` (``REPRO_BACKEND``
honored): the lane's `PipelineConfig.frontend_backend` /
``residual_backend`` drive the front end / DP, `LongReadConfig.
vote_backend` the vote reduction.  All three pairs are pinned
bit-identical (tests/test_location_vote.py), so `map_long_reads` returns
the same result on every backend.  The engine front door is
``Mapper.map_long`` / ``map_long_stream`` (`ExecutionConfig.long_read`);
`map_long_reads` stays as the one-shot oracle-style entry.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.dp_fallback import NEG, gotoh_semiglobal_banded
from repro.core.encoding import gather_windows_packed, ref_bases
from repro.core.light_align import gather_ref_windows
from repro.core.pair_filter import paired_adjacency_filter
from repro.core.pipeline import PipelineConfig
from repro.core.query import QueryResult, query_read_batch
from repro.core.seeding import seed_read_batch
from repro.core.seedmap import (
    INVALID_LOC,
    LinedCSRSeedMap,
    LinedSeedMap,
    PaddedSeedMap,
    SeedMap,
    frontend_layout,
)
from repro.kernels.backend import resolve_backend


@dataclasses.dataclass(frozen=True)
class LongReadConfig:
    segment_len: int = 150
    segment_stride: int = 300   # distance between pseudo-pair mates (< Δ)
    pipe: PipelineConfig = PipelineConfig()
    vote_bin: int = 64          # diagonal-vote bin width
    dp_halo: int = 64           # DP window halo around the voted diagonal
    # Half-width of the anchor-segment DP band around the window's center
    # diagonal.  None derives `vote_bin // 2 + pipe.max_gap`: the voted
    # position is known only to a bin, so the true start sits within half
    # a bin of the window center, plus max_gap of indel drift.  Any value
    # >= segment_len + 2*dp_halo recovers the exact unbanded DP; values
    # above `dp_halo` waste band on rows outside the window.
    dp_band: int | None = None
    # Backend of the `location_vote` reduction ("auto" resolves through
    # kernels/backend.py, like the pipe config's per-family backends).
    vote_backend: str = "auto"
    # Launch block for the fused vote reduction; None = the family's
    # hand-picked `DEFAULT_BLOCK` (tune-cache fillable, like the pipe
    # config's per-family `*_block` knobs).
    vote_block: int | None = None

    def band(self) -> int:
        """Resolved anchor-DP band half-width (`dp_band` or derived)."""
        if self.dp_band is not None:
            return self.dp_band
        return self.vote_bin // 2 + self.pipe.max_gap

    def n_segments(self, read_len: int) -> int:
        return (read_len - self.segment_len) // self.segment_stride + 1

    def pair_delta(self) -> int:
        """Adjacency threshold for pseudo-pairs: Δ widened by the in-read
        mate distance (consecutive segments map ``segment_stride`` apart)."""
        return self.segment_stride + self.pipe.delta


jax.tree_util.register_static(LongReadConfig)


class LongReadResult(NamedTuple):
    position: jnp.ndarray      # (B,) int32 voted read-start position
    votes: jnp.ndarray         # (B,) int32 winning vote count
    score: jnp.ndarray         # (B,) int32 banded-DP score of segment 0
    mapped: jnp.ndarray        # (B,) bool
    n_candidates: jnp.ndarray  # (B,) int32 surviving pseudo-pair candidates
    # (B,) bool: row is a real read (False for the rows `map_long_stream`
    # pads a ragged tail batch with).  Full-batch paths emit all-True.
    n_valid: jnp.ndarray


def segment_views(reads: jnp.ndarray, segment_len: int,
                  segment_stride: int) -> jnp.ndarray:
    """(B, L) -> (B, S, segment_len) windows every ``segment_stride`` bases.

    ``S`` is maximal: segment ``S-1`` still fits in the read, segment
    ``S`` would not.  A trailing remainder shorter than ``segment_len``
    is not segmented (the paper's interleaved decomposition).
    """
    L = reads.shape[-1]
    n_seg = (L - segment_len) // segment_stride + 1
    idx = (
        jnp.arange(n_seg)[:, None] * segment_stride
        + jnp.arange(segment_len)[None, :]
    )
    return reads[:, idx]


def _segments(reads: jnp.ndarray, cfg: LongReadConfig):
    """(B, L) -> ((B, S, segment_len), S) per ``cfg``'s segment geometry."""
    segs = segment_views(reads, cfg.segment_len, cfg.segment_stride)
    return segs, segs.shape[1]


def candidate_diagonals(pos1: jnp.ndarray, n_pairs: int,
                        segment_stride: int) -> jnp.ndarray:
    """Pseudo-pair candidates -> per-read diagonal rows for the vote.

    ``pos1`` is the (B*(S-1), C) INVALID_LOC-padded mate-1 candidate
    positions of the pseudo-pair front end (pair ``i`` = segments ``i``
    and ``i+1``).  Each candidate's read-start diagonal is its position
    minus the segment's in-read offset ``i * segment_stride`` — negative
    near the reference origin, which is why the vote bins with floored
    division.  Returns (B, (S-1)*C) int32, INVALID_LOC padded.
    """
    BP, C = pos1.shape
    B = BP // n_pairs
    seg_off = jnp.arange(n_pairs, dtype=jnp.int32) * segment_stride
    p = pos1.reshape(B, n_pairs, C)
    valid = p != INVALID_LOC
    diag = jnp.where(valid, p - seg_off[None, :, None], INVALID_LOC)
    return diag.reshape(B, n_pairs * C)


def _anchor_windows(ref: jnp.ndarray, position: jnp.ndarray,
                    mapped: jnp.ndarray, cfg: LongReadConfig) -> jnp.ndarray:
    """Reference windows around the voted diagonal, either ref flavor.

    The window is *centered* half a vote bin past the voted position
    (the bin's start), so the true read start — anywhere inside the bin —
    sits within ``vote_bin/2`` of the window center and the derived band
    (`cfg.band()`) covers it.  Unpacked refs clamp through the shared
    `clamp_window_starts` saturating clamp: near-origin votes (negative
    diagonals) produce the same all-``ref[0]``-padded window on every
    backend instead of diverging.
    """
    R = cfg.segment_len
    halo = cfg.dp_halo
    center = position + cfg.vote_bin // 2
    if ref.dtype == jnp.uint32:
        start = jnp.where(mapped, center, 0) - halo
        return gather_windows_packed(ref, start, R + 2 * halo)
    from repro.kernels._util import clamp_window_starts
    s = clamp_window_starts(center, mapped, ref.shape[0], R + 2 * halo, halo)
    return gather_ref_windows(ref, s, R, halo)


def map_long_impl(
    sm: SeedMap | PaddedSeedMap | LinedSeedMap | LinedCSRSeedMap,
    ref: jnp.ndarray,
    reads: jnp.ndarray,
    cfg: LongReadConfig = LongReadConfig(),
) -> LongReadResult:
    """Map long reads (B, L) uint8 (already in reference orientation).

    This is the traceable lane body — no jit, no warning — that both the
    engine's pre-built long-read step (`repro.engine.plan`) and the
    one-shot `map_long_reads` close over.  ``ref`` is the (L,) uint8 base
    array or, like the short-read pipeline, the (Lw,) uint32 2-bit
    packing, plain or as a session's `LinedRef` (the lane reads its
    ``bases``); ``sm`` the CSR `SeedMap` or any layout
    `core.pipeline.map_pairs_impl` takes.
    """
    ref = ref_bases(ref)
    p = cfg.pipe
    delta = cfg.pair_delta()
    # Stages run under `jax.named_scope`s (`lr.frontend`, `lr.vote`,
    # `lr.anchor_dp`, `assemble`), as in `core.pipeline.map_pairs_impl`.
    with jax.named_scope("lr.frontend"):
        segs, n_seg = _segments(reads, cfg)           # (B, S, R)
        B, S, R = segs.shape

        # -- front end: segments through the pseudo-pair pipeline ---------
        # Imported at call time for the same core-package circularity reason
        # as the short-read pipeline's kernel imports.
        from repro.kernels.pair_frontend.ops import segment_pair_frontend

        fe_backend = resolve_backend(p.frontend_backend,
                                     family="pair_frontend")
        if isinstance(sm, SeedMap) and fe_backend == "jnp":
            # Staged oracle: seed and query every segment ONCE (B*S flat),
            # then pair adjacent segments' sorted start lists for the Δ
            # filter — mathematically identical to running `pair_frontend`
            # over the S-1 pseudo-pairs, without re-seeding shared segments.
            flat = segs.reshape(B * S, R)
            seeds = seed_read_batch(flat, p.seed_len, p.seeds_per_read,
                                    sm.config.hash_seed)
            q = query_read_batch(sm, seeds, p.max_locs_per_seed)
            starts = q.starts.reshape(B, S, -1)
            hits = q.n_hits.reshape(B, S)
            q1 = QueryResult(starts=starts[:, :-1].reshape(B * (S - 1), -1),
                             n_hits=hits[:, :-1].reshape(-1))
            q2 = QueryResult(starts=starts[:, 1:].reshape(B * (S - 1), -1),
                             n_hits=hits[:, 1:].reshape(-1))
            cands = paired_adjacency_filter(q1, q2, delta, p.max_candidates)
            pos1, n_cand = cands.pos1, cands.n
        else:
            fe = segment_pair_frontend(
                frontend_layout(sm, p.max_locs_per_seed), reads,
                cfg.segment_len, cfg.segment_stride, p.seed_len,
                p.seeds_per_read, sm.config.hash_seed, delta, p.max_candidates,
                block=p.frontend_block, backend=fe_backend)
            pos1, n_cand = fe.pos1, fe.n

    with jax.named_scope("lr.vote"):
        # -- Location Voting (fused reduction) -----------------------------
        from repro.kernels.location_vote.ops import location_vote

        diag = candidate_diagonals(pos1, S - 1, cfg.segment_stride)
        vote = location_vote(diag, cfg.vote_bin, block=cfg.vote_block,
                             backend=cfg.vote_backend)
        votes = vote.votes
        mapped = votes > 0
        position = vote.win_bin * cfg.vote_bin

    with jax.named_scope("lr.anchor_dp"):
        # -- banded DP of the anchor segment at the voted diagonal ---------
        win = _anchor_windows(ref, position, mapped, cfg)
        band = cfg.band()
        dp_backend = resolve_backend(p.residual_backend, family="banded_sw")
        if dp_backend == "jnp":
            dp = gotoh_semiglobal_banded(segs[:, 0], win, band, p.scoring)
        else:
            from repro.kernels.banded_sw.ops import banded_sw
            dp = banded_sw(segs[:, 0], win, scoring=p.scoring, band=band,
                           backend=dp_backend)

    with jax.named_scope("assemble"):
        return LongReadResult(
            position=jnp.where(mapped, position, INVALID_LOC),
            votes=votes,
            score=jnp.where(mapped, dp.score, NEG),
            mapped=mapped,
            n_candidates=n_cand.reshape(B, S - 1).sum(-1).astype(jnp.int32),
            n_valid=jnp.ones((B,), bool),
        )


def long_stage_stat_counts(res: LongReadResult) -> dict:
    """Long-lane stage quantities as device int32 counts over valid rows.

    The lane's analogue of `core.pipeline.stage_stat_counts` — same
    device-resident accumulation contract (`engine/stats.py`
    LONG_STAT_KEYS); padded rows count toward nothing.
    """
    v = res.n_valid
    c = lambda x: jnp.sum(jnp.where(v, x, 0).astype(jnp.int32))
    return {
        "lr_no_vote": c(~res.mapped),
        "lr_mapped": c(res.mapped),
        "lr_candidates": c(res.n_candidates),
        "lr_winning_votes": c(res.votes),
        "n_reads": jnp.sum(v.astype(jnp.int32)),
    }


_jitted_map_long = jax.jit(map_long_impl, static_argnames=("cfg",))


def map_long_reads(
    sm: SeedMap | PaddedSeedMap, ref: jnp.ndarray, reads: jnp.ndarray,
    cfg: LongReadConfig = LongReadConfig(),
) -> LongReadResult:
    """One-shot long-read mapping; the session entry is `Mapper.map_long`."""
    return _jitted_map_long(sm, ref, reads, cfg)
