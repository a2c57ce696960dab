"""GenPair online pipeline (§4.1, Fig. 3): the paper's four steps end to end.

This module is the *math* of the pipeline — one jit-able function over
fixed-shape batches (`map_pairs_impl`: the front end, then
`map_candidates` for light alignment, the DP fallback and the assembly;
the sharded-index serve step runs its own front end and the same
`map_candidates`).  The way in is the session-style engine API in
`repro/engine`: ``Mapper.build(...)`` resolves the reference flavor
(2-bit packed or not), the SeedMap layout (CSR vs `PaddedSeedMap`) and
the kernel backends exactly once, then ``mapper.map`` /
``mapper.map_stream`` dispatch to a pre-jitted step built from this
module — the same code on one device and on a mesh (see docs/ENGINE.md).

Each pipeline step maps onto a kernel family (all behind the shared
backend layer, `repro/kernels/backend.py`):

  1. Partitioned Seeding   (repro.core.seeding)    -> kernels/pair_frontend
  2. SeedMap Query         (repro.core.query)      -> kernels/pair_frontend
  3. Paired-Adjacency Filtering (repro.core.pair_filter)
                                                   -> kernels/pair_frontend
  4. Light Alignment       (repro.core.light_align)-> kernels/candidate_align
  5. DP fallback           (repro.core.dp_fallback) for residual pairs
                                                   -> kernels/residual_dp

Steps 1-3 are one fused `pair_frontend` op under
``cfg.frontend_backend`` (the core modules are its bit-exact jnp
oracle); step 4 plus the best-pair reduction is one fused
`candidate_align` op under ``cfg.light_backend``; step 5 — the banded,
single-mate-aware Gotoh fallback over the compacted residual buffer — is
one fused `residual_dp` op under ``cfg.residual_backend`` (only the mate
whose Light Alignment failed is re-aligned; the passing mate keeps its
light score).  The standalone `kernels/xxhash`, `kernels/seed_gather`
and `kernels/banded_sw` families are building blocks (hashing unit, NMSL
row gather, the shared `dp_block` Gotoh recurrence) kept callable on
their own.

The whole pipeline is one jit-able function over fixed-shape batches.
Residual pairs are routed through a **fixed-capacity DP buffer**: the batch
is compacted so only `residual_capacity_frac * B` residual rows reach the
DP stage — the SPMD analogue of provisioning GenDP for the average fallback
rate (§7.4).  Overflowing pairs are flagged (hardware backpressure) rather
than silently dropped.  ``residual_capacity_frac=0`` statically removes
the whole DP stage (no gather, no DP traced) and routes every residual
row to ``M_DP_OVERFLOW``.

Method codes (MapResult.method):
  0 UNMAPPED          no candidate and no DP capacity spent
  1 LIGHT             mapped+aligned by Light Alignment
  2 DP                mapped by the filter, aligned by fallback DP
  3 RESIDUAL_FULL     no SeedMap/adjacency candidates -> full DP pipeline
  4 DP_OVERFLOW       needed DP but the residual buffer was full
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.encoding import pack_2bit, ref_bases
from repro.core.dp_fallback import NEG
from repro.core.pair_filter import CandidateSet, paired_adjacency_filter
from repro.core.query import query_read_batch
from repro.core.scoring import Scoring
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

M_UNMAPPED, M_LIGHT, M_DP, M_RESIDUAL_FULL, M_DP_OVERFLOW = 0, 1, 2, 3, 4


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    read_len: int = 150
    seed_len: int = 50
    seeds_per_read: int = 3
    max_locs_per_seed: int = 32   # K: per-seed location cap (query gather)
    delta: int = 500              # Paired-Adjacency threshold Δ
    max_candidates: int = 8       # C: candidate cap after filtering
    max_gap: int = 8              # E: Light Alignment max indel-run length
    dp_pad: int = 16              # DP fallback window halo
    light_mode: str = "minsplit"  # "paper" for the paper-faithful mechanism
    accept_threshold: int | None = None  # default: perfect - 24
    # Fraction of the batch the fixed-capacity residual DP buffer holds
    # (rows).  0 statically removes the DP stage: nothing is gathered or
    # traced, and every residual row reports M_DP_OVERFLOW.
    residual_capacity_frac: float = 0.25
    # Half-width of the residual DP band around the window's center
    # diagonal (`dp_fallback.band_center`; = dp_pad for the pipeline's
    # windows).  None derives `dp_pad + max_gap`: wide enough for any
    # alignment start inside the window plus max_gap of drift, at
    # (2*band+1)/(R+2*dp_pad) of the full DP's row work.  Any value
    # >= read_len + 2*dp_pad recovers the exact unbanded DP.
    dp_band: int | None = None
    scoring: Scoring = Scoring()
    # §Perf (genpair iteration G2, beyond-paper): rank candidate pairs by
    # their summed zero-shift Hamming distance (one XOR-compare per
    # candidate — the paper's own exact-match-first logic) and run the
    # full shifted-mask alignment only on the best `prescreen_top`.
    # 0 disables (paper-faithful baseline: align every candidate); None
    # means "unset" — same behavior as 0, but eligible for the tune
    # cache to fill in (`engine/config.py` resolution order: explicit
    # config > tune cache > defaults).
    prescreen_top: int | None = None
    # Backend for the fused candidate light-alignment op ("auto" resolves
    # to the Pallas kernel on TPU, the bit-exact jnp oracle elsewhere).
    light_backend: str = "auto"
    # Backend for the fused residual DP fallback (step 5: compacted
    # window gather + banded Gotoh of the failed mates as one
    # `residual_dp` op).  Same resolution rules; the staged
    # gather + `gotoh_semiglobal_banded` path is the "jnp" oracle.
    residual_backend: str = "auto"
    # Backend for the fused front end (steps 1-3: seeding + SeedMap query
    # + Paired-Adjacency filter as one `pair_frontend` op).  Same
    # resolution rules; the staged seeding/query/pair_filter modules are
    # the "jnp" oracle.  The kernel backends gather rows from a line
    # layout of the Location Table (`core.seedmap.frontend_layout`).
    frontend_backend: str = "auto"
    # Run the whole pipeline (candidate windows + DP fallback windows)
    # against the 2-bit packed reference: 4x less HBM window traffic, the
    # paper's SRAM encoding (§7.4).  Tri-state: None takes the plan's
    # default (`map_pairs_impl` and the replicated-index plan: unpacked;
    # the sharded-index serve plan: packed); True/False force the flavor
    # everywhere.  The two gather flavors clamp out-of-range windows
    # differently, so flips may change scores for candidates in the
    # outer E bases of the reference.
    packed_ref: bool | None = None
    # Per-family launch block sizes for the fused ops.  None resolves to
    # each family's hand-picked `DEFAULT_BLOCK` inside the op; the
    # autotuner (`repro.tune`) writes per-(backend, shape) winners into
    # the tune cache, and `engine/config.py` threads them in here at
    # `Mapper.build` time.  Pure launch geometry — bit-identical across
    # values on every backend.
    frontend_block: int | None = None   # pair_frontend / merge_filter
    light_block: int | None = None      # candidate_align
    residual_block: int | None = None   # residual_dp

    def threshold(self) -> int:
        if self.accept_threshold is not None:
            return self.accept_threshold
        return self.scoring.default_threshold(self.read_len)

    def packed(self, default: bool) -> bool:
        """Resolve the tri-state packed_ref against an entry point default."""
        return default if self.packed_ref is None else self.packed_ref

    def band(self) -> int:
        """Resolved residual-DP band half-width (`dp_band` or derived)."""
        if self.dp_band is not None:
            return self.dp_band
        return self.dp_pad + self.max_gap

    def prescreen(self) -> int:
        """Resolved prescreen_top (`None` — unset — behaves as 0/off)."""
        return self.prescreen_top or 0

    def residual_cap(self, batch: int) -> int:
        """Residual DP buffer row capacity for a ``batch``-row step.

        ``residual_capacity_frac=0`` means capacity 0 — the caller must
        statically skip the DP stage; any positive fraction provisions at
        least one row.
        """
        if self.residual_capacity_frac <= 0:
            return 0
        return max(1, int(round(batch * self.residual_capacity_frac)))


jax.tree_util.register_static(PipelineConfig)


class MapResult(NamedTuple):
    pos1: jnp.ndarray      # (B,) int32 mapped read-1 start (INVALID_LOC if not)
    pos2: jnp.ndarray      # (B,) int32 mapped read-2 window start
    score1: jnp.ndarray    # (B,) int32
    score2: jnp.ndarray    # (B,) int32
    method: jnp.ndarray    # (B,) int32 M_*
    cigar1: jnp.ndarray    # (B, 3, 2) int32 light-align CIGAR runs (M_LIGHT)
    cigar2: jnp.ndarray
    had_hits: jnp.ndarray        # (B,) bool both reads had SeedMap hits
    passed_adjacency: jnp.ndarray  # (B,) bool >=1 candidate survived Δ filter
    light_ok: jnp.ndarray          # (B,) bool light alignment accepted
    # (B,) bool per mate: this mate was re-aligned by the DP fallback
    # (its Light Alignment failed and the row won a residual-buffer
    # slot).  The single-mate-aware DP's work ledger: an M_DP row with
    # only one flag set reused the other mate's light score.
    dp_mate1: jnp.ndarray
    dp_mate2: jnp.ndarray
    # (B,) bool: row is a real pair (False for the rows `map_stream` pads a
    # ragged tail batch with).  Full-batch paths emit all-True.
    n_valid: jnp.ndarray


def stage_stat_counts(res: MapResult) -> dict:
    """Fig. 10 quantities as device int32 *counts* over the valid rows.

    The device-resident form of :func:`stage_stats`: everything stays a
    jnp scalar, so a serve loop can accumulate batch after batch with one
    tiny on-device add and fetch the totals once at the end — the
    per-batch ``float(v)`` host syncs of the pre-engine loop disappear.
    Padded rows (``n_valid`` False) count toward nothing, including
    ``n_pairs``.
    """
    v = res.n_valid
    c = lambda x: jnp.sum((x & v).astype(jnp.int32))
    return {
        "no_seed_hit": c(~res.had_hits),
        "adjacency_fail": c(res.had_hits & ~res.passed_adjacency),
        "light_align_fail": c(res.passed_adjacency & ~res.light_ok),
        "light_mapped": c(res.method == M_LIGHT),
        "dp_mapped": c(res.method == M_DP),
        "dp_overflow": c(res.method == M_DP_OVERFLOW),
        "residual_full_dp": c(res.method == M_RESIDUAL_FULL),
        # DP alignments actually run (<= 2 per DP row): the single-mate-
        # aware fallback's work ledger — (dp_mapped * 2 -
        # dp_mate_alignments) mates reused their light score.
        "dp_mate_alignments": c(res.dp_mate1) + c(res.dp_mate2),
        "n_pairs": jnp.sum(v.astype(jnp.int32)),
    }


def stage_stats(res: MapResult) -> dict:
    """Fig. 10 quantities as fractions of the (valid rows of the) batch.

    Convenience view over :func:`stage_stat_counts`; converting the values
    with ``float()`` forces a host sync each — accumulate the counts on
    device instead when looping over batches.
    """
    counts = stage_stat_counts(res)
    n = jnp.maximum(counts.pop("n_pairs"), 1)
    return {k: v / n for k, v in counts.items()}


def _best_candidate_light(
    ref,                       # (L,) u8 bases, (Lw,) u32 words or LinedRef
    reads1: jnp.ndarray,       # (B, R) mate 1, reference orientation
    reads2: jnp.ndarray,       # (B, R) mate 2, reference orientation
    cands: CandidateSet,
    cfg: PipelineConfig,
    packed: bool,
):
    """Fused step 4: gather + Light Alignment + best-pair reduction.

    One `candidate_pair_align` call replaces the per-mate window
    materialization and the post-hoc argmax/gather — the `(B, C, R+2E)`
    window tensor never reaches HBM on the kernel backends.
    """
    # Imported at call time: kernels.candidate_align depends on core
    # submodules, and `repro.core`'s package __init__ pulls in this module,
    # so a module-level import here would be circular when the kernel
    # package is imported first.
    from repro.kernels.candidate_align.ops import candidate_pair_align

    return candidate_pair_align(
        ref, reads1, reads2, cands.pos1, cands.pos2, cfg.max_gap,
        scoring=cfg.scoring, threshold=cfg.threshold(), mode=cfg.light_mode,
        prescreen_top=cfg.prescreen(), packed_ref=packed,
        block=cfg.light_block, backend=cfg.light_backend,
    )


def _residual_dp_stage(ref, reads1, reads2_fwd, pair, passed, light_ok,
                       cfg: PipelineConfig, packed: bool):
    """Step 5: the fixed-capacity, single-mate-aware banded DP fallback.

    One fused `residual_dp` call over the compacted residual rows
    replaces the staged window gather + double unbanded `gotoh_semiglobal`
    of the pre-fusion pipeline: the reference windows stream through the
    kernel (no ``(cap, R+2*dp_pad)`` tensors in HBM), the Gotoh scan is
    banded (``cfg.band()``), and only the mates whose Light Alignment
    failed are re-aligned — the passing mate of a residual row keeps its
    light score.  Part of `map_candidates`, which both plans' steps run.

    ``ref`` is whatever flavor the caller resolved (uint8 bases, or the
    2-bit packed uint32 words with ``packed=True``), plain or as a
    session's `LinedRef`.  Returns
    ``(score1, score2, dp_done, dp_overflow, dp_mate1, dp_mate2)``, all
    ``(B,)``: scores are the assembled per-row fallback scores (light
    score for passing mates, DP score for re-aligned ones; NEG
    elsewhere).

    With ``cfg.residual_capacity_frac=0`` the stage is statically absent:
    nothing is gathered, no DP launch is traced, and every ``needs_dp``
    row reports overflow.
    """
    # Imported at call time for the same core-package circularity reason
    # as the other kernel families.
    from repro.kernels.residual_dp.ops import residual_pair_dp

    B = passed.shape[0]
    needs_dp = passed & ~light_ok
    cap = cfg.residual_cap(B)
    zeros = jnp.zeros((B,), bool)
    if cap == 0:
        neg = jnp.full((B,), NEG, jnp.int32)
        return neg, neg, zeros, needs_dp, zeros, zeros

    order = jnp.argsort(~needs_dp, stable=True)
    dp_idx = order[:cap]
    dp_take = needs_dp[dp_idx]
    # Locality: re-order the selected rows by window start (mate-1
    # position) so the fused kernel's block-granular skip and the DMA
    # prefetch walk monotonically advancing reference windows instead of
    # batch order; non-taken filler rows sort last.  A pure permutation
    # of independent per-row items — WHICH rows get DP is decided above,
    # and every result scatters back through `dp_idx`, so the stage
    # stays bit-identical.
    locality = jnp.argsort(
        jnp.where(dp_take, pair.pos1[dp_idx],
                  jnp.iinfo(jnp.int32).max), stable=True)
    dp_idx = dp_idx[locality]
    dp_take = dp_take[locality]
    need1 = dp_take & ~pair.ok1[dp_idx]
    need2 = dp_take & ~pair.ok2[dp_idx]
    dp = residual_pair_dp(
        ref, reads1[dp_idx], reads2_fwd[dp_idx],
        pair.pos1[dp_idx], pair.pos2[dp_idx], need1, need2,
        cfg.dp_pad, band=cfg.band(), scoring=cfg.scoring,
        packed_ref=packed, block=cfg.residual_block,
        backend=cfg.residual_backend)
    # The passing mate of a re-aligned row reuses its light score.
    sc1 = jnp.where(need1, dp.score1, pair.score1[dp_idx])
    sc2 = jnp.where(need2, dp.score2, pair.score2[dp_idx])
    dp_sc1 = jnp.full((B,), NEG, jnp.int32).at[dp_idx].set(
        jnp.where(dp_take, sc1, NEG))
    dp_sc2 = jnp.full((B,), NEG, jnp.int32).at[dp_idx].set(
        jnp.where(dp_take, sc2, NEG))
    dp_done = zeros.at[dp_idx].set(dp_take)
    dp_overflow = needs_dp & ~dp_done
    dp_mate1 = zeros.at[dp_idx].set(need1)
    dp_mate2 = zeros.at[dp_idx].set(need2)
    return dp_sc1, dp_sc2, dp_done, dp_overflow, dp_mate1, dp_mate2


def map_candidates(
    ref,                       # (L,) u8 bases, (Lw,) u32 words or LinedRef
    reads1: jnp.ndarray,       # (B, R) mate 1, reference orientation
    reads2_fwd: jnp.ndarray,   # (B, R) mate 2, reference orientation
    had_hits: jnp.ndarray,     # (B,) bool both reads had SeedMap hits
    cands: CandidateSet,
    cfg: PipelineConfig,
    packed: bool,
) -> MapResult:
    """Steps 4-5 and the assembly: a batch's `MapResult` from its
    candidate set.

    The one body behind both plans' steps: `map_pairs_impl` (replicated
    index) and the sharded-index serve step (`core.genpairx_step`) each
    run their own front end and then this.  ``packed`` is the resolved
    reference flavor; a uint8 ``ref`` with ``packed=True`` is packed
    here, in every call.
    """
    B = reads1.shape[0]
    # `passed` is the front end's verdict: under its scope, the per-stage
    # device metrics count it with the front end on both plans.
    with jax.named_scope("frontend"):
        passed = cands.n > 0          # >= 1 candidate survived the Δ filter

    with jax.named_scope("light_align"):
        # -- 4. Light Alignment over candidates (fused kernel) -----------
        # With packed_ref both the candidate windows and the DP fallback
        # windows gather from the 2-bit packed reference (4x less HBM window
        # traffic).  Callers that already hold the packed words (uint32)
        # should pass them directly — packing a uint8 ref in here costs a
        # full reference read per jitted call, which at genome scale
        # dwarfs the window-DMA saving.
        if packed and ref.dtype != jnp.uint32:
            ref = pack_2bit(ref_bases(ref))
        pair = _best_candidate_light(ref, reads1, reads2_fwd, cands, cfg,
                                     packed)
        light_ok = passed & pair.ok1 & pair.ok2

    with jax.named_scope("residual_dp"):
        # -- 5. DP fallback on the fixed-capacity residual buffer --------
        # One fused `residual_dp` op (cfg.residual_backend): compacted window
        # gather + banded Gotoh of exactly the failed mates.
        (dp_sc1, dp_sc2, dp_done, dp_overflow, dp_m1,
         dp_m2) = _residual_dp_stage(ref, reads1, reads2_fwd, pair, passed,
                                     light_ok, cfg, packed)

    with jax.named_scope("assemble"):
        method = jnp.full((B,), M_UNMAPPED, jnp.int32)
        method = jnp.where(~had_hits, M_RESIDUAL_FULL, method)
        method = jnp.where(had_hits & ~passed, M_RESIDUAL_FULL, method)
        method = jnp.where(light_ok, M_LIGHT, method)
        method = jnp.where(dp_done, M_DP, method)
        method = jnp.where(dp_overflow, M_DP_OVERFLOW, method)

        mapped = light_ok | dp_done
        pos1 = jnp.where(mapped, pair.pos1, INVALID_LOC)
        pos2 = jnp.where(mapped, pair.pos2, INVALID_LOC)
        score1 = jnp.where(light_ok, pair.score1,
                           jnp.where(dp_done, dp_sc1, NEG))
        score2 = jnp.where(light_ok, pair.score2,
                           jnp.where(dp_done, dp_sc2, NEG))
        return MapResult(
            pos1=pos1, pos2=pos2, score1=score1, score2=score2,
            method=method, cigar1=pair.cigar1, cigar2=pair.cigar2,
            had_hits=had_hits, passed_adjacency=passed, light_ok=light_ok,
            dp_mate1=dp_m1, dp_mate2=dp_m2, n_valid=jnp.ones((B,), bool),
        )


def map_pairs_impl(
    sm: SeedMap | PaddedSeedMap | LinedSeedMap | LinedCSRSeedMap,
    ref: jnp.ndarray,
    reads1: jnp.ndarray,
    reads2: jnp.ndarray,
    cfg: PipelineConfig = PipelineConfig(),
) -> MapResult:
    """Map a batch of FR read pairs. reads2 is as-sequenced (reverse strand).

    This is the traceable pipeline body — no jit — that the engine's
    replicated-index steps (`repro.engine.plan`) close over: the front
    end, then `map_candidates`.  Callers that want it jitted on its own
    use ``jax.jit(map_pairs_impl, static_argnames=("cfg",))``.

    ``ref`` is the (L,) uint8 base array; with ``cfg.packed_ref=True`` it
    may instead be the (Lw,) uint32 2-bit packing (`pack_2bit`), which
    skips the in-step repack.  Either may come as a session's `LinedRef`
    (what a kernel-backend `Mapper` holds), whose aligner layout was
    built once; it is passed to the aligners unchanged.

    ``sm`` is the CSR `SeedMap`, the kernel-layout `PaddedSeedMap`
    (`to_padded`) or a device line layout a kernel-backend session holds
    (`LinedCSRSeedMap` or `LinedSeedMap`).  The kernel front-end
    backends gather rows from a line layout; a CSR map is cut into lines
    in-jit (`frontend_layout`).  A padded or lined layout's row width
    caps locations per seed, superseding ``cfg.max_locs_per_seed``.
    """
    R = reads1.shape[1]
    assert R == cfg.read_len, (R, cfg.read_len)
    # Each stage runs under a `jax.named_scope` (`frontend`,
    # `light_align`, `residual_dp`, `assemble`), so the device ops it
    # compiles to carry the stage in their op_name metadata, which a
    # profile reports per op (docs/ENGINE.md, "Tracing").
    with jax.named_scope("frontend"):
        reads2_fwd = (3 - reads2)[:, ::-1]  # reference orientation (revcomp)

        # -- 1-3. Front end: seeding + SeedMap query + adjacency filter ---
        # One fused `pair_frontend` op (kernel backends: the (B, S, K)
        # location tensor and the (B, S*K) sorted start lists stay in VMEM).
        # The staged core modules remain the bit-exact jnp path.  Imported at
        # call time for the same core-package circularity reason as the
        # candidate_align import in `_best_candidate_light`.
        from repro.kernels.pair_frontend.ops import pair_frontend

        fe_backend = resolve_backend(cfg.frontend_backend,
                                     family="pair_frontend")
        if isinstance(sm, SeedMap) and fe_backend == "jnp":
            seeds1 = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                                     sm.config.hash_seed)
            seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len,
                                     cfg.seeds_per_read, sm.config.hash_seed)
            q1 = query_read_batch(sm, seeds1, cfg.max_locs_per_seed)
            q2 = query_read_batch(sm, seeds2, cfg.max_locs_per_seed)
            had_hits = (q1.n_hits > 0) & (q2.n_hits > 0)
            cands: CandidateSet = paired_adjacency_filter(
                q1, q2, cfg.delta, cfg.max_candidates
            )
        else:
            fe = pair_frontend(
                frontend_layout(sm, cfg.max_locs_per_seed), reads1,
                reads2_fwd, cfg.seed_len, cfg.seeds_per_read,
                sm.config.hash_seed, cfg.delta, cfg.max_candidates,
                block=cfg.frontend_block, backend=fe_backend)
            had_hits = (fe.n_hits1 > 0) & (fe.n_hits2 > 0)
            cands = CandidateSet(pos1=fe.pos1, pos2=fe.pos2, n=fe.n)

    return map_candidates(ref, reads1, reads2_fwd, had_hits, cands, cfg,
                          cfg.packed(default=False))
