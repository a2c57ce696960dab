"""GenPairX sharded-index serve step: the paper's workload on the TPU mesh.

This module is the *mesh math* of the pipeline.  The front door for
running (or lowering) it is the engine API: a `repro.engine.Mapper` built
with ``ExecutionConfig(mesh=..., shard_index=True)`` shards the SeedMap
and places the packed reference once at build time and dispatches to a
pre-jitted wrapper of `make_genpair_serve_step`; `repro.engine.plan.
mesh_serve_jit` is the lowering entry the multi-pod dry-run uses.

The step itself (`--arch genpair`): SeedMap sharded by bucket range across the `model` axis
(the NMSL channel-striping analogue), read batch sharded across
(`pod`,)`data`, reference 2-bit packed and replicated, Light Alignment and
DP fallback fully data-parallel.  The post-query front end (start
conversion + sorted merge + Δ filter) runs as the fused
`kernels/pair_frontend` merge_filter op behind `cfg.frontend_backend`;
the lookup itself stays under shard_map because the tables are
bucket-sharded.

At human-genome scale (GRCh38): T = 2^30 buckets, ~3.0e9 locations,
packed reference 775 MB/device, per-device Location Table shard ~750 MB.
Positions are per-chromosome int32 offsets (as in the paper's
chromosome+offset layout); the dry-run flattens them into one coordinate
space for shape purposes (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.distributed import ShardedSeedMap, _local_query
from repro.core.encoding import BASES_PER_WORD, unpack_2bit
from repro.kernels.candidate_align.ops import candidate_pair_align
from repro.kernels.pair_frontend.ops import frontend_merge_filter
from repro.core.pipeline import (
    M_DP, M_DP_OVERFLOW, M_LIGHT, M_RESIDUAL_FULL, M_UNMAPPED, MapResult,
    PipelineConfig, _residual_dp_stage,
)
from repro.core.seeding import seed_offsets_tuple, seed_read_batch
from repro.core.seedmap import INVALID_LOC, SeedMapConfig


@dataclasses.dataclass(frozen=True)
class GenPairScale:
    """Genome-scale dimensioning for the dry-run."""

    genome_len: int = 3_000_000_000
    table_bits: int = 30
    n_locations: int = 3_000_000_000
    global_batch: int = 262_144     # read pairs per step
    read_len: int = 150


jax.tree_util.register_static(GenPairScale)


def genpair_input_specs(scale: GenPairScale, n_model_shards: int) -> dict:
    """ShapeDtypeStruct stand-ins for the genome-scale serve step."""
    T = 1 << scale.table_bits
    per = T // n_model_shards
    nmax = scale.n_locations // n_model_shards
    lw = scale.genome_len // 16 + 1
    B, R = scale.global_batch, scale.read_len
    return {
        "offsets": jax.ShapeDtypeStruct((n_model_shards, per + 1), jnp.int32),
        "locations": jax.ShapeDtypeStruct((n_model_shards, nmax), jnp.int32),
        "ref_words": jax.ShapeDtypeStruct((lw,), jnp.uint32),
        "reads1": jax.ShapeDtypeStruct((B, R), jnp.uint8),
        "reads2": jax.ShapeDtypeStruct((B, R), jnp.uint8),
    }


def genpair_shardings(mesh: Mesh, batch_axes=("data",), model_axis="model"):
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    return {
        "offsets": sh(model_axis),
        "locations": sh(model_axis),
        "ref_words": sh(),
        "reads1": sh(batch_axes),
        "reads2": sh(batch_axes),
    }


def make_genpair_serve_step(mesh: Mesh, pipe_cfg: PipelineConfig,
                            sm_cfg: SeedMapConfig,
                            batch_axes=("data",), model_axis="model"):
    """Returns serve_step(offsets, locations, ref_words, reads1, reads2).

    The whole step is one shard_map: each device queries its bucket
    shard (``pmin`` over ``model`` merges the shards' rows), then maps its
    ``batch_axes`` slice of the batch locally.  Mosaic kernels cannot be
    partitioned automatically, so the kernel backends need the local
    view; the residual DP buffer therefore holds
    ``residual_capacity_frac`` of each device's rows.
    """

    K = pipe_cfg.max_locs_per_seed

    def local_step(offsets, locations, ref_words, reads1, reads2):
        sid = jax.lax.axis_index(model_axis)

        def query(hashes):
            locs, _ = _local_query(offsets[0], locations[0], sid, hashes,
                                   sm_cfg, K)
            return jax.lax.pmin(locs, model_axis)

        cfg = pipe_cfg
        B, R = reads1.shape
        reads2_fwd = (3 - reads2)[:, ::-1]
        seeds1 = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                                 sm_cfg.hash_seed)
        seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len,
                                 cfg.seeds_per_read, sm_cfg.hash_seed)
        locs1 = query(seeds1.hashes)
        locs2 = query(seeds2.hashes)
        # Steps 2.5-3 fused (`kernels/pair_frontend`): start conversion +
        # sorted merge + Δ filter + compaction in one op.  The SeedMap
        # lookup itself stays under shard_map (tables are bucket-sharded
        # along `model`), so the serve step uses the post-query entry.
        fe = frontend_merge_filter(
            locs1, locs2,
            seed_offsets_tuple(R, cfg.seed_len, cfg.seeds_per_read),
            cfg.delta, cfg.max_candidates, block=cfg.frontend_block,
            backend=cfg.frontend_backend)
        had_hits = (fe.n_hits1 > 0) & (fe.n_hits2 > 0)
        cands = fe
        passed = cands.n > 0

        # Fused step 4: packed-window gather + G2 prescreen + Light
        # Alignment + best-pair reduction in one op (the kernel backends
        # stream 2-bit words straight from HBM, no (B, C, R+2E) tensor).
        # The serve step defaults to the packed flavor (775 MB/device at
        # genome scale); cfg.packed_ref=False forces an unpacked run for
        # flavor-parity debugging against map_pairs.  Caveat: the words
        # are the only length info here, so the debug unpack keeps the
        # final word's stored pad bases ('A') — windows within
        # BASES_PER_WORD-1 bases of the padded end clamp against those
        # pads (as the packed flavor does), not against a replicated true
        # last base as map_pairs' uint8 path would.  It also materializes
        # the full unpacked reference per step: debug scales only.
        packed = cfg.packed(default=True)
        la_ref = ref_words if packed else unpack_2bit(
            ref_words, ref_words.shape[0] * BASES_PER_WORD)
        pair = candidate_pair_align(
            la_ref, reads1, reads2_fwd, cands.pos1, cands.pos2,
            cfg.max_gap, scoring=cfg.scoring, threshold=cfg.threshold(),
            mode=cfg.light_mode, prescreen_top=cfg.prescreen(),
            packed_ref=packed, block=cfg.light_block,
            backend=cfg.light_backend)
        b_pos1, b_pos2 = pair.pos1, pair.pos2
        b_sc1, b_sc2 = pair.score1, pair.score2
        light_ok = passed & pair.ok1 & pair.ok2
        cig1, cig2 = pair.cigar1, pair.cigar2

        # fixed-capacity DP residual: the same fused single-mate-aware
        # banded `residual_dp` stage as map_pairs_impl, bit-for-bit.
        dp_sc1, dp_sc2, dp_done, dp_overflow, dp_m1, dp_m2 = \
            _residual_dp_stage(
                ref_words if packed else la_ref, reads1, reads2_fwd, pair,
                passed, light_ok, cfg, packed)
        neg = -(1 << 20)

        method = jnp.full((B,), M_UNMAPPED, jnp.int32)
        method = jnp.where(~had_hits | (had_hits & ~passed),
                           M_RESIDUAL_FULL, method)
        method = jnp.where(light_ok, M_LIGHT, method)
        method = jnp.where(dp_done, M_DP, method)
        method = jnp.where(dp_overflow, M_DP_OVERFLOW, method)
        mapped = light_ok | dp_done
        return MapResult(
            pos1=jnp.where(mapped, b_pos1, INVALID_LOC),
            pos2=jnp.where(mapped, b_pos2, INVALID_LOC),
            score1=jnp.where(light_ok, b_sc1,
                             jnp.where(dp_done, dp_sc1, neg)),
            score2=jnp.where(light_ok, b_sc2,
                             jnp.where(dp_done, dp_sc2, neg)),
            method=method, cigar1=cig1, cigar2=cig2,
            had_hits=had_hits, passed_adjacency=passed, light_ok=light_ok,
            dp_mate1=dp_m1, dp_mate2=dp_m2,
            n_valid=jnp.ones((B,), bool),
        )

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(model_axis), P(model_axis), P(), P(batch_axes),
                  P(batch_axes)),
        # Pallas outputs carry no varying-axes annotation; every result
        # derives from the ``pmin``-merged rows, identical across `model`.
        out_specs=P(batch_axes), check_vma=False)
