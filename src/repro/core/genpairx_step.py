"""GenPairX sharded-index serve step: the paper's workload on the TPU mesh.

This module is the *mesh math* of the pipeline.  The way in is the
engine API: a `repro.engine.Mapper` built with
``ExecutionConfig(mesh=..., shard_index=True)`` shards the SeedMap and
places the reference (in the flavor the session resolved) once at build
time and dispatches to a pre-jitted wrapper of `make_genpair_serve_step`.

The step: SeedMap sharded by bucket range across the `model` axis (the
NMSL channel-striping analogue), read batch sharded across the batch
axes, reference replicated, Light Alignment and DP fallback
fully data-parallel.  Only the front end differs from the replicated
plan: the lookup stays under shard_map because the tables are
bucket-sharded (`sharded_lookup`), and the post-query half (start
conversion + sorted merge + Δ filter) runs as the fused
`kernels/pair_frontend` merge_filter op behind `cfg.frontend_backend`
(`sharded_frontend`).  Steps 4-5 and the assembly are
`core.pipeline.map_candidates`, the replicated plan's own.

At human-genome scale (GRCh38): T = 2^30 buckets, ~3.0e9 locations,
packed reference 775 MB/device, per-device Location Table shard ~750 MB.
Positions are per-chromosome int32 offsets (as in the paper's
chromosome+offset layout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distributed import _local_query
from repro.core.pair_filter import CandidateSet
from repro.core.pipeline import MapResult, PipelineConfig, map_candidates
from repro.core.seeding import seed_offsets_tuple, seed_read_batch
from repro.core.seedmap import SeedMapConfig
from repro.kernels.pair_frontend.ops import frontend_merge_filter


def sharded_lookup(offsets, locations, hashes, sm_cfg: SeedMapConfig,
                   K: int, model_axis: str = "model") -> jnp.ndarray:
    """Bucket-sharded SeedMap lookup, per device under shard_map.

    ``offsets`` / ``locations`` are this device's (1, ...) block of the
    `ShardedSeedMap` tables; each device answers for the buckets it owns
    and INVALID_LOC elsewhere, so ``pmin`` over ``model_axis`` (INVALID_LOC
    is int32-max) merges the owners' rows.  Returns (..., K) int32.
    """
    sid = jax.lax.axis_index(model_axis)
    locs, _ = _local_query(offsets[0], locations[0], sid, hashes, sm_cfg, K)
    return jax.lax.pmin(locs, model_axis)


def sharded_frontend(offsets, locations, reads1, reads2_fwd,
                     cfg: PipelineConfig, sm_cfg: SeedMapConfig,
                     model_axis: str = "model"):
    """Steps 1-3 of the sharded-index plan, per device under shard_map.

    Seeding, `sharded_lookup` of both mates, then the fused merge/filter
    half of `kernels/pair_frontend` (start conversion + sorted merge + Δ
    filter + compaction in one op).  Both reads in reference
    orientation; returns the op's `FrontendResult`.
    """
    R = reads1.shape[1]
    K = cfg.max_locs_per_seed
    seeds1 = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                             sm_cfg.hash_seed)
    seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len, cfg.seeds_per_read,
                             sm_cfg.hash_seed)
    locs1 = sharded_lookup(offsets, locations, seeds1.hashes, sm_cfg, K,
                           model_axis)
    locs2 = sharded_lookup(offsets, locations, seeds2.hashes, sm_cfg, K,
                           model_axis)
    return frontend_merge_filter(
        locs1, locs2, seed_offsets_tuple(R, cfg.seed_len, cfg.seeds_per_read),
        cfg.delta, cfg.max_candidates, block=cfg.frontend_block,
        backend=cfg.frontend_backend)


def make_genpair_serve_step(mesh: Mesh, pipe_cfg: PipelineConfig,
                            sm_cfg: SeedMapConfig,
                            batch_axes=("data",), model_axis="model"):
    """Returns serve_step(offsets, locations, ref, reads1, reads2).

    The whole step is one shard_map: each device runs `sharded_frontend`
    on its ``batch_axes`` slice of the batch, then `map_candidates`.
    ``ref`` is replicated, in the flavor ``pipe_cfg.packed_ref`` names
    (packed uint32 words by default, else uint8 bases).  Mosaic kernels
    cannot be partitioned automatically, so the kernel backends need the
    local view; the residual DP buffer therefore holds
    ``residual_capacity_frac`` of each device's rows.
    """
    packed = pipe_cfg.packed(default=True)

    def local_step(offsets, locations, ref, reads1, reads2) -> MapResult:
        with jax.named_scope("frontend"):
            reads2_fwd = (3 - reads2)[:, ::-1]
            fe = sharded_frontend(offsets, locations, reads1, reads2_fwd,
                                  pipe_cfg, sm_cfg, model_axis)
            had_hits = (fe.n_hits1 > 0) & (fe.n_hits2 > 0)
            cands = CandidateSet(pos1=fe.pos1, pos2=fe.pos2, n=fe.n)
        return map_candidates(ref, reads1, reads2_fwd, had_hits, cands,
                              pipe_cfg, packed)

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(model_axis), P(model_axis), P(), P(batch_axes),
                  P(batch_axes)),
        # Pallas outputs carry no varying-axes annotation; every result
        # derives from the ``pmin``-merged rows, identical across `model`.
        out_specs=P(batch_axes), check_vma=False)
