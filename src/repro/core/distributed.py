"""Distributed GenPairX: the NMSL analogue on a TPU mesh (DESIGN.md §2).

The paper's NMSL stripes the Seed/Location tables across HBM channels and
keeps every channel busy (§5.2).  On a TPU mesh the "channels" are the HBM
stacks of the devices along the `model` axis: we shard both tables by
bucket range, replicate each data-shard's (tiny, 4 B/seed) hash queries
along `model`, let every device answer for the buckets it owns, and combine
with a single `pmin`/`psum` pair (INVALID_LOC is int32-max, so an
elementwise min across the model axis selects the owning device's answer).

Communication per seed: K * 4 B of locations reduced across the model axis
— the analogue of the paper's centralized-buffer traffic.  The batch is
sharded along (`pod`, `data`); the reference and tables along `model`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import warn_deprecated
from repro.core.pipeline import PipelineConfig
from repro.core.query import QueryResult, merge_read_starts
from repro.core.seedmap import INVALID_LOC, SeedMap, SeedMapConfig


class ShardedSeedMap(NamedTuple):
    """SeedMap sharded by bucket range along the `model` axis.

    offsets:   int32[D, T/D + 1]  per-shard CSR offsets (local, rebased)
    locations: int32[D, Nmax]     per-shard locations (INVALID_LOC padded)
    config:    SeedMapConfig
    """

    offsets: jnp.ndarray
    locations: jnp.ndarray
    config: SeedMapConfig

    @property
    def n_shards(self) -> int:
        return self.offsets.shape[0]


def shard_seedmap(sm: SeedMap, n_shards: int) -> ShardedSeedMap:
    """Split a CSR SeedMap into `n_shards` bucket-range shards (host
    arrays in and out)."""
    T = sm.config.table_size
    if T % n_shards:
        raise ValueError("table_size must divide by shard count")
    per = T // n_shards
    offsets = np.asarray(sm.offsets)
    locations = np.asarray(sm.locations)
    shard_off = []
    shard_loc = []
    for d in range(n_shards):
        o = offsets[d * per : (d + 1) * per + 1].astype(np.int64)
        base = o[0]
        shard_off.append((o - base).astype(np.int32))
        shard_loc.append(locations[o[0] : o[-1]])
    nmax = max(len(l) for l in shard_loc)
    nmax = max(nmax, 1)
    loc = np.full((n_shards, nmax), INVALID_LOC, np.int32)
    for d, l in enumerate(shard_loc):
        loc[d, : len(l)] = l
    # Host arrays: the session's placement puts shard d on its own
    # model-axis devices, and no full copy stays on the default device.
    return ShardedSeedMap(offsets=np.stack(shard_off), locations=loc,
                          config=sm.config)


def _local_query(offsets, locations, shard_id, hashes, cfg: SeedMapConfig, K: int):
    """Per-device bucket-range query: INVALID for buckets we don't own."""
    T = cfg.table_size
    per = offsets.shape[-1] - 1
    bucket = (hashes & jnp.uint32(T - 1)).astype(jnp.int32)
    local_b = bucket - shard_id * per
    owned = (local_b >= 0) & (local_b < per)
    lb = jnp.clip(local_b, 0, per - 1)
    start = offsets[lb]
    end = offsets[lb + 1]
    count = jnp.where(owned, jnp.minimum(end - start, K), 0)
    idx = start[..., None] + jnp.arange(K, dtype=jnp.int32)
    valid = jnp.arange(K, dtype=jnp.int32) < count[..., None]
    locs = locations[jnp.clip(idx, 0, locations.shape[0] - 1)]
    locs = jnp.where(valid, locs, INVALID_LOC)
    return locs, count


def make_sharded_locs(mesh: Mesh, model_axis: str = "model",
                      batch_axes=("data",)):
    """Build the raw shard_map'd SeedMap lookup over `mesh`.

    Returns locs_fn(ssm, hashes (B, S) u32, K) -> (B, S, K) int32
    locations (INVALID_LOC padded): tables sharded along `model_axis`,
    batch along `batch_axes`, result sharded along the batch axes and
    replicated along model.  This is the un-merged half that both
    `make_sharded_query` and the fused front end build on.
    """

    def _inner(offsets, locations, hashes, K, cfg):
        shard_id = jax.lax.axis_index(model_axis)
        locs, _ = _local_query(offsets[0], locations[0], shard_id, hashes,
                               cfg, K)
        # Owner selection: INVALID_LOC is int-max, so pmin picks the owner's
        # values (every non-owner reports INVALID).
        locs = jax.lax.pmin(locs, model_axis)
        return locs

    def locs_fn(ssm: ShardedSeedMap, hashes: jnp.ndarray,
                K: int) -> jnp.ndarray:
        batch_spec = P(batch_axes)
        fn = jax.shard_map(
            functools.partial(_inner, K=K, cfg=ssm.config),
            mesh=mesh,
            in_specs=(P(model_axis), P(model_axis), batch_spec),
            out_specs=batch_spec,
        )
        return fn(ssm.offsets, ssm.locations, hashes)

    return locs_fn


def make_sharded_query(mesh: Mesh, model_axis: str = "model",
                       batch_axes=("data",)):
    """Deprecated: a `repro.engine.Mapper` with ``shard_index=True`` owns
    the sharded lookup now (this factory's math lives on in its plan).

    Returns query_fn(ssm: ShardedSeedMap, hashes (B, S) u32, seed_offsets,
    K) -> QueryResult with starts (B, S*K).  Tables are sharded along
    `model_axis`; the batch along `batch_axes`; results end up sharded along
    the batch axes and replicated along model.
    """
    warn_deprecated(
        "make_sharded_query",
        "make_sharded_query is deprecated; build a repro.engine.Mapper "
        "with ExecutionConfig(mesh=..., shard_index=True) instead")
    locs_fn = make_sharded_locs(mesh, model_axis, batch_axes)

    def query_fn(ssm: ShardedSeedMap, hashes: jnp.ndarray,
                 seed_offsets: jnp.ndarray, K: int) -> QueryResult:
        return merge_read_starts(locs_fn(ssm, hashes, K), seed_offsets)

    return query_fn


def make_distributed_frontend(mesh: Mesh, cfg: PipelineConfig,
                              model_axis: str = "model",
                              batch_axes=("data",)):
    """Deprecated: the engine's sharded-index plan runs this front end as
    part of its pre-jitted serve step (`repro.engine.plan`).

    Sharded pipeline front end: bucket-sharded SeedMap lookup + the
    fused merge/filter half of `kernels/pair_frontend`.

    Returns frontend_fn(ssm, reads1, reads2_fwd) -> FrontendResult (both
    reads in reference orientation).  The lookup runs under shard_map
    (the NMSL channel-striping analogue); conversion + sorted merge +
    Δ-adjacency filter + compaction run in one per-device kernel behind
    ``cfg.frontend_backend`` — the per-read (B, S*K) start lists never
    reach HBM on the kernel backends.
    """
    from repro.core.seeding import seed_offsets_tuple, seed_read_batch
    from repro.kernels.pair_frontend.ops import frontend_merge_filter

    warn_deprecated(
        "make_distributed_frontend",
        "make_distributed_frontend is deprecated; build a "
        "repro.engine.Mapper with ExecutionConfig(mesh=..., "
        "shard_index=True) — its serve step fuses this front end")
    locs_fn = make_sharded_locs(mesh, model_axis, batch_axes)

    def frontend_fn(ssm: ShardedSeedMap, reads1: jnp.ndarray,
                    reads2_fwd: jnp.ndarray):
        sm_cfg = ssm.config
        R = reads1.shape[1]
        seeds1 = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                                 sm_cfg.hash_seed)
        seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len,
                                 cfg.seeds_per_read, sm_cfg.hash_seed)
        K = cfg.max_locs_per_seed
        locs1 = locs_fn(ssm, seeds1.hashes, K)
        locs2 = locs_fn(ssm, seeds2.hashes, K)
        offs = seed_offsets_tuple(R, cfg.seed_len, cfg.seeds_per_read)
        return frontend_merge_filter(locs1, locs2, offs, cfg.delta,
                                     cfg.max_candidates,
                                     backend=cfg.frontend_backend)

    return frontend_fn


def make_distributed_map_pairs(mesh: Mesh, cfg: PipelineConfig,
                               batch_axes=("data",)):
    """Deprecated: warn once and delegate to the engine's data-parallel
    plan (`repro.engine.plan.pipeline_step` — replicated index/reference,
    batch sharded over `batch_axes`, the placement this factory owned).
    Build a `repro.engine.Mapper` with ``ExecutionConfig(mesh=...)``
    instead: it also resolves backends/`packed_ref` once and keeps the
    pre-packed reference resident instead of re-packing per call."""
    warn_deprecated(
        "make_distributed_map_pairs",
        "make_distributed_map_pairs is deprecated; build a "
        "repro.engine.Mapper with ExecutionConfig(mesh=...) instead")
    # Imported lazily: repro.engine imports this module's building blocks.
    from repro.engine.config import resolved_pipeline
    from repro.engine.plan import pipeline_step

    step = pipeline_step(resolved_pipeline(cfg), mesh=mesh,
                         batch_axes=batch_axes)

    def legacy_step(sm, ref, reads1, reads2):
        return step(sm, ref, reads1, reads2,
                    jnp.int32(reads1.shape[0]))

    return legacy_step
