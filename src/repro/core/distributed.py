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
sharded along the batch axes, the tables along `model`; the reference is
replicated.  This module holds the host-side split (`shard_seedmap`) and
the per-device query (`_local_query`); the sharded-index serve step
(`core.genpairx_step`) runs that query under shard_map.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

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
