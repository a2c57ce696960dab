"""SeedMap Query (§4.4): retrieve candidate locations for hashed seeds.

The single-device path is a vectorized CSR gather; the multi-device path
(`sharded_lookup` in repro/core/genpairx_step.py) is the NMSL analogue
that stripes the tables across devices.  Locations are converted to *read start
positions* (location - seed offset in the read) and the per-read lists of
all seeds are merged sorted — exactly the sorted-merge the paper gets for
free from its contiguous layout.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.seedmap import INVALID_LOC, PaddedSeedMap, SeedMap
from repro.core.seeding import SeedSet


class QueryResult(NamedTuple):
    """Sorted candidate read-start positions per read.

    starts: (B, M) int32 ascending, INVALID_LOC padded
    n_hits: (B,)  int32 number of valid entries
    """

    starts: jnp.ndarray
    n_hits: jnp.ndarray


def query_csr(
    sm: SeedMap, hashes: jnp.ndarray, max_locs_per_seed: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather up to K locations per seed hash.

    hashes: (...,) uint32 -> locations (..., K) int32 (INVALID_LOC padded,
    ascending within the valid prefix), counts (...,) int32.
    """
    K = max_locs_per_seed
    bucket = (hashes & jnp.uint32(sm.config.table_size - 1)).astype(jnp.int32)
    start = sm.offsets[bucket]
    end = sm.offsets[bucket + 1]
    count = jnp.minimum(end - start, K)
    idx = start[..., None] + jnp.arange(K, dtype=jnp.int32)
    valid = jnp.arange(K, dtype=jnp.int32) < count[..., None]
    locs = sm.locations[jnp.clip(idx, 0, sm.locations.shape[0] - 1)]
    locs = jnp.where(valid, locs, INVALID_LOC)
    return locs, count


def query_padded(
    psm: PaddedSeedMap, hashes: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row-gather from the padded layout (fixed K = padded_cap)."""
    bucket = (hashes & jnp.uint32(psm.config.table_size - 1)).astype(jnp.int32)
    return psm.rows[bucket], psm.counts[bucket]


def padded_rows_device(sm: SeedMap, cap: int) -> jnp.ndarray:
    """In-jit CSR -> (T, cap) padded rows (device-side `to_padded` analog).

    Delegates to `query_csr` over every bucket id (``arange(T) & (T-1)``
    is the identity), so a row gather from the result is bit-identical to
    the CSR query at K = cap by construction.  Materializes T*cap int32 —
    fine at test scale; production callers should build a `PaddedSeedMap`
    host-side once (`to_padded`) instead of paying this per trace.
    """
    T = sm.config.table_size
    locs, _ = query_csr(sm, jnp.arange(T, dtype=jnp.uint32), cap)
    return locs


def merge_read_starts(
    locs: jnp.ndarray, seed_offsets: jnp.ndarray
) -> QueryResult:
    """Convert per-seed locations to read-start positions and merge sorted.

    locs: (B, S, K) int32 per-seed locations (INVALID_LOC padded)
    seed_offsets: (S,) int32 offset of each seed within the read
    -> QueryResult with starts (B, S*K) ascending.

    A seed at read offset o hitting reference position l implies the read
    begins at l - o.  INVALID_LOC entries stay INVALID_LOC (sentinel sorts
    last).
    """
    valid = locs != INVALID_LOC
    starts = jnp.where(
        valid, locs - seed_offsets[None, :, None].astype(jnp.int32), INVALID_LOC
    )
    flat = starts.reshape(starts.shape[0], -1)
    flat = jnp.sort(flat, axis=-1)
    n = valid.reshape(valid.shape[0], -1).sum(axis=-1).astype(jnp.int32)
    return QueryResult(starts=flat, n_hits=n)


def query_read_batch(
    sm: SeedMap, seeds: SeedSet, max_locs_per_seed: int
) -> QueryResult:
    """Full SeedMap Query step for one read of the pair."""
    locs, _ = query_csr(sm, seeds.hashes, max_locs_per_seed)
    return merge_read_starts(locs, seeds.offsets)
