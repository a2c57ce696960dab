"""Staged jnp oracle for the fused pair_frontend op.

This is the pipeline front end (steps 1-3 of `map_pairs`) exactly as the
core modules write it: Partitioned Seeding (`core.seeding`), padded-row
SeedMap lookup (a padded-row gather, or `core.query.query_csr` on the CSR
tables, + `merge_read_starts`), and
Paired-Adjacency Filtering (`core.pair_filter`).  The Pallas kernels in
`kernel.py` must match this path bit-for-bit; `map_pairs` results are
pinned against it.

The oracle deliberately *routes through* `seeding.py` / `query.py` /
`pair_filter.py` rather than re-implementing them, so any future change
to the staged front end automatically becomes the kernel's contract.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.pair_filter import paired_adjacency_filter
from repro.core.query import QueryResult, merge_read_starts, query_csr
from repro.core.seeding import extract_seeds, hash_seeds, seed_offsets
from repro.core.seedmap import SeedMap


class FrontendResult(NamedTuple):
    """Front-end output for a batch of read pairs.

    pos1, pos2: (B, C) int32 candidate read-start pairs (INVALID_LOC padded)
    n:          (B,)   int32 surviving candidate count (<= C)
    n_hits1/2:  (B,)   int32 SeedMap hit count per mate (for `had_hits`)

    The per-read sorted (B, S*K) start lists are internal to the op — on
    the kernel backends they never reach HBM.
    """

    pos1: jnp.ndarray
    pos2: jnp.ndarray
    n: jnp.ndarray
    n_hits1: jnp.ndarray
    n_hits2: jnp.ndarray


def seed_buckets_ref(reads: jnp.ndarray, seed_len: int, seeds_per_read: int,
                     hash_seed: int, table_size: int) -> jnp.ndarray:
    """(B, R) reads (reference orientation) -> (B, S) int32 bucket ids."""
    seeds = extract_seeds(reads, seed_len, seeds_per_read)
    hashes = hash_seeds(seeds, hash_seed=hash_seed)
    return (hashes & jnp.uint32(table_size - 1)).astype(jnp.int32)


def query_rows(index, buckets: jnp.ndarray, offsets: jnp.ndarray,
               cap: int | None = None) -> QueryResult:
    """SeedMap lookup + sorted merge for one mate.

    index: (T, K) int32 INVALID_LOC-padded location rows (`to_padded` /
    the in-jit CSR derivation), or a CSR `SeedMap`, queried as
    `query_csr` at ``cap`` locations a seed; buckets: (B, S) int32;
    offsets: (S,) int32.
    """
    if isinstance(index, SeedMap):
        locs, _ = query_csr(index, buckets.astype(jnp.uint32), cap)
    else:
        locs = index[buckets]                  # (B, S, K)
    return merge_read_starts(locs, offsets)


def pair_frontend_ref(
    index,                   # (T, K) int32 padded rows, or a CSR SeedMap
    reads1: jnp.ndarray,     # (B, R) mate 1, reference orientation
    reads2: jnp.ndarray,     # (B, R) mate 2, reference orientation (revcomp'd)
    seed_len: int,
    seeds_per_read: int,
    hash_seed: int,
    delta: int,
    max_candidates: int,
    cap: int | None = None,  # locations a seed on a CSR index
) -> FrontendResult:
    """Staged front end: seeding -> lookup -> merge -> Δ filter."""
    T = (index.config.table_size if isinstance(index, SeedMap)
         else index.shape[0])
    R = reads1.shape[1]
    offs = seed_offsets(R, seed_len, seeds_per_read)
    b1 = seed_buckets_ref(reads1, seed_len, seeds_per_read, hash_seed, T)
    b2 = seed_buckets_ref(reads2, seed_len, seeds_per_read, hash_seed, T)
    q1 = query_rows(index, b1, offs, cap)
    q2 = query_rows(index, b2, offs, cap)
    cands = paired_adjacency_filter(q1, q2, delta, max_candidates)
    return FrontendResult(pos1=cands.pos1, pos2=cands.pos2, n=cands.n,
                          n_hits1=q1.n_hits, n_hits2=q2.n_hits)


def merge_filter_ref(
    locs1: jnp.ndarray,      # (B, S, K) int32 per-seed locations
    locs2: jnp.ndarray,
    offsets: jnp.ndarray,    # (S,) int32 seed offsets within the read
    delta: int,
    max_candidates: int,
) -> FrontendResult:
    """Staged merge+filter half (post-query entry, e.g. the sharded serve
    step whose SeedMap lookup runs under shard_map)."""
    q1 = merge_read_starts(locs1, offsets)
    q2 = merge_read_starts(locs2, offsets)
    cands = paired_adjacency_filter(q1, q2, delta, max_candidates)
    return FrontendResult(pos1=cands.pos1, pos2=cands.pos2, n=cands.n,
                          n_hits1=q1.n_hits, n_hits2=q2.n_hits)
