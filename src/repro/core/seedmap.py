"""SeedMap (§4.2): the offline two-table index of the reference genome.

Layout (paper-faithful CSR):
  - Seed Table  -> `offsets`: int32[T + 1].  Bucket b's locations live at
    `locations[offsets[b]:offsets[b+1]]`, where b = xxhash32(seed) & (T-1).
  - Location Table -> `locations`: int32[N], reference positions, grouped by
    bucket and sorted ascending within a bucket (the paper sorts by hash so
    same-seed locations are contiguous; we additionally keep positions sorted
    so the Paired-Adjacency merge gets sorted inputs for free).

Index-filtering threshold (§5.2): buckets with more than `max_locations`
entries are physically removed from the Location Table (the paper filters
them out of SeedMap); queries to them return empty.

The Pallas row gather (`kernels/pair_frontend`) reads one of two device
layouts, both 128-lane lines because Mosaic DMAs only whole tiles:

  - `LinedCSRSeedMap`: the CSR tables, the locations cut into lines; a
    row starts at ``offsets[b]`` and is masked at its count.  T*4 bytes
    of offsets plus the locations.
  - `LinedSeedMap`: the bucket-major fixed-width rows of `PaddedSeedMap`
    (INVALID_LOC padded, row b at ``b*K``) in lines.  T*K*4 bytes.

`engine.mapper.index_layout` picks one from the sizes.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import xxhash32_words_np

INVALID_LOC = np.int32(2**31 - 1)  # sentinel: sorts after every real position

#: seed positions `build_seedmap` hashes per host pass
BUILD_CHUNK = 1 << 24


@dataclasses.dataclass(frozen=True)
class SeedMapConfig:
    seed_len: int = 50
    table_bits: int = 20          # T = 2**table_bits buckets
    max_locations: int = 500      # index-filtering threshold (paper: 500)
    hash_seed: int = 0
    padded_cap: int = 32          # row width of the padded (kernel) layout

    @property
    def table_size(self) -> int:
        return 1 << self.table_bits


class SeedMap(NamedTuple):
    """CSR index: host arrays until a session places it; a JAX pytree."""

    offsets: jnp.ndarray    # int32[T + 1]
    locations: jnp.ndarray  # int32[N]
    config: SeedMapConfig   # static (hashable) aux data

    @property
    def n_locations(self) -> int:
        return self.locations.shape[0]


class PaddedSeedMap(NamedTuple):
    """Bucket-major fixed-width layout for the TPU gather kernel."""

    rows: jnp.ndarray    # int32[T, cap], INVALID_LOC-padded
    counts: jnp.ndarray  # int32[T], min(count, cap)
    config: SeedMapConfig


class LinedSeedMap(NamedTuple):
    """A `PaddedSeedMap`'s rows as 128-lane lines: the device layout the
    Pallas row gather (`kernels/pair_frontend`) DMAs from.

    Row ``b`` is elements ``[b*K, (b+1)*K)`` of the flattened lines.  On a
    TPU a ``(T, K<128)`` int32 array is stored column-major, so the kernel
    cannot take the padded rows without a relayout through a 4x-padded
    temporary; the lines are the same dense bytes, reshaped on the host.
    """

    lines: jnp.ndarray      # int32[n, 128]
    config: SeedMapConfig   # static; padded_cap is the row width K


class LinedCSRSeedMap(NamedTuple):
    """A CSR `SeedMap` as the Pallas row gather (`kernels/pair_frontend`)
    DMAs it: the Seed Table as is, the Location Table as 128-lane lines.

    Bucket ``b``'s row is elements ``[offsets[b], offsets[b] + min(count,
    K))`` of the flattened lines, K = ``config.padded_cap``: a row starts
    at any lane, so the gather fetches the two lines a K <= 128 row can
    touch, cuts the row at its lane offset and masks the lanes past its
    count.  The layout costs the CSR bytes plus a few lines of padding,
    where the padded table costs T*K*4.
    """

    offsets: jnp.ndarray    # int32[T + 1]
    lines: jnp.ndarray      # int32[n, 128], the locations
    config: SeedMapConfig   # static; padded_cap is the per-seed cap K


jax.tree_util.register_static(SeedMapConfig)


def to_lined(psm: PaddedSeedMap) -> LinedSeedMap:
    """`PaddedSeedMap` -> `LinedSeedMap` (a free reshape for host rows
    whose size is a whole number of (8, 128) tiles, as at every power-of-
    two table size with K dividing 128)."""
    from repro.kernels._util import lines_spanned, to_lines

    K = psm.rows.shape[1]
    return LinedSeedMap(
        lines=to_lines(psm.rows.reshape(-1), lines_spanned(K, K)),
        config=dataclasses.replace(psm.config, padded_cap=K))


def to_lined_csr(sm: SeedMap, cap: int) -> LinedCSRSeedMap:
    """CSR `SeedMap` -> `LinedCSRSeedMap` at the per-seed cap ``cap``
    (host arrays stay on the host; the locations are padded by the lines
    a row's DMA may run past the last one)."""
    from repro.kernels._util import lines_spanned, to_lines

    return LinedCSRSeedMap(
        offsets=sm.offsets,
        lines=to_lines(sm.locations, lines_spanned(cap, 1)),
        config=dataclasses.replace(sm.config, padded_cap=cap))


def padded_to_csr(psm: PaddedSeedMap) -> SeedMap:
    """`PaddedSeedMap` -> the CSR `SeedMap` of its rows: every bucket's
    first ``counts[b]`` locations.  A query at the padded row width K is
    bit-identical on both (the rows already hold at most K)."""
    rows, counts = np.asarray(psm.rows), np.asarray(psm.counts)
    offsets = np.zeros(counts.shape[0] + 1, dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    filled = np.arange(rows.shape[1], dtype=np.int32) < counts[:, None]
    return SeedMap(offsets=offsets, locations=rows[filled],
                   config=psm.config)


def frontend_layout(sm, cap: int):
    """What the kernel front end gathers rows from: a placed lined layout
    as is, a `PaddedSeedMap`'s rows, or a CSR `SeedMap` in lines at the
    per-seed cap ``cap`` (inside a jit, one pad of the locations)."""
    if isinstance(sm, (LinedSeedMap, LinedCSRSeedMap)):
        return sm
    if isinstance(sm, PaddedSeedMap):
        return sm.rows
    return to_lined_csr(sm, cap)


def packed_words_all_positions(ref: np.ndarray, seed_len: int) -> np.ndarray:
    """2-bit pack the seed starting at every position: (L-seed_len+1, 4) u32.

    Vectorized rolling pack: pw[k] = bases k..k+15 packed little-endian, built
    with 16 shifted adds; word j of position p is pw[p + 16j]; the final
    partial word packs the remaining seed_len % 16 bases.
    """
    ref = np.asarray(ref, dtype=np.uint32)
    L = ref.shape[0]
    n_pos = L - seed_len + 1
    if n_pos <= 0:
        raise ValueError("reference shorter than seed length")
    n_full, rem = divmod(seed_len, 16)
    n_words = n_full + (1 if rem else 0)
    if n_words > 4:
        raise ValueError("seed_len > 64 not supported (4-word hash input)")
    # pw[k] for k in [0, L-16]
    pw = np.zeros(L - 15, dtype=np.uint32)
    for i in range(16):
        pw |= ref[i : L - 15 + i] << np.uint32(2 * i)
    words = np.zeros((n_pos, 4), dtype=np.uint32)
    for j in range(n_full):
        words[:, j] = pw[16 * j : 16 * j + n_pos]
    if rem:
        partial = np.zeros(n_pos, dtype=np.uint32)
        base0 = 16 * n_full
        for i in range(rem):
            partial |= ref[base0 + i : base0 + i + n_pos] << np.uint32(2 * i)
        words[:, n_full] = partial
    return words


def build_seedmap(ref: np.ndarray, config: SeedMapConfig = SeedMapConfig()) -> SeedMap:
    """Offline SeedMap construction (§4.2, Fig. 4a). Host-side numpy.

    Steps mirror the paper: (1) extract + hash all seeds, (2) sort by hash
    bucket into the temporary seed-locations table, (3) concatenate into the
    Location Table, (4) record per-bucket offsets in the Seed Table; then
    apply the index-filtering threshold.

    The seeds are hashed `BUILD_CHUNK` positions at a time into one
    uint64 key per position, ``bucket << 32 | position``: sorting the keys
    groups positions by bucket, ascending within one, so the host holds 8
    bytes a position plus the tables (a whole-array hash and a stable
    argsort held ~63).
    """
    ref = np.asarray(ref, dtype=np.uint8)
    n_pos = ref.shape[0] - config.seed_len + 1
    if n_pos <= 0:
        raise ValueError("reference shorter than seed length")
    mask = np.uint32(config.table_size - 1)
    keys = np.empty(n_pos, dtype=np.uint64)
    for lo in range(0, n_pos, BUILD_CHUNK):
        hi = min(lo + BUILD_CHUNK, n_pos)
        words = packed_words_all_positions(
            ref[lo:hi + config.seed_len - 1], config.seed_len)
        buckets = xxhash32_words_np(words, seed=config.hash_seed) & mask
        keys[lo:hi] = buckets.astype(np.uint64) << np.uint64(32)
        keys[lo:hi] |= np.arange(lo, hi, dtype=np.uint64)
    keys.sort()
    # Bucket sizes from the sorted keys' runs, chunk by chunk (a bucket's
    # run may cross a chunk edge, hence the accumulate).
    counts = np.zeros(config.table_size, dtype=np.int64)
    for lo in range(0, n_pos, BUILD_CHUNK):
        b = (keys[lo:lo + BUILD_CHUNK] >> np.uint64(32)).astype(np.int64)
        first = np.flatnonzero(np.diff(b, prepend=-1))
        counts[b[first]] += np.diff(first, append=b.shape[0])
    # Index-filtering threshold: physically remove over-full buckets.
    dropped = counts > config.max_locations
    counts[dropped] = 0
    locations = np.empty(int(counts.sum()), dtype=np.int32)
    at = 0
    for lo in range(0, n_pos, BUILD_CHUNK):
        k = keys[lo:lo + BUILD_CHUNK]
        k = k[~dropped[(k >> np.uint64(32)).astype(np.int64)]]
        locations[at:at + k.shape[0]] = k & np.uint64(0xFFFFFFFF)
        at += k.shape[0]
    del keys
    offsets = np.zeros(config.table_size + 1, dtype=np.int32)
    np.cumsum(counts, out=offsets[1:])
    # Host arrays: the session that consumes the index places it (CSR,
    # padded or lined) on its devices once, in the layout it needs.
    return SeedMap(offsets=offsets, locations=locations, config=config)


def to_padded(sm: SeedMap, cap: int | None = None) -> PaddedSeedMap:
    """CSR -> bucket-major fixed-width rows (truncating at ``cap``).

    Host arrays in, host arrays out.  ``cap`` defaults to
    ``config.padded_cap``; the engine passes the
    pipeline's ``max_locs_per_seed`` so the padded row width matches the
    per-seed location cap the CSR query would have applied (the rows are
    then bit-identical to `query.padded_rows_device` at the same cap —
    pinned by the round-trip property test).
    """
    cfg = sm.config
    if cap is not None and cap != cfg.padded_cap:
        cfg = dataclasses.replace(cfg, padded_cap=cap)
    offsets = np.asarray(sm.offsets)
    locations = np.asarray(sm.locations)
    T, cap = cfg.table_size, cfg.padded_cap
    starts = offsets[:-1]
    counts = np.minimum(offsets[1:] - starts, cap).astype(np.int32)
    rows = np.full((T, cap), INVALID_LOC, dtype=np.int32)
    # One column at a time: (T,)-sized temporaries, not (T, cap) int64
    # index tensors (8 GiB at a 2^25-bucket table).
    for k in range(cap):
        filled = counts > k
        rows[filled, k] = locations[starts[filled] + k]
    return PaddedSeedMap(rows=rows, counts=counts, config=cfg)


def seedmap_stats(sm: SeedMap) -> dict:
    """Observation-2 style stats: locations per non-empty bucket etc."""
    offsets = np.asarray(sm.offsets)
    counts = offsets[1:] - offsets[:-1]
    nonzero = counts[counts > 0]
    return {
        "table_size": sm.config.table_size,
        "n_locations": int(sm.locations.shape[0]),
        "n_nonempty_buckets": int((counts > 0).sum()),
        "mean_locs_per_nonempty_bucket": float(nonzero.mean()) if len(nonzero) else 0.0,
        "max_locs_per_bucket": int(counts.max()) if len(counts) else 0,
    }
