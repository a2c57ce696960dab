"""Jit'd public wrappers for the fused pipeline front end.

`pair_frontend` is the one-call steps-1-3 hot path: seed hashing +
SeedMap row gather (CSR or padded lines) + sorted merge +
Paired-Adjacency filter + front compaction, behind the standard ``backend`` switch resolved by
`kernels/backend.py`.  The jnp backend is the bit-exact staged oracle
(`ref.py`, which routes through `core.seeding` / `core.query` /
`core.pair_filter`); the pallas/interpret backends run the two fused
kernels, so the `(B, S, K)` location tensor and the `(B, S*K)` sorted
start lists never reach HBM.

`frontend_merge_filter` is the post-query half for callers whose SeedMap
lookup is sharded (`core/genpairx_step.py`'s shard_map query): it takes
the gathered `(B, S, K)` locations and fuses conversion + merge + filter
+ compaction in one kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.seeding import seed_offsets_tuple
from repro.core.seedmap import LinedCSRSeedMap, LinedSeedMap, SeedMap
from repro.kernels._util import (
    chunked_launch,
    lines_spanned,
    pad_rows,
    to_lines,
)
from repro.kernels.backend import resolve_backend
from repro.kernels.pair_frontend.kernel import (
    DEFAULT_BLOCK,
    HASH_BLOCK,
    LAUNCH_ROWS,
    merge_filter_pallas,
    pair_frontend_pallas,
    seed_buckets_pallas,
)
from repro.kernels.pair_frontend.ref import (
    FrontendResult,
    merge_filter_ref,
    pair_frontend_ref,
)


@functools.partial(
    jax.jit,
    static_argnames=("seed_len", "seeds_per_read", "hash_seed", "delta",
                     "max_candidates", "block", "backend"),
)
def pair_frontend(
    index,                   # LinedCSRSeedMap, LinedSeedMap or (T, K) rows
    reads1: jnp.ndarray,     # (B, R) mate 1, reference orientation
    reads2: jnp.ndarray,     # (B, R) mate 2, reference orientation
    seed_len: int,
    seeds_per_read: int = 3,
    hash_seed: int = 0,
    delta: int = 500,
    max_candidates: int = 8,
    block: int | None = None,
    backend: str = "auto",
) -> FrontendResult:
    """Fused front end for a batch of read pairs.

    ``index`` is a device layout a session places once — the CSR tables
    in lines (`LinedCSRSeedMap`) or the bucket-major padded rows in
    lines (`LinedSeedMap`) — or plain (T, K) padded rows
    (`to_padded(sm).rows`; laying those out is a relayout in-jit: test
    scales only).  Its row width K (``config.padded_cap``) caps the
    locations per seed.  Both reads are
    expected in reference orientation (mate 2 pre-revcomp'd, as
    everywhere in the pipeline).
    ``block=None`` resolves to `DEFAULT_BLOCK`; the autotuner
    (`repro.tune`) threads per-shape winners here through
    `PipelineConfig.frontend_block`.
    """
    backend = resolve_backend(backend, family="pair_frontend")
    block = block or DEFAULT_BLOCK
    csr = isinstance(index, LinedCSRSeedMap)
    if isinstance(index, (LinedCSRSeedMap, LinedSeedMap)):
        T, K = index.config.table_size, index.config.padded_cap
        table = index.lines
    else:
        T, K = index.shape
        table = None
    if backend == "jnp":
        if csr:        # the oracle queries the CSR tables (`query_csr`)
            oracle = SeedMap(offsets=index.offsets,
                             locations=table.reshape(-1),
                             config=index.config)
        else:
            oracle = (table.reshape(-1)[:T * K].reshape(T, K)
                      if table is not None else index)
        return pair_frontend_ref(oracle, reads1, reads2, seed_len,
                                 seeds_per_read, hash_seed, delta,
                                 max_candidates, cap=K)
    if table is None:
        table = to_lines(index.reshape(-1), lines_spanned(K, K))
    interpret = backend == "interpret"
    B, R = reads1.shape
    offs = seed_offsets_tuple(R, seed_len, seeds_per_read)

    # -- kernel 1: both mates' bucket ids in one launch -------------------
    reads = jnp.concatenate([reads1, reads2], 0).astype(jnp.int32)
    n = 2 * B
    n_pad = n + ((-n) % HASH_BLOCK)
    buckets = seed_buckets_pallas(
        pad_rows(reads, n_pad), offs, seed_len, hash_seed, T,
        interpret=interpret)[:n]

    # -- kernel 2: row gather + merge + filter ----------------------------
    # Scalar-prefetch tables hold flattened row starts into the line
    # table (and, CSR, row counts), 1-D per launch; padding rows aim at
    # element 0 (a safe in-bounds DMA) and are sliced off below.
    total, rows_per = chunked_launch(B, block, LAUNCH_ROWS)

    def tables(x):
        return (pad_rows(x[:B], total).reshape(-1),
                pad_rows(x[B:], total).reshape(-1))

    cnt = None
    if csr:
        # Both ends of every seed's bucket in one gather from the Seed
        # Table; the tables made from them stay under the scope.
        with jax.named_scope("index_offsets"):
            ends = index.offsets[jnp.stack([buckets, buckets + 1])]
            count = jnp.minimum(ends[1] - ends[0], K)
            # An empty bucket may start at the table's end: aim it at 0.
            sdma1, sdma2 = tables(jnp.where(count > 0, ends[0], 0))
            cnt = tables(count)
    else:
        sdma1, sdma2 = tables(buckets * K)
    per = rows_per * len(offs)
    parts = [
        pair_frontend_pallas(
            table, sdma1[s:s + per], sdma2[s:s + per], offs, K, delta,
            max_candidates,
            counts=None if cnt is None else (cnt[0][s:s + per],
                                             cnt[1][s:s + per]),
            block=block, interpret=interpret)
        for s in range(0, total * len(offs), per)
    ]
    outs = [jnp.concatenate(cols) if len(parts) > 1 else cols[0]
            for cols in zip(*parts)]
    pos1, pos2, nc, nh1, nh2 = (o[:B] for o in outs)
    return FrontendResult(pos1=pos1, pos2=pos2, n=nc,
                          n_hits1=nh1, n_hits2=nh2)


def segment_pair_frontend(
    index,                   # as `pair_frontend`
    reads: jnp.ndarray,      # (B, L) long reads, reference orientation
    segment_len: int,
    segment_stride: int,
    seed_len: int,
    seeds_per_read: int = 3,
    hash_seed: int = 0,
    delta: int = 500,
    max_candidates: int = 8,
    block: int | None = None,
    backend: str = "auto",
) -> FrontendResult:
    """Long-read pseudo-pair front end (§4.7): segmentation as a window op
    feeding the fused pair front end.

    Each (B, L) read is cut into ``segment_len``-wide views every
    ``segment_stride`` bases; consecutive segments become the mates of
    ``S - 1`` pseudo-pairs per read, routed through `pair_frontend`
    unchanged (mate 2 is NOT revcomp'd — both segments already sit in
    reference orientation).  Returns the FrontendResult over the
    row-major ``(B * (S-1),)`` pseudo-pair batch.
    """
    # Imported at call time: core.long_read imports core.pipeline, which
    # pulls in repro.kernels; a module-level import here would be circular
    # when the kernels package is imported first.
    from repro.core.long_read import segment_views

    segs = segment_views(reads, segment_len, segment_stride)
    B, S, R = segs.shape
    r1 = segs[:, :-1].reshape(B * (S - 1), R)
    r2 = segs[:, 1:].reshape(B * (S - 1), R)
    return pair_frontend(index, r1, r2, seed_len, seeds_per_read, hash_seed,
                         delta, max_candidates, block=block, backend=backend)


@functools.partial(
    jax.jit,
    static_argnames=("seed_offs", "delta", "max_candidates", "block",
                     "backend"),
)
def frontend_merge_filter(
    locs1: jnp.ndarray,      # (B, S, K) int32 per-seed locations
    locs2: jnp.ndarray,
    seed_offs: tuple,        # static per-seed read offsets (S ints)
    delta: int,
    max_candidates: int,
    block: int | None = None,
    backend: str = "auto",
) -> FrontendResult:
    """Fused conversion + sorted merge + Δ filter + compaction (steps 2.5-3)
    for locations already gathered by a (possibly sharded) SeedMap query."""
    backend = resolve_backend(backend, family="pair_frontend")
    block = block or DEFAULT_BLOCK
    offs_arr = jnp.asarray(seed_offs, jnp.int32)
    if backend == "jnp":
        return merge_filter_ref(locs1, locs2, offs_arr, delta,
                                max_candidates)
    interpret = backend == "interpret"
    B, S, K = locs1.shape
    total, rows_per = chunked_launch(B, block, LAUNCH_ROWS)
    l1 = pad_rows(locs1.reshape(B, S * K), total)
    l2 = pad_rows(locs2.reshape(B, S * K), total)
    parts = [
        merge_filter_pallas(
            l1[s:s + rows_per], l2[s:s + rows_per], seed_offs, K, delta,
            max_candidates, block=block, interpret=interpret)
        for s in range(0, total, rows_per)
    ]
    outs = [jnp.concatenate(cols) if len(parts) > 1 else cols[0]
            for cols in zip(*parts)]
    pos1, pos2, nc, nh1, nh2 = (o[:B] for o in outs)
    return FrontendResult(pos1=pos1, pos2=pos2, n=nc,
                          n_hits1=nh1, n_hits2=nh2)
