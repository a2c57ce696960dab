"""The front end's row gather from CSR lines (`LinedCSRSeedMap`).

Interpret-mode Pallas against the jnp oracle, which queries the CSR
tables directly (`core.query.query_csr`), and against the padded-row
path where a padded table holds the same index:

- seeded random genomes through `pair_frontend` and
  `segment_pair_frontend`;
- hand-made tables whose buckets are empty, hold more than K locations,
  start close enough to a line's end that their row straddles two
  lines, and end at the last location of the table;
- buckets dropped at ``max_locations`` by the index build;
- the lean index build against a plain argsort build, and the padded ->
  CSR conversion a padded store is loaded through;
- the layout choice from sizes, and a session whose device cannot hold
  the padded rows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs, to_padded,
)
from repro.core.hashing import xxhash32_words_np
from repro.core.seedmap import (
    LinedCSRSeedMap, SeedMap, packed_words_all_positions, padded_to_csr,
    to_lined, to_lined_csr,
)
from repro.engine import Mapper
from repro.engine.mapper import index_layout
from repro.kernels._util import LANES
from repro.kernels.pair_frontend import pair_frontend
from repro.kernels.pair_frontend.ops import segment_pair_frontend
from repro.kernels.pair_frontend.ref import seed_buckets_ref

FE = dict(seed_len=16, seeds_per_read=3, hash_seed=0, delta=60,
          max_candidates=4)


def _assert_same(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"field {f} {msg}")


def _genome_world(seed: int, n_ref=40_000, table_bits=12, max_locations=60,
                  n=12):
    ref = random_reference(n_ref, np.random.default_rng(seed))
    sm = build_seedmap(ref, SeedMapConfig(seed_len=16, table_bits=table_bits,
                                          max_locations=max_locations))
    sim = simulate_pairs(ref, n, ReadSimConfig(sub_rate=2e-3, read_len=64),
                         seed=seed + 1)
    r2 = (3 - sim.reads2)[:, ::-1]          # reference orientation
    return ref, sm, jnp.asarray(sim.reads1), jnp.asarray(np.ascontiguousarray(r2))


# ------------------------------------------------ seeded random genomes --
@pytest.mark.parametrize("seed,K,block,n", [
    pytest.param(1, 8, 4, 12, id="1-8"),
    pytest.param(2, 32, 4, 12, id="2-32"),
    pytest.param(3, 16, 4, 12, id="3-16"),
    # a lane-wide block over a batch that is not a multiple of it
    pytest.param(7, 32, 128, 130, id="7-32-block128"),
])
def test_csr_gather_matches_oracle_and_padded(seed, K, block, n):
    _, sm, r1, r2 = _genome_world(seed, n=n)
    csr = to_lined_csr(sm, K)
    got = pair_frontend(csr, r1, r2, backend="interpret", block=block, **FE)
    _assert_same(got, pair_frontend(csr, r1, r2, backend="jnp", **FE),
                 "vs oracle")
    _assert_same(got, pair_frontend(to_lined(to_padded(sm, cap=K)), r1, r2,
                                    backend="interpret", block=block, **FE),
                 "vs padded")
    assert int(np.asarray(got.n).sum()) > 0


@pytest.mark.parametrize("seed", [4, 5])
def test_csr_segment_gather_matches_oracle_and_padded(seed):
    ref, sm, _, _ = _genome_world(seed)
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, ref.shape[0] - 400, 3)
    reads = jnp.asarray(ref[starts[:, None] + np.arange(400)])
    kw = dict(segment_len=64, segment_stride=96, seed_len=16,
              seeds_per_read=3, hash_seed=0, delta=160, max_candidates=4)
    csr = to_lined_csr(sm, 8)
    got = segment_pair_frontend(csr, reads, backend="interpret", block=4,
                                **kw)
    _assert_same(got, segment_pair_frontend(csr, reads, backend="jnp",
                                            **kw), "vs oracle")
    _assert_same(got, segment_pair_frontend(
        to_lined(to_padded(sm, cap=8)), reads, backend="interpret",
        block=4, **kw), "vs padded")
    assert int(np.asarray(got.n).sum()) > 0


def test_dropped_buckets_read_empty():
    """Buckets over ``max_locations`` are removed by the build: the CSR
    gather returns no location for the seeds that hash there."""
    _, sm, r1, r2 = _genome_world(6, n_ref=30_000, table_bits=10,
                                  max_locations=30)
    counts = np.diff(sm.offsets)
    full = build_seedmap(
        random_reference(30_000, np.random.default_rng(6)),
        SeedMapConfig(seed_len=16, table_bits=10, max_locations=10**6))
    dropped = np.flatnonzero((np.diff(full.offsets) > 30) & (counts == 0))
    b = np.asarray(seed_buckets_ref(r1, 16, 3, 0, 1 << 10))
    assert np.isin(b, dropped).any()
    csr = to_lined_csr(sm, 8)
    got = pair_frontend(csr, r1, r2, backend="interpret", block=4, **FE)
    _assert_same(got, pair_frontend(csr, r1, r2, backend="jnp", **FE))


# -------------------------------------------------- hand-made tables ------
STRADDLES = 10    # bucket K + 5 locations long, its row across two lines


def _edge_table(T: int, K: int, rng) -> SeedMap:
    """A CSR table whose buckets cycle through the gather's edge cases:
    empty, one location, K - 1, K, more than K, and a row that starts a
    few lanes before a line's end; the last bucket ends at the table's
    last location, and the table fills whole lines."""
    sizes = np.array([0, 1, K - 1, K, K + 5, 3 * K], np.int64)
    counts = sizes[np.arange(T) % sizes.size]
    # Push bucket STRADDLES's start to lane LANES - 3, so its row
    # runs into the next line.
    offsets = np.zeros(T + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    counts[STRADDLES - 1] += (LANES - 3 - offsets[STRADDLES]) % LANES
    counts[-1] += (-(counts.sum()) % LANES)       # end on a whole line
    offsets = np.zeros(T + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    assert offsets[STRADDLES] % LANES == LANES - 3
    assert counts[STRADDLES] > 3
    assert counts[-1] > 0 and offsets[-1] % LANES == 0
    locs = np.concatenate([np.sort(rng.choice(400, c, replace=c > 400))
                           for c in counts]).astype(np.int32)
    cfg = SeedMapConfig(seed_len=16, table_bits=int(np.log2(T)),
                        padded_cap=K)
    return SeedMap(offsets=offsets.astype(np.int32), locations=locs,
                   config=cfg)


@pytest.mark.parametrize("K,block", [
    pytest.param(4, 4, id="4"), pytest.param(8, 4, id="8"),
    pytest.param(32, 4, id="32"),
    # rows of 0, 1, K - 1, K and more than K locations, 128 reads a step
    pytest.param(32, 128, id="32-block128"),
])
def test_edge_table_matches_oracle_and_padded(K, block):
    rng = np.random.default_rng(K)
    T = 64
    sm = _edge_table(T, K, rng)
    r1 = jnp.asarray(rng.integers(0, 4, (16, 64), np.uint8))
    r2 = jnp.asarray(rng.integers(0, 4, (16, 64), np.uint8))
    # the reads' seeds reach every kind of bucket, the straddling one
    # and the last one among them
    b = np.concatenate([np.asarray(seed_buckets_ref(r, 16, 3, 0, T)).ravel()
                        for r in (r1, r2)])
    counts = np.diff(sm.offsets)
    assert {0, 1, K - 1, K} <= set(counts[b].tolist())
    assert (counts[b] > K).any()
    csr = to_lined_csr(sm, K)
    got = pair_frontend(csr, r1, r2, backend="interpret", block=block, **FE)
    _assert_same(got, pair_frontend(csr, r1, r2, backend="jnp", **FE),
                 "vs oracle")
    _assert_same(got, pair_frontend(to_lined(to_padded(sm, cap=K)), r1, r2,
                                    backend="interpret", block=block, **FE),
                 "vs padded")


@pytest.mark.parametrize("bucket", [STRADDLES, 63],
                         ids=["straddles", "last"])
def test_edge_rows_gathered_whole(bucket):
    """A read whose three seeds all hash to one chosen bucket: the
    gather returns exactly that bucket's first K locations, for the row
    that straddles two lines and for the last bucket of the table."""
    K, T = 8, 64
    rng = np.random.default_rng(bucket)
    sm = _edge_table(T, K, rng)
    # Find a 16-base seed hashing to `bucket`, and place it at the
    # read's three seed offsets (0, 24, 48 of 64).
    cand = rng.integers(0, 4, (4096, 16)).astype(np.uint8)
    words = packed_words_all_positions(cand.reshape(-1), 16)[::16]
    hit = np.flatnonzero((xxhash32_words_np(words) & (T - 1)) == bucket)
    read = rng.integers(0, 4, (4, 64)).astype(np.uint8)
    for off in (0, 24, 48):
        read[:, off:off + 16] = cand[hit[0]]
    read = jnp.asarray(read)
    csr = to_lined_csr(sm, K)
    got = pair_frontend(csr, read, read, backend="interpret", block=4,
                        **{**FE, "delta": 10**6})
    lo, hi = int(sm.offsets[bucket]), int(sm.offsets[bucket + 1])
    n = min(hi - lo, K)
    assert np.asarray(got.n_hits1).tolist() == [3 * n] * 4
    want = pair_frontend(csr, read, read, backend="jnp",
                         **{**FE, "delta": 10**6})
    _assert_same(got, want)


def test_empty_table_tail_is_safe():
    """Every location in the first bucket, the rest empty: the empty
    buckets start at the table's end and are aimed at element 0."""
    K, T = 8, 16
    sm = SeedMap(offsets=np.r_[0, np.full(T, 5)].astype(np.int32),
                 locations=np.arange(5, dtype=np.int32) * 7,
                 config=SeedMapConfig(seed_len=16, table_bits=4))
    rng = np.random.default_rng(0)
    r1 = jnp.asarray(rng.integers(0, 4, (8, 64), np.uint8))
    csr = to_lined_csr(sm, K)
    got = pair_frontend(csr, r1, r1, backend="interpret", block=4, **FE)
    _assert_same(got, pair_frontend(csr, r1, r1, backend="jnp", **FE))


# --------------------------------------------------- index build & layout -
def test_build_matches_a_plain_argsort_build(monkeypatch):
    """The chunked key-sort build equals a whole-array stable argsort by
    bucket, with buckets over the threshold removed."""
    import repro.core.seedmap as seedmap

    monkeypatch.setattr(seedmap, "BUILD_CHUNK", 4096)   # many chunks
    ref = random_reference(50_000, np.random.default_rng(11))
    cfg = SeedMapConfig(seed_len=16, table_bits=11, max_locations=30)
    sm = build_seedmap(ref, cfg)
    words = packed_words_all_positions(ref, 16)
    buckets = (xxhash32_words_np(words) & np.uint32(2047)).astype(np.int64)
    order = np.argsort(buckets, kind="stable")
    counts = np.bincount(buckets, minlength=2048)
    keep = counts[buckets[order]] <= 30
    counts[counts > 30] = 0
    np.testing.assert_array_equal(sm.locations, order[keep].astype(np.int32))
    np.testing.assert_array_equal(sm.offsets,
                                  np.r_[0, np.cumsum(counts)].astype(np.int32))
    assert (np.bincount(buckets, minlength=2048) > 30).any()   # some drop


def test_padded_to_csr_queries_like_the_padded_rows():
    _, sm, r1, r2 = _genome_world(7)
    psm = to_padded(sm, cap=8)
    back = padded_to_csr(psm)
    np.testing.assert_array_equal(np.diff(back.offsets), psm.counts)
    _assert_same(pair_frontend(to_lined_csr(back, 8), r1, r2, backend="jnp",
                               **FE),
                 pair_frontend(to_lined(psm), r1, r2, backend="jnp", **FE))


@pytest.mark.parametrize("T,cap,limit,want", [
    (1 << 25, 32, None, "padded"),          # no limit known (a CPU)
    (1 << 25, 32, 16 * 2**30, "padded"),    # 4 GiB rows, 16 GiB chip
    (1 << 26, 32, 16 * 2**30, "padded"),    # 8 GiB: exactly half
    (1 << 27, 32, 16 * 2**30, "csr"),       # 16 GiB
    (1 << 28, 32, 16 * 2**30, "csr"),       # 32 GiB: chr1-chr3's table
    (1 << 28, 8, 16 * 2**30, "padded"),     # 8 GiB at a narrower cap
])
def test_index_layout_from_sizes(T, cap, limit, want):
    assert index_layout(T, cap, limit) == want


@pytest.fixture(scope="module")
def session_world():
    ref = random_reference(30_000, np.random.default_rng(12))
    sm = build_seedmap(ref, SeedMapConfig(table_bits=13))
    sim = simulate_pairs(ref, 8, ReadSimConfig(sub_rate=3e-3), seed=13)
    return ref, sm, sim


def _kernel_cfg():
    """The front end on its kernel; the aligners on their oracles (the
    layout changes only what the front end gathers from)."""
    return PipelineConfig(frontend_backend="interpret", light_backend="jnp",
                          residual_backend="jnp")


def test_session_takes_csr_where_padded_rows_do_not_fit(session_world,
                                                        monkeypatch):
    """A device too small for the padded rows: the session places CSR
    lines (no host padded table is made) and maps bit-identically to
    the jnp oracle session and to a padded session."""
    ref, sm, sim = session_world
    padded = Mapper.from_index(sm, ref, _kernel_cfg())
    # 2^13 buckets x 32 x 4 B = 1 MiB of rows, over half of the limit
    monkeypatch.setattr("repro.engine.mapper._bytes_limit",
                        lambda mesh: 2**20)

    def no_padding(*a, **k):
        raise AssertionError("a padded table was made")

    monkeypatch.setattr("repro.engine.mapper.to_padded", no_padding)
    csr = Mapper.from_index(sm, ref, _kernel_cfg())
    assert isinstance(csr._state[0], LinedCSRSeedMap)
    assert isinstance(csr.index, SeedMap)
    oracle = Mapper.from_index(sm, ref, PipelineConfig(
        frontend_backend="jnp", light_backend="jnp", residual_backend="jnp"))
    want = oracle.map(sim.reads1, sim.reads2)
    _assert_same(csr.map(sim.reads1, sim.reads2), want, "csr vs oracle")
    _assert_same(padded.map(sim.reads1, sim.reads2), want,
                 "padded vs oracle")
    long = np.tile(sim.reads1, (1, 4))[:2]
    _assert_same(csr.map_long(long), padded.map_long(long), "long lane")
    assert csr.pipe_cfg == padded.pipe_cfg
