"""Tests for the fused pipeline front end (kernels/pair_frontend) and the
shared backend layer (kernels/backend).

- interpret-mode Pallas kernels vs the staged seeding/query/pair_filter
  oracle across (S, K, Δ, C) grids, including all-invalid and
  duplicate-heavy rows and candidate overflow (n > C);
- `map_pairs_impl` end-to-end parity between frontend backends, for both the
  CSR SeedMap and the PaddedSeedMap input flavors;
- the (start1, start2) pair-dedup fix in paired_adjacency_filter;
- REPRO_BACKEND resolution.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, random_reference, simulate_pairs, to_padded,
)
from repro.core.pair_filter import paired_adjacency_filter
from repro.core.query import QueryResult
from repro.core.seeding import seed_offsets_np
from repro.core.seedmap import INVALID_LOC
from repro.kernels.backend import resolve_backend
from repro.kernels.pair_frontend import frontend_merge_filter, pair_frontend


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))

RNG = np.random.default_rng(0)


def _assert_same(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"field {f} {msg}")


# ------------------------------------------------------------- packaging --
def test_kernel_package_imports_standalone():
    """kernels.pair_frontend must import before repro.core (the core
    package __init__ pulls in pipeline.py, which uses the op)."""
    import os
    import subprocess
    import sys

    import repro
    src = os.path.dirname(list(repro.__path__)[0])  # namespace pkg: no __file__
    env = {**os.environ, "PYTHONPATH": src}
    out = subprocess.run(
        [sys.executable, "-c", "import repro.kernels.pair_frontend"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr


# ------------------------------------------------------ backend resolver --
def test_resolver_defaults_and_validation(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    # auto -> jnp off-TPU; explicit names pass through
    assert resolve_backend("auto") in ("jnp", "pallas")
    for b in ("jnp", "interpret", "pallas"):
        assert resolve_backend(b) == b
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("bogus", family="pair_frontend")


def test_resolver_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    assert resolve_backend("auto") == "interpret"
    # explicit backend beats the env
    assert resolve_backend("jnp") == "jnp"
    # bad env value is rejected, not silently ignored
    monkeypatch.setenv("REPRO_BACKEND", "nope")
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("auto")


def test_unknown_backend_raises():
    rows = jnp.full((8, 4), INVALID_LOC, jnp.int32)
    reads = jnp.zeros((2, 64), jnp.uint8)
    with pytest.raises(ValueError, match="unknown backend"):
        pair_frontend(rows, reads, reads, 16, backend="bogus")


# ------------------------------------------- fused op vs staged oracle ----
def _frontend_world(s, k, c, seed, t=64, b=12, r=64, seed_len=16,
                    lo_hi=200):
    """Synthetic padded table + reads.  The small location value range
    makes duplicate read-starts (several seeds -> same start) and
    candidate overflow (> C survivors) common; ~1/8 of the table rows and
    every row a no-hit read may touch are all-INVALID."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, lo_hi, (t, k)).astype(np.int32)
    rows[rng.random((t, k)) < 0.3] = INVALID_LOC
    rows[rng.random(t) < 0.125] = INVALID_LOC       # whole buckets empty
    reads1 = rng.integers(0, 4, (b, r), np.uint8)
    reads2 = rng.integers(0, 4, (b, r), np.uint8)
    return jnp.asarray(rows), jnp.asarray(reads1), jnp.asarray(reads2)


@pytest.mark.parametrize("s,k,delta,c,world", [
    pytest.param(*case, {}, id="-".join(map(str, case))) for case in
    [(1, 4, 30, 2), (2, 4, 0, 4), (3, 8, 30, 4), (3, 4, 500, 8), (2, 8, 5, 2)]
] + [
    # a lane-wide block over a batch that is not a multiple of it, at the
    # chr1 merge width M = 96 (not a power of two: 32 sentinel sublanes)
    pytest.param(3, 32, 500, 8, dict(block=128, b=130), id="block128-b130"),
    # M = 192 > 128: a 256-sublane sorting network
    pytest.param(3, 64, 60, 4, {}, id="m192"),
    # locations under the seed offsets (24, 48): negative starts
    pytest.param(3, 8, 30, 4, dict(lo_hi=40), id="negative-starts"),
])
def test_fused_frontend_matches_staged_oracle(s, k, delta, c, world):
    world = dict(world)
    block = world.pop("block", 4)
    rows, r1, r2 = _frontend_world(s, k, c, seed=s * 100 + k + delta + c,
                                   **world)
    kw = dict(seed_len=16, seeds_per_read=s, hash_seed=0, delta=delta,
              max_candidates=c)
    got = pair_frontend(rows, r1, r2, backend="interpret", block=block, **kw)
    want = pair_frontend(rows, r1, r2, backend="jnp", **kw)
    _assert_same(got, want, f"S={s} K={k} d={delta} C={c}")


def test_fused_frontend_all_invalid_table():
    """Every bucket empty: zero hits, zero candidates, INVALID output."""
    rows = jnp.full((64, 4), INVALID_LOC, jnp.int32)
    _, r1, r2 = _frontend_world(2, 4, 2, seed=3)
    kw = dict(seed_len=16, seeds_per_read=2, hash_seed=0, delta=100,
              max_candidates=2)
    got = pair_frontend(rows, r1, r2, backend="interpret", block=4, **kw)
    want = pair_frontend(rows, r1, r2, backend="jnp", **kw)
    _assert_same(got, want, "all-invalid")
    assert (np.asarray(got.n) == 0).all()
    assert (np.asarray(got.n_hits1) == 0).all()
    assert (np.asarray(got.pos1) == int(INVALID_LOC)).all()


def test_fused_frontend_overflow_rows():
    """More survivors than C: compaction truncates, n clamps to C."""
    # every bucket holds the same dense location run -> tons of candidates
    rng = np.random.default_rng(9)
    rows = np.tile(np.arange(8, dtype=np.int32) * 3, (64, 1))
    r1 = jnp.asarray(rng.integers(0, 4, (8, 64), np.uint8))
    r2 = jnp.asarray(rng.integers(0, 4, (8, 64), np.uint8))
    kw = dict(seed_len=16, seeds_per_read=3, hash_seed=0, delta=50,
              max_candidates=2)
    got = pair_frontend(jnp.asarray(rows), r1, r2, backend="interpret",
                        block=4, **kw)
    want = pair_frontend(jnp.asarray(rows), r1, r2, backend="jnp", **kw)
    _assert_same(got, want, "overflow")
    assert (np.asarray(got.n) == 2).all()


def test_merge_filter_matches_staged(s=3, k=4):
    """Post-query entry (the serve step's shape) against the oracle."""
    rng = np.random.default_rng(11)
    b = 13                                     # non-multiple of block
    locs1 = rng.integers(0, 150, (b, s, k)).astype(np.int32)
    locs2 = rng.integers(0, 150, (b, s, k)).astype(np.int32)
    locs1[rng.random((b, s, k)) < 0.4] = INVALID_LOC
    locs2[rng.random((b, s, k)) < 0.4] = INVALID_LOC
    offs = tuple(int(o) for o in seed_offsets_np(64, 16, s))
    for delta, c in ((25, 4), (0, 2)):
        got = frontend_merge_filter(jnp.asarray(locs1), jnp.asarray(locs2),
                                    offs, delta, c, block=4,
                                    backend="interpret")
        want = frontend_merge_filter(jnp.asarray(locs1), jnp.asarray(locs2),
                                     offs, delta, c, backend="jnp")
        _assert_same(got, want, f"delta={delta} C={c}")


def _edge_locs(case, rng, b, s, k, offs):
    """(locs1, locs2) (b, s, k) int32 for one merge/filter edge case."""
    if case == "same-start":        # every seed of every read: one start
        base = rng.integers(100, 1000, (b, 1, 1))
        l1 = base + np.asarray(offs)[None, :, None] + np.zeros((1, 1, k), int)
        return l1, l1 + rng.integers(-3, 4, (b, 1, 1))
    if case == "clip":
        # every read-1 start 5; read 2: the first seed no hits, the rest
        # starting under 5 - Δ (negative) but two near 5: lo + occ runs
        # past M - 1 and must clip there, to the sentinel, not probe a
        # sublane past M
        l1 = 5 + np.asarray(offs)[None, :, None] + np.zeros((b, 1, k), int)
        l2 = np.stack([np.full((b, k), INVALID_LOC, np.int64)] + [
            rng.integers(0, off - 15, (b, k)) for off in offs[1:]], axis=1)
        l2[:, -1, -2:] = 5 + offs[-1] + rng.integers(-3, 4, (b, 2))
        return l1, l2
    if case == "overflow":          # dense: far more than C survivors
        return rng.integers(0, 40, (b, s, k)), rng.integers(0, 40, (b, s, k))
    if case == "negative":          # locations under the seed offsets
        return rng.integers(0, 30, (b, s, k)), rng.integers(0, 30, (b, s, k))
    l1 = rng.integers(0, 150, (b, s, k))
    l2 = rng.integers(0, 150, (b, s, k))
    l1[rng.random((b, s, k)) < 0.3] = INVALID_LOC
    l1[::3] = INVALID_LOC           # "mixed": every third read no hits
    l2[1::4] = INVALID_LOC
    return l1, l2


@pytest.mark.parametrize("case,s,k,delta,c,block,b", [
    ("same-start", 3, 32, 500, 8, 4, 9),
    ("clip", 3, 8, 10, 4, 4, 8),
    ("clip", 3, 32, 10, 8, 128, 130),
    ("overflow", 3, 8, 50, 4, 4, 12),
    ("negative", 3, 8, 10, 4, 4, 12),
    ("mixed", 3, 64, 60, 8, 4, 12),          # M = 192 > 128
    ("mixed", 3, 32, 60, 8, 128, 130),       # batch not a multiple of 128
])
def test_merge_filter_edge_cases(case, s, k, delta, c, block, b):
    """The post-query entry against the oracle where the lane-layout
    merge/filter could slip: equal starts, the probe's clip at M - 1,
    overflow past C, negative starts, rows with no hits, M past one
    vreg column and a lane-wide block."""
    rng = np.random.default_rng(len(case) * 100 + k + b)
    offs = tuple(int(o) for o in seed_offsets_np(64, 16, s))
    l1, l2 = (jnp.asarray(x.astype(np.int32))
              for x in _edge_locs(case, rng, b, s, k, offs))
    got = frontend_merge_filter(l1, l2, offs, delta, c, block=block,
                                backend="interpret")
    want = frontend_merge_filter(l1, l2, offs, delta, c, backend="jnp")
    _assert_same(got, want, case)
    n = np.asarray(want.n)
    if case in ("clip", "overflow", "same-start"):
        assert (n > 0).all()
    if case == "overflow":
        assert (n == c).all()
    if case == "same-start":
        assert (n == 1).all()
    if case == "mixed":
        assert (np.asarray(want.n_hits1)[::3] == 0).all() and n.any()


def test_cap_exceeds_merge_width():
    """max_candidates > S*K: the jnp oracle must pad to the full (B, C)
    shape the kernel always emits (regression: `_row_filter` used to
    truncate its output at min(cap, M) columns)."""
    rng = np.random.default_rng(4)
    locs1 = rng.integers(0, 50, (4, 1, 2)).astype(np.int32)
    locs2 = rng.integers(0, 50, (4, 1, 2)).astype(np.int32)
    args = (jnp.asarray(locs1), jnp.asarray(locs2), (0,), 60, 8)
    want = frontend_merge_filter(*args, backend="jnp")
    got = frontend_merge_filter(*args, block=4, backend="interpret")
    assert want.pos1.shape == (4, 8)
    _assert_same(got, want, "cap > S*K")


# ------------------------------------------------- pair-dedup regression --
def test_filter_keeps_distinct_mate2_placements():
    """Two distinct mate-2 placements within Δ of the same mate-1 start
    must both surface (the old filter deduped on start1 alone and
    silently collapsed them onto the nearest partner)."""
    M = 8
    s1 = np.full(M, INVALID_LOC, np.int32)
    s1[:2] = [100, 100]            # same start found via two seeds
    s2 = np.full(M, INVALID_LOC, np.int32)
    s2[:2] = [80, 150]             # two placements, both within Δ=100
    q1 = QueryResult(starts=jnp.asarray(s1[None]),
                     n_hits=jnp.asarray([2], jnp.int32))
    q2 = QueryResult(starts=jnp.asarray(s2[None]),
                     n_hits=jnp.asarray([2], jnp.int32))
    cands = paired_adjacency_filter(q1, q2, 100, 4)
    assert int(cands.n[0]) == 2
    np.testing.assert_array_equal(np.asarray(cands.pos1[0])[:2], [100, 100])
    np.testing.assert_array_equal(np.asarray(cands.pos2[0])[:2], [80, 150])
    # equal (start1, start2) pairs still collapse to one
    s2b = np.full(M, INVALID_LOC, np.int32)
    s2b[:2] = [80, 80]
    q2b = QueryResult(starts=jnp.asarray(s2b[None]),
                      n_hits=jnp.asarray([2], jnp.int32))
    cands = paired_adjacency_filter(q1, q2b, 100, 4)
    assert int(cands.n[0]) == 1
    assert int(cands.pos2[0, 0]) == 80


# -------------------------------------------- map_pairs_impl end to end ---
@pytest.fixture(scope="module")
def small_world():
    rng = np.random.default_rng(1)
    ref = random_reference(40_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 24, ReadSimConfig(sub_rate=2e-3), seed=5)
    return (jnp.asarray(ref), sm,
            jnp.asarray(sim.reads1), jnp.asarray(sim.reads2))


def test_map_pairs_frontend_backends_agree(small_world):
    ref_j, sm, reads1, reads2 = small_world
    res_jnp = map_pairs_jit(sm, ref_j, reads1, reads2,
                            PipelineConfig(frontend_backend="jnp"))
    res_int = map_pairs_jit(sm, ref_j, reads1, reads2,
                            PipelineConfig(frontend_backend="interpret"))
    for f in ("pos1", "pos2", "score1", "score2", "method",
              "cigar1", "cigar2", "had_hits", "passed_adjacency"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_jnp, f)), np.asarray(getattr(res_int, f)),
            err_msg=f"field {f}")
    assert (np.asarray(res_jnp.method) == 1).mean() > 0.5


def test_map_pairs_padded_seedmap_input(small_world):
    """A PaddedSeedMap input maps identically to the CSR map (padded_cap ==
    max_locs_per_seed), on both frontend backends."""
    ref_j, sm, reads1, reads2 = small_world
    psm = to_padded(sm)
    base = map_pairs_jit(sm, ref_j, reads1, reads2,
                         PipelineConfig(frontend_backend="jnp"))
    for be in ("jnp", "interpret"):
        res = map_pairs_jit(psm, ref_j, reads1, reads2,
                            PipelineConfig(frontend_backend=be))
        for f in ("pos1", "pos2", "score1", "score2", "method"):
            np.testing.assert_array_equal(
                np.asarray(getattr(base, f)), np.asarray(getattr(res, f)),
                err_msg=f"padded backend={be} field {f}")
