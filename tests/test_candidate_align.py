"""Tests for the fused candidate light-alignment op (kernels/candidate_align).

- interpret-mode Pallas kernel vs the unfused jnp oracle, both gather
  flavors (unpacked bases / 2-bit packed words), both light modes,
  prescreen on/off, INVALID_LOC-padded candidate rows;
- a jitted `map_pairs_impl` end-to-end regression pinning MapResult (pos/score/method)
  against the seed implementation's unfused math on a fixed RNG batch.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, random_reference, simulate_pairs,
)
from repro.core.encoding import pack_2bit
from repro.core.light_align import gather_ref_windows, light_align
from repro.core.pair_filter import paired_adjacency_filter
from repro.core.pipeline import M_LIGHT
from repro.core.query import query_read_batch
from repro.core.seeding import seed_read_batch
from repro.core.seedmap import INVALID_LOC
from repro.kernels.candidate_align import candidate_pair_align
from repro.kernels.light_align.kernel import count_align_block_calls


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))

L, R, E = 5000, 100, 6


def test_kernel_package_imports_standalone():
    """kernels.candidate_align must import before repro.core (the core
    package __init__ pulls in pipeline.py, which uses the op)."""
    import os
    import subprocess
    import sys

    import repro
    src = os.path.dirname(list(repro.__path__)[0])  # namespace pkg: no __file__
    env = {**os.environ, "PYTHONPATH": src}
    out = subprocess.run(
        [sys.executable, "-c", "import repro.kernels.candidate_align"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr


def test_unknown_backend_raises():
    ref = jnp.zeros((500,), jnp.uint8)
    r = jnp.zeros((2, R), jnp.uint8)
    p = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="unknown backend"):
        candidate_pair_align(ref, r, r, p, p, E, backend="bogus")


def _world(b, c, seed=0, all_invalid_row=True):
    """Synthetic ref + reads + candidate sets with planted true positions."""
    rng = np.random.default_rng(seed)
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    pos1 = rng.integers(E, L - R - E, (b, c)).astype(np.int32)
    pos2 = np.clip(pos1 + rng.integers(-200, 200, (b, c)),
                   E, L - R - E).astype(np.int32)
    inval = rng.random((b, c)) < 0.3
    if all_invalid_row:
        inval[b // 2, :] = True
    pos1[inval] = INVALID_LOC
    pos2[inval] = INVALID_LOC
    reads1 = rng.integers(0, 4, (b, R), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (b, R), dtype=np.uint8)
    for i in range(b):
        if pos1[i, 0] != INVALID_LOC and i % 2 == 0:
            reads1[i] = ref[pos1[i, 0]:pos1[i, 0] + R]
            reads2[i] = ref[pos2[i, 0]:pos2[i, 0] + R]
    return (ref, jnp.asarray(reads1), jnp.asarray(reads2),
            jnp.asarray(pos1), jnp.asarray(pos2))


def _assert_same(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"field {f} {msg}")


@pytest.mark.parametrize("b,c", [(8, 4), (13, 4), (16, 8)])
@pytest.mark.parametrize("mode", ["minsplit", "paper"])
def test_kernel_matches_oracle_unpacked(b, c, mode):
    ref, r1, r2, p1, p2 = _world(b, c, seed=b * 10 + c)
    args = (jnp.asarray(ref), r1, r2, p1, p2, E)
    kw = dict(mode=mode)
    got = candidate_pair_align(*args, backend="interpret", block=8, **kw)
    want = candidate_pair_align(*args, backend="jnp", **kw)
    _assert_same(got, want, f"b={b} c={c} mode={mode}")


@pytest.mark.parametrize("prescreen", [0, 2])
def test_kernel_matches_oracle_packed(prescreen):
    ref, r1, r2, p1, p2 = _world(12, 4, seed=7)
    words = jnp.asarray(pack_2bit(ref))
    args = (words, r1, r2, p1, p2, E)
    kw = dict(packed_ref=True, prescreen_top=prescreen)
    got = candidate_pair_align(*args, backend="interpret", block=4, **kw)
    want = candidate_pair_align(*args, backend="jnp", **kw)
    _assert_same(got, want, f"packed prescreen={prescreen}")


def test_kernel_matches_oracle_prescreen_unpacked():
    ref, r1, r2, p1, p2 = _world(16, 8, seed=3)
    args = (jnp.asarray(ref), r1, r2, p1, p2, E)
    for ps in (2, 3):
        got = candidate_pair_align(*args, backend="interpret", block=8,
                                   prescreen_top=ps)
        want = candidate_pair_align(*args, backend="jnp", prescreen_top=ps)
        _assert_same(got, want, f"prescreen={ps}")


def test_out_of_range_candidate_starts_match_oracle():
    """Negative candidate starts (merge_read_starts emits start =
    location - seed_offset, negative near the reference origin) and
    starts past L gather the same clamped windows on both backends —
    regression for the kernel prep clamping to [0, L-1] while the
    unpacked oracle clamps per element."""
    rng = np.random.default_rng(33)
    b = 4
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    pos1 = np.array([[-2, -30, 0, 5],
                     [-(R + 2 * E + 3), 7, L - 1, L + 4],
                     [L + 300, -1, 3, 9],
                     [2, 4, 6, 8]], np.int32)
    pos2 = pos1[:, ::-1].copy()
    reads1 = rng.integers(0, 4, (b, R), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (b, R), dtype=np.uint8)
    args = (jnp.asarray(ref), jnp.asarray(reads1), jnp.asarray(reads2),
            jnp.asarray(pos1), jnp.asarray(pos2), E)
    got = candidate_pair_align(*args, backend="interpret", block=4)
    want = candidate_pair_align(*args, backend="jnp")
    _assert_same(got, want, "out-of-range starts")


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_invalid_candidates_masked(backend):
    """Fully padded rows: masked scores, not ok, and slot 0 wins."""
    ref, r1, r2, p1, p2 = _world(8, 4, seed=11)
    res = candidate_pair_align(jnp.asarray(ref), r1, r2, p1, p2, E,
                               backend=backend, block=8)
    row = 4  # _world invalidates row b//2 entirely
    assert int(res.pos1[row]) == int(INVALID_LOC)
    assert int(res.score1[row]) == -(1 << 20)
    assert not bool(res.ok1[row]) and not bool(res.ok2[row])
    assert int(res.slot[row]) == 0
    # planted rows map with positive scores
    assert bool(res.ok1[0]) and int(res.score1[0]) > 0


def test_planted_exact_pair_wins():
    """The planted candidate (slot 0) beats random candidates."""
    ref, r1, r2, p1, p2 = _world(8, 4, seed=2)
    res = candidate_pair_align(jnp.asarray(ref), r1, r2, p1, p2, E,
                               backend="interpret", block=8)
    for i in (0, 2):
        if int(p1[i, 0]) != int(INVALID_LOC):
            assert int(res.slot[i]) == 0
            assert int(res.pos1[i]) == int(p1[i, 0])
            assert int(res.score1[i]) == 2 * R  # perfect match score


def test_wide_candidate_set_all_invalid_row():
    """C >= 128 once made the kernel's non-selected key floor overlap the
    worst selected key (all-invalid prescreen picks), turning the one-hot
    reduction multi-hot; regression for the key_floor fix."""
    rng = np.random.default_rng(21)
    r_, e_, c_ = 16, 1, 128
    ref = rng.integers(0, 4, (600,), dtype=np.uint8)
    pos1 = rng.integers(e_, 600 - r_ - e_, (2, c_)).astype(np.int32)
    pos2 = pos1.copy()
    pos1[0, :] = INVALID_LOC   # row 0: every candidate invalid
    pos2[0, :] = INVALID_LOC
    reads1 = rng.integers(0, 4, (2, r_), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (2, r_), dtype=np.uint8)
    reads1[1] = ref[pos1[1, 0]:pos1[1, 0] + r_]
    reads2[1] = ref[pos2[1, 0]:pos2[1, 0] + r_]
    args = (jnp.asarray(ref), jnp.asarray(reads1), jnp.asarray(reads2),
            jnp.asarray(pos1), jnp.asarray(pos2), e_)
    got = candidate_pair_align(*args, prescreen_top=2, backend="interpret",
                               block=2)
    want = candidate_pair_align(*args, prescreen_top=2, backend="jnp")
    _assert_same(got, want, "wide-C all-invalid row")
    assert int(got.slot[0]) < c_   # in-range slot, not a multi-hot sum


@pytest.mark.parametrize("packed", [False, True])
def test_prescreen_sweep_bit_exact(packed):
    """Kernel == oracle across prescreen_top in {0, 1, C//2, C}, both
    gather flavors (acceptance sweep for the in-kernel prescreen skip).
    C=4 / two grid steps keeps interpret-mode compile time tolerable
    while still exercising the ping-pong banks and the skip gather."""
    C = 4
    ref, r1, r2, p1, p2 = _world(8, C, seed=17)
    ref_in = jnp.asarray(pack_2bit(jnp.asarray(ref))) if packed \
        else jnp.asarray(ref)
    for ps in (0, 1, C // 2, C):
        got = candidate_pair_align(ref_in, r1, r2, p1, p2, E,
                                   backend="interpret", block=4,
                                   prescreen_top=ps, packed_ref=packed)
        want = candidate_pair_align(ref_in, r1, r2, p1, p2, E,
                                    backend="jnp",
                                    prescreen_top=ps, packed_ref=packed)
        _assert_same(got, want, f"packed={packed} prescreen={ps}")


@pytest.mark.parametrize("packed", [False, True])
def test_prescreen_skip_traces_at_most_top_alignments(packed):
    """The G2 compute saving is real skipped work on the Pallas backend:
    with the prescreen on, the kernel traces exactly `prescreen_top` full
    `align_block` alignments per mate (not C) — i.e. <= prescreen_top
    alignments per row.  `align_block` is statically unrolled per
    candidate, so the trace-time call count IS the per-row work."""
    C = 4
    ref, r1, r2, p1, p2 = _world(8, C, seed=13)
    ref_in = jnp.asarray(pack_2bit(jnp.asarray(ref))) if packed \
        else jnp.asarray(ref)
    for ps, expect_per_mate in [(0, C), (1, 1), (C // 2, C // 2), (C, C)]:
        candidate_pair_align.clear_cache()   # force a fresh trace
        with count_align_block_calls() as ctr:
            candidate_pair_align(ref_in, r1, r2, p1, p2, E,
                                 backend="interpret", block=4,
                                 prescreen_top=ps, packed_ref=packed)
        assert ctr.count == 2 * expect_per_mate, \
            f"packed={packed} prescreen={ps}: traced {ctr.count} alignments"
        if 0 < ps < C:
            assert ctr.count // 2 <= ps


def _seed_best_candidate_light(ref, reads, starts, cfg):
    """The seed repo's unfused `_best_candidate_light`, kept verbatim as the
    regression oracle for the fused rewrite."""
    B, C = starts.shape
    R_ = cfg.read_len
    valid = starts != INVALID_LOC
    safe = jnp.where(valid, starts, 0)
    wins = gather_ref_windows(ref, safe, R_, cfg.max_gap)
    reads_t = jnp.broadcast_to(reads[:, None, :], (B, C, R_))
    res = light_align(reads_t.reshape(B * C, R_), wins.reshape(B * C, -1),
                      cfg.max_gap, cfg.scoring, cfg.threshold(),
                      cfg.light_mode)
    score = jnp.where(valid.reshape(-1), res.score, -(1 << 20)).reshape(B, C)
    return res, score, valid


def test_map_pairs_regression_vs_seed_math():
    """The pipeline through the fused op == the seed's unfused step-4
    math."""
    rng = np.random.default_rng(0)
    ref = random_reference(60_000, rng)
    cfg = PipelineConfig()
    sm = build_seedmap(ref, SeedMapConfig(table_bits=15))
    sim = simulate_pairs(ref, 48, ReadSimConfig(sub_rate=5e-3, ins_rate=5e-4,
                                                del_rate=5e-4), seed=3)
    reads1, reads2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    ref_j = jnp.asarray(ref)
    res = map_pairs_jit(sm, ref_j, reads1, reads2, cfg)

    # Recompute the light stage with the seed implementation.
    reads2_fwd = (3 - reads2)[:, ::-1]
    seeds1 = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                             sm.config.hash_seed)
    seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len, cfg.seeds_per_read,
                             sm.config.hash_seed)
    q1 = query_read_batch(sm, seeds1, cfg.max_locs_per_seed)
    q2 = query_read_batch(sm, seeds2, cfg.max_locs_per_seed)
    cands = paired_adjacency_filter(q1, q2, cfg.delta, cfg.max_candidates)
    _, sc1, _ = _seed_best_candidate_light(ref_j, reads1, cands.pos1, cfg)
    _, sc2, _ = _seed_best_candidate_light(ref_j, reads2_fwd, cands.pos2, cfg)
    best = jnp.argmax(sc1 + sc2, axis=-1)
    b_pos1 = jnp.take_along_axis(cands.pos1, best[:, None], 1)[:, 0]
    b_sc1 = jnp.take_along_axis(sc1, best[:, None], 1)[:, 0]

    light = np.asarray(res.method) == M_LIGHT
    assert light.mean() > 0.5, "simulated batch should mostly light-map"
    np.testing.assert_array_equal(np.asarray(res.pos1)[light],
                                  np.asarray(b_pos1)[light])
    np.testing.assert_array_equal(np.asarray(res.score1)[light],
                                  np.asarray(b_sc1)[light])
    # light-mapped rows must have cleared the acceptance threshold
    assert (np.asarray(b_sc1)[light] >= cfg.threshold()).all()
    hist = np.bincount(np.asarray(res.method), minlength=5)
    assert hist.sum() == 48


def test_map_pairs_interpret_backend_matches_jnp():
    """The whole pipeline agrees between jnp and interpret backends."""
    rng = np.random.default_rng(1)
    ref = random_reference(40_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 24, ReadSimConfig(sub_rate=2e-3), seed=5)
    reads1, reads2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    ref_j = jnp.asarray(ref)
    res_jnp = map_pairs_jit(sm, ref_j, reads1, reads2,
                            PipelineConfig(light_backend="jnp"))
    res_int = map_pairs_jit(sm, ref_j, reads1, reads2,
                            PipelineConfig(light_backend="interpret"))
    for f in ("pos1", "pos2", "score1", "score2", "method",
              "cigar1", "cigar2"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_jnp, f)), np.asarray(getattr(res_int, f)),
            err_msg=f"field {f}")


def test_map_pairs_packed_ref():
    """cfg.packed_ref=True runs the whole pipeline against the 2-bit
    packed reference: jnp and interpret backends agree bit-for-bit, and
    the mapping matches the unpacked flavor away from reference edges."""
    rng = np.random.default_rng(6)
    ref = random_reference(40_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 24, ReadSimConfig(sub_rate=2e-3), seed=4)
    reads1, reads2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    ref_j = jnp.asarray(ref)
    res_pj = map_pairs_jit(
        sm, ref_j, reads1, reads2,
        PipelineConfig(packed_ref=True, light_backend="jnp"))
    res_pi = map_pairs_jit(sm, ref_j, reads1, reads2,
                           PipelineConfig(packed_ref=True,
                                          light_backend="interpret"))
    for f in ("pos1", "pos2", "score1", "score2", "method",
              "cigar1", "cigar2"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_pj, f)), np.asarray(getattr(res_pi, f)),
            err_msg=f"packed field {f}")
    res_u = map_pairs_jit(sm, ref_j, reads1, reads2,
                          PipelineConfig(light_backend="jnp"))
    same = (np.asarray(res_pj.pos1) == np.asarray(res_u.pos1)).mean()
    assert same >= 0.95, f"packed flavor changed {1 - same:.1%} of positions"
    light = np.asarray(res_pj.method) == M_LIGHT
    assert light.mean() > 0.5


def test_prescreen_keeps_mapping_in_map_pairs():
    """prescreen_top keeps the mapping in the replicated-index
    pipeline."""
    rng = np.random.default_rng(2)
    ref = random_reference(40_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 32, ReadSimConfig(sub_rate=2e-3), seed=9)
    reads1, reads2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    ref_j = jnp.asarray(ref)
    base = map_pairs_jit(sm, ref_j, reads1, reads2, PipelineConfig())
    pre = map_pairs_jit(sm, ref_j, reads1, reads2,
                        PipelineConfig(prescreen_top=2))
    same = (np.asarray(base.pos1) == np.asarray(pre.pos1)).mean()
    assert same >= 0.95, f"prescreen changed {1 - same:.1%} of positions"
