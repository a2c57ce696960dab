"""Integration tests: end-to-end GenPair pipeline, simulator, baseline,
long reads, residual routing."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    INVALID_LOC, PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, random_reference, simulate_pairs, stage_stats,
)
from repro.core.baseline import exact_match_rate, map_single_end
from repro.core.long_read import LongReadConfig, map_long_reads
from repro.core.pipeline import (
    M_DP, M_DP_OVERFLOW, M_LIGHT, M_RESIDUAL_FULL, M_UNMAPPED,
)


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    ref = random_reference(150_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=18, max_locations=128))
    return ref, sm


def test_perfect_reads_all_light_mapped(world):
    ref, sm = world
    sim = simulate_pairs(ref, 32, ReadSimConfig(sub_rate=0, ins_rate=0, del_rate=0), seed=1)
    res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2))
    assert (np.asarray(res.method) == M_LIGHT).all()
    np.testing.assert_array_equal(np.asarray(res.pos1), sim.true_start1)
    np.testing.assert_array_equal(np.asarray(res.pos2), sim.true_start2)
    assert (np.asarray(res.score1) == 300).all()
    assert (np.asarray(res.score2) == 300).all()


def test_noisy_reads_mostly_mapped_correctly(world):
    ref, sm = world
    sim = simulate_pairs(ref, 128, ReadSimConfig(sub_rate=0.005), seed=2)
    res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2))
    pos1 = np.asarray(res.pos1)
    mapped = pos1 != INVALID_LOC
    assert mapped.mean() > 0.9
    correct = np.abs(pos1[mapped] - sim.true_start1[mapped]) <= 8
    assert correct.mean() > 0.98
    # no NaN-analogue: scores of mapped reads are sane
    assert (np.asarray(res.score1)[mapped] > 0).all()


def test_stage_stats_consistency(world):
    ref, sm = world
    sim = simulate_pairs(ref, 64, ReadSimConfig(sub_rate=0.01), seed=3)
    res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2))
    st = {k: float(v) for k, v in stage_stats(res).items()}
    total = (st["light_mapped"] + st["dp_mapped"] + st["dp_overflow"]
             + st["residual_full_dp"])
    # unmapped-without-flag is impossible: every pair is accounted for
    assert total <= 1.0 + 1e-6
    assert st["light_mapped"] > 0.3


def test_method_codes_partition_batch(world):
    """Every row carries exactly one M_UNMAPPED..M_DP_OVERFLOW code,
    consistent with the had_hits/passed_adjacency/light_ok flags, and
    stage_stats fractions are non-negative, bounded by 1, and partition
    the batch.  Two regimes: mostly-light and DP-starved (overflow)."""
    ref, sm = world
    for sub, frac, seed in ((0.01, 0.25, 12), (0.05, 0.02, 13)):
        sim = simulate_pairs(ref, 96, ReadSimConfig(sub_rate=sub), seed=seed)
        res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                            jnp.asarray(sim.reads2),
                            PipelineConfig(residual_capacity_frac=frac))
        m = np.asarray(res.method)
        had = np.asarray(res.had_hits)
        passed = np.asarray(res.passed_adjacency)
        lok = np.asarray(res.light_ok)
        assert ((m >= M_UNMAPPED) & (m <= M_DP_OVERFLOW)).all()
        # flag implications: candidates need hits, acceptance needs cands
        assert (passed <= had).all()
        assert (lok <= passed).all()
        # the method code is a function of the flags (a partition)
        np.testing.assert_array_equal(m == M_LIGHT, lok)
        np.testing.assert_array_equal(m == M_RESIDUAL_FULL, ~passed)
        np.testing.assert_array_equal(
            (m == M_DP) | (m == M_DP_OVERFLOW), passed & ~lok)
        st = {k: float(v) for k, v in stage_stats(res).items()}
        for k, v in st.items():
            assert 0.0 <= v <= 1.0 + 1e-9, (k, v)
        assert abs(st["light_mapped"] + st["dp_mapped"] + st["dp_overflow"]
                   + st["residual_full_dp"] - 1.0) < 1e-6
        assert abs(st["no_seed_hit"] + st["adjacency_fail"]
                   - st["residual_full_dp"]) < 1e-6


def test_residual_capacity_overflow():
    """With a tiny DP buffer, overflow pairs must be flagged, not dropped."""
    rng = np.random.default_rng(4)
    ref = random_reference(80_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=16))
    # very noisy reads force DP fallback
    sim = simulate_pairs(ref, 64, ReadSimConfig(sub_rate=0.06), seed=5)
    cfg = PipelineConfig(residual_capacity_frac=0.05)
    res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2), cfg)
    m = np.asarray(res.method)
    needs_dp = np.asarray(res.passed_adjacency & ~res.light_ok)
    cap = max(1, round(64 * 0.05))
    assert (m == M_DP).sum() <= cap
    assert (m == M_DP).sum() + (m == 4).sum() == needs_dp.sum()


def test_dp_rescues_noisy_pairs(world):
    ref, sm = world
    sim = simulate_pairs(ref, 64, ReadSimConfig(sub_rate=0.03), seed=6)
    cfg = PipelineConfig(residual_capacity_frac=0.9)
    res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2), cfg)
    m = np.asarray(res.method)
    assert (m == M_DP).sum() > 0
    dp_pos = np.asarray(res.pos1)[m == M_DP]
    dp_true = sim.true_start1[m == M_DP]
    assert (np.abs(dp_pos - dp_true) <= 8).mean() > 0.9


def test_paper_mode_vs_minsplit_accept_rate(world):
    """minsplit (beyond-paper) must accept at least as many pairs."""
    ref, sm = world
    sim = simulate_pairs(ref, 128, ReadSimConfig(sub_rate=0.01), seed=7)
    r_paper = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                            jnp.asarray(sim.reads2),
                            PipelineConfig(light_mode="paper"))
    r_ms = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                         jnp.asarray(sim.reads2),
                         PipelineConfig(light_mode="minsplit"))
    n_paper = (np.asarray(r_paper.method) == M_LIGHT).sum()
    n_ms = (np.asarray(r_ms.method) == M_LIGHT).sum()
    assert n_ms >= n_paper


def test_simulator_ground_truth(world):
    ref, _ = world
    sim = simulate_pairs(ref, 16, ReadSimConfig(sub_rate=0, ins_rate=0, del_rate=0), seed=8)
    for i in range(16):
        np.testing.assert_array_equal(
            sim.reads1[i], ref[sim.true_start1[i] : sim.true_start1[i] + 150]
        )
        # read2 is revcomp of its reference window
        from repro.core.encoding import revcomp
        fwd = np.asarray(revcomp(jnp.asarray(sim.reads2[i])))
        np.testing.assert_array_equal(
            fwd, ref[sim.true_start2[i] : sim.true_start2[i] + 150]
        )


def test_exact_match_rate_observation(world):
    """§3.2: paired-end both-exact rate < single-end exact rate."""
    ref, _ = world
    sim = simulate_pairs(ref, 256, ReadSimConfig(sub_rate=0.004), seed=9)
    r1 = float(exact_match_rate(jnp.asarray(sim.reads1), jnp.asarray(ref),
                                jnp.asarray(sim.true_start1)))
    from repro.core.encoding import revcomp
    r2fwd = np.asarray(revcomp(jnp.asarray(sim.reads2)))
    r2 = float(exact_match_rate(jnp.asarray(r2fwd), jnp.asarray(ref),
                                jnp.asarray(sim.true_start2)))
    single = (r1 + r2) / 2
    # paired = both reads exact
    w1 = np.abs(sim.reads1 - np.stack([ref[s:s+150] for s in sim.true_start1])).sum(1) == 0
    w2 = np.abs(r2fwd - np.stack([ref[s:s+150] for s in sim.true_start2])).sum(1) == 0
    paired = (w1 & w2).mean()
    assert paired <= single + 1e-9


def test_baseline_single_end(world):
    ref, sm = world
    sim = simulate_pairs(ref, 32, ReadSimConfig(sub_rate=0.005), seed=10)
    res = map_single_end(sm, jnp.asarray(ref), jnp.asarray(sim.reads1))
    pos = np.asarray(res.pos)
    mapped = np.asarray(res.mapped)
    assert mapped.mean() > 0.9
    assert (np.abs(pos[mapped] - sim.true_start1[mapped]) <= 16).mean() > 0.95


def test_long_reads(world):
    ref, sm = world
    rng = np.random.default_rng(11)
    B, L = 4, 1500
    starts = rng.integers(0, len(ref) - L - 64, B)
    reads = np.stack([ref[s : s + L] for s in starts]).astype(np.uint8)
    # sprinkle 0.5% substitutions
    mask = rng.random(reads.shape) < 0.005
    reads = np.where(mask, (reads + 1) % 4, reads).astype(np.uint8)
    res = map_long_reads(sm, jnp.asarray(ref), jnp.asarray(reads),
                         LongReadConfig())
    assert np.asarray(res.mapped).all()
    err = np.abs(np.asarray(res.position) - starts)
    assert (err <= 64).all()  # within one vote bin
