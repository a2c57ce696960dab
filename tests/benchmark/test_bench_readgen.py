"""The benchmark's vectorised read generator against the program's
per-base simulator (`repro.core.simulate`): the same error model, so
the same edits per read and fragment lengths within sampling error, and
true starts that reproduce the reads."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench.readgen import make_genome, make_pool, simulate_long, \
    simulate_pairs
from repro.core.simulate import ReadSimConfig
from repro.core.simulate import simulate_pairs as program_simulate_pairs

GENOME = make_genome(200_000, 7, repeat_frac=0.5, n_families=8,
                     family_len=300, divergence=0.1)
RATES = {"sub_rate": 0.01, "ins_rate": 0.002, "del_rate": 0.002}
SHAPE = {"read_len": 150, "insert_mean": 300.0, "insert_std": 30.0,
         "edge_pad": 64}


def test_genome_is_fixed_by_its_seed():
    again = make_genome(200_000, 7, repeat_frac=0.5, n_families=8,
                        family_len=300, divergence=0.1)
    assert np.array_equal(GENOME, again)
    assert GENOME.dtype == np.uint8 and GENOME.max() <= 3


def test_repeat_copies_diverge_by_the_stated_share():
    g = make_genome(300 * 400, 3, repeat_frac=1.0, n_families=1,
                    family_len=300, divergence=0.1)
    copies = g.reshape(400, 300)
    # every copy is the motif with at most 30 positions changed; two
    # copies differ in at most 60 and share most bases
    diff = (copies[0] != copies[1:]).sum(axis=1)
    assert diff.max() <= 60 and diff.mean() > 30


def test_error_free_reads_are_the_reference_at_the_true_starts():
    p = simulate_pairs(GENOME, 500, np.random.default_rng(1), **SHAPE,
                       sub_rate=0.0, ins_rate=0.0, del_rate=0.0)
    idx = np.arange(150)
    assert np.array_equal(p["reads1"], GENOME[p["true1"][:, None] + idx])
    fwd2 = (3 - p["reads2"])[:, ::-1]
    assert np.array_equal(fwd2, GENOME[p["true2"][:, None] + idx])
    assert (p["edits"] == 0).all()


def test_substitutions_alone_are_the_edits_counted():
    p = simulate_pairs(GENOME, 500, np.random.default_rng(2), **SHAPE,
                       sub_rate=0.02, ins_rate=0.0, del_rate=0.0)
    idx = np.arange(150)
    ham = (p["reads1"] != GENOME[p["true1"][:, None] + idx]).sum(axis=1)
    assert np.array_equal(ham, p["edits"][:, 0])


def test_matches_the_program_simulator_in_distribution():
    n = 3000
    ours = simulate_pairs(GENOME, n, np.random.default_rng(3), **SHAPE,
                          **RATES)
    theirs = program_simulate_pairs(
        GENOME, n, ReadSimConfig(**SHAPE, **RATES), seed=3)
    # edits per read: a sum of ~150 Bernoulli steps at 1.4%, mean ~2.1,
    # sd ~1.45 per read; the mean of 6000 reads has sd ~0.02 per side
    mean_ours = ours["edits"].mean()
    mean_theirs = theirs.n_edits.mean()
    assert mean_ours == pytest.approx(mean_theirs, abs=0.12)
    assert mean_ours == pytest.approx(150 * 0.014, rel=0.06)
    # fragment length N(300, 30): means agree within ~5 sd of the mean
    ins_theirs = theirs.true_start2 - theirs.true_start1 + 150
    assert ours["insert"].mean() == pytest.approx(ins_theirs.mean(), abs=3.0)
    assert ours["insert"].std() == pytest.approx(ins_theirs.std(), rel=0.08)


def test_long_reads_carry_their_substitutions():
    r = simulate_long(GENOME, 20, np.random.default_rng(4), read_len=3000,
                      sub_rate=0.01, edge_pad=64)
    ref = GENOME[r["true"][:, None] + np.arange(3000)]
    assert np.array_equal((r["reads"] != ref).sum(axis=1), r["edits"])
    assert r["edits"].mean() == pytest.approx(30, rel=0.25)


def test_pool_is_fixed_by_the_seed_and_checks_the_lane():
    mix = {"lane": "pairs", "pool_batches": 2, **SHAPE, **RATES}
    a = make_pool(GENOME, "pairs", 64, mix, seed=2**31 + 11)
    b = make_pool(GENOME, "pairs", 64, mix, seed=2**31 + 11)
    assert len(a) == 2 and a[0]["reads1"].shape == (64, 150)
    assert all(np.array_equal(x["reads2"], y["reads2"]) for x, y in zip(a, b))
    with pytest.raises(ValueError):
        make_pool(GENOME, "long", 64, mix, seed=1)
