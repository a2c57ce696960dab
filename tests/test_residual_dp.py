"""Tests for the fused residual-DP fallback op (kernels/residual_dp).

- interpret-mode Pallas kernel vs the staged jnp oracle across a
  (band, dp_pad, packed_ref, residual-mix) grid, including all-light
  (zero items), all-residual (every mate failed) and INVALID_LOC rows;
- the ``band >= W`` exactness anchor against the unbanded
  `gotoh_semiglobal`;
- runtime single-mate skip instrumentation: at ``block=1`` the kernel
  executes DP for exactly the failed mates (`dp_lanes`), and both DP
  kernel families trace the one shared `dp_block` recurrence;
- `map_pairs_impl` end-to-end parity between the jnp oracle and the interpret
  kernel behind ``PipelineConfig.residual_backend``, plus the
  ``residual_capacity_frac=0`` static-skip semantics (no DP traced, all
  residual rows routed to M_DP_OVERFLOW).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs,
)
from repro.core.dp_fallback import NEG, gotoh_semiglobal
from repro.core.encoding import pack_2bit
from repro.core.pipeline import (
    M_DP, M_DP_OVERFLOW, M_LIGHT, map_pairs_impl, stage_stat_counts,
)
from repro.core.seedmap import INVALID_LOC
from repro.kernels.banded_sw.kernel import count_dp_block_calls
from repro.kernels.banded_sw.ops import banded_sw
from repro.kernels.residual_dp import residual_pair_dp


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))

L, R = 5000, 100
_CMP = ("score1", "ref_end1", "score2", "ref_end2")  # the bit-exact fields


def _world(n, seed=0, need_rate=0.6, invalid_row=True):
    """Synthetic ref + residual rows with random per-mate need masks."""
    rng = np.random.default_rng(seed)
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    pos1 = rng.integers(0, L - R - 32, (n,)).astype(np.int32)
    pos2 = rng.integers(0, L - R - 32, (n,)).astype(np.int32)
    need1 = rng.random(n) < need_rate
    need2 = rng.random(n) < need_rate
    if invalid_row:
        pos1[0] = INVALID_LOC       # padding row: no candidate at all
        pos2[0] = INVALID_LOC
        need1[0] = need2[0] = False
    reads1 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    # half the needed rows: the read is a (noisy) copy of its window
    for i in range(1, n, 2):
        if pos1[i] != INVALID_LOC:
            reads1[i] = ref[pos1[i]:pos1[i] + R]
            reads2[i] = ref[pos2[i]:pos2[i] + R]
    return (ref, jnp.asarray(reads1), jnp.asarray(reads2),
            jnp.asarray(pos1), jnp.asarray(pos2),
            jnp.asarray(need1), jnp.asarray(need2))


def _assert_cmp(a, b, msg=""):
    for f in _CMP:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"field {f} {msg}")


def test_unknown_backend_raises():
    ref, r1, r2, p1, p2, n1, n2 = _world(4)
    with pytest.raises(ValueError, match="unknown backend"):
        residual_pair_dp(jnp.asarray(ref), r1, r2, p1, p2, n1, n2, 8,
                         backend="bogus")


@pytest.mark.parametrize("n", [5, 8, 16])
@pytest.mark.parametrize("band", [4, 12, 24, None])
def test_kernel_matches_oracle_unpacked(n, band):
    ref, r1, r2, p1, p2, n1, n2 = _world(n, seed=n * 7 + (band or 99))
    args = (jnp.asarray(ref), r1, r2, p1, p2, n1, n2, 12)
    got = residual_pair_dp(*args, band=band, backend="interpret", block=4)
    want = residual_pair_dp(*args, band=band, backend="jnp")
    _assert_cmp(got, want, f"n={n} band={band}")


@pytest.mark.parametrize("dp_pad", [8, 16])
@pytest.mark.parametrize("band", [6, 20, None])
def test_kernel_matches_oracle_packed(dp_pad, band):
    ref, r1, r2, p1, p2, n1, n2 = _world(9, seed=dp_pad + (band or 50))
    words = jnp.asarray(pack_2bit(jnp.asarray(ref)))
    args = (words, r1, r2, p1, p2, n1, n2, dp_pad)
    got = residual_pair_dp(*args, band=band, packed_ref=True,
                           backend="interpret", block=2)
    want = residual_pair_dp(*args, band=band, packed_ref=True, backend="jnp")
    _assert_cmp(got, want, f"packed dp_pad={dp_pad} band={band}")


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_band_ge_w_is_exact_full_dp(backend):
    """The exactness anchor: band >= W reproduces gotoh_semiglobal."""
    dp_pad = 12
    W = R + 2 * dp_pad
    ref, r1, r2, p1, p2, n1, n2 = _world(8, seed=3)
    res = residual_pair_dp(jnp.asarray(ref), r1, r2, p1, p2, n1, n2,
                           dp_pad, band=W, backend=backend, block=4)
    # Staged full-DP recomputation on the needed mates.
    from repro.core.light_align import gather_ref_windows
    for reads, pos, need, sc, end in (
            (r1, p1, n1, res.score1, res.ref_end1),
            (r2, p2, n2, res.score2, res.ref_end2)):
        safe = jnp.where(pos != INVALID_LOC, pos, 0)
        win = gather_ref_windows(jnp.asarray(ref), safe, R, dp_pad)
        dp = gotoh_semiglobal(reads, win)
        nd = np.asarray(need)
        np.testing.assert_array_equal(np.asarray(sc)[nd],
                                      np.asarray(dp.score)[nd])
        np.testing.assert_array_equal(np.asarray(end)[nd],
                                      np.asarray(dp.ref_end)[nd])
        assert (np.asarray(sc)[~nd] == NEG).all()


@pytest.mark.parametrize("packed", [False, True])
def test_all_light_batch_zero_items(packed):
    """No failed mates: every block is dead — sentinels, zero DP lanes."""
    ref, r1, r2, p1, p2, _, _ = _world(8, seed=11)
    zeros = jnp.zeros((8,), bool)
    ref_in = jnp.asarray(pack_2bit(jnp.asarray(ref))) if packed \
        else jnp.asarray(ref)
    got = residual_pair_dp(ref_in, r1, r2, p1, p2, zeros, zeros, 12,
                           packed_ref=packed, backend="interpret", block=4)
    assert (np.asarray(got.score1) == NEG).all()
    assert (np.asarray(got.score2) == NEG).all()
    assert int(got.dp_lanes) == 0


def test_all_residual_batch_both_mates():
    """Every mate failed: items fill the whole buffer, all lanes execute."""
    ref, r1, r2, p1, p2, _, _ = _world(8, seed=12, invalid_row=False)
    ones = jnp.ones((8,), bool)
    args = (jnp.asarray(ref), r1, r2, p1, p2, ones, ones, 12)
    got = residual_pair_dp(*args, backend="interpret", block=4)
    want = residual_pair_dp(*args, backend="jnp")
    _assert_cmp(got, want, "all-residual")
    assert int(got.dp_lanes) == 16
    assert (np.asarray(got.score1) > NEG).all()


@pytest.mark.parametrize("packed", [False, True])
def test_single_mate_skip_runs_exactly_failed_mates(packed):
    """The single-mate saving is real skipped work: at block=1 the kernel
    executes the DP scan for exactly the failed-mate items (grid steps
    past the compacted item count skip at runtime), not 2 per residual
    row."""
    ref, r1, r2, p1, p2, n1, n2 = _world(10, seed=21, need_rate=0.4)
    ref_in = jnp.asarray(pack_2bit(jnp.asarray(ref))) if packed \
        else jnp.asarray(ref)
    got = residual_pair_dp(ref_in, r1, r2, p1, p2, n1, n2, 12,
                           packed_ref=packed, backend="interpret", block=1)
    expect = int(np.asarray(n1).sum() + np.asarray(n2).sum())
    assert int(got.dp_lanes) == expect
    assert expect < 2 * 10  # the mix really is single-mate-ish


@pytest.mark.parametrize("band", [2, 24, None])
def test_out_of_range_starts_match_oracle(band):
    """Negative starts (merge_read_starts emits start = location -
    seed_offset, negative near the reference origin) and starts past L
    must gather the same clamped windows on every backend — regression
    for the kernel prep clamping to [0, L-1] while the oracle clamps per
    element."""
    rng = np.random.default_rng(31)
    n, dp_pad = 8, 8
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    pos1 = np.array([-3, -40, -(R + 2 * dp_pad + 5), 0, 2, L - 1,
                     L + 7, L + 500], np.int32)
    pos2 = pos1[::-1].copy()
    need = jnp.ones((n,), bool)
    reads1 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    reads1[0, :R - 3] = ref[:R - 3]          # planted truncated-edge read
    reads2 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    args = (jnp.asarray(ref), jnp.asarray(reads1), jnp.asarray(reads2),
            jnp.asarray(pos1), jnp.asarray(pos2), need, need, dp_pad)
    got = residual_pair_dp(*args, band=band, backend="interpret", block=4)
    want = residual_pair_dp(*args, band=band, backend="jnp")
    _assert_cmp(got, want, f"out-of-range starts band={band}")


@pytest.mark.parametrize("b,r,w", [(8, 150, 182), (5, 40, 56), (3, 100, 132)])
def test_frame_oracle_matches_masked_reference(b, r, w):
    """The O(R*K) moving-frame jnp oracle == the independent O(R*W)
    masked-full-width formulation, cell-for-cell, across bands and odd
    W-R centers (the cross-check that keeps oracle and kernels honest
    about sharing one arithmetic)."""
    from repro.core.dp_fallback import (
        _gotoh_banded_masked, gotoh_semiglobal_banded,
    )

    rng = np.random.default_rng(b + r + w)
    read = jnp.asarray(rng.integers(0, 4, (b, r), np.uint8))
    win = jnp.asarray(rng.integers(0, 4, (b, w), np.uint8))
    for band in (1, 5, 24, w):
        fr = gotoh_semiglobal_banded(read, win, band)
        mk = _gotoh_banded_masked(read.astype(jnp.int32),
                                  win.astype(jnp.int32), band)
        np.testing.assert_array_equal(np.asarray(fr.score),
                                      np.asarray(mk.score), f"band={band}")
        np.testing.assert_array_equal(np.asarray(fr.ref_end),
                                      np.asarray(mk.ref_end), f"band={band}")


def test_dp_families_share_one_dp_block():
    """banded_sw and residual_dp route through the same `dp_block`
    recurrence: each launch traces it exactly once (the kernel body is
    traced once regardless of grid size)."""
    ref, r1, r2, p1, p2, n1, n2 = _world(8, seed=5)
    residual_pair_dp.clear_cache()
    with count_dp_block_calls() as ctr:
        residual_pair_dp(jnp.asarray(ref), r1, r2, p1, p2, n1, n2, 12,
                         band=16, backend="interpret", block=4)
    assert ctr.count == 1, ctr.count
    banded_sw.clear_cache()
    win = jnp.asarray(np.random.default_rng(0).integers(
        0, 4, (8, R + 24), np.uint8))
    with count_dp_block_calls() as ctr:
        banded_sw(r1, win, band=16, backend="interpret", block=8)
    assert ctr.count == 1, ctr.count


# ---------------------------------------------------------- pipeline ----
def _sim_world(ref_len=40_000, bits=14, n=24, sub=2e-2, seed=5):
    rng = np.random.default_rng(seed)
    ref = random_reference(ref_len, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=bits))
    sim = simulate_pairs(ref, n, ReadSimConfig(sub_rate=sub), seed=seed)
    return ref, sm, jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)


@pytest.mark.parametrize("cfg_kw", [
    dict(),                                           # default band, mixed
    dict(dp_band=8),                                  # tight band
    dict(dp_band=1 << 10),                            # band >= W: exact DP
    dict(packed_ref=True),                            # packed windows
    dict(residual_capacity_frac=0.9),                 # near-all-residual
    dict(residual_capacity_frac=0.05),                # overflow regime
])
def test_map_pairs_residual_backend_parity(cfg_kw):
    """The pipeline with residual_backend=interpret is bit-identical to
    the jnp oracle across the (band, packed, residual-mix) grid."""
    ref, sm, r1, r2 = _sim_world(sub=3e-2)
    refj = jnp.asarray(ref)
    res_j = map_pairs_jit(sm, refj, r1, r2,
                          PipelineConfig(residual_backend="jnp", **cfg_kw))
    res_i = map_pairs_jit(
        sm, refj, r1, r2,
        PipelineConfig(residual_backend="interpret", **cfg_kw))
    for f in res_j._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res_j, f)), np.asarray(getattr(res_i, f)),
            err_msg=f"field {f} cfg={cfg_kw}")


def test_map_pairs_all_light_and_all_residual_parity():
    """Degenerate mixes: a perfect batch (zero DP items) and a garbage
    batch (nothing light-maps) agree across residual backends."""
    rng = np.random.default_rng(7)
    ref = random_reference(40_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    refj = jnp.asarray(ref)
    perfect = simulate_pairs(ref, 16, ReadSimConfig(
        sub_rate=0, ins_rate=0, del_rate=0), seed=1)
    noisy = simulate_pairs(ref, 16, ReadSimConfig(sub_rate=0.12), seed=2)
    for sim in (perfect, noisy):
        r1, r2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
        res_j = map_pairs_jit(sm, refj, r1, r2,
                              PipelineConfig(residual_backend="jnp",
                                             residual_capacity_frac=0.9))
        res_i = map_pairs_jit(sm, refj, r1, r2,
                              PipelineConfig(residual_backend="interpret",
                                             residual_capacity_frac=0.9))
        for f in res_j._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(res_j, f)),
                np.asarray(getattr(res_i, f)), err_msg=f"field {f}")
    m = np.asarray(map_pairs_jit(sm, refj, jnp.asarray(perfect.reads1),
                                 jnp.asarray(perfect.reads2)).method)
    assert (m == M_LIGHT).all()


def test_residual_capacity_zero_statically_skips_dp():
    """frac=0: no DP work is traced at all (count_dp_block_calls stays 0
    on the kernel backend), and every residual row reports overflow."""
    ref, sm, r1, r2 = _sim_world(sub=5e-2, seed=9)
    refj = jnp.asarray(ref)
    cfg0 = PipelineConfig(residual_capacity_frac=0.0,
                          residual_backend="interpret")
    with count_dp_block_calls() as ctr:
        res = map_pairs_impl(sm, refj, r1, r2, cfg0)  # un-jitted: traces
    assert ctr.count == 0, "frac=0 must not trace any DP"
    m = np.asarray(res.method)
    needs = np.asarray(res.passed_adjacency & ~res.light_ok)
    assert (m == M_DP).sum() == 0
    assert ((m == M_DP_OVERFLOW) == needs).all()
    assert not np.asarray(res.dp_mate1).any()
    assert not np.asarray(res.dp_mate2).any()
    assert int(stage_stat_counts(res)["dp_mate_alignments"]) == 0
    # sanity: the same batch with capacity does trace DP (fresh trace —
    # the op is jitted and other tests may have warmed its cache)
    residual_pair_dp.clear_cache()
    with count_dp_block_calls() as ctr:
        map_pairs_impl(sm, refj, r1, r2,
                       PipelineConfig(residual_backend="interpret"))
    assert ctr.count == 1


def test_residual_items_dispatched_in_window_start_order(monkeypatch):
    """ISSUE-8 satellite: `_residual_dp_stage` orders the compacted DP
    items by mate-1 window start before the kernel dispatch (locality
    for the kernel's window DMA), with filler rows last.  A pure
    permutation — the parity tests above pin that results are unchanged;
    this pins the ordering itself."""
    from repro.kernels.residual_dp import ops as rd_ops

    ref, sm, r1, r2 = _sim_world(n=32, sub=3e-2, seed=17)
    captured = {}
    real = rd_ops.residual_pair_dp

    def spy(ref_in, reads1, reads2, pos1, pos2, need1, need2, *a, **kw):
        captured["pos1"] = np.asarray(pos1)
        captured["need1"] = np.asarray(need1)
        captured["need2"] = np.asarray(need2)
        captured["taken"] = captured["need1"] | captured["need2"]
        return real(ref_in, reads1, reads2, pos1, pos2, need1, need2,
                    *a, **kw)

    monkeypatch.setattr(rd_ops, "residual_pair_dp", spy)
    res = map_pairs_impl(sm, jnp.asarray(ref), r1, r2, PipelineConfig())
    assert captured, "residual stage did not dispatch"
    taken = captured["taken"]
    assert taken.any(), "want real DP items in this regime"
    # taken items first, sorted by window start; filler strictly after
    key = np.where(taken, captured["pos1"], np.iinfo(np.int32).max)
    assert (np.diff(key.astype(np.int64)) >= 0).all(), key
    # and the permutation scattered back losslessly: the dp_mate ledger
    # counts exactly the dispatched items' mates
    dispatched = int(captured["need1"].sum() + captured["need2"].sum())
    assert int(np.asarray(res.dp_mate1).sum()
               + np.asarray(res.dp_mate2).sum()) == dispatched


def test_single_mate_reuses_light_score_in_map_pairs():
    """M_DP rows where one mate's light alignment passed keep that mate's
    light score, and the dp_mate flags ledger the re-aligned mates."""
    ref, sm, r1, r2 = _sim_world(n=48, sub=2.5e-2, seed=13)
    refj = jnp.asarray(ref)
    cfg = PipelineConfig(residual_capacity_frac=0.9)
    res = map_pairs_jit(sm, refj, r1, r2, cfg)
    m = np.asarray(res.method)
    dp1 = np.asarray(res.dp_mate1)
    dp2 = np.asarray(res.dp_mate2)
    dp_rows = m == M_DP
    assert dp_rows.any(), "want some DP rows in this regime"
    # every DP row re-aligned at least one mate, none re-aligned a mate
    # on a non-DP row
    assert ((dp1 | dp2) == dp_rows).all()
    counts = stage_stat_counts(res)
    assert int(counts["dp_mate_alignments"]) == dp1.sum() + dp2.sum()
    assert int(counts["dp_mate_alignments"]) <= 2 * int(counts["dp_mapped"])
    # a passing mate of a DP row keeps a light-accepted (>= threshold)
    # score
    thr = cfg.threshold()
    reused1 = dp_rows & ~dp1
    if reused1.any():
        assert (np.asarray(res.score1)[reused1] >= thr).all()
    reused2 = dp_rows & ~dp2
    if reused2.any():
        assert (np.asarray(res.score2)[reused2] >= thr).all()
