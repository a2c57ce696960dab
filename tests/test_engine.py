"""Engine API tests: Mapper sessions, execution plans, streaming, shims.

Covers the ISSUE-4 acceptance points that run on one device:
  * `Mapper.map` is bit-identical to pre-refactor `map_pairs` on both the
    jnp-oracle and interpret-kernel backends;
  * CSR `SeedMap` -> `PaddedSeedMap` relayout round-trips (property test
    vs the in-jit `padded_rows_device` derivation);
  * ragged tail batches flow through `map_stream` as padding + an
    `n_valid` mask, and the device-side stage totals/reductions exclude
    the padded rows;
  * the deprecation shims warn exactly once per process and delegate.

(The mesh plans — data-parallel and sharded-index — are pinned by
tests/_distributed_worker.py checks 2, 3 and 6.)
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.compat import reset_deprecation_warnings
from repro.core import (
    INVALID_LOC, PipelineConfig, ReadSimConfig, SeedMapConfig,
    build_seedmap, map_pairs, random_reference, simulate_pairs,
    stage_stat_counts, to_padded,
)
from repro.core.query import padded_rows_device, query_csr, query_padded
from repro.engine import ExecutionConfig, Mapper


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    ref = random_reference(120_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=16))
    sim = simulate_pairs(ref, 48, ReadSimConfig(sub_rate=3e-3), seed=1)
    return ref, sm, sim


@pytest.fixture(scope="module")
def small_world():
    rng = np.random.default_rng(3)
    ref = random_reference(30_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 16, ReadSimConfig(sub_rate=3e-3), seed=4)
    return ref, sm, sim


def _assert_same_result(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f)


# ------------------------------------------------------- bit-exactness ---
def test_mapper_matches_map_pairs_jnp(world):
    ref, sm, sim = world
    cfg = PipelineConfig(light_backend="jnp", frontend_backend="jnp")
    mapper = Mapper.from_index(sm, ref, cfg,
                               ExecutionConfig(backend="jnp"))
    res_e = mapper.map(sim.reads1, sim.reads2)
    res_l = map_pairs(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                      jnp.asarray(sim.reads2), cfg)
    _assert_same_result(res_e, res_l)
    assert np.asarray(res_e.n_valid).all()


def test_mapper_matches_map_pairs_interpret(small_world):
    ref, sm, sim = small_world
    cfg = PipelineConfig(light_backend="interpret",
                         frontend_backend="interpret")
    # The engine session resolves the CSR map to a host-side
    # `PaddedSeedMap`; map_pairs re-derives padded rows in-jit — the
    # round-trip property below is what makes these meet bit-for-bit.
    mapper = Mapper.from_index(sm, ref, cfg,
                               ExecutionConfig(backend="interpret"))
    from repro.core.seedmap import PaddedSeedMap
    assert isinstance(mapper.index, PaddedSeedMap)
    res_e = mapper.map(sim.reads1, sim.reads2)
    res_l = map_pairs(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                      jnp.asarray(sim.reads2), cfg)
    _assert_same_result(res_e, res_l)


def test_mapper_packed_ref_matches_unpacked_positions(world):
    ref, sm, sim = world
    m_u = Mapper.from_index(sm, ref, PipelineConfig(packed_ref=False))
    m_p = Mapper.from_index(sm, ref, PipelineConfig(packed_ref=True))
    assert m_p.pipe_cfg.packed_ref is True
    res_u = m_u.map(sim.reads1, sim.reads2)
    res_p = m_p.map(sim.reads1, sim.reads2)
    # The two gather flavors clamp reference-edge windows differently;
    # mapped positions away from the edges must agree.
    pos_u, pos_p = np.asarray(res_u.pos1), np.asarray(res_p.pos1)
    interior = (pos_u > 64) & (pos_u < len(ref) - 500)
    np.testing.assert_array_equal(pos_u[interior], pos_p[interior])


def test_build_resolves_once(world):
    ref, _, _ = world
    mapper = Mapper.build(ref, SeedMapConfig(table_bits=16))
    assert mapper.pipe_cfg.light_backend in ("pallas", "interpret", "jnp")
    assert mapper.pipe_cfg.frontend_backend in ("pallas", "interpret",
                                                "jnp")
    assert isinstance(mapper.pipe_cfg.packed_ref, bool)


def test_exec_backend_override(world):
    ref, sm, _ = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(backend="jnp"))
    assert mapper.pipe_cfg.light_backend == "jnp"
    assert mapper.pipe_cfg.frontend_backend == "jnp"
    with pytest.raises(ValueError):
        Mapper.from_index(sm, ref, PipelineConfig(),
                          ExecutionConfig(backend="nope"))


def test_shard_index_requires_mesh():
    with pytest.raises(ValueError):
        ExecutionConfig(shard_index=True)


# ---------------------------------------------- CSR -> padded round-trip --
# (The randomized Hypothesis version of this property lives in
# tests/test_properties.py; this parametrized grid keeps the contract
# pinned even on a minimal install without hypothesis.)
@pytest.mark.parametrize("ref_len,table_bits,cap,data_seed", [
    (2_000, 8, 2, 0),
    (5_000, 10, 7, 1),
    (12_000, 12, 32, 2),
    (8_000, 9, 48, 3),
])
def test_padded_relayout_round_trip(ref_len, table_bits, cap, data_seed):
    """Host-side `to_padded` == in-jit `padded_rows_device` at any cap,
    and a padded-row query == the CSR query (the contract that lets the
    engine swap layouts without changing results)."""
    rng = np.random.default_rng(data_seed)
    ref = random_reference(ref_len, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=table_bits))
    psm = to_padded(sm, cap=cap)
    assert psm.rows.shape == (sm.config.table_size, cap)
    np.testing.assert_array_equal(
        np.asarray(psm.rows), np.asarray(padded_rows_device(sm, cap)))
    hashes = rng.integers(0, 2**32, size=64, dtype=np.uint32)
    locs_csr, n_csr = query_csr(sm, jnp.asarray(hashes), cap)
    locs_pad, n_pad = query_padded(psm, jnp.asarray(hashes))
    np.testing.assert_array_equal(np.asarray(locs_csr),
                                  np.asarray(locs_pad))
    np.testing.assert_array_equal(np.asarray(n_csr), np.asarray(n_pad))


# --------------------------------------------------------- map_stream ----
def test_map_stream_ragged_tail_and_totals(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))
    tail = 13
    seen = []
    sr = mapper.map_stream(
        iter([(sim.reads1, sim.reads2),
              (sim.reads1[:tail], sim.reads2[:tail])]),
        on_result=lambda i, res, n: seen.append((i, n, res)))
    assert sr.n_pairs == 48 + tail == sr.totals["n_pairs"]
    assert sr.n_batches == 2
    assert [s[:2] for s in seen] == [(0, 48), (1, tail)]
    # the tail result is padded to the stream shape and masked
    tail_res = seen[1][2]
    assert tail_res.pos1.shape[0] == 48
    nv = np.asarray(tail_res.n_valid)
    assert nv[:tail].all() and not nv[tail:].any()
    # device totals == full-batch counts + head-slice counts
    res_full = mapper.map(sim.reads1, sim.reads2)
    full = {k: int(v) for k, v in stage_stat_counts(res_full).items()}
    head = {k: int(v) for k, v in stage_stat_counts(
        jax.tree.map(lambda x: x[:tail], res_full)).items()}
    assert sr.totals == {k: full[k] + head[k] for k in full}


def test_map_stream_reduce_fn_with_aux(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))

    def reduce(acc, res, aux):
        (truth,) = aux
        ok = (res.pos1 != INVALID_LOC) & res.n_valid
        hit = ok & (jnp.abs(res.pos1 - truth) <= 8)
        return acc + jnp.sum(hit.astype(jnp.int32))

    tail = 7
    sr = mapper.map_stream(
        iter([(sim.reads1, sim.reads2, (sim.true_start1,)),
              (sim.reads1[:tail], sim.reads2[:tail],
               (sim.true_start1[:tail],))]),
        reduce_fn=reduce, reduce_init=jnp.zeros((), jnp.int32),
        warmup_batch=(sim.reads1, sim.reads2, (sim.true_start1,)))
    res = mapper.map(sim.reads1, sim.reads2)
    pos1 = np.asarray(res.pos1)
    ok = pos1 != INVALID_LOC
    hits = (np.abs(pos1[ok] - sim.true_start1[ok]) <= 8).sum()
    head_ok = ok[:tail]
    hits_head = (np.abs(pos1[:tail][head_ok]
                        - sim.true_start1[:tail][head_ok]) <= 8).sum()
    assert int(sr.reduced) == int(hits + hits_head)


def test_map_stream_reduce_init_survives_donation(world):
    """The fused step donates its carry; the caller's reduce_init arrays
    must be copied, not consumed, so a state can seed several streams."""
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))
    init = jnp.zeros((), jnp.int32)
    reduce = lambda acc, res, aux: acc + jnp.sum(
        res.n_valid.astype(jnp.int32))
    a = mapper.map_stream(iter([(sim.reads1, sim.reads2)]),
                          reduce_fn=reduce, reduce_init=init)
    b = mapper.map_stream(iter([(sim.reads1, sim.reads2)]),
                          reduce_fn=reduce, reduce_init=init)
    assert int(init) == 0  # untouched
    assert int(a.reduced) == int(b.reduced) == 48


def test_map_stream_oversized_batch_raises(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=16))
    with pytest.raises(ValueError, match="exceeds"):
        mapper.map_stream(iter([(sim.reads1, sim.reads2)]))


# ------------------------------------- stream edge cases (frontdoor) -----
def test_map_stream_empty_iterator(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))
    init = jnp.zeros((), jnp.int32)
    sr = mapper.map_stream(iter([]), reduce_fn=lambda a, r, x: a,
                           reduce_init=init)
    assert sr.n_pairs == 0 and sr.n_batches == 0
    assert sr.seconds == 0.0
    assert all(v == 0 for v in sr.totals.values())
    assert int(sr.reduced) == 0


def test_map_stream_tail_batch_of_one_row(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))
    seen = []
    sr = mapper.map_stream(
        iter([(sim.reads1[:1], sim.reads2[:1])]),
        on_result=lambda i, res, n: seen.append((i, n, res)))
    assert sr.n_pairs == 1 == sr.totals["n_pairs"]
    res = seen[0][2]
    assert res.pos1.shape[0] == 48
    nv = np.asarray(res.n_valid)
    assert nv[0] and not nv[1:].any()
    from repro.engine.stream import pad_tail
    direct = mapper.map(pad_tail(sim.reads1[:1], 48),
                        pad_tail(sim.reads2[:1], 48))
    np.testing.assert_array_equal(np.asarray(res.pos1)[:1],
                                  np.asarray(direct.pos1)[:1])


def test_map_stream_scalar_aux_leaf_through_pad_tail(world):
    """Aux pytrees may carry 0-d (per-batch) leaves: no batch axis to
    pad, passed through to the reduce_fn unchanged."""
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))

    def reduce(acc, res, aux):
        truth, step_id = aux
        ok = (res.pos1 != INVALID_LOC) & res.n_valid
        return acc + step_id * jnp.sum(ok.astype(jnp.int32))

    tail = 5
    sr = mapper.map_stream(
        iter([(sim.reads1, sim.reads2, (sim.true_start1, 1)),
              (sim.reads1[:tail], sim.reads2[:tail],
               (sim.true_start1[:tail], 10))]),
        reduce_fn=reduce, reduce_init=jnp.zeros((), jnp.int32))
    from repro.engine.stream import pad_tail
    full = int((np.asarray(mapper.map(sim.reads1, sim.reads2).pos1)
                != INVALID_LOC).sum())
    head_pos = np.asarray(mapper.map(pad_tail(sim.reads1[:tail], 48),
                                     pad_tail(sim.reads2[:tail], 48)).pos1)
    head = int((head_pos[:tail] != INVALID_LOC).sum())
    assert int(sr.reduced) == full + 10 * head


# -------------------------------------------- stream bugfix regressions --
def test_stream_result_mbp_per_s_is_lane_aware():
    """PR-6 regression: the long lane counts single reads per item, so
    mbp must not hardcode the pair lane's 2-mates factor."""
    from repro.engine.stream import StreamResult
    pairs = StreamResult(n_pairs=100, n_batches=1, seconds=2.0, totals={})
    longs = StreamResult(n_pairs=100, n_batches=1, seconds=2.0, totals={},
                         reads_per_item=1)
    assert pairs.reads_per_item == 2
    assert pairs.mbp_per_s(150) == pytest.approx(100 * 2 * 150 / 2.0 / 1e6)
    assert longs.mbp_per_s(600) == pytest.approx(100 * 600 / 2.0 / 1e6)


def test_map_long_stream_sets_single_read_factor(world):
    from repro.core.simulate import simulate_long_reads
    ref, sm, _ = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=4))
    reads, _ = simulate_long_reads(ref, 4, 600, 0.01, seed=7)
    sr = mapper.map_long_stream(iter([(reads,)]))
    assert sr.reads_per_item == 1
    assert sr.mbp_per_s(600) == pytest.approx(
        sr.n_pairs * 600 / max(sr.seconds, 1e-9) / 1e6)
    sp = mapper.map_stream(iter([(np.zeros((4, 150), np.uint8),
                                  np.zeros((4, 150), np.uint8))]))
    assert sp.reads_per_item == 2


def test_fused_cache_reuses_factory_reduce_and_stays_bounded(world):
    """PR-6 regression: a fresh reduce closure per stream recompiled the
    fused step every call and grew the cache unboundedly.  The cached
    factories hand back the *same* callable — one cache entry however
    many streams — and the cache itself is a bounded LRU."""
    from repro.core.simulate import simulate_long_reads
    from repro.engine.mapper import _FUSED_CACHE_MAX
    from repro.launch.serve import (
        _make_accuracy_reduce, _make_vote_accuracy_reduce,
    )
    assert _make_accuracy_reduce(8) is _make_accuracy_reduce(8)
    assert _make_vote_accuracy_reduce(64) is _make_vote_accuracy_reduce(64)

    ref, sm, _ = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=4))
    reads, starts = simulate_long_reads(ref, 4, 600, 0.01, seed=7)
    init = {"mapped": jnp.zeros((), jnp.int32),
            "correct": jnp.zeros((), jnp.int32)}
    for _ in range(3):   # repeated serve_long-style streams: one entry
        mapper.map_long_stream(
            iter([(reads, (jnp.asarray(starts),))]),
            reduce_fn=_make_vote_accuracy_reduce(64), reduce_init=init)
    assert len(mapper._fused_cache) == 1
    # the same (lane, reduce_fn) key returns the identical jitted step
    step = mapper._fused_step(_make_vote_accuracy_reduce(64), "long")
    assert step is mapper._fused_step(_make_vote_accuracy_reduce(64), "long")
    # fresh closures (the old bug) can no longer grow the cache past the
    # bound (jit construction is lazy, so no compiles happen here)
    for i in range(2 * _FUSED_CACHE_MAX):
        mapper._fused_step(lambda acc, res, aux, i=i: acc, "pairs")
    assert len(mapper._fused_cache) <= _FUSED_CACHE_MAX


def test_run_stream_clock_starts_at_first_dispatch(world):
    """`StreamResult.seconds` covers first dispatch -> drain: host-side
    generation of the *first* batch must not count (the docstring
    contract `run_stream` used to violate)."""
    import time as _time
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=48))
    delay = 1.0

    def gen():
        _time.sleep(delay)       # slow host-side read generation
        yield sim.reads1, sim.reads2

    sr = mapper.map_stream(gen(),
                           warmup_batch=(sim.reads1, sim.reads2))
    assert sr.n_pairs == 48
    assert sr.seconds < 0.8 * delay


# ------------------------------------------------------------- shims -----
def test_shims_warn_once_and_delegate(world):
    ref, sm, sim = world
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        r1 = map_pairs(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                       jnp.asarray(sim.reads2), PipelineConfig())
        map_pairs(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                  jnp.asarray(sim.reads2), PipelineConfig())
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "Mapper" in str(dep[0].message)
    mapper = Mapper.from_index(sm, ref, PipelineConfig())
    _assert_same_result(mapper.map(sim.reads1, sim.reads2), r1)


def test_engine_path_is_warning_clean(world):
    ref, sm, sim = world
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                                   ExecutionConfig(stream_batch=48))
        mapper.map(sim.reads1, sim.reads2)
        mapper.map_stream(iter([(sim.reads1, sim.reads2)]))
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert not dep, [str(w.message) for w in dep]
