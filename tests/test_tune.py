"""Tests for the autotuner + tune cache (repro.tune, ISSUE 8).

- cache round-trip (save_cache / load_cache / session build pickup);
- corrupt or stale cache files degrade to hand-picked defaults with a
  warning, never an error;
- ``REPRO_TUNE_CACHE`` env override (and ``tune=False`` beating it);
- resolution order: explicit config > tune cache > defaults;
- nearest-batch-bucket fallback lookup;
- the staged-oracle floor: the tuner can never select a fused config
  that loses to the staged jnp candidate (the C=8/no-prescreen case the
  cand_align bench documents), both structurally (`_winner`) and on a
  real `tune_session` run.
"""
import json
import warnings

import numpy as np
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs,
)
from repro.engine import ExecutionConfig, Mapper
from repro.tune import (
    CACHE_VERSION, ENV_CACHE, _family_backends, _winner,
    apply_tuned_pipeline, cache_path, entry_key, load_cache, lookup,
    pipeline_buckets, save_cache, session_cache, tune_session,
)


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    ref = random_reference(30_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=14))
    sim = simulate_pairs(ref, 16, ReadSimConfig(sub_rate=3e-3), seed=4)
    return ref, sm, sim


def _entries_for(batch, *, prescreen=4, packed=True, fe_block=128,
                 la_block=16, rd_block=32):
    """Hand-made cache entries keyed for this session's resolved
    backends/buckets (CPU CI: every family resolves to jnp)."""
    cfg = PipelineConfig()
    backends = _family_backends(cfg, None)
    buckets = pipeline_buckets(cfg, batch)
    return {
        entry_key(backends["pair_frontend"], "pair_frontend",
                  buckets["pair_frontend"]): {
            "params": {"block": fe_block}, "us": 10.0, "staged_us": 20.0},
        entry_key(backends["candidate_align"], "candidate_align",
                  buckets["candidate_align"]): {
            "params": {"block": la_block, "prescreen_top": prescreen,
                       "packed_ref": packed},
            "us": 10.0, "staged_us": 20.0},
        entry_key(backends["residual_dp"], "residual_dp",
                  buckets["residual_dp"]): {
            "params": {"block": rd_block}, "us": 10.0, "staged_us": 20.0},
    }


# ---------------------------------------------------------- round trip --
def test_cache_round_trip(tmp_path):
    p = tmp_path / "tc.json"
    entries = _entries_for(64)
    save_cache(entries, p)
    assert json.loads(p.read_text())["version"] == CACHE_VERSION
    assert load_cache(p) == entries


def test_mapper_build_picks_up_tuned_knobs(world, tmp_path):
    ref, sm, sim = world
    batch = 16
    p = tmp_path / "tc.json"
    save_cache(_entries_for(batch), p)
    mapper = Mapper.from_index(
        sm, ref, PipelineConfig(),
        ExecutionConfig(stream_batch=batch, tune=str(p)))
    cfg = mapper.pipe_cfg
    assert cfg.prescreen_top == 4 and cfg.prescreen() == 4
    assert cfg.packed_ref is True
    assert cfg.frontend_block == 128
    assert cfg.light_block == 16
    assert cfg.residual_block == 32
    # ...and the tuned session still maps: same positions as an untuned
    # build on well-separated interior reads (prescreen keeps the true
    # candidate; packed/unpacked differ only at reference edges).
    plain = Mapper.from_index(sm, ref, PipelineConfig(),
                              ExecutionConfig(stream_batch=batch))
    pos_t = np.asarray(mapper.map(sim.reads1, sim.reads2).pos1)
    pos_p = np.asarray(plain.map(sim.reads1, sim.reads2).pos1)
    interior = (pos_p > 64) & (pos_p < len(ref) - 500)
    np.testing.assert_array_equal(pos_t[interior], pos_p[interior])


def test_default_build_ignores_cache_without_opt_in(world, monkeypatch):
    """No tune flag, no env: the session must stay bit-stable (the
    engine-vs-`map_pairs_impl` parity contract) whatever sits on disk."""
    ref, sm, _ = world
    monkeypatch.delenv(ENV_CACHE, raising=False)
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=16))
    assert mapper.pipe_cfg.prescreen_top is None
    assert mapper.pipe_cfg.light_block is None


# ------------------------------------------------- corrupt/stale files --
@pytest.mark.parametrize("payload", [
    "{not json",
    json.dumps([1, 2, 3]),
    json.dumps({"version": CACHE_VERSION + 1, "entries": {}}),   # stale
    json.dumps({"version": CACHE_VERSION, "entries": "nope"}),
])
def test_corrupt_or_stale_cache_warns_and_defaults(tmp_path, payload):
    p = tmp_path / "bad.json"
    p.write_text(payload)
    with pytest.warns(UserWarning, match="tune cache"):
        assert load_cache(p) == {}


def test_corrupt_cache_mapper_falls_back_to_defaults(world, tmp_path):
    ref, sm, _ = world
    p = tmp_path / "bad.json"
    p.write_text("{definitely not json")
    with pytest.warns(UserWarning, match="tune cache"):
        mapper = Mapper.from_index(
            sm, ref, PipelineConfig(),
            ExecutionConfig(stream_batch=16, tune=str(p)))
    assert mapper.pipe_cfg.prescreen() == 0
    assert mapper.pipe_cfg.light_block is None


def test_missing_cache_is_silent_empty(tmp_path):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert load_cache(tmp_path / "nope.json") == {}


# ------------------------------------------------------- env override --
def test_env_override_resolves_path_and_opts_in(tmp_path, monkeypatch):
    env_p = tmp_path / "env.json"
    save_cache(_entries_for(64), env_p)
    monkeypatch.setenv(ENV_CACHE, str(env_p))
    assert cache_path() == str(env_p)
    # explicit arg still beats the env
    assert cache_path("elsewhere.json") == "elsewhere.json"
    # tune=None + env set: opted in, entries come from the env path
    assert session_cache(None) == load_cache(env_p)
    # tune=False beats the env — never tune
    assert session_cache(False) == {}


def test_session_cache_env_unset_is_opt_out(monkeypatch):
    monkeypatch.delenv(ENV_CACHE, raising=False)
    assert session_cache(None) == {}


def test_env_cache_applies_to_mapper_build(world, tmp_path, monkeypatch):
    ref, sm, _ = world
    env_p = tmp_path / "env.json"
    save_cache(_entries_for(16, prescreen=2), env_p)
    monkeypatch.setenv(ENV_CACHE, str(env_p))
    mapper = Mapper.from_index(sm, ref, PipelineConfig(),
                               ExecutionConfig(stream_batch=16))
    assert mapper.pipe_cfg.prescreen_top == 2


# ------------------------------------------------- resolution order ----
def test_explicit_config_beats_cache():
    entries = _entries_for(64, prescreen=4, packed=True)
    explicit = PipelineConfig(prescreen_top=1, packed_ref=False,
                              light_block=8, frontend_block=4,
                              residual_block=16)
    out = apply_tuned_pipeline(explicit, entries, batch=64)
    assert out is explicit or out == explicit   # nothing to fill
    assert out.prescreen_top == 1
    assert out.packed_ref is False
    assert out.light_block == 8
    # unset knobs do get filled
    filled = apply_tuned_pipeline(PipelineConfig(), entries, batch=64)
    assert filled.prescreen_top == 4
    assert filled.light_block == 16


def test_exec_packed_override_beats_cached_packed_ref():
    entries = _entries_for(64, packed=True)
    out = apply_tuned_pipeline(PipelineConfig(), entries, batch=64,
                               exec_packed=False)
    assert out.packed_ref is None     # left for exec resolution, not cache


def test_lookup_nearest_batch_fallback():
    entries = _entries_for(64)
    cfg = PipelineConfig()
    bk = _family_backends(cfg, None)["candidate_align"]
    near = pipeline_buckets(cfg, 128)["candidate_align"]   # B128, not B64
    assert lookup(entries, bk, "candidate_align", near) is not None
    # different static suffix must not match
    other = near.replace(f"_R{cfg.read_len}_", "_R999_")
    assert lookup(entries, bk, "candidate_align", other) is None
    assert lookup(entries, "pallas", "candidate_align", near) is None


# ------------------------------------------- staged-oracle floor -------
def test_winner_never_picks_fused_slower_than_staged():
    timed = {"staged": ({"backend": "jnp"}, 100.0),
             "block8": ({"block": 8}, 250.0),
             "block16": ({"block": 16}, 140.0)}
    params, us, staged_us = _winner(timed, "staged")
    assert params == {"backend": "jnp"} and us == staged_us == 100.0
    timed["block16"] = ({"block": 16}, 60.0)
    params, us, _ = _winner(timed, "staged")
    assert params == {"block": 16} and us == 60.0


def test_tune_session_winners_never_lose_to_staged(world, tmp_path):
    """The real-tuner form of the regression: on the C=8/no-prescreen
    default shape every family's recorded winner is at least as fast as
    its staged-oracle candidate (staged is always in the running, so a
    losing fused config structurally cannot be selected)."""
    ref, sm, _ = world
    entries = tune_session(ref, sm, batch=32, reps=1, seed=1,
                           path=tmp_path / "tc.json")
    assert entries, "tuner recorded no winners"
    assert PipelineConfig().max_candidates == 8   # the C=8 shape
    for key, e in entries.items():
        assert e["us"] <= e["staged_us"] or np.isnan(e["staged_us"]), (
            key, e)
    # and the written cache is immediately consumable
    assert load_cache(tmp_path / "tc.json") == entries


def test_frontend_block_sweep_runs_chip_blocks(world, tmp_path):
    """The front end's block sweep offers only blocks the chip takes (a
    read on each lane: multiples of 128, the default among them), and
    every one of them runs: none is dropped as a failed candidate."""
    from repro.kernels._util import LANES
    from repro.kernels.pair_frontend.kernel import DEFAULT_BLOCK
    from repro.tune import BLOCK_GRID

    grid = BLOCK_GRID["pair_frontend"]
    assert DEFAULT_BLOCK in grid
    assert all(b % LANES == 0 for b in grid)
    ref, sm, _ = world
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        entries = tune_session(ref, sm, exec_cfg=ExecutionConfig(
                                   backend="interpret"),
                               batch=32, reps=1, seed=1,
                               families=("pair_frontend",),
                               path=tmp_path / "tc.json")
    (entry,) = entries.values()
    assert entry["params"] in ({"backend": "jnp"},
                               *({"block": b} for b in grid))


# ------------------------------------------- no fallback on the chip ---
def _failing():
    raise RuntimeError("Mosaic refused the kernel")


def test_failed_kernel_candidate_raises_on_tpu(monkeypatch):
    """On a TPU a Pallas candidate that fails to compile is a bug: the
    tuner raises instead of letting the staged oracle win by default."""
    import jax

    from repro import tune

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="failed on the TPU"):
        tune._time_candidates([("staged", {"backend": "jnp"}, lambda: 0),
                               ("block8", {"block": 8}, _failing)], reps=1)
    # A failing staged-oracle candidate is still just dropped.
    with pytest.warns(UserWarning, match="staged"):
        timed = tune._time_candidates(
            [("staged", {"backend": "jnp"}, _failing),
             ("block8", {"block": 8}, lambda: 0)], reps=1)
    assert list(timed) == ["block8"]


def test_failed_candidate_is_dropped_off_the_chip():
    from repro import tune

    with pytest.warns(UserWarning, match="block8"):
        timed = tune._time_candidates(
            [("staged", {"backend": "jnp"}, lambda: 0),
             ("block8", {"block": 8}, _failing)], reps=1)
    assert list(timed) == ["staged"]
