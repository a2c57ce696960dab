"""Fleet index store tests: save/load round-trips, degradation, hot-swap.

Pins the ISSUE-9 acceptance points that run on one device:
  * `Mapper.load(path)` maps (and long-maps) bit-identically to the
    in-memory session that saved the store — with `build_seedmap`
    instrumented to prove the load path never calls it;
  * corrupt / stale / checksum-flipped stores warn and degrade (tune-
    cache contract): `load_store` -> None, `Mapper.load` -> full build
    from ``fallback_ref``, `swap_index` -> "kept";
  * `from_index` accepts a `PaddedSeedMap` directly and builds the same
    session a CSR map does (and syncs ``max_locs_per_seed`` to the row
    width);
  * `swap_index` mid-stream: same-shape stores swap under the compiled
    fused step ("reused", next dispatch serves the new index), and the
    swapped session is bit-identical to a fresh session on the new
    store; `FrontDoor.reload_index` quiesces one dispatch boundary with
    no accepted request lost;
  * `engine.multihost.map_stream` degrades to the single-host loop at
    ``process_count() == 1`` (the two-process path is
    tests/test_multihost.py);
  * a kernel session on a device too small for padded rows saves and
    loads its CSR lines through the "csr" layout, and a padded store
    loads into such a session converted to CSR.
"""
import json
import os

import numpy as np
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs, to_padded,
)
from repro.engine import ExecutionConfig, Mapper
from repro.engine import multihost
from repro.engine.index_store import (
    IndexStoreError, MANIFEST, load_store, save_store, store_size_bytes,
)

TB = 15


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    ref = random_reference(60_000, rng)
    sim = simulate_pairs(ref, 16, ReadSimConfig(sub_rate=3e-3), seed=1)
    mapper = Mapper.build(ref, SeedMapConfig(table_bits=TB),
                          PipelineConfig())
    return ref, sim, mapper


@pytest.fixture(scope="module")
def other_store(tmp_path_factory):
    """A second reference release of the same length -> same-shape store."""
    ref_b = random_reference(60_000, np.random.default_rng(7))
    mb = Mapper.build(ref_b, SeedMapConfig(table_bits=TB), PipelineConfig())
    path = tmp_path_factory.mktemp("store_b")
    mb.save(path)
    return ref_b, mb, path


def _assert_same(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


def _long_reads(sim, n=4):
    return np.tile(sim.reads1, (1, 4))[:n]


# ------------------------------------------------------ round-tripping ---
def test_save_load_bit_identity_no_build(world, tmp_path, monkeypatch):
    ref, sim, mapper = world
    store = tmp_path / "store"
    manifest = mapper.save(store)
    assert os.path.exists(manifest)
    assert store_size_bytes(store) > 0

    def boom(*a, **k):
        raise AssertionError("Mapper.load called build_seedmap")

    # Instrument every import site: the load path must never build.
    monkeypatch.setattr("repro.core.seedmap.build_seedmap", boom)
    monkeypatch.setattr("repro.engine.mapper.build_seedmap", boom)
    loaded = Mapper.load(store)

    _assert_same(mapper.map(sim.reads1, sim.reads2),
                 loaded.map(sim.reads1, sim.reads2))
    _assert_same(mapper.map_long(_long_reads(sim)),
                 loaded.map_long(_long_reads(sim)))
    assert loaded.pipe_cfg == mapper.pipe_cfg
    assert loaded.lr_cfg == mapper.lr_cfg
    assert loaded.sm_config == mapper.sm_config


def test_loaded_stream_matches_in_memory(world, tmp_path):
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    loaded = Mapper.load(store)

    def batches():
        yield sim.reads1, sim.reads2
        yield sim.reads1[:5], sim.reads2[:5]   # ragged tail

    a = mapper.map_stream(batches())
    b = loaded.map_stream(batches())
    assert a.totals == b.totals
    assert a.n_pairs == b.n_pairs == 21


def test_load_forces_tune_off(world, tmp_path, monkeypatch):
    """A load-time REPRO_TUNE_CACHE must not re-resolve stored knobs."""
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    cache = tmp_path / "tune_cache.json"
    cache.write_text(json.dumps({"version": 1, "entries": {}}))
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache))
    loaded = Mapper.load(store)
    assert loaded.exec_cfg.tune is False
    assert loaded.pipe_cfg == mapper.pipe_cfg


# -------------------------------------------------------- degradation ----
def test_version_mismatch_degrades(world, tmp_path):
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    mpath = store / MANIFEST
    doc = json.loads(mpath.read_text())
    doc["version"] = 99
    mpath.write_text(json.dumps(doc))

    with pytest.warns(UserWarning, match="version-1"):
        assert load_store(store) is None
    with pytest.raises(IndexStoreError, match="version"):
        load_store(store, strict=True)
    # no fallback: nothing to build from
    with pytest.raises(IndexStoreError, match="fallback_ref"):
        with pytest.warns(UserWarning):
            Mapper.load(store)
    # with fallback: warn + full rebuild, same results
    with pytest.warns(UserWarning, match="rebuilding"):
        rebuilt = Mapper.load(store, fallback_ref=ref,
                              seedmap_cfg=SeedMapConfig(table_bits=TB))
    _assert_same(mapper.map(sim.reads1, sim.reads2),
                 rebuilt.map(sim.reads1, sim.reads2))


def test_checksum_corruption_degrades(world, tmp_path):
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    payloads = [f for f in os.listdir(store) if f.endswith(".npy")]
    target = store / sorted(payloads)[0]
    raw = bytearray(target.read_bytes())
    raw[-1] ^= 0xFF
    target.write_bytes(bytes(raw))
    with pytest.warns(UserWarning, match="checksum"):
        assert load_store(store) is None


def test_manifest_shape_mismatch_degrades(world, tmp_path):
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    mpath = store / MANIFEST
    doc = json.loads(mpath.read_text())
    name = next(iter(doc["arrays"]))
    entry = doc["arrays"][name]
    entry["shape"] = [s + 1 for s in entry["shape"]]
    # keep the checksum valid so the shape check itself is exercised:
    # rewriting only the manifest leaves payload sha intact
    mpath.write_text(json.dumps(doc))
    with pytest.warns(UserWarning, match="payload is"):
        assert load_store(store) is None


def test_unknown_config_field_degrades(world, tmp_path):
    """A store from a future release with new config fields is stale."""
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    mpath = store / MANIFEST
    doc = json.loads(mpath.read_text())
    doc["pipeline_config"]["from_the_future"] = 42
    mpath.write_text(json.dumps(doc))
    with pytest.warns(UserWarning, match="index store"):
        assert load_store(store) is None


# --------------------------------------------- from_index(PaddedSeedMap) --
def test_from_index_padded_equals_csr(world):
    ref, sim, _ = world
    sm = build_seedmap(ref, SeedMapConfig(table_bits=TB))
    cfg = PipelineConfig()
    m_csr = Mapper.from_index(sm, ref, cfg)
    m_pad = Mapper.from_index(to_padded(sm, cap=cfg.max_locs_per_seed),
                              ref, cfg)
    _assert_same(m_csr.map(sim.reads1, sim.reads2),
                 m_pad.map(sim.reads1, sim.reads2))
    _assert_same(m_csr.map_long(_long_reads(sim)),
                 m_pad.map_long(_long_reads(sim)))


def test_from_index_padded_syncs_row_width(world):
    ref, _, _ = world
    sm = build_seedmap(ref, SeedMapConfig(table_bits=TB))
    m = Mapper.from_index(to_padded(sm, cap=8), ref, PipelineConfig())
    assert m.pipe_cfg.max_locs_per_seed == 8
    assert m.lr_cfg.pipe.max_locs_per_seed == 8


# ------------------------------------------------------------ hot-swap ---
def test_swap_index_reused_and_bit_identical(world, other_store, tmp_path):
    ref, sim, _ = world
    ref_b, m_fresh, path_b = other_store
    m = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig())
    step_before = m._step
    assert m.swap_index(path_b) == "reused"
    assert m._step is step_before          # compiled step survives
    _assert_same(m.map(sim.reads1, sim.reads2),
                 m_fresh.map(sim.reads1, sim.reads2))


def test_swap_index_mid_stream(world, other_store):
    """Swap between dispatches: batch 0 serves the old index, batch 1 the
    new one — each bit-identical to a fresh session on that index."""
    ref, sim, _ = world
    ref_b, m_fresh, path_b = other_store
    m = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig(),
                     ExecutionConfig(stream_batch=16))
    m_old = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig())
    got = {}

    def batches():
        yield sim.reads1, sim.reads2
        # generator side effect between dispatch 0 and dispatch 1: the
        # fused step re-reads mapper._state at every dispatch
        assert m.swap_index(path_b) == "reused"
        yield sim.reads1, sim.reads2

    m.map_stream(batches(),
                 on_result=lambda i, res, n: got.__setitem__(i, res))
    _assert_same(got[0], m_old.map(sim.reads1, sim.reads2))
    _assert_same(got[1], m_fresh.map(sim.reads1, sim.reads2))


def test_swap_index_rebuilds_on_shape_change(world, tmp_path):
    ref, sim, _ = world
    ref_c = random_reference(90_000, np.random.default_rng(11))
    m_c = Mapper.build(ref_c, SeedMapConfig(table_bits=TB), PipelineConfig())
    path_c = tmp_path / "store_c"
    m_c.save(path_c)
    m = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig())
    with pytest.warns(UserWarning, match="rebuilding in place"):
        assert m.swap_index(path_c) == "rebuilt"
    _assert_same(m.map(sim.reads1, sim.reads2),
                 m_c.map(sim.reads1, sim.reads2))


def test_swap_index_unreadable_keeps(world, tmp_path):
    ref, sim, mapper = world
    store = tmp_path / "store"
    mapper.save(store)
    (store / MANIFEST).write_text("not json at all")
    m = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig())
    before = m.map(sim.reads1, sim.reads2)
    with pytest.warns(UserWarning, match="keeping"):
        assert m.swap_index(store) == "kept"
    _assert_same(before, m.map(sim.reads1, sim.reads2))


def test_frontdoor_reload_index(world, other_store):
    """One dispatch boundary quiesce: requests accepted before the swap
    retire against the old index, requests after serve the new one, and
    every accepted request completes."""
    from repro.engine import FrontDoor, FrontDoorConfig

    ref, sim, _ = world
    ref_b, m_fresh, path_b = other_store
    m = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig(),
                     ExecutionConfig(stream_batch=16))
    m_old = Mapper.build(ref, SeedMapConfig(table_bits=TB), PipelineConfig())
    old_res = m_old.map(sim.reads1, sim.reads2)
    new_res = m_fresh.map(sim.reads1, sim.reads2)

    with FrontDoor(m, FrontDoorConfig()) as fd:
        r_pre = fd.submit("pairs", (sim.reads1, sim.reads2))
        fd.dispatch_ready()            # in flight against the old index
        assert fd.reload_index(path_b) == "reused"
        assert r_pre.status == "done"  # quiesced at the boundary
        r_post = fd.submit("pairs", (sim.reads1, sim.reads2))
        fd.drain()
    assert r_post.status == "done"
    _assert_same(r_pre.result, old_res)
    _assert_same(r_post.result, new_res)
    assert fd.stats.accepted == fd.stats.completed == 2


# ----------------------------------------------------------- multihost ---
def test_multihost_degrades_to_single_host(world):
    ref, sim, mapper = world
    assert multihost.process_count() == 1
    assert multihost.is_coordinator()

    def batches():
        yield sim.reads1, sim.reads2
        yield sim.reads1[:7], sim.reads2[:7]

    a = multihost.map_stream(mapper, batches())
    b = mapper.map_stream(batches())
    assert a.totals == b.totals
    assert a.n_pairs == b.n_pairs == 23


# ------------------------------------------------------- serve.py flags --
def test_serve_save_then_index(tmp_path):
    from repro.launch.serve import save_index, serve

    store = tmp_path / "store"
    saved = save_index(str(store), ref_len=60_000, batch=16,
                       table_bits=TB, verbose=False)
    assert saved["store_mb"] > 0
    built = serve(ref_len=60_000, batch=16, batches=2, table_bits=TB,
                  verbose=False)
    loaded = serve(ref_len=60_000, batch=16, batches=2, table_bits=TB,
                   verbose=False, index_path=str(store))
    for k in ("pairs", "mapped_frac", "correct_of_mapped",
              "pair_mapped_frac"):
        assert built[k] == loaded[k], k


def test_save_store_rejects_unknown_index(world, tmp_path):
    ref, _, mapper = world
    with pytest.raises(TypeError, match="cannot persist"):
        save_store(tmp_path / "x", index=object(), ref=np.asarray(ref),
                   pipe_cfg=mapper.pipe_cfg, sm_config=mapper.sm_config)


# ------------------------------------------------- CSR kernel sessions ---
def _interpret_cfg():
    """The front end, which reads the index layout, on its kernel."""
    return PipelineConfig(frontend_backend="interpret", light_backend="jnp",
                          residual_backend="jnp")


@pytest.fixture
def small_device(monkeypatch):
    """A device whose memory cannot hold the padded rows (2^15 buckets x
    32 x 4 B = 4 MiB, over half of 4 MiB): kernel sessions take CSR."""
    monkeypatch.setattr("repro.engine.mapper._bytes_limit",
                        lambda mesh: 4 * 2**20)


def test_csr_kernel_session_round_trips(world, tmp_path, small_device):
    """A CSR kernel session saves the store's "csr" layout and loads
    back into the same CSR lines, mapping bit-identically."""
    from repro.core.seedmap import LinedCSRSeedMap, SeedMap

    ref, sim, _ = world
    sm = build_seedmap(ref, SeedMapConfig(table_bits=TB))
    mapper = Mapper.from_index(sm, ref, _interpret_cfg())
    assert isinstance(mapper._state[0], LinedCSRSeedMap)
    store = tmp_path / "store"
    mapper.save(store)
    with open(store / MANIFEST) as f:
        assert json.load(f)["layout"] == "csr"
    loaded = Mapper.load(store)
    assert isinstance(loaded.index, SeedMap)
    assert isinstance(loaded._state[0], LinedCSRSeedMap)
    _assert_same(mapper.map(sim.reads1[:4], sim.reads2[:4]),
                 loaded.map(sim.reads1[:4], sim.reads2[:4]))
    assert loaded.pipe_cfg == mapper.pipe_cfg


def test_padded_store_loads_into_a_csr_session(world, tmp_path,
                                               monkeypatch):
    """A store saved in the padded layout loads into a session that
    cannot hold padded rows: converted to CSR at the same row width, it
    maps as the padded session that saved it."""
    from repro.core.seedmap import LinedCSRSeedMap

    ref, sim, _ = world
    sm = build_seedmap(ref, SeedMapConfig(table_bits=TB))
    padded = Mapper.from_index(to_padded(sm, cap=16), ref, _interpret_cfg())
    store = tmp_path / "store"
    padded.save(store)
    with open(store / MANIFEST) as f:
        assert json.load(f)["layout"] == "padded"
    monkeypatch.setattr("repro.engine.mapper._bytes_limit",
                        lambda mesh: 2**20)
    loaded = Mapper.load(store)
    assert isinstance(loaded._state[0], LinedCSRSeedMap)
    assert loaded.pipe_cfg.max_locs_per_seed == 16
    _assert_same(padded.map(sim.reads1[:4], sim.reads2[:4]),
                 loaded.map(sim.reads1[:4], sim.reads2[:4]))
