"""The aligners' reference layout held once per session (`LinedRef`).

- `candidate_pair_align` and `residual_pair_dp` given a `LinedRef` return
  field for field what they return given the plain reference, in both
  flavours, with INVALID_LOC slots, negative starts near the origin and
  starts within a window of ``L - 1``; one layout, padded for the wider
  DP window, serves the narrower light window too;
- a kernel-backend `Mapper` holds its reference as a `LinedRef`, maps
  exactly as the per-call layout does, builds the layout once per
  placement (span ``session.ref_layout``) and again on a reused swap,
  and saves the plain uint8 reference.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    random_reference, simulate_pairs,
)
from repro.core.encoding import LinedRef, pack_2bit
from repro.core.seedmap import INVALID_LOC
from repro.engine import ExecutionConfig, Mapper, plan, spans
from repro.engine.index_store import load_store
from repro.kernels._util import lined_ref, lines_spanned, window_elems
from repro.kernels.candidate_align import candidate_pair_align
from repro.kernels.residual_dp import residual_pair_dp

L, R, E, DP = 4000, 100, 6, 12
WIDTHS = (R + 2 * E, R + 2 * DP)     # light window, wider DP window


def _starts(rng, n):
    """Window starts: edges of the reference and a random interior."""
    edge = np.array([-2, -30, -(R + 2 * DP + 3), 0, L - 1, L - R - 2,
                     L - R + 5, L + 4, INVALID_LOC], np.int32)
    inner = rng.integers(DP, L - R - DP, (n,)).astype(np.int32)
    return np.concatenate([edge, inner])


def _assert_same(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


def _flavour(ref, packed):
    bases = jnp.asarray(pack_2bit(jnp.asarray(ref)) if packed else ref)
    return bases, lined_ref(bases, packed, WIDTHS)


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_shared_layout_is_padded_for_the_wider_window(packed):
    ref = np.random.default_rng(0).integers(0, 4, (L,), dtype=np.uint8)
    bases, lined = _flavour(ref, packed)
    elems = [window_elems(bases.shape[0], packed, w) for w in WIDTHS]
    assert lined.pad == max(elems) and lined.packed == packed
    assert lined.nl == max(lines_spanned(e) for e in elems)
    assert lined.lines.dtype == jnp.int32 and lined.lines.shape[1] == 128
    assert lined.dtype == bases.dtype


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_candidate_align_session_layout_bit_exact(packed):
    rng = np.random.default_rng(41)
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    s = _starts(rng, 7)                                  # 16 starts
    pos1 = s.reshape(4, 4)
    pos2 = rng.permutation(s).reshape(4, 4)
    pos2[1, :] = INVALID_LOC                             # an all-invalid row
    reads1 = rng.integers(0, 4, (4, R), dtype=np.uint8)
    reads2 = rng.integers(0, 4, (4, R), dtype=np.uint8)
    reads1[3] = ref[pos1[3, 0]:pos1[3, 0] + R]           # a planted hit
    bases, lined = _flavour(ref, packed)
    args = (jnp.asarray(reads1), jnp.asarray(reads2), jnp.asarray(pos1),
            jnp.asarray(pos2), E)
    kw = dict(packed_ref=packed, block=4)
    got = candidate_pair_align(lined, *args, backend="interpret", **kw)
    want = candidate_pair_align(bases, *args, backend="interpret", **kw)
    _assert_same(got, want)
    _assert_same(candidate_pair_align(lined, *args, backend="jnp", **kw),
                 candidate_pair_align(bases, *args, backend="jnp", **kw))


@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_residual_dp_session_layout_bit_exact(packed):
    rng = np.random.default_rng(43)
    ref = rng.integers(0, 4, (L,), dtype=np.uint8)
    pos1 = _starts(rng, 3)                               # 12 rows
    pos2 = rng.permutation(pos1)
    n = pos1.shape[0]
    need1 = jnp.asarray(pos1 != INVALID_LOC)
    need2 = jnp.asarray(rng.random(n) < 0.5)
    reads1 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    reads1[0, :R - 3] = ref[:R - 3]                      # truncated-edge read
    reads2 = rng.integers(0, 4, (n, R), dtype=np.uint8)
    bases, lined = _flavour(ref, packed)
    args = (jnp.asarray(reads1), jnp.asarray(reads2), jnp.asarray(pos1),
            jnp.asarray(pos2), need1, need2, DP)
    kw = dict(band=8, packed_ref=packed, block=4)
    got = residual_pair_dp(lined, *args, backend="interpret", **kw)
    want = residual_pair_dp(bases, *args, backend="interpret", **kw)
    _assert_same(got, want)
    _assert_same(residual_pair_dp(lined, *args, backend="jnp", **kw),
                 residual_pair_dp(bases, *args, backend="jnp", **kw))


def test_layout_narrower_than_the_window_is_refused():
    ref = jnp.zeros((L,), jnp.uint8)
    narrow = lined_ref(ref, False, (WIDTHS[0],))
    rows = jnp.zeros((4,), jnp.int32)
    reads = jnp.zeros((4, R), jnp.uint8)
    need = jnp.ones((4,), bool)
    with pytest.raises(AssertionError, match="too narrow"):
        residual_pair_dp(narrow, reads, reads, rows, rows, need, need, DP,
                         backend="interpret")


# ------------------------------------------------------------ session ---
B = 8
KERNEL_ALIGNERS = PipelineConfig(light_backend="interpret",
                                 residual_backend="interpret",
                                 frontend_backend="jnp", max_gap=E,
                                 dp_pad=DP)


def _session_world(seed):
    rng = np.random.default_rng(seed)
    ref = random_reference(20_000, rng)
    sm = build_seedmap(ref, SeedMapConfig(table_bits=13))
    return ref, sm


@pytest.fixture(scope="module")
def world():
    ref, sm = _session_world(2)
    sim = simulate_pairs(ref, 2 * B, ReadSimConfig(sub_rate=5e-3), seed=3)
    return ref, sm, sim


def _ref_layout_count():
    entry = spans.snapshot()["spans"].get("session.ref_layout")
    return entry["count"] if entry else 0


def test_session_maps_like_the_per_call_layout(world):
    ref, sm, sim = world
    mapper = Mapper.from_index(sm, ref, KERNEL_ALIGNERS)
    index, held = mapper._state
    assert isinstance(held, LinedRef) and not held.packed
    assert held.pad == mapper.pipe_cfg.read_len + 2 * DP
    np.testing.assert_array_equal(np.asarray(held.bases), ref)
    per_call = jax.jit(plan.raw_pipeline_step(mapper.pipe_cfg))
    r1, r2 = jnp.asarray(sim.reads1[:B]), jnp.asarray(sim.reads2[:B])
    want = per_call(index, held.bases, r1, r2, jnp.int32(B))
    _assert_same(mapper.map(r1, r2), want)


def test_ref_layout_is_built_once_per_placement(world, tmp_path):
    ref, sm, sim = world
    spans.reset()
    mapper = Mapper.from_index(sm, ref, KERNEL_ALIGNERS,
                               ExecutionConfig(stream_batch=B))
    assert _ref_layout_count() == 1
    batches = [(sim.reads1[i:i + B], sim.reads2[i:i + B])
               for i in range(0, 2 * B, B)] * 2
    assert mapper.map_stream(iter(batches)).n_batches == 4
    assert _ref_layout_count() == 1

    ref_b, sm_b = _session_world(9)          # same length: same shapes
    other = Mapper.from_index(sm_b, ref_b, KERNEL_ALIGNERS)
    other.save(tmp_path / "b")
    spans.reset()
    assert mapper.swap_index(tmp_path / "b") == "reused"
    assert _ref_layout_count() == 1
    held = mapper._state[1]
    assert isinstance(held, LinedRef)
    np.testing.assert_array_equal(np.asarray(held.bases), ref_b)
    np.testing.assert_array_equal(np.asarray(held.lines),
                                  np.asarray(other._state[1].lines))
    spans.reset()


def test_jnp_aligners_hold_the_plain_reference(world):
    ref, sm, _ = world
    spans.reset()
    mapper = Mapper.from_index(sm, ref, PipelineConfig(
        light_backend="jnp", residual_backend="jnp", frontend_backend="jnp"))
    assert not isinstance(mapper._state[1], LinedRef)
    assert _ref_layout_count() == 0


def test_save_load_round_trips_the_uint8_store(world, tmp_path):
    ref, sm, _ = world
    mapper = Mapper.from_index(sm, ref, KERNEL_ALIGNERS)
    mapper.save(tmp_path / "s")
    payload = load_store(tmp_path / "s", strict=True)
    assert payload.ref.dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(payload.ref), ref)
    loaded = Mapper.load(tmp_path / "s")
    held = loaded._state[1]
    assert isinstance(held, LinedRef)
    np.testing.assert_array_equal(np.asarray(held.bases), ref)
    np.testing.assert_array_equal(np.asarray(held.lines),
                                  np.asarray(mapper._state[1].lines))
