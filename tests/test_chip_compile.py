"""Every fused Pallas kernel of the main path compiles for a TPU v5e chip.

The chip is described, not attached (`jax.experimental.topologies`): the
TPU compiler installed with jaxlib lowers each kernel entry point at the
shapes of a chr1-scale deployment (R=150, S=3, K=32, C=8, a 248,956,422-
base reference, a 2^25-bucket table, each family's ``LAUNCH_ROWS``) and
raises what Mosaic would raise on the chip.  Interpret mode accepts
slices, primitives and casts that Mosaic refuses, so these compiles are
the guard that the kernels still lower.  Nothing runs: results are the
interpret-mode and oracle tests' business.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.  The persistent compilation cache is
off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.encoding import BASES_PER_WORD, packed_gather_coords
from repro.core.long_read import LongReadConfig
from repro.core.pipeline import PipelineConfig
from repro.core.seeding import seed_offsets_tuple
from repro.kernels._util import lines_spanned, to_lines
from repro.kernels.candidate_align import kernel as ca
from repro.kernels.location_vote import kernel as lv
from repro.kernels.pair_frontend import kernel as pf
from repro.kernels.residual_dp import kernel as rd

REF_LEN = 248_956_422          # GRCh38 chr1
TABLE_BITS = 25
CFG = PipelineConfig()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def sds(one_chip):
    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _lines(sds, n_elems: int, width: int, align: int = 1):
    """The (n, 128) line-layout stand-in a ``width``-element DMA reads."""
    shape = jax.eval_shape(
        functools.partial(to_lines, nl=lines_spanned(width, align)),
        jax.ShapeDtypeStruct((n_elems,), jnp.int32)).shape
    return sds(shape)


def _ref_lines(sds, width: int, packed: bool):
    """(reference lines, per-window elements) as the ops wrappers lay out
    a chr1-length reference for ``width``-base windows."""
    if packed:
        n_ref_words = REF_LEN // BASES_PER_WORD + 1
        win_elems, _ = packed_gather_coords(n_ref_words, width)
        n = n_ref_words + win_elems
    else:
        win_elems = width
        n = REF_LEN + 2 * width - 1
    return _lines(sds, n, win_elems), win_elems


def _assert_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


OFFS = seed_offsets_tuple(CFG.read_len, CFG.seed_len, CFG.seeds_per_read)
S, K, C, R = len(OFFS), CFG.max_locs_per_seed, CFG.max_candidates, \
    CFG.read_len


def test_seed_buckets_compiles(sds):
    _assert_compiles(
        lambda r: pf.seed_buckets_pallas(r, OFFS, CFG.seed_len, 0,
                                         1 << TABLE_BITS),
        sds((2 * pf.LAUNCH_ROWS, R)))


def test_pair_frontend_compiles(sds):
    rows = pf.LAUNCH_ROWS
    _assert_compiles(
        lambda t, a, b: pf.pair_frontend_pallas(t, a, b, OFFS, K, CFG.delta,
                                                C),
        _lines(sds, (1 << TABLE_BITS) * K, K, K),
        sds((rows * S,)), sds((rows * S,)))


def test_merge_filter_compiles(sds):
    rows = pf.LAUNCH_ROWS
    _assert_compiles(
        lambda a, b: pf.merge_filter_pallas(a, b, OFFS, K, CFG.delta, C),
        sds((rows, S * K)), sds((rows, S * K)))


@pytest.mark.parametrize("packed,prescreen_top", [
    (False, 0), (True, 0), (False, 4)],
    ids=["unpacked", "packed", "prescreen4"])
def test_candidate_align_compiles(sds, packed, prescreen_top):
    E = CFG.max_gap
    ref, win_elems = _ref_lines(sds, R + 2 * E, packed)
    B = ca.LAUNCH_ROWS
    _assert_compiles(
        lambda ref, r1, r2, s1, s2, o1, o2, v1, v2: ca.candidate_align_pallas(
            ref, r1, r2, s1, s2, o1, o2, v1, v2, E, CFG.scoring,
            CFG.threshold(), CFG.light_mode, prescreen_top, packed,
            win_elems),
        ref, sds((B, R)), sds((B, R)), sds((B * C,)), sds((B * C,)),
        sds((B, C)), sds((B, C)), sds((B, C)), sds((B, C)))


@pytest.mark.parametrize("packed", [False, True], ids=["banded", "packed"])
def test_residual_dp_compiles(sds, packed):
    pad = CFG.dp_pad
    ref, win_elems = _ref_lines(sds, R + 2 * pad, packed)
    rows = rd.LAUNCH_ROWS
    _assert_compiles(
        lambda ref, sd, n, reads, off: rd.residual_dp_pallas(
            ref, sd, n, reads, off, pad, CFG.band(), CFG.scoring, packed,
            win_elems),
        ref, sds((rows,)), sds((1,)), sds((rows, R)), sds((rows, 1)))


def test_location_vote_compiles(sds):
    lr = LongReadConfig()
    n_seg = (2000 - lr.segment_len) // lr.segment_stride + 1
    M = (n_seg - 1) * C        # candidate diagonals of a 2 kbp long read
    Mp = -(-M // 128) * 128
    _assert_compiles(
        lambda d, n: lv.location_vote_pallas(d, n, lr.vote_bin, M),
        sds((lv.LAUNCH_ROWS, Mp)), sds((1,)))
