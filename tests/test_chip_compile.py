"""Every fused Pallas kernel of the main path compiles for a TPU v5e chip.

The chip is described, not attached (`jax.experimental.topologies`): the
TPU compiler installed with jaxlib lowers each kernel entry point at the
shapes of a chr1-scale deployment (R=150, S=3, K=32, C=8, a 248,956,422-
base reference, a 2^25-bucket table, each family's ``LAUNCH_ROWS``) and
raises what Mosaic would raise on the chip.  Interpret mode accepts
slices, primitives and casts that Mosaic refuses, so these compiles are
the guard that the kernels still lower.  Nothing runs: results are the
interpret-mode and oracle tests' business.

Two lowerings of the whole fused stream step (pairs and long reads)
pin the names a device profile is read by: every stage's
`jax.named_scope` in the op locations, and each kernel family's stable
``name=`` on its custom calls.  The CSR row gather compiles at the
shapes of chip 0 of a whole-genome deployment (chr1-chr3, 2^28
buckets), with its ``index_offsets`` scope.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.  The persistent compilation cache is
off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
from __future__ import annotations

import functools
import os
import re

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
def v5e_2x2():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


#: chip 0 of a four-chip whole-genome deployment: chr1-chr3 on 2^28 buckets
CHR1TO3_LEN = 689_445_510
CHR1TO3_TABLE_BITS = 28


def test_pair_frontend_csr_compiles(sds):
    """The CSR row gather: four scalar-prefetch tables (each mate's row
    starts and counts) and two-line row DMAs from chr1-chr3's locations."""
    rows = pf.LAUNCH_ROWS
    tables = [sds((rows * S,)) for _ in range(4)]
    _assert_compiles(
        lambda t, a, b, c, d: pf.pair_frontend_pallas(
            t, a, b, OFFS, K, CFG.delta, C, counts=(c, d)),
        _lines(sds, CHR1TO3_LEN, K), *tables)


def test_csr_frontend_op_carries_index_offsets(sds):
    """The whole CSR front-end op at the chr1-chr3 configuration's
    shapes (2^28 buckets, B = 4096) compiles; its Seed Table lookup is
    under the ``index_offsets`` scope and its kernels keep their name."""
    import dataclasses

    from repro.core.seedmap import LinedCSRSeedMap, SeedMapConfig
    from repro.kernels.pair_frontend.ops import pair_frontend

    smc = dataclasses.replace(
        SeedMapConfig(table_bits=CHR1TO3_TABLE_BITS), padded_cap=K)
    index = LinedCSRSeedMap(offsets=sds(((1 << CHR1TO3_TABLE_BITS) + 1,)),
                            lines=_lines(sds, CHR1TO3_LEN, K), config=smc)
    reads = sds((4096, R), jnp.uint8)
    lowered = jax.jit(lambda idx, a, b: pair_frontend(
        idx, a, b, CFG.seed_len, S, 0, CFG.delta, C,
        backend="pallas")).lower(index, reads, reads)
    text = lowered.as_text(debug_info=True)
    assert _has_scope(text, "index_offsets")
    assert _kernel_names(text) == {"pair_frontend"}
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_merge_filter_compiles(sds):
    rows = pf.LAUNCH_ROWS
    _assert_compiles(
        lambda a, b: pf.merge_filter_pallas(a, b, OFFS, K, CFG.delta, C),
        sds((rows, S * K)), sds((rows, S * K)))


def _frontend_entry(sds, entry: str, block: int):
    """(fn, args) of one front-end kernel entry at chr1 (padded,
    merge-only) or chr1-chr3 (CSR) launch shapes."""
    rows = pf.LAUNCH_ROWS
    kw = dict(block=block)
    if entry == "padded":
        return (lambda t, a, b: pf.pair_frontend_pallas(
            t, a, b, OFFS, K, CFG.delta, C, **kw),
            (_lines(sds, (1 << TABLE_BITS) * K, K, K), sds((rows * S,)),
             sds((rows * S,))))
    if entry == "csr":
        return (lambda t, a, b, c, d: pf.pair_frontend_pallas(
            t, a, b, OFFS, K, CFG.delta, C, counts=(c, d), **kw),
            (_lines(sds, CHR1TO3_LEN, K),
             *[sds((rows * S,)) for _ in range(4)]))
    return (lambda a, b: pf.merge_filter_pallas(a, b, OFFS, K, CFG.delta, C,
                                                **kw),
            (sds((rows, S * K)), sds((rows, S * K))))


FRONTEND_ENTRIES = ["padded", "csr", "merge"]


@pytest.mark.parametrize("entry", FRONTEND_ENTRIES)
def test_frontend_compiles_at_tuned_block(sds, entry):
    """Each front-end entry at the tuner's other block (256 reads a step,
    `repro.tune.BLOCK_GRID`)."""
    fn, args = _frontend_entry(sds, entry, 256)
    _assert_compiles(fn, *args)


@pytest.mark.parametrize("entry", FRONTEND_ENTRIES)
def test_frontend_refuses_block_off_lanes(sds, entry):
    """Reads lie along lanes: a block that is not a multiple of 128 is
    refused before Mosaic sees it."""
    fn, args = _frontend_entry(sds, entry, 64)
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(fn).lower(*args)


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


# ------------------------------------------------ names a profile reads ---
def _fused_step_text(sds, lane: str) -> str:
    """StableHLO, with locations, of a session's fused stream step for
    ``lane`` on the described chip: every family on its Pallas kernel, a
    small unpacked session (lowering only, nothing compiles)."""
    import dataclasses

    from repro.core.seedmap import LinedSeedMap, SeedMapConfig
    from repro.engine import ExecutionConfig, Mapper, plan
    from repro.engine.mapper import _session_ref
    from repro.engine.stats import LONG_STAT_KEYS, STAT_KEYS
    from repro.launch.serve import (
        ACC_KEYS, _make_accuracy_reduce, _make_vote_accuracy_reduce)

    cfg = PipelineConfig(frontend_backend="pallas", light_backend="pallas",
                         residual_backend="pallas", packed_ref=False)
    lr = LongReadConfig(pipe=cfg, vote_backend="pallas")
    smc = dataclasses.replace(SeedMapConfig(table_bits=14), padded_cap=K)
    # The session's reference: its aligner lines built once (`LinedRef`).
    ref = jax.eval_shape(functools.partial(_session_ref, cfg=cfg),
                         sds((100_000,), jnp.uint8))
    state = (LinedSeedMap(lines=_lines(sds, (1 << 14) * K, K, K),
                          config=smc),
             jax.tree.map(lambda x: sds(x.shape, x.dtype), ref))
    mapper = Mapper(state=state, state_shardings=None,
                    raw_step=plan.raw_pipeline_step(cfg), pipe_cfg=cfg,
                    exec_cfg=ExecutionConfig(stream_batch=64),
                    sm_config=smc, index=None, lr_cfg=lr,
                    raw_long_step=plan.raw_long_read_step(lr))
    i32 = sds(())
    if lane == "pairs":
        step = mapper._fused_step(_make_accuracy_reduce(cfg.max_gap), lane)
        carry = ({k: i32 for k in STAT_KEYS}, {k: i32 for k in ACC_KEYS})
        batch = (sds((64, R), jnp.uint8), sds((64, R), jnp.uint8), i32,
                 (sds((64,)), sds((64,))))
    else:
        step = mapper._fused_step(_make_vote_accuracy_reduce(lr.vote_bin),
                                  lane)
        carry = ({k: i32 for k in LONG_STAT_KEYS},
                 {k: i32 for k in ("mapped", "correct")})
        batch = (sds((8, 2000), jnp.uint8), i32, (sds((8,)),))
    return step.lower(state, carry, *batch).as_text(debug_info=True)


def _kernel_names(text: str) -> set:
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def _has_scope(text: str, scope: str) -> bool:
    """A location under ``scope``: nested jits lower to functions of
    their own, whose locations start at the scope (``"ref_layout/..."``)."""
    return re.search(rf'["/]{re.escape(scope)}/', text) is not None


def test_pairs_step_carries_stage_scopes_and_kernel_names(sds):
    text = _fused_step_text(sds, "pairs")
    for scope in ("frontend", "light_align", "residual_dp", "assemble",
                  "stage_stats", "reduce"):
        assert _has_scope(text, scope), scope
    # The session holds the aligners' reference lines: no step lays
    # them out.
    assert not _has_scope(text, "ref_layout")
    assert _kernel_names(text) == {"pair_frontend", "candidate_pair_align",
                                   "residual_pair_dp"}


def test_both_aligners_scope_their_reference_layout(sds):
    from repro.kernels.candidate_align.ops import candidate_pair_align
    from repro.kernels.residual_dp.ops import residual_pair_dp

    ref, reads = sds((100_000,), jnp.uint8), sds((64, R))
    cands = sds((64, C))
    text = jax.jit(lambda *a: candidate_pair_align(
        *a, CFG.max_gap, backend="pallas")).lower(
            ref, reads, reads, cands, cands).as_text(debug_info=True)
    assert _has_scope(text, "ref_layout")
    rows = sds((64,))
    text = jax.jit(lambda *a: residual_pair_dp(
        *a, CFG.dp_pad, band=CFG.band(), backend="pallas")).lower(
            ref, reads, reads, rows, rows, sds((64,), jnp.bool_),
            sds((64,), jnp.bool_)).as_text(debug_info=True)
    assert _has_scope(text, "ref_layout")


def test_long_step_carries_stage_scopes_and_kernel_names(sds):
    text = _fused_step_text(sds, "long")
    for scope in ("lr.frontend", "lr.vote", "lr.anchor_dp", "assemble",
                  "stage_stats", "reduce"):
        assert _has_scope(text, scope), scope
    assert _kernel_names(text) == {"pair_frontend", "location_vote",
                                   "banded_sw"}


def test_sharded_step_carries_stage_scopes_and_kernel_names(v5e_2x2):
    """The sharded-index plan's step on a (data 2, model 2) mesh of the
    described chips, at chr1 scale (2^25 buckets split over `model`, the
    packed reference replicated, 4096 pairs a batch): its own front end
    (bucket-sharded lookup + merge/filter) and the replicated plan's
    stages carry the same scopes and kernel names."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.seedmap import SeedMapConfig
    from repro.engine import plan

    mesh = Mesh(np.array(v5e_2x2.devices).reshape(2, 2), ("data", "model"))
    cfg = PipelineConfig(frontend_backend="pallas", light_backend="pallas",
                         residual_backend="pallas", packed_ref=True)
    n_model, B = mesh.shape["model"], 4096
    state = (jax.ShapeDtypeStruct(
                 (n_model, (1 << TABLE_BITS) // n_model + 1), jnp.int32),
             jax.ShapeDtypeStruct((n_model, REF_LEN // n_model), jnp.int32),
             jax.ShapeDtypeStruct((REF_LEN // BASES_PER_WORD + 1,),
                                  jnp.uint32))
    step = plan.jit_step(
        plan.raw_sharded_index_step(
            mesh, cfg, SeedMapConfig(table_bits=TABLE_BITS)),
        mesh=mesh, state_shardings=plan.serve_state_shardings(mesh))
    reads = jax.ShapeDtypeStruct((B, R), jnp.uint8)
    text = step.lower(*state, reads, reads,
                      jax.ShapeDtypeStruct((), jnp.int32)).as_text(
                          debug_info=True)
    for scope in ("frontend", "light_align", "residual_dp", "assemble"):
        assert _has_scope(text, scope), scope
    assert _kernel_names(text) == {"pair_frontend", "candidate_pair_align",
                                   "residual_pair_dp"}
