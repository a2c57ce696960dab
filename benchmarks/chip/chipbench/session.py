"""The system under test: a `repro` `Mapper` session for a cell.

A deployment restarting a worker loads its index store (`Mapper.load`)
rather than indexing the genome again, so a cell's first run in a
checkout builds the index and saves the store under
``benchmarks/chip/store/`` (git-ignored), and every later run loads it:
`build_seedmap` is then never called.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import time

import numpy as np

from chipbench.cell import Cell


def program_configs(cell: Cell, control: bool = False):
    """(SeedMapConfig, PipelineConfig, LongReadConfig) as stated."""
    from repro.core import PipelineConfig, SeedMapConfig
    from repro.core.long_read import LongReadConfig
    from repro.core.scoring import Scoring

    nums = cell.program_numbers(control)
    pipe = dict(nums["pipeline"])
    pipe_cfg = PipelineConfig(**{**pipe, "scoring": Scoring(**pipe["scoring"])})
    lr_cfg = LongReadConfig(pipe=pipe_cfg, **nums["long_read"])
    return SeedMapConfig(**nums["seedmap"]), pipe_cfg, lr_cfg


def open_session(cell: Cell, genome: np.ndarray, *, control: bool = False):
    """The cell's `Mapper`, loaded from the store (built on first use)."""
    from repro.engine import ExecutionConfig, Mapper
    from repro.engine.index_store import load_store, store_size_bytes

    sm_cfg, pipe_cfg, lr_cfg = program_configs(cell, control)
    exec_cfg = ExecutionConfig(stream_batch=cell.batch, tune=False,
                               long_read=lr_cfg)
    store = cell.bench_dir / "store" / cell.store_key()
    info = {"store": os.fspath(store.relative_to(cell.root))}
    if not (store / "manifest.json").exists():
        from repro.core import build_seedmap

        t0 = time.perf_counter()
        sm = build_seedmap(genome, sm_cfg)
        info["index_build_s"] = time.perf_counter() - t0
        n_pos = genome.shape[0] - sm_cfg.seed_len + 1
        counts = np.diff(np.asarray(sm.offsets))
        nonempty = counts[counts > 0]
        info["index"] = {
            "n_seed_positions": n_pos,
            "n_locations": int(sm.locations.shape[0]),
            "share_positions_dropped_at_threshold":
                1 - int(sm.locations.shape[0]) / n_pos,
            "n_nonempty_buckets": int(nonempty.size),
            "locs_per_nonempty_bucket": float(nonempty.mean()),
            "share_nonempty_buckets_over_cap":
                float((nonempty > pipe_cfg.max_locs_per_seed).mean()),
        }
        t0 = time.perf_counter()
        _, base_pipe, base_lr = program_configs(cell)
        built = Mapper.from_index(
            sm, genome, base_pipe,
            dataclasses.replace(exec_cfg, long_read=base_lr))
        del sm
        tmp = store.with_name(store.name + ".tmp")
        shutil.rmtree(tmp, ignore_errors=True)
        built.save(tmp)
        shutil.rmtree(store, ignore_errors=True)
        os.replace(tmp, store)
        info["store_build_s"] = time.perf_counter() - t0
        if not control:
            info["store_bytes"] = store_size_bytes(store)
            return built, info
        del built
    t0 = time.perf_counter()
    if control:
        payload = load_store(store, strict=True)
        mapper = Mapper.from_index(payload.index, payload.ref, pipe_cfg,
                                   exec_cfg)
    else:
        mapper = Mapper.load(store, dataclasses.replace(exec_cfg,
                                                        long_read=None))
    info["store_load_s"] = time.perf_counter() - t0
    info["store_bytes"] = store_size_bytes(store)
    return mapper, info


def backends(mapper) -> dict:
    return {"pair_frontend": mapper.pipe_cfg.frontend_backend,
            "candidate_align": mapper.pipe_cfg.light_backend,
            "residual_dp": mapper.pipe_cfg.residual_backend,
            "location_vote": mapper.lr_cfg.vote_backend}


def accuracy_reduce(cell: Cell, mapper):
    """The serve CLI's device-side accuracy reduction for the lane."""
    import jax.numpy as jnp
    from repro.launch.serve import (
        ACC_KEYS,
        _make_accuracy_reduce,
        _make_vote_accuracy_reduce,
    )

    if cell.lane == "pairs":
        fn = _make_accuracy_reduce(mapper.pipe_cfg.max_gap)
        keys = ACC_KEYS
    else:
        fn = _make_vote_accuracy_reduce(mapper.lr_cfg.vote_bin)
        keys = ("mapped", "correct")
    return fn, {k: jnp.zeros((), jnp.int32) for k in keys}


def stream(cell: Cell, mapper, batches, **kwargs):
    """`map_stream` or `map_long_stream`, by the cell's lane."""
    if cell.lane == "pairs":
        return mapper.map_stream(batches, **kwargs)
    return mapper.map_long_stream(batches, **kwargs)


def stream_item(cell: Cell, batch: dict) -> tuple:
    """A pool batch as the lane's stream item, with its truth as aux."""
    if cell.lane == "pairs":
        return (batch["reads1"], batch["reads2"],
                (batch["true1"], batch["true2"]))
    return (batch["reads"], (batch["true"],))
