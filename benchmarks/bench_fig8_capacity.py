"""Fig. 8 — sizing sweep: buffer capacity vs throughput (TPU analogue).

The paper sweeps the NMSL sliding-window size and picks 1024 (91.8% of
asymptotic throughput, 11.93 MB SRAM).  The SPMD analogues of those queues
are the static capacity knobs: K (locations gathered per seed) and C
(candidates kept after Paired-Adjacency).  We sweep both and report
throughput + recall — the same knee-shaped tradeoff the paper tunes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import reads_for, row, time_fn
from repro.core import PipelineConfig, map_pairs_impl
from repro.core.seedmap import INVALID_LOC


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


def _recall(res, sim, tol=8):
    pos = np.asarray(res.pos1)
    ok = pos != INVALID_LOC
    return float((ok & (np.abs(pos - sim.true_start1) <= tol)).mean())


def run() -> list[dict]:
    ref, sm, ref_j, sim = reads_for(300_000, 1024, 0.004, seed=47,
                                    repetitive=True)
    r1, r2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    rows = []
    for K in (4, 16, 32, 64):
        cfg = PipelineConfig(max_locs_per_seed=K)
        t = time_fn(lambda cfg=cfg: map_pairs_jit(sm, ref_j, r1, r2, cfg))
        res = map_pairs_jit(sm, ref_j, r1, r2, cfg)
        rows.append(row(f"fig8/K_locs_{K}", t,
                        recall=round(_recall(res, sim), 4),
                        rel_cost=round(t / rows[0]["us_per_call"], 2)
                        if rows else 1.0))
    for C in (2, 8, 16):
        cfg = PipelineConfig(max_candidates=C)
        t = time_fn(lambda cfg=cfg: map_pairs_jit(sm, ref_j, r1, r2, cfg))
        res = map_pairs_jit(sm, ref_j, r1, r2, cfg)
        rows.append(row(f"fig8/C_cands_{C}", t,
                        recall=round(_recall(res, sim), 4)))
    rows.append(row("fig8/paper_note", 0.0,
                    expected="knee curve; paper picks window=1024 at 91.8%"
                             " of asymptote"))
    return rows
