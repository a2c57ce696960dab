"""Fig. 13 — index-filtering-threshold sensitivity (precision/recall/F1).

The paper sweeps the max-locations-per-seed filter on SeedMap built from
GRCh38 and measures mapping precision/recall (paftools-style: position
check only, no alignment check).  We sweep the same knob on the planted-
repeat reference (uniform references have no crowded buckets, so the
filter would be a no-op — see bench_obs2).  GenPair runs WITHOUT DP
fallback, as in the paper's Fig. 13 protocol.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, simulate_pairs,
)
from repro.core.pipeline import M_LIGHT
from repro.core.seedmap import INVALID_LOC
from repro.core.simulate import repetitive_reference


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))

THRESHOLDS = (4, 16, 64, 500)


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    ref = repetitive_reference(300_000, rng)
    # paper protocol: SNP 1e-3, INDEL 2e-4, Mason default error profile
    sim = simulate_pairs(ref, 1024, ReadSimConfig(
        sub_rate=1e-3 + 1e-3, ins_rate=2e-4, del_rate=2e-4), seed=31)
    cfg = PipelineConfig(residual_capacity_frac=1e-9)  # no DP fallback
    rows = []
    for thr in THRESHOLDS:
        sm = build_seedmap(ref, SeedMapConfig(table_bits=19,
                                              max_locations=thr))
        res = map_pairs_jit(sm, jnp.asarray(ref), jnp.asarray(sim.reads1),
                            jnp.asarray(sim.reads2), cfg)
        pos = np.asarray(res.pos1)
        method = np.asarray(res.method)
        mapped = (pos != INVALID_LOC) & (method == M_LIGHT)
        correct = mapped & (np.abs(pos - sim.true_start1) <= cfg.max_gap)
        precision = correct.sum() / max(mapped.sum(), 1)
        recall = correct.sum() / len(pos)
        f1 = (2 * precision * recall / max(precision + recall, 1e-9))
        rows.append(row(
            f"fig13/threshold_{thr}", 0.0,
            mapped=int(mapped.sum()), precision=round(float(precision), 4),
            recall=round(float(recall), 4), f1=round(float(f1), 4)))
    rows.append(row("fig13/paper_note", 0.0,
                    expected="recall rises with threshold, precision falls;"
                             " F1 plateaus (paper picks 500)"))
    return rows
