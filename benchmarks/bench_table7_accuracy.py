"""Table 7 — accuracy: GenPair+fallback vs full-DP baseline, with/without
the index filter.

The paper's Table 7 runs variant calling (freebayes + vcfdist); position-
level mapping accuracy is the layer we can evaluate end to end on
simulated ground truth (the same proxy its Fig. 13 uses via paftools).
Reproduction targets: (1) GenPair's accuracy within noise of the full-DP
baseline, (2) the 500-location index filter costs ~nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.core import (
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, simulate_pairs,
)
from repro.core.baseline import map_single_end
from repro.core.seedmap import INVALID_LOC
from repro.core.simulate import repetitive_reference


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


def _prf(pos, true, mapped, tol=8):
    correct = mapped & (np.abs(pos - true) <= tol)
    prec = correct.sum() / max(mapped.sum(), 1)
    rec = correct.sum() / len(pos)
    f1 = 2 * prec * rec / max(prec + rec, 1e-9)
    return round(float(prec), 4), round(float(rec), 4), round(float(f1), 4)


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    ref = repetitive_reference(300_000, rng)
    sim = simulate_pairs(ref, 1024, ReadSimConfig(
        sub_rate=2e-3, ins_rate=2e-4, del_rate=2e-4), seed=53)
    r1, r2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
    ref_j = jnp.asarray(ref)
    cfg = PipelineConfig(residual_capacity_frac=0.5)
    rows = []

    for tag, max_loc in (("with_filter", 500), ("no_filter", 1 << 30)):
        sm = build_seedmap(ref, SeedMapConfig(table_bits=19,
                                              max_locations=max_loc))
        res = map_pairs_jit(sm, ref_j, r1, r2, cfg)
        pos = np.asarray(res.pos1)
        p, r, f1 = _prf(pos, sim.true_start1, pos != INVALID_LOC)
        rows.append(row(f"table7/genpair_{tag}", 0.0,
                        precision=p, recall=r, f1=f1))

    sm = build_seedmap(ref, SeedMapConfig(table_bits=19, max_locations=500))
    bl = map_single_end(sm, ref_j, r1, cfg)
    p, r, f1 = _prf(np.asarray(bl.pos), sim.true_start1,
                    np.asarray(bl.mapped))
    rows.append(row("table7/fulldp_baseline", 0.0,
                    precision=p, recall=r, f1=f1,
                    paper="GenPair+MM2 F1 within 0.0026 of MM2; filter "
                          "costs <=0.0001 F1"))
    return rows
