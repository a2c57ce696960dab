"""Fig. 12 — DP-fallback ratio and throughput vs per-base error rate.

Paper: below ~0.2% error the pipeline is query-bound and throughput is
flat (~192 MPair/s); above it, DP fallback grows and throughput drops.
We sweep Mason-style uniform error rates, measuring (a) fallback after
Paired-Adjacency, (b) fallback after Light Alignment, (c) end-to-end
pairs/s of the jitted pipeline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import reads_for, row, time_fn
from repro.core import PipelineConfig, map_pairs_impl, stage_stats


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))

RATES = (0.0005, 0.001, 0.002, 0.005, 0.01)


def run() -> list[dict]:
    cfg = PipelineConfig()
    rows = []
    base_tput = None
    for e in RATES:
        ref, sm, ref_j, sim = reads_for(
            300_000, 1024, e * 0.8, ins_rate=e * 0.1, del_rate=e * 0.1,
            seed=23)
        r1, r2 = jnp.asarray(sim.reads1), jnp.asarray(sim.reads2)
        res = map_pairs_jit(sm, ref_j, r1, r2, cfg)
        st = {k: float(v) for k, v in stage_stats(res).items()}
        t = time_fn(lambda r1=r1, r2=r2: map_pairs_jit(sm, ref_j, r1, r2, cfg))
        tput = 1024 / t  # MPair/s-scale-free: pairs per us
        base_tput = base_tput or tput
        rows.append(row(
            f"fig12/error_{e:g}", t,
            adj_fallback_pct=round(100 * (st["adjacency_fail"]
                                          + st["no_seed_hit"]), 2),
            light_fallback_pct=round(100 * st["light_align_fail"], 2),
            rel_throughput=round(tput / base_tput, 3)))
    return rows
