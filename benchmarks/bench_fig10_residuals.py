"""Fig. 10 — residual read pairs per GenPair stage.

Paper (HG002, GRCh38): 2.09% fail SeedMap query, 8.79% fail
Paired-Adjacency, 13.06% fail Light Alignment (=> 76.1% light-aligned,
89.1% mapped without full DP seeding/chaining).

We measure the same quantities at the calibrated effective error rate and
report paper values alongside.  The trend (query residual < adjacency
residual < light-align residual) and the ~3/4 light-aligned fraction are
the reproduction targets; exact percentages depend on the repeat content
of the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import reads_for, row, time_fn
from repro.core import PipelineConfig, map_pairs_impl, stage_stats


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


def run() -> list[dict]:
    cfg = PipelineConfig()
    ref, sm, ref_j, sim = reads_for(300_000, 2048, 0.007, ins_rate=6e-4,
                                    del_rate=6e-4, seed=17)
    res = map_pairs_jit(sm, ref_j, jnp.asarray(sim.reads1),
                        jnp.asarray(sim.reads2), cfg)
    st = {k: float(v) for k, v in stage_stats(res).items()}
    light = st["light_mapped"]
    mapped_no_full_dp = light + st["dp_mapped"]
    return [
        row("fig10/no_seedmap_hit", 0.0,
            measured_pct=round(100 * st["no_seed_hit"], 2), paper_pct=2.09),
        row("fig10/adjacency_fail", 0.0,
            measured_pct=round(100 * st["adjacency_fail"], 2),
            paper_pct=8.79),
        row("fig10/light_align_fail", 0.0,
            measured_pct=round(100 * st["light_align_fail"], 2),
            paper_pct=13.06),
        row("fig10/light_aligned", 0.0,
            measured_pct=round(100 * light, 2), paper_pct=76.1),
        row("fig10/mapped_wo_full_dp", 0.0,
            measured_pct=round(100 * mapped_no_full_dp, 2), paper_pct=89.1),
    ]
