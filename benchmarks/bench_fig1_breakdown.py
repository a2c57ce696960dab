"""Fig. 1 — execution-time breakdown of read mapping stages.

The paper profiles Minimap2 and finds DP chaining+alignment at 83-85% of
runtime.  We reproduce the *baseline* breakdown with our full-DP mapper
(chaining+alignment emulated by DP-scoring every candidate) and contrast
with the GenPair pipeline where light alignment replaces most DP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import reads_for, row, time_fn
from repro.core import PipelineConfig, map_pairs_impl
from repro.core.baseline import map_single_end
from repro.core.dp_fallback import gotoh_semiglobal
from repro.core.light_align import gather_ref_windows, light_align
from repro.core.pair_filter import paired_adjacency_filter
from repro.core.query import query_read_batch
from repro.core.seeding import seed_read_batch


map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


def run() -> list[dict]:
    cfg = PipelineConfig()
    ref, sm, ref_j, sim = reads_for(300_000, 256, 1e-3)
    reads1 = jnp.asarray(sim.reads1)
    reads2 = jnp.asarray(sim.reads2)
    B, R = reads1.shape

    # ---- stage timings (jitted separately) -------------------------------
    seed_fn = jax.jit(lambda r: seed_read_batch(
        r, cfg.seed_len, cfg.seeds_per_read, sm.config.hash_seed))
    t_seed = time_fn(seed_fn, reads1)

    seeds = seed_fn(reads1)
    query_fn = jax.jit(lambda s: query_read_batch(sm, s,
                                                  cfg.max_locs_per_seed))
    t_query = time_fn(query_fn, seeds)

    q1 = query_fn(seeds)
    q2 = query_fn(seed_fn((3 - reads2)[:, ::-1]))
    adj_fn = jax.jit(lambda a, b: paired_adjacency_filter(
        a, b, cfg.delta, cfg.max_candidates))
    t_adj = time_fn(adj_fn, q1, q2)

    cands = adj_fn(q1, q2)
    starts = jnp.where(cands.pos1 != jnp.int32(2**31 - 1), cands.pos1, 0)

    def light_fn(r, s):
        wins = gather_ref_windows(ref_j, s, R, cfg.max_gap)
        C = s.shape[1]
        rt = jnp.broadcast_to(r[:, None], (B, C, R)).reshape(B * C, R)
        return light_align(rt, wins.reshape(B * C, -1), cfg.max_gap,
                           cfg.scoring, cfg.threshold(), cfg.light_mode)
    t_light = time_fn(jax.jit(light_fn), reads1, starts)

    def dp_fn(r, s):
        wins = gather_ref_windows(ref_j, s[:, 0], R, cfg.dp_pad)
        return gotoh_semiglobal(r, wins, cfg.scoring)
    t_dp_one = time_fn(jax.jit(dp_fn), reads1, starts)

    # ---- end-to-end: GenPair vs full-DP baseline --------------------------
    t_pair = time_fn(
        lambda: map_pairs_jit(sm, ref_j, reads1, reads2, cfg))
    t_base = time_fn(
        lambda: (map_single_end(sm, ref_j, reads1, cfg),
                 map_single_end(sm, ref_j, (3 - reads2)[:, ::-1], cfg)))

    total = t_seed + t_query + t_adj + t_light + t_dp_one
    # baseline DP share: everything except seeding+query is DP
    base_dp_share = 1.0 - (t_seed + t_query) / t_base
    return [
        row("fig1/seeding", t_seed, pct=round(100 * t_seed / total, 1)),
        row("fig1/seedmap_query", t_query,
            pct=round(100 * t_query / total, 1)),
        row("fig1/paired_adjacency", t_adj,
            pct=round(100 * t_adj / total, 1)),
        row("fig1/light_align", t_light,
            pct=round(100 * t_light / total, 1)),
        row("fig1/dp_fallback_1cand", t_dp_one,
            pct=round(100 * t_dp_one / total, 1)),
        row("fig1/e2e_genpair", t_pair, pairs=int(reads1.shape[0])),
        row("fig1/e2e_fulldp_baseline", t_base,
            dp_share_pct=round(100 * base_dp_share, 1),
            paper_dp_share_pct="83.4-84.9",
            speedup_vs_baseline=round(t_base / t_pair, 2)),
    ]
