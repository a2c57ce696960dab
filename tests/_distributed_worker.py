"""Multi-device worker for tests/test_distributed.py.

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 in a
subprocess (the main pytest process must keep seeing 1 device).  Asserts:

  1. the sharded-index plan's bucket-sharded SeedMap lookup
     (`sharded_lookup`: `_local_query` under shard_map plus ``pmin``)
     returns exactly the single-device CSR query's locations;
  2. the engine's sharded-index plan (Mapper with shard_index=True —
     packed reference, sharded tables) equals a replicated packed
     session on every `MapResult` field, with no DP overflow on either
     side;
  3. the engine's data-parallel plan (Mapper with mesh=...) equals the
     single-device pipeline on every field;
  4. the G2 prescreen (prescreen_top=2) preserves the mapping;
  5. the sharded plan's front end (`sharded_frontend`) equals the staged
     single-device front end;
  6. mapper.map_stream on the mesh plan handles a ragged tail batch
     (padding + n_valid) and its device-side stage totals match;
  7. a shard_index session with packed_ref=False (uint8 bases placed)
     equals the replicated unpacked plan on every field.

Exit code 0 = all checks passed.
"""
import dataclasses
import functools
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import (  # noqa: E402
    PipelineConfig, ReadSimConfig, SeedMapConfig, build_seedmap,
    map_pairs_impl, random_reference, simulate_pairs, stage_stat_counts,
)
from repro.core.distributed import shard_seedmap  # noqa: E402
from repro.core.genpairx_step import (  # noqa: E402
    sharded_frontend, sharded_lookup,
)
from repro.core.pair_filter import paired_adjacency_filter  # noqa: E402
from repro.core.query import query_csr, query_read_batch  # noqa: E402
from repro.core.seeding import seed_read_batch  # noqa: E402
from repro.engine import ExecutionConfig, Mapper  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402

map_pairs_jit = jax.jit(map_pairs_impl, static_argnames=("cfg",))


def assert_same(got, want, what):
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f"{what}: {f}")


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_auto_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    ref = random_reference(120_000, rng)
    cfg = PipelineConfig()
    K = cfg.max_locs_per_seed
    sm = build_seedmap(ref, SeedMapConfig(table_bits=16))
    sim = simulate_pairs(ref, 64, ReadSimConfig(sub_rate=2e-3), seed=1)
    reads1 = jnp.asarray(sim.reads1)
    reads2 = jnp.asarray(sim.reads2)
    ssm = shard_seedmap(sm, 4)
    tables = (P("model"), P("model"))

    # ---- 1. sharded lookup == single-device CSR query --------------------
    seeds = seed_read_batch(reads1, cfg.seed_len, cfg.seeds_per_read,
                            sm.config.hash_seed)
    locs_single, _ = query_csr(sm, seeds.hashes, K)
    lookup = jax.shard_map(
        functools.partial(sharded_lookup, sm_cfg=sm.config, K=K),
        mesh=mesh, in_specs=tables + (P("data"),), out_specs=P("data"))
    locs_shard = lookup(ssm.offsets, ssm.locations, seeds.hashes)
    np.testing.assert_array_equal(np.asarray(locs_single),
                                  np.asarray(locs_shard))
    print("ok: sharded lookup == CSR query")

    # ---- 2. sharded-index plan == replicated packed session --------------
    cfg_packed = dataclasses.replace(cfg, packed_ref=True)
    m_shard = Mapper.from_index(
        sm, ref, cfg, ExecutionConfig(mesh=mesh, shard_index=True))
    assert m_shard.pipe_cfg.packed_ref is True
    res_d = m_shard.map(reads1, reads2)
    res_rp = Mapper.from_index(sm, ref, cfg_packed).map(reads1, reads2)
    for res in (res_d, res_rp):
        assert int(stage_stat_counts(res)["dp_overflow"]) == 0
    assert_same(res_d, res_rp, "sharded vs replicated, packed")
    print("ok: engine sharded-index plan == replicated packed session")

    # ---- 3. engine data-parallel plan == single-device pipeline ----------
    res_s = map_pairs_jit(sm, jnp.asarray(ref), reads1, reads2, cfg)
    m_dp = Mapper.from_index(sm, ref, cfg, ExecutionConfig(mesh=mesh))
    assert_same(m_dp.map(reads1, reads2), res_s, "data-parallel")
    print("ok: engine data-parallel plan == single-device pipeline")

    # ---- 4. G2 prescreen keeps the mapping (§Perf beyond-paper opt) ----
    cfg_p = dataclasses.replace(cfg, prescreen_top=2)
    m_p = Mapper.from_index(
        sm, ref, cfg_p, ExecutionConfig(mesh=mesh, shard_index=True))
    res_p = m_p.map(reads1, reads2)
    same_pos = (np.asarray(res_p.pos1) == np.asarray(res_s.pos1)).mean()
    assert same_pos >= 0.97, f"prescreen changed {1-same_pos:.1%} of pos"
    light_p = (np.asarray(res_p.method) == 1).mean()
    light_s = (np.asarray(res_s.method) == 1).mean()
    assert light_p >= light_s - 0.05, (light_p, light_s)
    print(f"ok: prescreen_top=2 preserves mapping ({same_pos:.1%} same)")

    # ---- 5. sharded plan's front end == staged single-device front end --
    reads2_fwd = (3 - reads2)[:, ::-1]
    seeds2 = seed_read_batch(reads2_fwd, cfg.seed_len, cfg.seeds_per_read,
                             sm.config.hash_seed)
    q1 = query_read_batch(sm, seeds, K)
    q2 = query_read_batch(sm, seeds2, K)
    cands = paired_adjacency_filter(q1, q2, cfg.delta, cfg.max_candidates)
    frontend = jax.shard_map(
        functools.partial(sharded_frontend, cfg=m_shard.pipe_cfg,
                          sm_cfg=sm.config),
        mesh=mesh, in_specs=tables + (P("data"), P("data")),
        out_specs=P("data"), check_vma=False)
    fe = frontend(ssm.offsets, ssm.locations, reads1, reads2_fwd)
    np.testing.assert_array_equal(np.asarray(fe.pos1), np.asarray(cands.pos1))
    np.testing.assert_array_equal(np.asarray(fe.pos2), np.asarray(cands.pos2))
    np.testing.assert_array_equal(np.asarray(fe.n), np.asarray(cands.n))
    np.testing.assert_array_equal(np.asarray(fe.n_hits1),
                                  np.asarray(q1.n_hits))
    np.testing.assert_array_equal(np.asarray(fe.n_hits2),
                                  np.asarray(q2.n_hits))
    print("ok: sharded plan's front end == staged front end")

    # ---- 6. mesh map_stream: ragged tail padding + device stage totals --
    m_stream = Mapper.from_index(
        sm, ref, cfg, ExecutionConfig(mesh=mesh, stream_batch=64))
    tail = 24  # ragged: padded to 64 on device, masked via n_valid
    sr = m_stream.map_stream(
        iter([(sim.reads1, sim.reads2),
              (sim.reads1[:tail], sim.reads2[:tail])]))
    assert sr.n_pairs == 64 + tail == sr.totals["n_pairs"], sr.totals
    full = {k: int(v) for k, v in stage_stat_counts(res_s).items()}
    head = {k: int(v) for k, v in stage_stat_counts(
        jax.tree.map(lambda x: x[:tail], res_s)).items()}
    want = {k: full[k] + head[k] for k in full}
    assert sr.totals == want, (sr.totals, want)
    print("ok: mesh map_stream ragged tail + device-side stage totals")

    # ---- 7. unpacked sharded-index session == replicated unpacked plan --
    m_shard_u = Mapper.from_index(
        sm, ref, dataclasses.replace(cfg, packed_ref=False),
        ExecutionConfig(mesh=mesh, shard_index=True))
    assert m_shard_u._state[2].dtype == jnp.uint8
    res_du = m_shard_u.map(reads1, reads2)
    assert int(stage_stat_counts(res_s)["dp_overflow"]) == 0
    assert_same(res_du, res_s, "sharded vs replicated, unpacked")
    print("ok: unpacked sharded-index session == replicated unpacked plan")


if __name__ == "__main__":
    main()
