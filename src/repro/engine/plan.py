"""Execution-plan construction: the engine's pre-jitted steps.

One step shape for every placement: ``step(*state, reads1, reads2, n) ->
MapResult`` with ``n_valid = arange(B) < n`` — the same code
single-device and on a mesh; `ExecutionConfig(mesh=...)` only adds
in/out shardings (replicated-index data parallel) or swaps in the
sharded-index serve math of `core.genpairx_step` (``shard_index=True``).
``state`` is the session's device-resident index + reference (2 arrays
replicated, or 3 — sharded tables + the reference — on the sharded-index
plan).

The ``raw_*`` builders return the *traceable* step so `Mapper.map_stream`
can fuse it with the device-side stage-stat accumulator and a user
reduction into one jitted dispatch per batch; `jit_step` wraps a raw step
with the placement's shardings/donation for the synchronous ``map`` path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.genpairx_step import make_genpair_serve_step
from repro.core.long_read import LongReadConfig, map_long_impl
from repro.core.pipeline import PipelineConfig, map_pairs_impl
from repro.core.seedmap import SeedMapConfig


def _mask_tail(res, n: jnp.ndarray):
    """Set a step result's ``n_valid`` from the step's ``n`` argument.

    Works for any result NamedTuple with a (B,) ``n_valid`` field
    (`MapResult`, `LongReadResult`).  ``n`` is either the scalar count of
    valid *leading* rows (the single-host stream contract) or a (B,)
    per-row validity mask — the multi-host path, where each host's tail
    padding sits inside its own shard of the global batch, so validity is
    not a global prefix (`engine.multihost`).  The rank check is static
    at trace time: the two flavors compile to distinct steps.
    """
    with jax.named_scope("assemble"):
        if getattr(n, "ndim", 0) == 1:
            return res._replace(n_valid=n.astype(bool))
        B = res.n_valid.shape[0]
        return res._replace(n_valid=jnp.arange(B, dtype=jnp.int32) < n)


def raw_pipeline_step(cfg: PipelineConfig):
    """Traceable replicated-index step for ``cfg``.

    ``step(sm, ref, reads1, reads2, n) -> MapResult`` where ``sm`` is the
    CSR `SeedMap` or `PaddedSeedMap` the session resolved, ``ref`` the
    resolved reference flavor (uint8 bases or packed uint32 words) and
    ``n`` the count of valid leading rows (a traced scalar, so tail
    batches don't recompile).
    """

    def step(sm, ref, reads1, reads2, n):
        return _mask_tail(map_pairs_impl(sm, ref, reads1, reads2, cfg), n)

    return step


def raw_long_read_step(cfg: LongReadConfig):
    """Traceable replicated-index long-read lane step for ``cfg``.

    ``step(sm, ref, reads, n) -> LongReadResult`` — same state layout as
    `raw_pipeline_step` (the lane shares the session's index +
    reference), one read batch instead of two mates.
    """

    def step(sm, ref, reads, n):
        return _mask_tail(map_long_impl(sm, ref, reads, cfg), n)

    return step


def raw_sharded_index_step(
    mesh: Mesh,
    cfg: PipelineConfig,
    sm_cfg: SeedMapConfig,
    batch_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
):
    """Traceable sharded-index (NMSL) serve step with an ``n`` tail mask.

    ``step(offsets, locations, ref, reads1, reads2, n)`` — the
    bucket-sharded front end under shard_map, then the replicated plan's
    `map_candidates` (`make_genpair_serve_step`).
    """
    serve = make_genpair_serve_step(mesh, cfg, sm_cfg, batch_axes,
                                    model_axis)

    def step(offsets, locations, ref, reads1, reads2, n):
        return _mask_tail(serve(offsets, locations, ref, reads1, reads2), n)

    return step


def jit_step(raw, mesh: Mesh | None = None,
             state_shardings: tuple | None = None,
             batch_axes: tuple[str, ...] = ("data",),
             n_batch_args: int = 2):
    """Jit a raw step for the synchronous ``map`` path.

    ``n_batch_args`` is how many read-batch arrays follow the state
    arguments (2 mates for the pair step, 1 for the long-read lane),
    before the trailing ``n`` scalar; with ``mesh``, ``state_shardings``
    gives one sharding per state arg and the batch arrays shard over
    ``batch_axes``.
    """
    kwargs = {}
    if mesh is not None:
        batch_spec = NamedSharding(mesh, P(batch_axes))
        repl = NamedSharding(mesh, P())
        kwargs = dict(
            in_shardings=tuple(state_shardings)
            + (batch_spec,) * n_batch_args + (repl,),
            out_shardings=batch_spec,
        )
    return jax.jit(raw, **kwargs)


def serve_state_shardings(mesh: Mesh, model_axis: str = "model"):
    """(offsets, locations, ref) shardings of the sharded-index plan."""
    model_sh = NamedSharding(mesh, P(model_axis))
    return (model_sh, model_sh, NamedSharding(mesh, P()))
