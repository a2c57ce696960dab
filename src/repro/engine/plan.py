"""Execution-plan construction: the engine's pre-jitted steps.

One step shape for every placement: ``step(*state, reads1, reads2, n) ->
MapResult`` with ``n_valid = arange(B) < n`` — the same code
single-device and on a mesh; `ExecutionConfig(mesh=...)` only adds
in/out shardings (replicated-index data parallel) or swaps in the
sharded-index serve math of `core.genpairx_step` (``shard_index=True``).
``state`` is the session's device-resident index + reference (2 arrays
replicated, or 3 — sharded tables + packed words — on the sharded-index
plan).

The ``raw_*`` builders return the *traceable* step so `Mapper.map_stream`
can fuse it with the device-side stage-stat accumulator and a user
reduction into one jitted dispatch per batch; `jit_step` wraps a raw step
with the placement's shardings/donation for the synchronous ``map`` path.

`mesh_serve_jit` is the lowering/compilation entry the multi-pod dry-run
(`launch/dryrun.py`) uses for the ``genpair`` cell — the same jit a
``shard_index=True`` Mapper executes, minus the session state and tail
mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.genpairx_step import make_genpair_serve_step
from repro.core.long_read import LongReadConfig, map_long_impl
from repro.core.pipeline import MapResult, PipelineConfig, map_pairs_impl
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

    ``step(offsets, locations, ref_words, reads1, reads2, n)`` — the
    bucket-sharded SeedMap lookup under shard_map plus the fused
    merge/filter and candidate-align ops of `make_genpair_serve_step`.
    """
    serve = make_genpair_serve_step(mesh, cfg, sm_cfg, batch_axes,
                                    model_axis)

    def step(offsets, locations, ref_words, reads1, reads2, n):
        return _mask_tail(serve(offsets, locations, ref_words, reads1,
                                reads2), n)

    return step


def jit_step(raw, n_state: int, mesh: Mesh | None = None,
             state_shardings: tuple | None = None,
             batch_axes: tuple[str, ...] = ("data",),
             donate_reads: bool = False, n_batch_args: int = 2):
    """Jit a raw step for the synchronous ``map`` path.

    ``n_state`` is how many leading state arguments the raw step takes
    and ``n_batch_args`` how many read-batch arrays follow (2 mates for
    the pair step, 1 for the long-read lane), before the trailing ``n``
    scalar; with ``mesh``, ``state_shardings`` gives one sharding per
    state arg and the batch arrays shard over ``batch_axes``.
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
    if donate_reads:
        kwargs["donate_argnums"] = tuple(
            range(n_state, n_state + n_batch_args))
    return jax.jit(raw, **kwargs)


def pipeline_step(
    cfg: PipelineConfig,
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = ("data",),
    donate_reads: bool = False,
):
    """Jitted replicated-index step (the `make_distributed_map_pairs`
    placement when ``mesh`` is given: index/reference replicated, batch
    sharded over ``batch_axes``)."""
    shardings = None
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        shardings = (repl, repl)
    return jit_step(raw_pipeline_step(cfg), 2, mesh=mesh,
                    state_shardings=shardings, batch_axes=batch_axes,
                    donate_reads=donate_reads)


def serve_state_shardings(mesh: Mesh, model_axis: str = "model"):
    """(offsets, locations, ref_words) shardings of the sharded-index plan."""
    model_sh = NamedSharding(mesh, P(model_axis))
    return (model_sh, model_sh, NamedSharding(mesh, P()))


def mesh_serve_jit(
    mesh: Mesh,
    cfg: PipelineConfig,
    sm_cfg: SeedMapConfig,
    batch_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
):
    """The bare genome-scale serve step, jitted with its shardings.

    Signature ``(offsets, locations, ref_words, reads1, reads2)`` — no
    tail mask — so the multi-pod dry-run can ``.lower()`` it against
    `genpair_input_specs` unchanged.  Callers pass an already-resolved
    config (`engine.config.resolved_pipeline`).
    """
    serve = make_genpair_serve_step(mesh, cfg, sm_cfg, batch_axes,
                                    model_axis)
    batch_spec = NamedSharding(mesh, P(batch_axes))
    return jax.jit(
        serve,
        in_shardings=serve_state_shardings(mesh, model_axis)
        + (batch_spec, batch_spec),
        out_shardings=batch_spec,
    )
