"""ExecutionConfig + the once-per-session resolution of scattered knobs.

`resolved_pipeline` resolves the kernel backends and the `packed_ref`
tri-state exactly once, at `Mapper` build time: the `PipelineConfig` it
returns has concrete backend names and a concrete ``packed_ref`` bool, so
nothing on the per-batch path consults the environment or a plan
default again.
"""
from __future__ import annotations

import dataclasses

from jax.sharding import Mesh

from repro.core.long_read import LongReadConfig
from repro.core.pipeline import PipelineConfig
from repro.kernels.backend import resolve_backend


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """How a `Mapper` session executes its pre-built step.

    mesh:         run on this jax Mesh (None: single-device jit).
    batch_axes:   mesh axes the read batch shards over.
    model_axis:   mesh axis the SeedMap shards over (``shard_index``).
    shard_index:  bucket-shard the SeedMap along ``model_axis`` (the NMSL
                  channel-striping serve plan,
                  `core.genpairx_step.make_genpair_serve_step`); False
                  replicates the index and runs data-parallel.
                  Requires ``mesh``.
    stream_batch: fixed batch shape for `map_stream` (None: the first
                  batch's row count).  Ragged tail batches are padded up
                  to it and masked via `MapResult.n_valid`.
    donate_reads: donate the H2D read buffers of each `map_stream` step
                  to XLA (they are never reused host-side).
    backend:      unified kernel-backend override for *all* families,
                  resolved once at build (None: resolve the pipe config's
                  per-family settings, honoring ``REPRO_BACKEND``).
    packed_ref:   overrides the `PipelineConfig.packed_ref` tri-state at
                  build (None: resolve the tri-state against the plan's
                  default — packed for the sharded-index serve plan,
                  unpacked otherwise).
    long_read:    the session's long-read lane (`Mapper.map_long` /
                  ``map_long_stream``).  None builds the lane with the
                  default `LongReadConfig` on replicated-index plans;
                  setting it on a ``shard_index`` plan raises (the lane
                  has no sharded-index step yet).
    tune:         consult the autotuner's cache (`repro.tune`) at build.
                  A path string names the cache file; True uses the
                  default location; False never tunes; None (default)
                  opts in only when the ``REPRO_TUNE_CACHE`` env var is
                  set — so sessions stay on the hand-picked defaults
                  (and bit-stable against `map_pairs_impl`) unless
                  tuning is asked for.  Cached winners fill only knobs the
                  configs left unset: explicit config > tune cache >
                  defaults.
    """

    mesh: Mesh | None = None
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    shard_index: bool = False
    stream_batch: int | None = None
    donate_reads: bool = True
    backend: str | None = None
    packed_ref: bool | None = None
    long_read: LongReadConfig | None = None
    tune: bool | str | None = None

    def __post_init__(self):
        if self.shard_index and self.mesh is None:
            raise ValueError("shard_index=True requires a mesh")
        if self.shard_index and self.long_read is not None:
            raise ValueError(
                "the long-read lane is not available on shard_index plans")


def resolved_pipeline(
    pipe_cfg: PipelineConfig,
    exec_cfg: ExecutionConfig | None = None,
    *,
    tune_cache: dict | None = None,
) -> PipelineConfig:
    """Resolve every deferred `PipelineConfig` knob to a concrete value.

    Returns a config whose ``light_backend`` / ``frontend_backend`` /
    ``residual_backend`` are concrete backend names (env override and
    auto rule applied now, not per trace) and whose ``packed_ref`` is a
    concrete bool: ``ExecutionConfig.packed_ref``, else the config's
    own, else the plan's default (packed on the sharded-index plan).
    ``tune_cache`` — entries from `repro.tune` (`Mapper` loads them per
    `ExecutionConfig.tune`) — fills knobs the configs left unset
    *before* the backend/packed resolution, so explicit settings always
    win over cached winners.
    """
    exec_cfg = exec_cfg or ExecutionConfig()
    if tune_cache:
        from repro.tune import apply_tuned_pipeline
        pipe_cfg = apply_tuned_pipeline(
            pipe_cfg, tune_cache, batch=exec_cfg.stream_batch or 1024,
            exec_backend=exec_cfg.backend,
            exec_packed=exec_cfg.packed_ref)
    light = exec_cfg.backend or pipe_cfg.light_backend
    frontend = exec_cfg.backend or pipe_cfg.frontend_backend
    residual = exec_cfg.backend or pipe_cfg.residual_backend
    packed = exec_cfg.packed_ref
    if packed is None:
        packed = pipe_cfg.packed(default=exec_cfg.shard_index)
    return dataclasses.replace(
        pipe_cfg,
        light_backend=resolve_backend(light, family="candidate_align"),
        frontend_backend=resolve_backend(frontend, family="pair_frontend"),
        residual_backend=resolve_backend(residual, family="residual_dp"),
        packed_ref=bool(packed),
    )


def resolved_long_read(
    pipe_cfg: PipelineConfig,
    exec_cfg: ExecutionConfig | None = None,
    *,
    tune_cache: dict | None = None,
) -> LongReadConfig:
    """Resolve the session's long-read lane config, once, at build time.

    The lane's ``pipe`` resolves with the same rules as the session
    pipeline (`resolved_pipeline` — so ``ExecutionConfig.backend`` and
    ``REPRO_BACKEND`` govern the lane too) and its ``vote_backend``
    through the shared backend layer (family ``location_vote``).  Two
    knobs are forced to the session's resolved values because they are
    coupled to session state built once: ``max_locs_per_seed`` (the
    padded SeedMap row width) and ``packed_ref`` (the device reference
    flavor).  ``pipe_cfg`` must already be resolved.
    """
    exec_cfg = exec_cfg or ExecutionConfig()
    lr = exec_cfg.long_read or LongReadConfig()
    if tune_cache:
        from repro.tune import apply_tuned_long_read
        lr = apply_tuned_long_read(
            lr, tune_cache, batch=exec_cfg.stream_batch or 1024,
            exec_backend=exec_cfg.backend)
    lane_pipe = dataclasses.replace(
        lr.pipe,
        max_locs_per_seed=pipe_cfg.max_locs_per_seed,
        packed_ref=pipe_cfg.packed_ref,
    )
    lane_pipe = resolved_pipeline(lane_pipe, exec_cfg,
                                  tune_cache=tune_cache)
    vote = exec_cfg.backend or lr.vote_backend
    return dataclasses.replace(
        lr, pipe=lane_pipe,
        vote_backend=resolve_backend(vote, family="location_vote"))
