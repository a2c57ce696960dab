"""Jit'd public wrapper for the fused Location Voting op.

`location_vote` reduces each long read's (M,) candidate-diagonal row to
its winning vote bin + count (§4.7), behind the same
``backend="auto"|"pallas"|"interpret"|"jnp"`` switch as the other kernel
families.  The jnp backend is the bit-exact sorted-multiplicity oracle
(`ref.py`); the pallas/interpret backends run the all-pairs-count kernel,
which streams the diagonal rows through VMEM with the ping-pong DMA
protocol and never materializes counts in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.seedmap import INVALID_LOC
from repro.kernels._util import LANES, chunked_launch, pad_rows
from repro.kernels.backend import resolve_backend
from repro.kernels.location_vote.kernel import (
    DEFAULT_BLOCK,
    LAUNCH_ROWS,
    location_vote_pallas,
)
from repro.kernels.location_vote.ref import VoteResult, location_vote_ref


@functools.partial(
    jax.jit, static_argnames=("vote_bin", "block", "backend"))
def location_vote(
    diag: jnp.ndarray,       # (B, M) int32 diagonals, INVALID_LOC padded
    vote_bin: int,
    block: int | None = None,
    backend: str = "auto",
) -> VoteResult:
    """Per-read diagonal-bin vote + argmax for a batch of long reads.

    ``backend="auto"`` resolves through ``kernels/backend.py``
    (``REPRO_BACKEND`` honored).  The winning bin is the smallest among
    the maximally-voted bins; ``votes == 0`` (no valid candidate) pins
    ``win_bin`` to 0 — callers map that case to INVALID_LOC.  ``block=
    None`` resolves to `DEFAULT_BLOCK`; the autotuner (`repro.tune`)
    threads per-shape winners here through `LongReadConfig.vote_block`.
    """
    backend = resolve_backend(backend, family="location_vote")
    block = block or DEFAULT_BLOCK
    if backend == "jnp":
        return location_vote_ref(diag, vote_bin)

    B, M = diag.shape
    # Chunk the launch so every chunk shares one trace/compile
    # (identical shapes) whatever the batch; rows are padded to whole
    # 128-lane lines with non-voting INVALID_LOC slots.
    total, rows = chunked_launch(B, block, LAUNCH_ROWS)
    diag = jnp.pad(pad_rows(diag.astype(jnp.int32), total),
                   ((0, 0), (0, (-M) % LANES)), constant_values=INVALID_LOC)
    parts = [
        location_vote_pallas(
            diag[s:s + rows],
            jnp.full((1,), min(max(B - s, 0), rows), jnp.int32),
            vote_bin, M, block, interpret=(backend == "interpret"))
        for s in range(0, total, rows)
    ]
    outs = [jnp.concatenate(cols) if len(parts) > 1 else cols[0]
            for cols in zip(*parts)]
    win_bin, votes, _did = (o[:B] for o in outs)
    return VoteResult(win_bin=win_bin, votes=votes)
