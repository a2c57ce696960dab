"""Jit'd public wrapper for the fused candidate light-alignment op.

`candidate_pair_align` is the one-call step-4 hot path: candidate window
gather + Light Alignment of both mates + prescreen + best-pair reduction,
behind the same ``backend="auto"|"pallas"|"jnp"|"interpret"`` switch as
`kernels/light_align/ops.py`.  The jnp backend is the bit-exact unfused
oracle (`ref.py`); the pallas/interpret backends run the fused kernel,
which never materializes the `(B, C, R+2E)` window tensor in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.encoding import LinedRef, ref_bases
from repro.core.scoring import Scoring
from repro.core.seedmap import INVALID_LOC
from repro.kernels._util import (
    chunked_launch,
    lined_ref,
    pad_rows,
    window_elems,
    window_lines,
)
from repro.kernels.backend import resolve_backend
from repro.kernels.candidate_align.kernel import (
    DEFAULT_BLOCK,
    LAUNCH_ROWS,
    candidate_align_pallas,
)
from repro.kernels.candidate_align.ref import (
    PairAlignResult,
    best_fields_to_cigars,
    candidate_pair_align_ref,
)


@functools.partial(
    jax.jit,
    static_argnames=("max_gap", "scoring", "threshold", "mode",
                     "prescreen_top", "packed_ref", "block", "backend"),
)
def candidate_pair_align(
    ref,                     # (L,) u8 bases, (Lw,) u32 words or LinedRef
    reads1: jnp.ndarray,     # (B, R) mate 1, reference orientation
    reads2: jnp.ndarray,     # (B, R) mate 2, reference orientation
    pos1: jnp.ndarray,       # (B, C) candidate starts, INVALID_LOC padded
    pos2: jnp.ndarray,       # (B, C)
    max_gap: int,
    scoring: Scoring = Scoring(),
    threshold: int | None = None,
    mode: str = "minsplit",
    prescreen_top: int = 0,
    packed_ref: bool = False,
    block: int | None = None,
    backend: str = "auto",
) -> PairAlignResult:
    """Fused best-candidate Light Alignment for a batch of read pairs.

    ``backend="auto"`` resolves through ``kernels/backend.py``: the Pallas
    kernel on TPU, the jnp oracle elsewhere, with the ``REPRO_BACKEND``
    env var overriding the auto choice — CI uses it to drive the whole
    pipeline through the interpret-mode kernels on CPU.  The override is
    read at trace time, so set it before the first call in a process.

    ``ref`` is a plain reference, whose line layout the kernel backends
    build in the call (`reference_lines`), or a session's `LinedRef`,
    whose layout was built once; the jnp oracle reads its ``bases``.

    ``block=None`` resolves to the hand-picked family default
    (`DEFAULT_BLOCK`); the autotuner (`repro.tune`) threads per-shape
    winners here through `PipelineConfig.light_block`.
    """
    backend = resolve_backend(backend, family="candidate_align")
    block = block or DEFAULT_BLOCK
    if backend == "jnp":
        return candidate_pair_align_ref(
            ref_bases(ref), reads1, reads2, pos1, pos2, max_gap, scoring,
            threshold, mode, prescreen_top, packed_ref)

    B, R = reads1.shape
    C = pos1.shape[1]
    E = max_gap
    W = R + 2 * E
    if threshold is None:
        threshold = scoring.default_threshold(R)

    valid1 = pos1 != INVALID_LOC
    valid2 = pos2 != INVALID_LOC
    # The kernel DMAs each window from the reference's line layout
    # (kernels/_util.py): a session's, built once, or laid out here.
    if not isinstance(ref, LinedRef):
        ref = lined_ref(ref, packed_ref, (W,))
    assert ref.packed == packed_ref, (ref.packed, packed_ref)
    win_elems = window_elems(ref.bases.shape[0], packed_ref, W)
    sdma1, off1 = window_lines(ref, pos1, valid1, W, E)
    sdma2, off2 = window_lines(ref, pos2, valid2, W, E)

    # Chunk the launch so the scalar-prefetch DMA tables (SMEM, 2*rows*C*4
    # bytes per launch) stay bounded for arbitrarily large batches; every
    # chunk shares one trace/compile (identical shapes).
    total, rows = chunked_launch(B, block, LAUNCH_ROWS)

    reads1, reads2, sdma1, sdma2, off1, off2, valid1, valid2 = (
        pad_rows(x, total) for x in (
            reads1.astype(jnp.int32), reads2.astype(jnp.int32),
            sdma1, sdma2, off1, off2,
            valid1.astype(jnp.int32), valid2.astype(jnp.int32)))
    parts = [
        candidate_align_pallas(
            ref.lines, reads1[s:s + rows], reads2[s:s + rows],
            # SMEM start tables flattened 1-D (row-major (rows, C))
            sdma1[s:s + rows].reshape(-1), sdma2[s:s + rows].reshape(-1),
            *(x[s:s + rows] for x in (off1, off2, valid1, valid2)),
            E, scoring, threshold, mode, prescreen_top, packed_ref,
            win_elems, block, interpret=(backend == "interpret"),
        )
        for s in range(0, total, rows)
    ]
    outs = [jnp.concatenate(cols) if len(parts) > 1 else cols[0]
            for cols in zip(*parts)]
    sl = slice(0, B)
    (slot, rank, sc1, sc2, ok1, ok2,
     et1, el1, ep1, et2, el2, ep2) = (o[sl] for o in outs)
    b_pos1 = jnp.take_along_axis(pos1, slot[:, None], 1)[:, 0]
    b_pos2 = jnp.take_along_axis(pos2, slot[:, None], 1)[:, 0]
    return PairAlignResult(
        best=rank, slot=slot, pos1=b_pos1, pos2=b_pos2,
        score1=sc1, score2=sc2,
        ok1=ok1.astype(bool), ok2=ok2.astype(bool),
        cigar1=best_fields_to_cigars(et1, el1, ep1, R),
        cigar2=best_fields_to_cigars(et2, el2, ep2, R),
    )
