"""Jit'd public wrapper for the fused residual-DP fallback op.

`residual_pair_dp` is the one-call step-5 hot path: window gather +
banded Gotoh DP of both mates of every compacted residual row, behind the
same ``backend="auto"|"pallas"|"interpret"|"jnp"`` switch as the other
kernel families.  The jnp backend is the bit-exact staged oracle
(`ref.py`); the pallas/interpret backends run the fused kernel, which
never materializes the ``(N, R + 2*dp_pad)`` window tensors in HBM and
executes DP only for the failed-mate work items.

Item compaction (the single-mate-aware part) happens here, in-jit: the
``2*N`` (row, mate) slots are stably partitioned so the items whose
``need`` mask is set come first, the kernel runs over item blocks (dead
blocks skip at runtime), and the results scatter back to per-mate
``(N,)`` arrays through the inverse permutation.  Mates whose Light
Alignment succeeded never reach the kernel as live items and come back as
the ``NEG`` sentinel — the pipeline reuses their light score instead.
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
from repro.kernels.banded_sw.kernel import NEG
from repro.kernels.residual_dp.kernel import (
    DEFAULT_BLOCK,
    LAUNCH_ROWS,
    residual_dp_pallas,
)
from repro.kernels.residual_dp.ref import (
    ResidualDPResult,
    residual_pair_dp_ref,
)


@functools.partial(
    jax.jit,
    static_argnames=("dp_pad", "band", "scoring", "packed_ref", "block",
                     "backend"),
)
def residual_pair_dp(
    ref,                     # (L,) u8 bases, (Lw,) u32 words or LinedRef
    reads1: jnp.ndarray,     # (N, R) mate 1, reference orientation
    reads2: jnp.ndarray,     # (N, R) mate 2, reference orientation
    pos1: jnp.ndarray,       # (N,) best-candidate starts, INVALID_LOC padded
    pos2: jnp.ndarray,
    need1: jnp.ndarray,      # (N,) bool: mate 1's Light Alignment failed
    need2: jnp.ndarray,
    dp_pad: int,
    band: int | None = None,
    scoring: Scoring = Scoring(),
    packed_ref: bool = False,
    block: int | None = None,
    backend: str = "auto",
) -> ResidualDPResult:
    """Fused banded DP fallback for a compacted batch of residual pairs.

    ``backend="auto"`` resolves through ``kernels/backend.py``
    (``REPRO_BACKEND`` honored).  ``band`` is the half-width around the
    window's center diagonal (``None`` or ``>= R + 2*dp_pad``: exact full
    DP, the `gotoh_semiglobal` equivalence anchor).  ``ref`` is a plain
    reference, laid out in the call, or a session's `LinedRef`, as in
    `candidate_pair_align`.  ``block=None`` resolves to `DEFAULT_BLOCK`;
    the autotuner (`repro.tune`) threads per-shape winners here through
    `PipelineConfig.residual_block`.
    """
    backend = resolve_backend(backend, family="residual_dp")
    block = block or DEFAULT_BLOCK
    need1 = need1.astype(bool)
    need2 = need2.astype(bool)
    if backend == "jnp":
        return residual_pair_dp_ref(
            ref_bases(ref), reads1, reads2, pos1, pos2, need1, need2,
            dp_pad, band, scoring, packed_ref)

    N, R = reads1.shape
    W = R + 2 * dp_pad
    # The kernel DMAs each window from the reference's line layout
    # (kernels/_util.py): a session's, built once, or laid out here.
    if not isinstance(ref, LinedRef):
        ref = lined_ref(ref, packed_ref, (W,))
    assert ref.packed == packed_ref, (ref.packed, packed_ref)
    win_elems = window_elems(ref.bases.shape[0], packed_ref, W)
    sd1, off1 = window_lines(ref, pos1, pos1 != INVALID_LOC, W, dp_pad)
    sd2, off2 = window_lines(ref, pos2, pos2 != INVALID_LOC, W, dp_pad)

    # ---- single-mate-aware item compaction ------------------------------
    # Slot layout is row-major, mate-minor: slot 2*r + m is (row r, mate
    # m).  Stable partition puts the failed-mate items first; everything
    # after `n_items` is dead weight the kernel's grid steps skip.
    need = jnp.stack([need1, need2], -1).reshape(2 * N)
    sd = jnp.stack([sd1, sd2], -1).reshape(2 * N)
    off = jnp.stack([off1, off2], -1).reshape(2 * N)
    order = jnp.argsort(~need, stable=True)              # (2N,)
    n_items = jnp.sum(need.astype(jnp.int32))
    # Slot 2*r + m holds (row r, mate m), so one gather of the
    # mate-interleaved read stack compacts the item reads.
    item_reads = jnp.stack(
        [reads1.astype(jnp.int32), reads2.astype(jnp.int32)],
        axis=1).reshape(2 * N, R)[order]
    sd_c = sd[order]
    off_c = off[order][:, None]

    # Chunk the launch so the scalar-prefetch start table (SMEM, rows*4
    # bytes per launch) stays bounded for arbitrarily large residual
    # buffers; every chunk shares one trace/compile (identical shapes).
    total, rows = chunked_launch(2 * N, block, LAUNCH_ROWS)
    ins = tuple(pad_rows(x, total) for x in (sd_c, item_reads, off_c))
    parts = [
        residual_dp_pallas(
            ref.lines, ins[0][s:s + rows],
            jnp.clip(n_items - s, 0, rows).astype(jnp.int32)[None],
            ins[1][s:s + rows], ins[2][s:s + rows],
            dp_pad, band, scoring, packed_ref, win_elems, block,
            interpret=(backend == "interpret"),
        )
        for s in range(0, total, rows)
    ]
    outs = [jnp.concatenate(cols) if len(parts) > 1 else cols[0]
            for cols in zip(*parts)]
    score_c, end_c, did = (o[:2 * N] for o in outs)

    # ---- scatter back through the inverse permutation -------------------
    inv = jnp.argsort(order)                             # slot -> compacted
    score = jnp.where(need, score_c[inv], NEG).reshape(N, 2)
    end = jnp.where(need, end_c[inv], 0).reshape(N, 2)
    return ResidualDPResult(
        score1=score[:, 0], ref_end1=end[:, 0],
        score2=score[:, 1], ref_end2=end[:, 1],
        dp_lanes=jnp.sum(did),
    )
