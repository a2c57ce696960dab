"""Shared launch plumbing for the fused-op ops.py wrappers.

Ops whose Pallas launches carry scalar-prefetch DMA tables (SMEM) chunk
large batches into bounded launches; the pad-and-chunk protocol is the
same for every family, so it lives here once — as does the in-kernel
2-bit window unpack every packed-ref kernel shares.

Line layout
-----------
Mosaic only DMAs a slice of a 1-D HBM array at 1024-element tile
boundaries, so no kernel can fetch an arbitrary reference window or
Location-Table row from a flat array.  Every gathered table is instead
laid out as 128-lane *lines* (`to_lines`, a free reshape of the dense
flat array — no footprint growth): a kernel DMAs the whole lines a span
touches (`lines_spanned`) and cuts the span out in VMEM with a per-row
lane shift (`cut_lanes`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.encoding import BASES_PER_WORD

LANES = 128          # lanes per line of a line-layout table
_SUBLANES = 8        # line count granularity of an (8, 128)-tiled table


def lines_spanned(width: int, align: int = 1) -> int:
    """Most lines a ``width``-element span can touch when it starts at a
    multiple of ``align`` (its worst lane offset is ``LANES - gcd``)."""
    return -(-(width + LANES - math.gcd(align, LANES)) // LANES)


def to_lines(flat, nl: int):
    """1-D array -> (n, LANES) lines, zero padded so a DMA of ``nl``
    lines from any line holding an element stays in bounds, and n is a
    multiple of the sublane tiling.  Host (numpy) arrays stay on the host:
    without padding this is a free reshape, so a session can place a
    genome-scale table in line layout without a second copy."""
    xp = np if isinstance(flat, np.ndarray) else jnp
    n = flat.shape[0]
    n_lines = -(-n // LANES) + nl - 1
    n_lines += (-n_lines) % _SUBLANES
    if n_lines * LANES != n:
        flat = xp.pad(flat, (0, n_lines * LANES - n))
    return flat.reshape(n_lines, LANES)


def gather_lines(buf, rows: int, nl: int) -> jnp.ndarray:
    """Kernel-side load of DMA'd lines: a (rows*nl, LANES) VMEM ref whose
    row r holds its ``nl`` lines at [r*nl, r*nl + nl) -> (rows, nl*LANES),
    each row's lines laid end to end (strided sublane loads)."""
    if nl == 1:
        return buf[...]
    return jnp.concatenate(
        [buf[pl.ds(q, rows, stride=nl), :] for q in range(nl)], axis=1)


def cut_lanes(x: jnp.ndarray, shift, width: int,
              align: int = 1) -> jnp.ndarray:
    """Kernel-side per-row lane cut: ``out[r] = x[r, shift_r : shift_r +
    width]`` for shifts in ``[0, LANES)`` that are multiples of ``align``
    (a power of two).

    ``shift`` is a (BLK, 1) vector or a scalar.  A barrel shifter of
    static slices and selects (one stage per shift bit), because Mosaic
    lowers no value-level dynamic lane slice; ``x`` must hold at least
    ``width + LANES - align`` lanes.
    """
    m = x.shape[1]
    assert m >= width + LANES - align, (m, width, align)
    for b in range(align.bit_length() - 1, LANES.bit_length() - 1):
        step = 1 << b
        m -= step
        x = jnp.where(((shift >> b) & 1) == 1, x[:, step:step + m], x[:, :m])
    return x[:, :width]


def first_index(hit: jnp.ndarray) -> jnp.ndarray:
    """(BLK, n) bool -> (BLK,) int32 index of the first True along lanes
    (n where none) — `argmin`/`argmax` tie-breaking without the
    float-only Mosaic arg-reductions."""
    idx = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    return jnp.min(jnp.where(hit, idx, hit.shape[1]), axis=1)


def prefix_scan(x: jnp.ndarray, op, fill) -> jnp.ndarray:
    """Inclusive running ``op`` (``jnp.add``, ``jnp.maximum``) along axis
    -1 with identity ``fill``: Hillis–Steele, statically unrolled, since
    Mosaic lowers no `cumsum`/`cummax`."""
    n = x.shape[-1]
    d = 1
    while d < n:
        x = op(x, jnp.concatenate(
            [jnp.full(x.shape[:-1] + (d,), fill, x.dtype), x[..., :-d]], -1))
        d *= 2
    return x


def unpack_window_block(raw: jnp.ndarray, off: jnp.ndarray,
                        width: int) -> jnp.ndarray:
    """Kernel-side 2-bit window unpack: (BLK, n_words) packed int32 words
    + (BLK, 1) intra-word base offsets -> (BLK, width) base codes.

    Base ``k`` of the window is base ``(off + k) % 16`` (bits [2i, 2i+2))
    of word ``(off + k) // 16``: one select per word spreads each word
    over the lanes it covers (off varies per row, and Mosaic has neither
    a dynamic lane gather nor the word->base interleaving reshape), then
    one variable shift extracts the bases.  Shared by the candidate_align
    and residual_dp kernels; must keep mirroring
    `core.encoding.gather_windows_packed` bit-for-bit.
    """
    BLK, n_words = raw.shape
    base = off + jax.lax.broadcasted_iota(jnp.int32, (BLK, width), 1)
    word = base // BASES_PER_WORD
    spread = jnp.zeros((BLK, width), raw.dtype)
    for w in range(n_words):
        spread = jnp.where(word == w, raw[:, w:w + 1], spread)
    shift = 2 * (base % BASES_PER_WORD)
    return jax.lax.shift_right_logical(spread, shift) & 3


def clamp_window_starts(pos: jnp.ndarray, valid: jnp.ndarray, ref_len: int,
                        width: int, lead: int) -> jnp.ndarray:
    """Saturating clamp of candidate window starts (the PR 5 fix).

    ``pos`` are candidate start positions whose ``width``-wide reference
    window begins ``lead`` bases earlier (``window = [pos - lead, pos -
    lead + width)``); ``valid`` masks INVALID_LOC slots to 0.  The result
    is clamped to ``[lead - width, ref_len - 1 + lead]`` — exactly the
    range where `gather_ref_windows`' per-element index clamp saturates
    the whole window to all-``ref[0]`` / all-``ref[ref_len-1]`` anyway —
    so a contiguous DMA against a ``width``-lead edge-padded reference
    (DMA start ``result + (width - lead)``) reproduces the oracle's
    window for EVERY int32 start, including the negative starts
    `merge_read_starts` emits near the reference origin and the
    negative-diagonal vote positions of the long-read lane.  Shared by
    the candidate_align / residual_dp unpacked preps and the long-read
    diagonal windows, so kernel and oracle cannot diverge at the edges.
    """
    return jnp.clip(jnp.where(valid, pos, 0),
                    lead - width, ref_len - 1 + lead).astype(jnp.int32)


def pad_rows(x: jnp.ndarray, total: int) -> jnp.ndarray:
    """Zero-pad axis 0 of ``x`` up to ``total`` rows (no-op if equal)."""
    if total == x.shape[0]:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((total - x.shape[0],) + x.shape[1:], x.dtype)], 0)


def chunked_launch(n_rows: int, block: int, launch_rows: int) -> tuple[int, int]:
    """(padded_total, rows_per_launch) for a ``block``-aligned batch.

    Batches above ``launch_rows`` are padded to a multiple of the largest
    block-aligned chunk <= ``launch_rows`` and launched chunk by chunk
    (every chunk shares one trace/compile — identical shapes); smaller
    batches pad to one block-aligned launch.
    """
    chunk = max(block, launch_rows - launch_rows % block)
    padded = n_rows + ((-n_rows) % block)
    if padded > chunk:
        padded = n_rows + ((-n_rows) % chunk)
    return padded, min(padded, chunk)
