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
lane shift (`cut_lanes`).  The aligners' reference is laid out by
`reference_lines`, per call from a plain array or once per session as a
`LinedRef`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.encoding import (
    BASES_PER_WORD,
    LinedRef,
    packed_gather_coords,
)

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
    so a contiguous DMA against a reference edge-padded by ``pad >=
    width`` (DMA start ``result + (pad - lead)``) reproduces the oracle's
    window for EVERY int32 start, including the negative starts
    `merge_read_starts` emits near the reference origin and the
    negative-diagonal vote positions of the long-read lane.  Shared by
    the aligners' unpacked window tables (`window_lines`) and the
    long-read diagonal windows, so kernel and oracle cannot diverge at
    the edges.
    """
    return jnp.clip(jnp.where(valid, pos, 0),
                    lead - width, ref_len - 1 + lead).astype(jnp.int32)


def window_elems(n_ref: int, packed: bool, width: int) -> int:
    """Elements of the aligners' DMA source one ``width``-base window
    spans: its bases, or the packed words `packed_gather_coords` fetches."""
    return packed_gather_coords(n_ref, width)[0] if packed else width


@functools.partial(jax.jit, static_argnames=("packed", "pad", "nl"))
def reference_lines(ref: jnp.ndarray, packed: bool, pad: int,
                    nl: int) -> jnp.ndarray:
    """The candidate_align / residual_dp kernels' DMA source, in lines.

    Unpacked: the bases cast to int32 and edge-padded with ``pad`` copies
    of the first base in front and ``pad - 1`` of the last behind, so a
    window clamped by `clamp_window_starts` is one contiguous DMA that
    reproduces the oracle's per-element index clamp.  Packed: the words
    back-padded with ``pad`` copies of the last word, so word reads past
    the end see what the oracle's clamp produces.  Then `to_lines` for
    DMAs of up to ``nl`` lines.  The one layout code: an op given a
    plain reference builds it per call, a session once (`LinedRef`).
    """
    with jax.named_scope("ref_layout"):
        if packed:
            words = jax.lax.bitcast_convert_type(ref, jnp.int32)
            flat = jnp.concatenate(
                [words, jnp.broadcast_to(words[-1:], (pad,))])
        else:
            r32 = ref.astype(jnp.int32)
            flat = jnp.concatenate([
                jnp.broadcast_to(r32[:1], (pad,)), r32,
                jnp.broadcast_to(r32[-1:], (pad - 1,)),
            ])
        return to_lines(flat, nl)


def lined_ref(ref: jnp.ndarray, packed: bool, widths) -> LinedRef:
    """``ref`` with the DMA source for windows of every width in
    ``widths``: padded for the widest, with the most lines any spans."""
    elems = [window_elems(ref.shape[0], packed, w) for w in widths]
    pad, nl = max(elems), max(lines_spanned(e) for e in elems)
    return LinedRef(bases=ref, lines=reference_lines(ref, packed=packed,
                                                     pad=pad, nl=nl),
                    pad=pad, nl=nl, packed=packed)


def window_lines(ref: LinedRef, pos: jnp.ndarray, valid: jnp.ndarray,
                 width: int, lead: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(first line, lane offset) of each window in ``ref.lines``.

    The window of a start ``pos`` is ``width`` bases from ``pos - lead``;
    ``valid`` masks INVALID_LOC slots.  Unpacked, the start is clamped by
    `clamp_window_starts` and shifted by the layout's front pad; packed,
    clamped as `gather_windows_packed` clamps it, and the offset is 16 x
    the word's lane plus the base in the word.
    """
    elems = window_elems(ref.bases.shape[0], ref.packed, width)
    assert elems <= ref.pad and lines_spanned(elems) <= ref.nl, (
        "reference layout too narrow for the window", width, ref.pad, ref.nl)
    if ref.packed:
        _, hi = packed_gather_coords(ref.bases.shape[0], width)
        s = jnp.clip(jnp.where(valid, pos - lead, 0), 0, hi)
        e = s // BASES_PER_WORD
        off = (e % LANES) * BASES_PER_WORD + s % BASES_PER_WORD
    else:
        s = clamp_window_starts(pos, valid, ref.bases.shape[0], width, lead)
        e = s + (ref.pad - lead)
        off = e % LANES
    return (e // LANES).astype(jnp.int32), off.astype(jnp.int32)


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
