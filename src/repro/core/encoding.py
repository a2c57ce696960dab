"""2-bit DNA base encoding utilities.

Bases are encoded A=0, C=1, G=2, T=3 (uint8).  The packed representation
stores 16 bases per uint32 word, base i occupying bits [2i, 2i+2) — this is
the layout the XOR-based Light Alignment kernel operates on, mirroring the
paper's 2-bit encoding (§7.4: "These SRAM FIFOs use 2-bit encoding").
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BASES = "ACGT"
A, C, G, T = 0, 1, 2, 3
BASES_PER_WORD = 16  # 2 bits/base, 32-bit words


def encode_str(s: str) -> np.ndarray:
    """Encode an ACGT string into uint8 codes (host-side helper)."""
    lut = np.full(256, 255, dtype=np.uint8)
    for i, b in enumerate(BASES):
        lut[ord(b)] = i
        lut[ord(b.lower())] = i
    out = lut[np.frombuffer(s.encode(), dtype=np.uint8)]
    if (out == 255).any():
        raise ValueError("non-ACGT character in sequence")
    return out


def decode_to_str(codes) -> str:
    codes = np.asarray(codes)
    return "".join(BASES[int(c)] for c in codes)


def revcomp(codes: jnp.ndarray) -> jnp.ndarray:
    """Reverse complement along the last axis.  A<->T, C<->G is 3-x."""
    return (3 - codes)[..., ::-1]


def pack_2bit(codes: jnp.ndarray, n_words: int | None = None) -> jnp.ndarray:
    """Pack uint8 base codes (…, L) into uint32 words (…, ceil(L/16)).

    Base i of a word occupies bits [2*i, 2*i+2).  Padding bases are 0 (='A');
    callers that compare packed sequences must mask tail bases themselves.
    """
    L = codes.shape[-1]
    if n_words is None:
        n_words = (L + BASES_PER_WORD - 1) // BASES_PER_WORD
    pad = n_words * BASES_PER_WORD - L
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros(codes.shape[:-1] + (pad,), codes.dtype)], axis=-1
        )
    w = codes.reshape(codes.shape[:-1] + (n_words, BASES_PER_WORD)).astype(jnp.uint32)
    shifts = (2 * jnp.arange(BASES_PER_WORD, dtype=jnp.uint32))
    return (w << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_2bit(words: jnp.ndarray, length: int) -> jnp.ndarray:
    """Inverse of pack_2bit: (…, W) uint32 -> (…, length) uint8."""
    shifts = 2 * jnp.arange(BASES_PER_WORD, dtype=jnp.uint32)
    codes = (words[..., :, None] >> shifts) & jnp.uint32(3)
    codes = codes.reshape(words.shape[:-1] + (-1,))
    return codes[..., :length].astype(jnp.uint8)


def mismatch_mask_packed(a_words: jnp.ndarray, b_words: jnp.ndarray) -> jnp.ndarray:
    """XOR two packed sequences and collapse bit-pairs: result uint32 words
    where bit-pair (2i,2i+1) is nonzero iff base i differs.

    This is the paper's core Light Alignment primitive: "simple vectorized
    logical XOR operators" (§1).  The caller usually wants a per-base bool —
    see mismatch_bools_packed.
    """
    x = a_words ^ b_words
    # OR the two bits of each pair into the low bit of the pair.
    lo = x & jnp.uint32(0x55555555)
    hi = (x >> 1) & jnp.uint32(0x55555555)
    return lo | hi


def mismatch_bools(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-base mismatch booleans on unpacked codes (broadcasting ok)."""
    return a != b


def packed_gather_coords(n_ref_words: int, length: int) -> tuple[int, int]:
    """(n_words, start clamp hi) for a `length`-base packed-window gather.

    Single source of truth for the word-count and scalar-clamp formulas,
    shared by `gather_windows_packed` and the candidate_align kernel's DMA
    planning (which must mirror this gather bit-for-bit).
    """
    n_words = length // BASES_PER_WORD + 2
    # int32 positions address <=2^31-1 bases: at full-genome scale (3.1 Gbp)
    # real coordinates are per-chromosome (chrom, int32 offset) as in the
    # paper; a flattened coordinate space past 2^31 clamps the gather
    # bound so the jitted scalar stays in int32 range.
    hi = min(n_ref_words * BASES_PER_WORD - length - 1, 2**31 - 1)
    return n_words, hi


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["bases", "lines"],
                   meta_fields=["pad", "nl", "packed"])
@dataclasses.dataclass(frozen=True)
class LinedRef:
    """A session's reference with the aligners' DMA source built once.

    ``bases`` is the resolved reference (uint8 bases, or the packed
    uint32 words with ``packed``); the jnp oracles, the long-read lane
    and the index store read it.  ``lines`` is the int32 layout the
    candidate_align and residual_dp kernels DMA their windows from
    (`kernels._util.reference_lines`): unpacked, ``pad`` copies of the
    first base before the reference and ``pad - 1`` of the last after
    it; packed, ``pad`` copies of the last word after it; then cut into
    128-lane lines padded for DMAs of up to ``nl`` lines.  One layout
    serves every kernel whose window needs at most ``pad`` elements of
    padding and ``nl`` lines.  ``pad``, ``nl`` and ``packed`` are static.
    """

    bases: jnp.ndarray
    lines: jnp.ndarray
    pad: int
    nl: int
    packed: bool

    @property
    def dtype(self):
        """The dtype of ``bases``: how a step tells the two flavours apart."""
        return self.bases.dtype


def ref_bases(ref):
    """The reference array itself: ``ref.bases`` of a `LinedRef`, else
    ``ref``."""
    return ref.bases if isinstance(ref, LinedRef) else ref


def gather_windows_packed(ref_words: jnp.ndarray, starts: jnp.ndarray,
                          length: int) -> jnp.ndarray:
    """Gather base windows from a 2-bit packed reference.

    ref_words: uint32[Lw] packing of the reference (16 bases/word);
    starts: (...,) int32 window starts (clamped); -> (..., length) uint8.

    4x less HBM traffic than an unpacked uint8 reference — at human-genome
    scale (3.1 Gbp) this is what lets the reference replicate per device
    (775 MB instead of 3.1 GB), mirroring the paper's 2-bit SRAM encoding.
    """
    Lw = ref_words.shape[0]
    n_words, hi = packed_gather_coords(Lw, length)
    starts = jnp.clip(starts, 0, hi)
    w0 = starts // BASES_PER_WORD
    off = (starts % BASES_PER_WORD).astype(jnp.int32)
    idx = w0[..., None] + jnp.arange(n_words, dtype=jnp.int32)
    words = ref_words[jnp.clip(idx, 0, Lw - 1)]            # (..., n_words)
    codes = unpack_2bit(words, n_words * BASES_PER_WORD)   # (..., n_words*16)
    take = off[..., None] + jnp.arange(length, dtype=jnp.int32)
    return jnp.take_along_axis(codes, take, axis=-1)
