"""Pallas TPU kernel: xxHash32 over 16-byte seeds (Partitioned Seeding unit).

The paper's Partitioned Seeding module instantiates six pipelined xxHash
units (§5.1).  On TPU the analogue is one VPU kernel hashing a whole block
of seeds per grid step: each lane hashes one seed, so a (BLK, 4) uint32 tile
yields BLK hashes of pure 32-bit ALU work with no memory traffic beyond the
streamed input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import PRIME1, PRIME2, PRIME3

DEFAULT_BLOCK = 1024


def _i32(x: int):
    """A uint32 constant as the int32 with the same bits."""
    x %= 1 << 32
    return jnp.int32(x - (1 << 32) if x >= 1 << 31 else x)


def _shr(x, r: int):
    return jax.lax.shift_right_logical(x, jnp.int32(r))


def _rotl(x, r: int):
    return (x << r) | _shr(x, 32 - r)


def _round(acc, lane):
    return _rotl(acc + lane * _i32(PRIME2), 13) * _i32(PRIME1)


def xxhash32_lanes(w0, w1, w2, w3, seed: int):
    """Elementwise xxHash32 of a 16-byte message given as four int32 lanes
    (the uint32 words' bits); returns the hash's bits as int32.

    The kernel-body hashing unit, shared with the fused pair_frontend
    kernel (which packs seeds and hashes them in-kernel).  All operands
    broadcast; the result has the broadcast shape.  The arithmetic is
    int32 with two's-complement wraparound (the low 32 bits of every
    add and multiply match uint32's) and logical right shifts, so the
    kernels that pack seeds stay in int32 end to end.
    """
    v1 = _round(_i32(seed + PRIME1 + PRIME2), w0)
    v2 = _round(_i32(seed + PRIME2), w1)
    v3 = _round(_i32(seed), w2)
    v4 = _round(_i32(seed - PRIME1), w3)
    acc = _rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)
    acc = acc + 16
    acc = acc ^ _shr(acc, 15)
    acc = acc * _i32(PRIME2)
    acc = acc ^ _shr(acc, 13)
    acc = acc * _i32(PRIME3)
    acc = acc ^ _shr(acc, 16)
    return acc


def _xxhash_kernel(words_ref, out_ref, *, seed: int):
    w = words_ref[...]  # (BLK, 4) int32: the uint32 words' bits
    acc = xxhash32_lanes(w[:, 0], w[:, 1], w[:, 2], w[:, 3], seed)
    out_ref[...] = acc[:, None]


def xxhash32_pallas(
    words: jnp.ndarray,
    seed: int = 0,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """(N, 4) uint32 -> (N,) uint32.  N must be a multiple of `block`
    (ops.py pads).  The kernel sees the words' bits as int32."""
    n = words.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    out = pl.pallas_call(
        functools.partial(_xxhash_kernel, seed=seed),
        grid=grid,
        in_specs=[pl.BlockSpec((block, 4), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(words, jnp.int32))
    return jax.lax.bitcast_convert_type(out[:, 0], jnp.uint32)
