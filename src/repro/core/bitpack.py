"""Bit-packing of the matching-bit block (§4.3's BRAM word, TPU edition).

The FPGA stores each vertex's matching state as ONE L-bit word in BRAM.
A dense bool layout spends a byte (the unpacked kernel a 32-bit word)
per substream bit. This module defines the packed *bit-plane* layout
every caller sees:

    mb_packed[v, k] : uint8, bit j of word k  ==  substream 8*k + j of v

i.e. substream index i lives at byte ``i // 8``, bit ``i % 8`` (LSB
first). ``L`` need not divide 8; the high bits of the last byte are
always zero. Pack/unpack are exact inverses on the first L bits and are
cheap enough to run lazily on host access (see
:class:`repro.core.types.MatchingResult`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BITS = 8  # bits per packed word (uint8 lanes)


def packed_width(L: int) -> int:
    """Number of uint8 words holding L substream bits: ceil(L / 8)."""
    return -(-L // BITS)


def pack_bits(mb: jax.Array) -> jax.Array:
    """bool/int [..., L] -> uint8 [..., ceil(L/8)], LSB-first bit planes."""
    L = mb.shape[-1]
    W = packed_width(L)
    x = mb.astype(jnp.uint8)
    pad = W * BITS - L
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(mb.shape[:-1] + (pad,), jnp.uint8)], axis=-1
        )
    x = x.reshape(mb.shape[:-1] + (W, BITS))
    weights = (1 << jnp.arange(BITS, dtype=jnp.int32)).astype(jnp.int32)
    return (x.astype(jnp.int32) * weights).sum(axis=-1).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, L: int) -> jax.Array:
    """uint8 [..., W] -> bool [..., L]; inverse of :func:`pack_bits`."""
    W = packed.shape[-1]
    if W < packed_width(L):
        raise ValueError(f"{W} words cannot hold {L} bits")
    shifts = jnp.arange(BITS, dtype=jnp.uint8)
    bits = (packed[..., :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(packed.shape[:-1] + (W * BITS,))[..., :L].astype(bool)
