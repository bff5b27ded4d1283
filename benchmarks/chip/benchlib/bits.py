"""Mask bits on the wire: uint32 words, LSB first (bit ``j % 32`` of
word ``j // 32`` is element ``j``), tail bits zero — the layout MaTU
clients and servers exchange.  Written here from that definition so
that the traffic generator and the reference need nothing of the
program."""

from __future__ import annotations

import jax.numpy as jnp

WORD = 32


def n_words(d: int) -> int:
    return -(-d // WORD)


def pack(bits):
    """(..., d) bool -> (..., ceil(d/32)) uint32."""
    d = bits.shape[-1]
    pad = (-d) % WORD
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = bits.reshape(bits.shape[:-1] + (-1, WORD)).astype(jnp.uint32)
    return jnp.sum(b << jnp.arange(WORD, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def unpack(words, d: int):
    """(..., W) uint32 -> (..., d) bool."""
    w = jnp.asarray(words, jnp.uint32)
    b = (w[..., None] >> jnp.arange(WORD, dtype=jnp.uint32)) & 1
    return b.reshape(w.shape[:-1] + (-1,))[..., :d].astype(bool)
