"""Bit-packed mask wire format: the single definition of the layout.

Binary modulator masks are the round's largest tensors — at
``(n_max, k_max, d)`` a bool layout spends 8 bits per mask bit and is
the reason the CPU round is memory-bound.  The wire format packs every
32 mask bits into one ``uint32`` word:

* element ``j`` of a d-length mask lives in word ``j // 32``,
  bit ``j % 32``, **LSB-first** (``(word >> (j % 32)) & 1``);
* a d-length mask occupies ``packed_width(d) = ceil(d / 32)`` words;
* tail bits of the last word (elements ``d .. 32*ceil(d/32)``) are
  always zero — packing enforces it, consumers may rely on it (popcount
  over whole words needs no tail correction).

The same convention is produced by the host-side numpy packer
(``pack_bits_np``: ``np.packbits(bitorder="little")`` + little-endian
``uint32`` view), the jnp packer used inside jitted rounds, and the
in-kernel Pallas packers — so packed tensors are byte-identical across
the client → uplink → engine → downlink path.

Sign bit-planes: a ternary sign vector ``sgn(x) ∈ {-1, 0, +1}`` packs
into two planes, ``pos = pack(x > 0)`` and ``nz = pos | pack(x < 0)``.
The Eq. 5 sign dot becomes pure popcount algebra (see
``packed_sign_dots``), and Eq. 3 sign election becomes bitwise ANDs
against the mask words.

This word layout is also the substrate of the optional entropy-coded
wire layer: :mod:`repro.fed.compression` Golomb-Rice codes whole rows
of these words into self-describing byte streams (and decodes them
back bit-identically) at the host edge — ``wire_bits`` here stays the
single RAW packed accounting; coded streams are accounted off their
measured byte length.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32

# (1, 32) uint32 bit-index row, broadcast against (..., n_words, 1)
_BITS = np.arange(WORD_BITS, dtype=np.uint32)


def packed_width(d: int) -> int:
    """Words per d-length mask: ceil(d / 32)."""
    return -(-d // WORD_BITS)


def wire_bits(d: int, k: int, *, vec_bytes_per_elem: int = 2,
              float_bits: int = 32) -> int:
    """Measured wire size of one client's packed upload/downlink: the
    vector buffer (bf16 by default) + ``k`` packed mask rows + one
    scaler per row.  THE single accounting for the packed wire format —
    client/engine/compression all delegate here."""
    return (8 * vec_bytes_per_elem * d
            + k * (8 * 4 * packed_width(d) + float_bits))


def pack_bits(mask: jax.Array) -> jax.Array:
    """(..., d) bool/{0,1} -> (..., ceil(d/32)) uint32, LSB-first.

    Tail bits beyond d are zero.  Pure jnp — used inside jitted rounds
    and as the "ref" dispatch of ``ops.pack_masks``.
    """
    d = mask.shape[-1]
    pad = (-d) % WORD_BITS
    bits = mask.astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(bits.shape[:-1] + (-1, WORD_BITS))
    return jnp.sum(bits << jnp.asarray(_BITS), axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, d: int, dtype=jnp.bool_) -> jax.Array:
    """(..., w) uint32 -> (..., d) of ``dtype`` (bool by default).

    ``d`` may be any length ≤ 32*w; trailing packed bits are dropped.
    """
    bits = (words[..., None] >> jnp.asarray(_BITS)) & jnp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (-1,))
    return flat[..., :d].astype(dtype)


def pack_bits_np(mask: np.ndarray) -> np.ndarray:
    """Host-side packer (same layout as :func:`pack_bits`), via the C
    fast path ``np.packbits(bitorder='little')`` + a little-endian
    uint32 view."""
    mask = np.asarray(mask, bool)
    d = mask.shape[-1]
    pad = (-d) % WORD_BITS
    if pad:
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), bool)], axis=-1)
    packed_u8 = np.packbits(mask, axis=-1, bitorder="little")
    words = np.ascontiguousarray(packed_u8).view(np.dtype("<u4"))
    if sys.byteorder != "little":          # normalise storage on BE hosts
        words = words.astype(np.uint32)
    return words


def unpack_bits_np(words: np.ndarray, d: int) -> np.ndarray:
    """Host-side inverse of :func:`pack_bits_np` -> (..., d) bool."""
    words = np.asarray(words).astype("<u4", copy=False)
    u8 = words.view(np.uint8)
    bits = np.unpackbits(u8, axis=-1, bitorder="little")
    return bits[..., :d].astype(bool)


def tile_bits(words: jax.Array) -> jax.Array:
    """(R, W) uint32 -> (R, W*32) bool tile unpack for Pallas kernel
    bodies: uses ``broadcasted_iota`` (TPU needs ≥2-D iota) and no tail
    slicing — kernel tiles are always word-aligned."""
    r, w = words.shape
    iota = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, WORD_BITS), 2)
    bits = (words[:, :, None] >> iota) & jnp.uint32(1)
    return bits.reshape(r, w * WORD_BITS) != 0


def unpack_tile(words: jax.Array, dtype=jnp.float32) -> jax.Array:
    """:func:`tile_bits` as {0, 1} values of ``dtype``, through a
    select: Mosaic has no uint32 -> float cast."""
    return jnp.where(tile_bits(words), jnp.ones((), dtype),
                     jnp.zeros((), dtype))


def pack_tile(bits: jax.Array) -> jax.Array:
    """(R, D) bool/{0,1} -> (R, D/32) uint32 tile pack for Pallas kernel
    bodies, the wire row's words along the lanes (D must be a multiple
    of 4096, so each row's words fill whole 128-lane rows).

    Mosaic cannot split the lane axis into 32-bit groups, so each
    128-lane row is reduced one 32-lane quarter at a time — an int32
    sum of distinct powers of two, which is their bitwise OR (bit 31
    wraps to the sign bit and is bitcast back).  That leaves four words
    per 128-lane row; each group of 32 such rows is then spread onto
    one row of 128 lanes — word ``4·k + q`` to lane ``4·k + q`` by a
    select and a sum over the rows, exact since each lane receives one
    word.  (Four words a row written out as they are would make an
    array whose minor axis of 4 XLA pads to 128 lanes in HBM, 32x the
    words' bytes.)"""
    r, dd = bits.shape
    rows = r * (dd // 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
    shifted = jnp.where(bits.reshape(rows, 128) != 0,
                        jnp.left_shift(jnp.int32(1), lane % WORD_BITS), 0)
    quarters = [jnp.sum(shifted[:, q * WORD_BITS:(q + 1) * WORD_BITS],
                        axis=-1, keepdims=True, dtype=jnp.int32)
                for q in range(4)]                  # (R·D/128, 1) each
    k = jax.lax.broadcasted_iota(jnp.int32, (32, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (32, 128), 1)
    out = []
    for g in range(r * dd // 4096):                 # 32 rows -> 128 words
        spread = sum(jnp.where(lane == 4 * k + q,
                               quarters[q][32 * g:32 * (g + 1)], 0)
                     for q in range(4))
        out.append(jnp.sum(spread, axis=0, keepdims=True))
    words = jnp.concatenate(out, axis=0).reshape(r, dd // WORD_BITS)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def row_words(words: jax.Array, rows: int, cols: int) -> jax.Array:
    """(..., ceil(rows·cols/32)) packed row-major (rows, cols) mask ->
    (..., rows, ceil(cols/32)) with every matrix row re-aligned to start
    at bit 0 of its own word (zero tail bits): :func:`slice_bits`
    applied to all rows at once.  Kernels unpack each row's words into
    its own lanes, which needs no in-kernel reshape across rows."""
    wc = packed_width(cols)
    if cols % WORD_BITS == 0:       # rows already start on word boundaries
        return words.reshape(words.shape[:-1] + (rows, wc))
    start = np.arange(rows) * cols
    sh = (start % WORD_BITS).astype(np.uint32)[:, None]          # (rows, 1)
    idx = (start // WORD_BITS)[:, None] + np.arange(wc)[None, :]  # (rows, wc)
    need = int(idx.max()) + 2 - words.shape[-1]
    if need > 0:      # zero-pad so the shifted neighbour read is safe
        words = jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, need)])
    lo, hi = words[..., idx], words[..., idx + 1]
    out = (lo >> sh) | jnp.where(sh > 0, hi << ((WORD_BITS - sh) % WORD_BITS),
                                 jnp.uint32(0))
    keep = np.full(wc, 0xFFFFFFFF, np.uint32)
    keep[-1] = (1 << (cols % WORD_BITS)) - 1
    return out & keep


def scatter_bits_np(positions: np.ndarray, n_bytes: int) -> np.ndarray:
    """Set the given bit positions (LSB-first within each byte — the
    module's one bit convention) in a zeroed ``n_bytes``-byte buffer.

    The substrate of the batched Golomb-Rice encoder's prefix-sum
    bit-scatter (:mod:`repro.fed.compression`): every row's unary
    terminators and remainder bits land in one preallocated bit-space
    with a single fancy-index write + one ``np.packbits`` — no
    per-row/per-symbol Python loop, and no read-modify-write hazard
    (duplicate byte indices are fine because the OR happens in
    bit-space, where positions are unique)."""
    bit_space = np.zeros(8 * n_bytes, np.uint8)
    if positions.size:
        bit_space[positions] = 1
    return np.packbits(bit_space, bitorder="little")


def slice_bits(words: jax.Array, start: int, length: int) -> jax.Array:
    """Re-aligned bit-range extract: bits ``[start, start + length)`` of
    a packed row, returned as ``ceil(length/32)`` words whose bit 0 is
    the bit at ``start`` (same LSB-first convention, zero tail bits).

    This is how a consumer slices one manifest leaf's mask bits out of
    a whole-d packed row WITHOUT unpacking to bool: each output word is
    the OR of two shifted neighbour words.  ``words`` may carry leading
    batch axes (the slice applies to the last axis); ``start``/``length``
    are static ints.  Bit j of the result == bit ``start + j`` of the
    input row, verified against the unpack→slice→pack oracle in
    tests/test_serve_multitenant.py.
    """
    if length < 0 or start < 0:
        raise ValueError(f"slice_bits needs start/length >= 0, got "
                         f"({start}, {length})")
    n_out = packed_width(length)
    w0, sh = start // WORD_BITS, start % WORD_BITS
    need = n_out + (1 if sh else 0)
    avail = words.shape[-1] - w0
    if avail < need:   # zero-pad so the shifted neighbour read is safe
        pad = [(0, 0)] * (words.ndim - 1) + [(0, need - avail)]
        words = jnp.pad(words, pad)
    lo = words[..., w0:w0 + n_out]
    if sh:
        hi = words[..., w0 + 1:w0 + 1 + n_out]
        out = (lo >> jnp.uint32(sh)) | (hi << jnp.uint32(WORD_BITS - sh))
    else:
        out = lo
    # zero the tail bits past `length` of the last word (layout contract)
    tail = length % WORD_BITS
    if tail:
        keep = jnp.uint32((1 << tail) - 1)
        last = out[..., -1:] & keep
        out = jnp.concatenate([out[..., :-1], last], axis=-1)
    return out


# Largest (..., d) uint32 bit array ``sign_bits`` packs in one piece.
# XLA on a TPU lays the (..., d/32, 32) reshape of ``pack_bits`` out
# with 128 lanes, so one pack holds several times this much: at 16.8 M
# coords a row, the 30-32 rows of one taskvec shard of qwen2.5-32b's
# round would hold 8 GB.  qwen2-0.5b's round (459 MB) packs whole.
PACK_BLOCK_BYTES = 1 << 30
# size of each column block once a pack is split
PACK_PIECE_BYTES = 1 << 26


def sign_bits(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(pack_bits(x > 0), pack_bits(x < 0))`` over the last axis, in
    word-aligned column blocks, one block per step of a ``fori_loop``:
    one block up to ``PACK_BLOCK_BYTES`` of bits (or when d is not a
    whole number of words), past it blocks of at most
    ``PACK_PIECE_BYTES``; packing is exact, so the words are the same."""
    d = x.shape[-1]
    size = int(np.prod(x.shape[:-1])) * d * 4
    n_blocks = 1
    while (size > PACK_BLOCK_BYTES and size > PACK_PIECE_BYTES * n_blocks
           and d % (WORD_BITS * n_blocks * 2) == 0):
        n_blocks *= 2
    block = d // n_blocks
    words = jnp.zeros(x.shape[:-1] + (packed_width(d),), jnp.uint32)

    def step(i, planes):
        xs = jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=-1)
        at = i * packed_width(block)
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            p, pack_bits(b), at, axis=-1)
            for p, b in zip(planes, (xs > 0, xs < 0)))

    return jax.lax.fori_loop(0, n_blocks, step, (words, words))


def sign_planes(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pack ``sgn(x)`` over the last axis into (pos, nz) bit-planes:
    ``pos`` has bit j set iff x_j > 0, ``nz`` iff x_j != 0."""
    pos, neg = sign_bits(x)
    return pos, pos | neg


def packed_sign_dots(pos: jax.Array, nz: jax.Array) -> jax.Array:
    """Pairwise sign dots Σ_j sgn(x_t)_j · sgn(x_t')_j from (T, w)
    bit-planes, as popcount algebra — exactly the integer the fp32
    ``sgn(X) @ sgn(X).T`` matmul produces (both are exact for d < 2²⁴):

        both  = nz_t & nz_t'                  (coords where neither is 0)
        agree = both & ~(pos_t ^ pos_t')      (equal sign bits)
        dot   = popcnt(agree) - popcnt(both & (pos ^ pos'))
              = popcnt(both) - 2·popcnt(both & (pos ^ pos'))

    Returns (T, T) int32.
    """
    both = nz[:, None, :] & nz[None, :, :]
    diff = both & (pos[:, None, :] ^ pos[None, :, :])
    n_both = jnp.sum(jax.lax.population_count(both), axis=-1,
                     dtype=jnp.int32)
    n_diff = jnp.sum(jax.lax.population_count(diff), axis=-1,
                     dtype=jnp.int32)
    return n_both - 2 * n_diff
