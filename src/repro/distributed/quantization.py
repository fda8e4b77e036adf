"""Quantized operands of the VQ tiers (DESIGN.md sections 13/15).

``quantize_tensor`` is the per-output-channel symmetric int8/fp8 quantizer
(``QTensor``: values plus f32 scales); ``quantize_codewords`` quantizes
codeword snapshots the same way with a scale per branch and channel, and a
drift band that keeps the grid fixed while the codebook barely moves.  The nibble-packed assignment
machinery (``pack_nibbles`` / ``unpack_nibbles`` / ``PackedAssignment``)
backs the ``+a4`` tiers for k <= 16 product branches.  ``dtype_nbits`` is
the one sub-byte-aware size table, shared by the state-bytes accounting
(``tree_bytes``) and the static checks.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    q: jax.Array        # int8/fp8, same shape as the original
    scale: jax.Array    # f32 [..., 1, out] per-output-channel scales


# numpy reports itemsize=1 for the ml_dtypes sub-byte ints (one id per host
# byte); on device they pack two per byte, and the size accounting here is
# about device residency.
_SUB_BYTE_NBITS = {"int4": 4, "uint4": 4}


def dtype_nbits(dt) -> int:
    """Bits per element of anything ``jnp.dtype`` accepts, sub-byte aware."""
    d = jnp.dtype(dt)
    return _SUB_BYTE_NBITS.get(d.name, d.itemsize * 8)


def quantize_tensor(w: jax.Array, dtype=jnp.int8) -> QTensor:
    """Per-output-channel (last axis) symmetric int8 or fp8.

    ``dtype`` picks the storage grid (:func:`codeword_qmax`): int8 rounds
    to the integer lattice, float8_e4m3fn keeps the mantissa rounding of
    the hardware cast -- both dequantize as ``q * scale``."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)),
                   keepdims=True)
    qmax = codeword_qmax(dtype)
    scale = amax / qmax + 1e-12
    scaled = w32 / scale
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    else:
        q = jnp.clip(scaled, -qmax, qmax).astype(dtype)
    return QTensor(q, scale)


# Drift band of the codeword-table requantization (kernels int8 path,
# DESIGN.md section 13): the previous step's scale is reused while the new
# amax stays within [prev_amax / drift, prev_amax], so the quantization grid
# only moves when the codebook actually drifts -- stable grids keep the
# serving-side int8 tables byte-identical across EMA steps that barely move.
CODEWORD_SCALE_DRIFT = 1.25

# Largest representable magnitude per codeword storage dtype: the quantizer
# maps each (branch, channel) amax onto it, so scale = amax / qmax.
_CODEWORD_QMAX = {
    jnp.dtype(jnp.int8): 127.0,
    jnp.dtype(jnp.float8_e4m3fn): 448.0,
}


def codeword_qmax(dtype) -> float:
    """amax -> grid-top mapping for a codeword storage dtype."""
    d = jnp.dtype(dtype)
    if d not in _CODEWORD_QMAX:
        raise ValueError(
            f"unsupported codeword storage dtype {d.name!r}; want one of "
            f"{sorted(x.name for x in _CODEWORD_QMAX)}")
    return _CODEWORD_QMAX[d]


def quantize_codewords(cw: jax.Array,
                       prev: "QTensor | None" = None,
                       drift: float = CODEWORD_SCALE_DRIFT,
                       dtype=jnp.int8) -> QTensor:
    """Per-branch/per-channel symmetric int8 or fp8 for codeword tables.

    cw: [n_branches, k, f_blk] -> QTensor(q int8/fp8 [nb, k, f_blk],
    scale f32 [nb, 1, f_blk]): the amax reduces over the k codewords only,
    so every (branch, channel) pair keeps its own scale -- the layout the
    quantized context/SpMM kernels consume as a flat [1, nb * f_blk]
    epilogue row (scales are k-independent, so the dequant multiply
    commutes with the over-neighbors accumulate and runs once per output
    tile).

    ``dtype`` picks the storage grid: ``jnp.int8`` (uniform, amax/127
    steps) or ``jnp.float8_e4m3fn`` (amax scaled onto +-448, keeping fp8's
    3-mantissa-bit relative precision across the whole per-channel dynamic
    range -- the tier for codebooks whose channels span decades).  When
    ``prev`` is given its storage dtype wins, so quantize-on-update
    requantizes in whatever tier the serving state was built with.

    ``prev`` enables the drift-aware rescale (quantize-on-update): the
    previous scale is kept wherever the new amax still fits its range and
    has not shrunk below ``1/drift`` of it.  jit-friendly (``jnp.where``).
    """
    if prev is not None:
        dtype = prev.q.dtype
    qmax = codeword_qmax(dtype)
    cw32 = cw.astype(jnp.float32)
    amax = jnp.max(jnp.abs(cw32), axis=-2, keepdims=True)   # [nb, 1, f_blk]
    scale = amax / qmax + 1e-12
    if prev is not None:
        prev_amax = (prev.scale - 1e-12) * qmax
        keep = jnp.logical_and(amax <= prev_amax,
                               amax >= prev_amax / drift)
        scale = jnp.where(keep, prev.scale, scale)
    scaled = cw32 / scale
    if jnp.dtype(dtype) == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    else:
        # fp8: round-to-nearest happens in the cast; clip keeps drift-band
        # outliers (amax marginally above the reused grid top) finite.
        q = jnp.clip(scaled, -qmax, qmax).astype(dtype)
    return QTensor(q, scale)


# ---------------------------------------------------------------------------
# nibble-packed assignment tables (the +a4 tiers, k <= 16)
# ---------------------------------------------------------------------------


def pack_nibbles(ids: jax.Array) -> jax.Array:
    """Pack ids (< 16) along the last axis, two per byte -> uint8.

    [..., m] -> [..., ceil(m / 2)]; even index -> low nibble, odd index ->
    high nibble; an odd-length tail pads the final high nibble with 0.
    """
    m = ids.shape[-1]
    u = ids.astype(jnp.uint8)
    if m % 2:
        pad = [(0, 0)] * (u.ndim - 1) + [(0, 1)]
        u = jnp.pad(u, pad)
    lo = u[..., 0::2]
    hi = u[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of ``pack_nibbles``: [..., ceil(n/2)] uint8 -> [..., n] uint8."""
    lo = packed & 0xF
    hi = packed >> 4
    out = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    return out[..., :n].astype(jnp.uint8)


def gather_nibbles(packed: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather ids' nibbles from a last-axis-packed table.

    packed [..., ceil(n/2)] uint8, ids [...] int -> uint8 values with shape
    packed.shape[:-1] + ids.shape (the same broadcast a plain
    ``table[..., ids]`` gather would produce on the unpacked table).
    """
    idx = ids.astype(jnp.int32)
    byte = packed[..., idx >> 1].astype(jnp.int32)
    return ((byte >> ((idx & 1) * 4)) & 0xF).astype(jnp.uint8)


def scatter_nibbles(packed: jax.Array, ids: jax.Array,
                    vals: jax.Array) -> jax.Array:
    """Scatter vals (< 16) into a last-axis-packed table at node ids.

    packed [..., nbytes] uint8, ids [m] int (DISTINCT -- duplicate ids
    would race within a parity pass), vals [..., m] uint8.  Two passes,
    one per parity: within a pass every touched byte index is unique, so a
    read-modify-write of the byte (keep the sibling nibble, replace ours)
    is exact; entries of the other parity scatter to an out-of-range byte
    index and drop.
    """
    nbytes = packed.shape[-1]
    idx = ids.astype(jnp.int32)
    byte_ids = idx >> 1
    v = (vals & 0xF).astype(jnp.uint8)
    for parity in (0, 1):
        cur = packed[..., byte_ids]            # re-gather: sees pass 0's writes
        if parity == 0:
            newb = (cur & 0xF0) | v
        else:
            newb = (cur & 0x0F) | (v << 4)
        dst = jnp.where((idx & 1) == parity, byte_ids, nbytes)
        packed = packed.at[..., dst].set(newb, mode="drop")
    return packed


@jax.tree_util.register_pytree_node_class
class PackedAssignment:
    """Nibble-packed [n_branches, n] VQ assignment table (k <= 16).

    ``packed`` holds two node ids per byte along the node axis
    ([n_branches, ceil(n/2)] uint8) -- 0.5 bytes/entry, 8x smaller than
    the int32 table and half the uint8 one in HBM (DESIGN.md section
    15); the context kernel's XLA-side gather unpacks it.  The node
    count ``n`` is static pytree aux data, so the wrapper flows through
    jit / scan / shard_map like any array leaf.
    """

    def __init__(self, packed: jax.Array, n: int):
        self.packed = packed
        self.n = int(n)

    @classmethod
    def pack(cls, assignment: jax.Array) -> "PackedAssignment":
        return cls(pack_nibbles(assignment), assignment.shape[-1])

    @property
    def shape(self) -> tuple:
        return (*self.packed.shape[:-1], self.n)

    def unpack(self) -> jax.Array:
        return unpack_nibbles(self.packed, self.n)

    def gather(self, ids: jax.Array) -> jax.Array:
        return gather_nibbles(self.packed, ids)

    def scatter(self, ids: jax.Array, vals: jax.Array) -> "PackedAssignment":
        return PackedAssignment(scatter_nibbles(self.packed, ids, vals),
                                self.n)

    def tree_flatten(self):
        return (self.packed,), (self.n,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        obj.packed = children[0]
        obj.n = aux[0]
        return obj

    def __repr__(self):
        return f"PackedAssignment(shape={self.shape}, packed={self.packed!r})"


def tree_bytes(params: Any) -> int:
    """Device-resident bytes of a pytree, sub-byte dtypes counted exactly.

    ``PackedAssignment`` leaves are already their packed uint8 buffer;
    ml_dtypes int4 arrays (one id per host byte) count 4 bits/element.
    """
    return sum((x.size * dtype_nbits(x.dtype) + 7) // 8
               for x in jax.tree_util.tree_leaves(params))
