"""Vectorized 64-bit bit-manipulation primitives.

Device-side equivalents of the host helpers in m3_tpu.utils.bitstream,
operating elementwise on uint64 tensors. These underpin the batched M3TSZ
kernels (m3_tpu.encoding.m3tsz.tpu); the scalar semantics they must match are
the reference's (/root/reference/src/dbnode/encoding/encoding.go:29-43).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

import m3_tpu.ops  # noqa: F401  (enables x64)

U64 = jnp.uint64
I64 = jnp.int64

# numpy scalars inline as trace literals; module-level jnp scalars become
# hoisted jaxpr constants and trip a jit fastpath buffer-count bug.
import numpy as _np

_ZERO = _np.uint64(0)
_ONE = _np.uint64(1)
_SIXTYFOUR = _np.uint64(64)


def u64(x) -> jnp.ndarray:
    return jnp.asarray(x, dtype=U64)


def clz64(v: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros; clz(0) = 64. Returns uint64."""
    v = v.astype(U64)
    return jnp.where(v == 0, _SIXTYFOUR, lax.clz(v).astype(U64))


def ctz64(v: jnp.ndarray) -> jnp.ndarray:
    """Count trailing zeros; ctz(0) = 0 (reference convention for XOR
    streams: LeadingAndTrailingZeros(0) = (64, 0))."""
    v = v.astype(U64)
    iso = v & (jnp.uint64(0) - v)  # lowest set bit
    return jnp.where(v == 0, _ZERO, jnp.uint64(63) - lax.clz(iso).astype(U64))


def shl(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Left shift, safe for n in [0, 64] (n>=64 -> 0)."""
    v = v.astype(U64)
    n = jnp.asarray(n, dtype=U64)
    return jnp.where(n >= 64, _ZERO, v << jnp.minimum(n, jnp.uint64(63)))


def shr(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Logical right shift, safe for n in [0, 64] (n>=64 -> 0)."""
    v = v.astype(U64)
    n = jnp.asarray(n, dtype=U64)
    return jnp.where(n >= 64, _ZERO, v >> jnp.minimum(n, jnp.uint64(63)))


def mask_low(n: jnp.ndarray) -> jnp.ndarray:
    """(1 << n) - 1, safe for n in [0, 64]."""
    n = jnp.asarray(n, dtype=U64)
    return jnp.where(n >= 64, ~_ZERO, shl(_ONE, n) - _ONE)


def sign_extend64(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Interpret low n bits of v as an n-bit two's-complement int64."""
    v = v.astype(U64) & mask_low(n)
    sign = shl(_ONE, jnp.asarray(n, U64) - _ONE)
    return (v ^ sign).astype(I64) - sign.astype(I64)


def f64_to_bits(v: jnp.ndarray) -> jnp.ndarray:
    """CPU only: the TPU X64 rewriter does not implement this direction
    (UNIMPLEMENTED bitcast-convert on the v5e, PR 21)."""
    return lax.bitcast_convert_type(jnp.asarray(v, jnp.float64), U64)


def bits_to_f64(v: jnp.ndarray) -> jnp.ndarray:
    """Lowers on the TPU, but as a conversion to the device's float64 (a
    pair of float32: f32 exponent range, about 49 mantissa bits), not a
    bit cast: exact on the CPU, within 1.8e-15 relative for in-range
    values on the v5e (PR 21)."""
    return lax.bitcast_convert_type(v.astype(U64), jnp.float64)


# ---------------------------------------------------------------------------
# Multi-limb registers: limb 0 is the MOST significant word; bit 63 of limb 0
# is stream bit 0 (streams are MSB-first).
# ---------------------------------------------------------------------------


def reg3_insert(
    reg: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    used: jnp.ndarray,
    field_hi: jnp.ndarray,
    field_lo: jnp.ndarray,
    field_len: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """OR a <=128-bit field (right-aligned in (hi, lo)) into a 192-bit
    register so its first bit lands at bit position `used`.

    The field occupies bits [used, used+field_len); callers guarantee those
    bits are currently zero and used+field_len <= 192.
    """
    used = jnp.asarray(used, U64)
    field_len = jnp.asarray(field_len, U64)
    # Left-shift the 128-bit value into a 192-bit register:
    # shift amount from right-aligned-192 position.
    s = jnp.uint64(192) - used - field_len
    ls = s >> jnp.uint64(6)  # limb shift 0..2
    bs = s & jnp.uint64(63)  # bit shift 0..63
    # in-limbs of the right-aligned 192-bit value: [0, hi, lo]
    in_limbs = (_ZERO * field_hi, field_hi.astype(U64), field_lo.astype(U64))

    def limb_at(idx):
        # in_limbs[idx] with idx possibly out of range -> 0
        out = _ZERO * field_lo.astype(U64)
        for k in range(3):
            out = jnp.where(idx == k, in_limbs[k], out)
        return out

    out = []
    for j in range(3):
        jj = jnp.asarray(j, U64)
        lo_part = shl(limb_at(jj + ls), bs)
        # carry bits from the next-lower limb
        hi_part = jnp.where(bs == 0, _ZERO, shr(limb_at(jj + ls + _ONE), _SIXTYFOUR - bs))
        out.append(reg[j] | lo_part | hi_part)
    return tuple(out)


def reg3_shift_right_to4(
    reg: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray], r: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shift a 192-bit register right by r in [0, 63], producing 4 limbs."""
    r = jnp.asarray(r, U64)
    p0, p1, p2 = (x.astype(U64) for x in reg)
    inv = _SIXTYFOUR - r
    carry = lambda v: jnp.where(r == 0, _ZERO, shl(v, inv))  # noqa: E731
    o0 = shr(p0, r)
    o1 = shr(p1, r) | carry(p0)
    o2 = shr(p2, r) | carry(p1)
    o3 = carry(p2)
    return o0, o1, o2, o3


def read_window(words: jnp.ndarray, bitoff: jnp.ndarray) -> jnp.ndarray:
    """Read 64 bits starting at absolute bit offset from a uint64 word array
    (MSB-first). Out-of-range reads return zero bits."""
    bitoff = jnp.asarray(bitoff, U64)
    w = (bitoff >> jnp.uint64(6)).astype(jnp.int64)
    r = bitoff & jnp.uint64(63)
    first = words.at[w].get(mode="fill", fill_value=0)
    second = words.at[w + 1].get(mode="fill", fill_value=0)
    return jnp.where(r == 0, first, shl(first, r) | shr(second, _SIXTYFOUR - r))
