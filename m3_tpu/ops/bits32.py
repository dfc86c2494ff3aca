"""u32 multi-limb bit-stream machinery for the batched M3TSZ kernels.

TPUs have no native 64-bit integers: every u64 op in a kernel is emulated
by the XLA X64 rewriter, and scatter/gather lower to per-element loops. On
the v5e (PR 21, one run each) the u64 scatter packer took 0.50 s for a
[12288, 128] block against 0.027 s for the limb-tree packer built from
these helpers, and the gather decoder 0.145 s against 0.003 s at
[1024, 1024 steps]. These helpers exist so the codec hot loops can run as
pure 32-bit elementwise ops on whole `[..., W]` limb tensors:

- **limb registers**: a bit stream is a row of u32 limbs, MSB-first
  (stream bit 0 = bit 31 of limb 0 — "top-aligned").
- **variable shifts without gathers**: shifting a register by a
  data-dependent bit count decomposes into log2(W) static rolls selected
  per element by the shift's bits, plus an elementwise bit funnel. A
  static roll is a slice+pad, so the whole operation stays elementwise —
  no scatter, no gather, no per-lane dynamic indexing.

The scalar semantics these mirror are the reference bit stream's
(/root/reference/src/dbnode/encoding/encoding.go:29-43); the batched
layout they enable replaces the reference's per-stream sequential
OStream/IStream with whole-block tensor ops (SURVEY.md section 7's
"blockwise two-pass design").
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

import m3_tpu.ops  # noqa: F401  (enables x64)

U32 = jnp.uint32
import numpy as _np

_Z32 = _np.uint32(0)  # numpy scalar: inlines as a literal, never a hoisted const


def u64_to_pair(v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split u64 -> (hi, lo) u32."""
    v = v.astype(jnp.uint64)
    return (v >> jnp.uint64(32)).astype(U32), v.astype(U32)


def pair_to_u64(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    return (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)


def shl32(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Left shift, safe for n in [0, 32] (n>=32 -> 0)."""
    n = jnp.asarray(n, U32)
    return jnp.where(n >= 32, _Z32, v.astype(U32) << jnp.minimum(n, jnp.uint32(31)))


def shr32(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Logical right shift, safe for n in [0, 32] (n>=32 -> 0)."""
    n = jnp.asarray(n, U32)
    return jnp.where(n >= 32, _Z32, v.astype(U32) >> jnp.minimum(n, jnp.uint32(31)))


def clz32(v: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros of u32; clz32(0) = 32. Returns uint32."""
    v = v.astype(U32)
    return jnp.where(v == 0, jnp.uint32(32), lax.clz(v).astype(U32))


def pair_clz(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """clz of the 64-bit (hi, lo) pair; 64 for zero."""
    return jnp.where(hi == 0, jnp.uint32(32) + clz32(lo), clz32(hi))


def pair_ctz(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """ctz of the 64-bit (hi, lo) pair; 0 for zero (reference convention
    LeadingAndTrailingZeros(0) = (64, 0))."""
    ctz_lo = jnp.uint32(31) - clz32(lo & (_Z32 - lo))
    ctz_hi = jnp.uint32(31) - clz32(hi & (_Z32 - hi))
    both_zero = (hi == 0) & (lo == 0)
    out = jnp.where(lo == 0, jnp.uint32(32) + ctz_hi, ctz_lo)
    return jnp.where(both_zero, _Z32, out)


def pair_shl(hi: jnp.ndarray, lo: jnp.ndarray, n: jnp.ndarray):
    """64-bit left shift of a (hi, lo) pair, n in [0, 64]."""
    n = jnp.asarray(n, U32)
    big = n >= 32
    nb = jnp.where(big, n - 32, n)
    h = jnp.where(big, shl32(lo, nb), shl32(hi, nb) | shr32(lo, 32 - nb))
    l = jnp.where(big, _Z32, shl32(lo, nb))  # noqa: E741
    return h, l


def pair_shr(hi: jnp.ndarray, lo: jnp.ndarray, n: jnp.ndarray):
    """64-bit logical right shift of a (hi, lo) pair, n in [0, 64]."""
    n = jnp.asarray(n, U32)
    big = n >= 32
    nb = jnp.where(big, n - 32, n)
    l = jnp.where(big, shr32(hi, nb), shr32(lo, nb) | shl32(hi, 32 - nb))  # noqa: E741
    h = jnp.where(big, _Z32, shr32(hi, nb))
    return h, l


def _bit(n: jnp.ndarray, k: int) -> jnp.ndarray:
    return (jnp.asarray(n, U32) >> jnp.uint32(k)) & jnp.uint32(1)


def roll_right_words(x: jnp.ndarray, n_words: jnp.ndarray, max_words: int) -> jnp.ndarray:
    """Shift limbs toward higher index by a per-row word count (zero fill).

    x: [..., W]; n_words: broadcastable to x[..., 0] (without the limb
    axis); max_words bounds n_words statically so only ceil(log2) levels of
    static rolls are emitted.
    """
    n = jnp.asarray(n_words, U32)[..., None]
    k = 0
    while (1 << k) <= max_words:
        step = 1 << k
        if step < x.shape[-1]:
            rolled = jnp.concatenate(
                [jnp.zeros_like(x[..., :step]), x[..., :-step]], axis=-1
            )
        else:
            rolled = jnp.zeros_like(x)
        x = jnp.where(_bit(n[..., 0], k)[..., None] == 1, rolled, x)
        k += 1
    return x


def roll_left_words(x: jnp.ndarray, n_words: jnp.ndarray, max_words: int) -> jnp.ndarray:
    """Shift limbs toward lower index by a per-row word count (zero fill)."""
    n = jnp.asarray(n_words, U32)[..., None]
    k = 0
    while (1 << k) <= max_words:
        step = 1 << k
        if step < x.shape[-1]:
            rolled = jnp.concatenate(
                [x[..., step:], jnp.zeros_like(x[..., :step])], axis=-1
            )
        else:
            rolled = jnp.zeros_like(x)
        x = jnp.where(_bit(n[..., 0], k)[..., None] == 1, rolled, x)
        k += 1
    return x


def shift_right_bits(x: jnp.ndarray, n_bits: jnp.ndarray, max_bits: int) -> jnp.ndarray:
    """Shift a top-aligned limb register right by per-row n_bits (stream
    moves toward higher offsets; zeros shift in at the top)."""
    n = jnp.asarray(n_bits, U32)
    x = roll_right_words(x, n >> jnp.uint32(5), max_bits // 32)
    r = (n & jnp.uint32(31))[..., None]
    prev = jnp.concatenate([jnp.zeros_like(x[..., :1]), x[..., :-1]], axis=-1)
    return jnp.where(r == 0, x, shr32(x, r) | shl32(prev, 32 - r))


def shift_left_bits(x: jnp.ndarray, n_bits: jnp.ndarray, max_bits: int) -> jnp.ndarray:
    """Shift a top-aligned limb register left by per-row n_bits (consumes
    the stream head; zeros shift in at the bottom)."""
    n = jnp.asarray(n_bits, U32)
    x = roll_left_words(x, n >> jnp.uint32(5), max_bits // 32)
    r = (n & jnp.uint32(31))[..., None]
    nxt = jnp.concatenate([x[..., 1:], jnp.zeros_like(x[..., :1])], axis=-1)
    return jnp.where(r == 0, x, shl32(x, r) | shr32(nxt, 32 - r))


def pad_limbs(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad a top-aligned limb register on the right to `width` limbs
    (or truncate — callers only truncate streams already flagged as
    overflowing their capacity)."""
    w = x.shape[-1]
    if width == w:
        return x
    if width < w:
        return x[..., :width]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - w)]
    return jnp.pad(x, pad)


def field128_to_limbs(hi: jnp.ndarray, lo: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
    """Convert a right-aligned <=128-bit (hi, lo) u64 field into a
    top-aligned 4-limb u32 register: bit 0 of the field lands at bit 31 of
    limb 0.  length in [0, 128]."""
    h1, h0 = u64_to_pair(hi)
    l1, l0 = u64_to_pair(lo)
    reg = jnp.stack([h1, h0, l1, l0], axis=-1)  # right-aligned 128-bit
    return shift_left_bits(reg, jnp.uint32(128) - jnp.asarray(length, U32), 128)
