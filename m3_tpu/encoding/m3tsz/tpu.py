"""Batched M3TSZ encode/decode as JAX/XLA kernels.

The scalar codec (encoder.py/decoder.py) processes one datapoint at a time;
these kernels process a whole (series x timestep) block per dispatch:

- **encode**: two-pass vectorized bit-packing — compute every datapoint's
  field bit-lengths elementwise, prefix-sum them into bit offsets, assemble
  each datapoint's payload in a 192-bit register, then scatter-add the
  (disjoint) bit pieces into the output word tensor. Because every bit is
  produced by exactly one datapoint, integer add == bitwise or.
- **decode**: a loop over timesteps (the format is inherently sequential
  per stream) batched over series — throughput comes from the batch axis;
  the TPU lowering's loop ends where every stream has met its end marker.

Streams are bit-identical to the scalar encoder configured with
int_optimized=False and a fixed time unit (the storage engine's block-write
configuration for device-resident blocks). Annotations, time-unit changes,
and the int optimization stay on the scalar/host path; this mirrors the
reference's split where the hot loop handles the common shape
(/root/reference/src/dbnode/encoding/m3tsz/float_encoder_iterator.go) and
markers are rare control-plane events.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from m3_tpu.ops.bits import (
    I64,
    U64,
    clz64,
    ctz64,
    mask_low,
    read_window,
    reg3_insert,
    reg3_shift_right_to4,
    shl,
    shr,
    sign_extend64,
)
from m3_tpu.utils.xtime import TimeUnit, unit_value_ns

_EOS_FIELD = np.uint64(0x100 << 2)  # 9-bit marker opcode + 2-bit EOS value
_EOS_LEN = np.uint64(11)

# Max bits one datapoint can occupy: timestamp default bucket (4+64) +
# uncontained XOR (2+6+6+64).
MAX_BITS_PER_DP = 146


class EncodedBlocks(NamedTuple):
    """Batch of encoded streams as device tensors."""

    words: jnp.ndarray  # [B, W] uint64, MSB-first bit stream
    bit_lengths: jnp.ndarray  # [B] uint64, total bits incl. EOS marker
    # True if any series exceeded capacity_words OR its start was not
    # aligned to the encode unit (either way the streams are unusable —
    # re-encode with more capacity / an aligned block start).
    overflow: jnp.ndarray  # [] bool


def _dod_fields(dod_units: jnp.ndarray, default_value_bits: int):
    """Per-element timestamp field (hi, lo, len) for a delta-of-delta.

    Bucket scheme per /root/reference/src/dbnode/encoding/scheme.go:44-52:
    0 -> '0'; 7/9/12-bit buckets with opcodes 10/110/1110; default 1111 +
    32 or 64 bits.
    """
    d = dod_units
    zero = d == 0
    fits = lambda n: (d >= -(1 << (n - 1))) & (d <= (1 << (n - 1)) - 1)  # noqa: E731
    in7, in9, in12 = fits(7), fits(9), fits(12)

    db = default_value_bits
    ud = d.astype(U64)
    # Select (len, value) by bucket; value = opcode followed by dod bits.
    length = jnp.where(
        zero,
        jnp.uint64(1),
        jnp.where(in7, jnp.uint64(9), jnp.where(in9, jnp.uint64(12), jnp.where(in12, jnp.uint64(16), jnp.uint64(4 + db)))),
    )
    val7 = (jnp.uint64(0b10) << 7) | (ud & mask_low(7))
    val9 = (jnp.uint64(0b110) << 9) | (ud & mask_low(9))
    val12 = (jnp.uint64(0b1110) << 12) | (ud & mask_low(12))
    if db == 32:
        val_def_hi = jnp.zeros_like(ud)
        val_def_lo = (jnp.uint64(0b1111) << 32) | (ud & mask_low(32))
    else:
        val_def_hi = jnp.full_like(ud, jnp.uint64(0b1111))
        val_def_lo = ud
    lo = jnp.where(
        zero, jnp.uint64(0), jnp.where(in7, val7, jnp.where(in9, val9, jnp.where(in12, val12, val_def_lo)))
    )
    hi = jnp.where(zero | in7 | in9 | in12, jnp.uint64(0), val_def_hi)
    return hi, lo, length


def _xor_fields(xor: jnp.ndarray, prev_xor: jnp.ndarray):
    """Per-element XOR value field (hi, lo, len).

    Zero / contained / uncontained opcodes per the reference float codec
    (/root/reference/src/dbnode/encoding/m3tsz/float_encoder_iterator.go:82-103).
    """
    pl, pt = clz64(prev_xor), ctz64(prev_xor)
    cl, ct = clz64(xor), ctz64(xor)
    zero = xor == 0
    contained = (cl >= pl) & (ct >= pt) & ~zero

    # contained: '10' + xor >> prev_trailing in (64 - pl - pt) bits
    m_prev = jnp.uint64(64) - pl - pt
    c_lo_val = shr(xor, pt)
    c_len = jnp.uint64(2) + m_prev
    # field value = (0b10 << m_prev) | mantissa; may reach 66 bits
    c_hi = shr(jnp.uint64(0b10), jnp.uint64(64) - m_prev)
    c_lo = shl(jnp.uint64(0b10), m_prev) | c_lo_val

    # uncontained: '11' + 6-bit leading + 6-bit (m-1) + m bits
    m = jnp.uint64(64) - cl - ct
    top = (jnp.uint64(0b11) << 12) | (cl << 6) | (m - jnp.uint64(1))  # 14 bits
    mant = shr(xor, ct)
    u_len = jnp.uint64(14) + m
    u_lo = shl(top, m) | mant
    u_hi = shr(top, jnp.uint64(64) - m)

    length = jnp.where(zero, jnp.uint64(1), jnp.where(contained, c_len, u_len))
    lo = jnp.where(zero, jnp.uint64(0), jnp.where(contained, c_lo, u_lo))
    hi = jnp.where(zero, jnp.uint64(0), jnp.where(contained, c_hi, u_hi))
    return hi, lo, length


def _trunc_div(a: jnp.ndarray, b: int) -> jnp.ndarray:
    """Go-style truncating integer division (toward zero)."""
    q = jnp.abs(a) // b
    return jnp.where(a < 0, -q, q).astype(I64)


def encode(
    times: jnp.ndarray,
    values: jnp.ndarray,  # [B, T] float64
    start: jnp.ndarray,
    n_points: jnp.ndarray,
    unit: TimeUnit = TimeUnit.SECOND,
    capacity_words: int | None = None,
    impl: str | None = None,
) -> EncodedBlocks:
    """Encode from float64 values.

    Host convenience wrapper: the values are bitcast to uint64 HERE, on
    the host, and the jitted kernel (encode_bits) takes the bits. On the
    TPU the f64->u64 bitcast does not exist (v5e, jax 0.9.0, PR 21:
    "UNIMPLEMENTED: While rewriting computation to not contain X64
    element types, XLA encountered an HLO for which this rewriting is not
    implemented: bitcast-convert"), and a float64 could not carry the
    bits anyway: the device holds one as a pair of float32 (see
    DecodedBlocks). Bits are a free numpy view on the host ingest path
    and the representation the storage engine keeps.
    """
    unit_ns = unit_value_ns(unit)
    if (np.asarray(start) % unit_ns != 0).any():
        raise ValueError(
            f"block start must be aligned to the encode unit ({unit.name}); "
            "the batched kernel never writes time-unit-change markers"
        )
    # device-resident callers hold bits and call encode_bits directly
    vb = jnp.asarray(np.asarray(values, dtype=np.float64).view(np.uint64))
    return encode_bits(times, vb, start, n_points, unit, capacity_words, impl)


def encode_bits(
    times: jnp.ndarray,  # [B, T] int64 unix nanos
    value_bits: jnp.ndarray,  # [B, T] uint64 IEEE-754 bit patterns
    start: jnp.ndarray,  # [B] int64 block start unix nanos
    n_points: jnp.ndarray,  # [B] int32 valid points per series
    unit: TimeUnit = TimeUnit.SECOND,
    capacity_words: int | None = None,
    impl: str | None = None,
) -> EncodedBlocks:
    """Batched M3TSZ float-mode encode of B series with up to T points
    each. `impl` selects the packer backend (resolved per platform by
    default); it keys the jit cache so env/impl changes retrace."""
    return _encode_bits_jit(times, value_bits, start, n_points, unit,
                            capacity_words, _resolve_impl(impl))


@functools.partial(jax.jit, static_argnames=("unit", "capacity_words", "impl"))
def _encode_bits_jit(
    times: jnp.ndarray,
    value_bits: jnp.ndarray,
    start: jnp.ndarray,
    n_points: jnp.ndarray,
    unit: TimeUnit = TimeUnit.SECOND,
    capacity_words: int | None = None,
    impl: str = "tree",
) -> EncodedBlocks:
    B, T = times.shape  # noqa: N806
    unit_ns = unit_value_ns(unit)
    default_bits = 32 if unit in (TimeUnit.SECOND, TimeUnit.MILLISECOND) else 64
    if capacity_words is None:
        capacity_words = (64 + MAX_BITS_PER_DP * T + 11 + 63) // 64

    times = times.astype(I64)
    idx = jnp.arange(T)
    valid = idx[None, :] < n_points[:, None]

    # --- timestamp fields ---
    prev_t = jnp.concatenate([start[:, None].astype(I64), times[:, :-1]], axis=1)
    dt = times - prev_t
    prev_dt = jnp.concatenate([jnp.zeros((B, 1), I64), dt[:, :-1]], axis=1)
    dod_ns = dt - prev_dt
    dod_units = _trunc_div(dod_ns, unit_ns)
    ts_hi, ts_lo, ts_len = _dod_fields(dod_units, default_bits)

    # --- value fields ---
    vb = value_bits.astype(U64)
    prev_vb = jnp.concatenate([jnp.zeros((B, 1), U64), vb[:, :-1]], axis=1)
    xor = vb ^ prev_vb
    # prev_xor chain: prev_xor[i] = xor[i-1]; xor[0] == vb[0] which is
    # exactly the prevXOR state after the first (full) float write.
    prev_xor = jnp.concatenate([jnp.zeros((B, 1), U64), xor[:, :-1]], axis=1)
    x_hi, x_lo, x_len = _xor_fields(xor, prev_xor)
    # first datapoint: raw 64-bit float
    is_first = idx[None, :] == 0
    v_hi = jnp.where(is_first, jnp.uint64(0), x_hi)
    v_lo = jnp.where(is_first, vb, x_lo)
    v_len = jnp.where(is_first, jnp.uint64(64), x_len)

    # --- layout ---
    dp_len = jnp.where(valid, ts_len + v_len, jnp.uint64(0))
    end_off = jnp.uint64(64) + jnp.sum(dp_len, axis=1)
    total_bits = end_off + _EOS_LEN
    # A start that isn't a multiple of the unit would make the scalar
    # encoder emit a time-unit-change marker (initial_time_unit -> NONE);
    # this kernel never writes markers, so flag the batch as unusable.
    misaligned = jnp.any(start.astype(I64) % unit_ns != 0)
    overflow = jnp.any(total_bits > jnp.uint64(capacity_words * 64)) | misaligned
    if default_bits == 32:
        # The scalar encoder raises when a dod exceeds the 32-bit default
        # bucket for s/ms units (timestamp_encoder semantics); the batch
        # kernel can't raise mid-trace, so flag the batch unusable instead.
        in32 = (dod_units >= -(1 << 31)) & (dod_units <= (1 << 31) - 1)
        overflow = overflow | jnp.any(valid & ~in32)

    with jax.named_scope("m3.encode.pack"):
        words = _pack_stream(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len,
                             valid, start, capacity_words, impl)
    return EncodedBlocks(words=words, bit_lengths=total_bits, overflow=overflow)


_DP_LIMBS = 7  # one datapoint's (ts + value) fields: <=196 bits -> 7 u32 limbs


def _resolve_impl(impl: str | None = None) -> str:
    """Implementation choice, resolved OUTSIDE jit so it can key the jit
    cache: the log-tree/shifting-buffer u32 kernels ('tree') avoid the
    scatter/gather + u64-emulation costs that dominate on TPU (both
    seen on the v5e, PR 21); CPU XLA lowers the original scatter/gather
    design ('scatter') several times faster."""
    if impl is not None:
        if impl not in ("tree", "scatter"):
            raise ValueError(
                f"unknown codec impl {impl!r}: want 'tree' or 'scatter'")
        return impl
    return "scatter" if jax.default_backend() == "cpu" else "tree"


def _pack_stream(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len, valid,
                 start, capacity_words: int, impl: str) -> jnp.ndarray:
    """Stream packer, dispatched on the statically-resolved impl."""
    if impl == "tree":
        return _pack_stream_tree(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len,
                                 valid, start, capacity_words)
    return _pack_stream_scatter(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len,
                                valid, start, capacity_words)


def _pack_stream_scatter(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len, valid,
                         start, capacity_words: int) -> jnp.ndarray:
    """Assemble per-dp (timestamp, value) fields into word tensors via the
    192-bit register + disjoint scatter-add scheme, and cap with EOS.
    CPU path: XLA:CPU lowers these scatters well. On the v5e this packer
    ran 18x slower than the tree packer at [12288, 128] (0.50 s against
    0.027 s, one run each, PR 21)."""
    B, T = ts_len.shape  # noqa: N806
    dp_len = jnp.where(valid, ts_len + v_len, jnp.uint64(0))
    csum = jnp.cumsum(dp_len, axis=1)
    offsets = jnp.uint64(64) + csum - dp_len
    end_off = (jnp.uint64(64) + csum[:, -1]) if T > 0 else jnp.full((B,), 64, U64)
    zero_reg = (jnp.zeros((B, T), U64),) * 3
    reg = reg3_insert(zero_reg, jnp.uint64(0), ts_hi, ts_lo, ts_len)
    reg = reg3_insert(reg, ts_len, v_hi, v_lo, v_len)
    r = offsets & jnp.uint64(63)
    pieces = reg3_shift_right_to4(reg, r)
    w0 = (offsets >> jnp.uint64(6)).astype(jnp.int32)

    words = jnp.zeros((B, capacity_words), U64)
    # 64-bit start prefix occupies word 0 of every series.
    words = words.at[:, 0].set(start.astype(I64).astype(U64))
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    for k, piece in enumerate(pieces):
        words = words.at[b_idx, w0 + k].add(jnp.where(valid, piece, jnp.uint64(0)), mode="drop")

    # --- EOS marker ---
    eos_reg = reg3_insert(
        (jnp.zeros((B,), U64),) * 3, jnp.uint64(0), jnp.zeros((B,), U64),
        jnp.uint64(_EOS_FIELD), jnp.uint64(_EOS_LEN)
    )
    eos_pieces = reg3_shift_right_to4(eos_reg, end_off & jnp.uint64(63))
    ew0 = (end_off >> jnp.uint64(6)).astype(jnp.int32)
    bb = jnp.arange(B)
    for k, piece in enumerate(eos_pieces):
        words = words.at[bb, ew0 + k].add(piece, mode="drop")

    return words


def _pack_stream_tree(ts_hi, ts_lo, ts_len, v_hi, v_lo, v_len, valid,
                      start, capacity_words: int) -> jnp.ndarray:
    """Assemble per-dp (timestamp, value) u64 bit fields into the output
    word tensor by log-tree bit concatenation — no scatter.

    Scatter is slow on the TPU (see _pack_stream_scatter), which made the
    original 4-piece scatter-add packer the encode bottleneck.
    Instead: each datapoint becomes a top-aligned u32 limb register; the
    [start prefix] + T dp registers + [EOS] slot sequence is then combined
    pairwise — result = A | (B >> lenA), with the variable shift decomposed
    into log2 static rolls (ops/bits32.py) — doubling register width each
    of the log2(T) levels until one register holds the whole stream. Pure
    elementwise u32 work that XLA fuses and tiles.
    """
    from m3_tpu.ops import bits32 as b32

    B, T = ts_len.shape  # noqa: N806
    w32_cap = capacity_words * 2

    ts_limbs = b32.field128_to_limbs(ts_hi, ts_lo, ts_len)  # [B, T, 4]
    v_limbs = b32.field128_to_limbs(v_hi, v_lo, v_len)
    ts_len32 = ts_len.astype(b32.U32)
    dp = b32.pad_limbs(ts_limbs, _DP_LIMBS) | b32.shift_right_bits(
        b32.pad_limbs(v_limbs, _DP_LIMBS), ts_len32, 128
    )
    dp_len = ts_len32 + v_len.astype(b32.U32)
    dp = jnp.where(valid[..., None], dp, jnp.uint32(0))
    dp_len = jnp.where(valid, dp_len, jnp.uint32(0))

    # slot sequence: [start(64b)] + T dps + [EOS(11b)], padded to a power
    # of two with zero-length slots (no-ops under concatenation).
    # All slots derive from traced data (zeros as 0*traced) — materialized
    # trace-time constants trip a jit fastpath bug ("supplied N buffers but
    # compiled program expected M") on repeat calls.
    s_hi, s_lo = b32.u64_to_pair(start.astype(I64).astype(U64))
    zcol = jnp.zeros_like(s_hi)  # [B] (shape-independent of T: T=0 works)
    start_slot = jnp.stack(
        [s_hi, s_lo] + [zcol] * (_DP_LIMBS - 2), axis=-1
    )[:, None, :]
    eos_slot = jnp.stack(
        [zcol + jnp.uint32(int(_EOS_FIELD) << 21)] + [zcol] * (_DP_LIMBS - 1),
        axis=-1,
    )[:, None, :]
    n_slots = T + 2
    n_pad = 1
    while n_pad < n_slots:
        n_pad *= 2
    pad_slots = [
        jnp.broadcast_to(zcol[:, None, None], (B, n_pad - n_slots, _DP_LIMBS))
    ] if n_pad > n_slots else []
    slots = jnp.concatenate([start_slot, dp, eos_slot] + pad_slots, axis=1)
    zlen = zcol[:, None]  # [B, 1]
    pad_lens = [
        jnp.broadcast_to(zlen, (B, n_pad - n_slots))
    ] if n_pad > n_slots else []
    lens = jnp.concatenate(
        [zlen + jnp.uint32(64), dp_len, zlen + jnp.uint32(int(_EOS_LEN))] + pad_lens,
        axis=1,
    )

    width = _DP_LIMBS
    while slots.shape[1] > 1:
        width = min(width * 2, max(w32_cap, _DP_LIMBS))
        a, bb = slots[:, 0::2], slots[:, 1::2]
        len_a, len_b = lens[:, 0::2], lens[:, 1::2]
        # clamp so pathological (overflowing) lengths still shift to zero
        shift = jnp.minimum(len_a, jnp.uint32(32 * width))
        slots = b32.pad_limbs(a, width) | b32.shift_right_bits(
            b32.pad_limbs(bb, width), shift, 32 * width
        )
        lens = len_a + len_b
    limbs = b32.pad_limbs(slots[:, 0], w32_cap)
    return b32.pair_to_u64(limbs[:, 0::2], limbs[:, 1::2])


def _decode_ts_fields(series_words, off, win, default_bits: int):
    """(dod_units int64, ts_len) decoded at the cursor (shared by the
    float-mode and int-optimized decode scans)."""
    b1 = shr(win, jnp.uint64(63))
    p2 = shr(win, jnp.uint64(62))
    p3 = shr(win, jnp.uint64(61))
    p4 = shr(win, jnp.uint64(60))
    zero = b1 == 0
    in7 = p2 == jnp.uint64(0b10)
    in9 = p3 == jnp.uint64(0b110)
    in12 = p4 == jnp.uint64(0b1110)
    d7 = sign_extend64(shr(win, jnp.uint64(55)), jnp.uint64(7))
    d9 = sign_extend64(shr(win, jnp.uint64(52)), jnp.uint64(9))
    d12 = sign_extend64(shr(win, jnp.uint64(48)), jnp.uint64(12))
    if default_bits == 32:
        ddef = sign_extend64(shr(win, jnp.uint64(28)), jnp.uint64(32))
    else:
        win2 = read_window(series_words, off + jnp.uint64(4))
        ddef = sign_extend64(win2, jnp.uint64(64))
    dod_u = jnp.where(
        zero, 0, jnp.where(in7, d7, jnp.where(in9, d9, jnp.where(in12, d12, ddef)))
    ).astype(I64)
    ts_len = jnp.where(
        zero,
        jnp.uint64(1),
        jnp.where(
            in7,
            jnp.uint64(9),
            jnp.where(in9, jnp.uint64(12), jnp.where(in12, jnp.uint64(16), jnp.uint64(4 + default_bits))),
        ),
    )
    return dod_u, ts_len


class DecodedBlocks(NamedTuple):
    times: jnp.ndarray  # [B, T] int64
    # IEEE-754 bit patterns, NOT floats. Seen on the v5e (PR 21): a device
    # float64 is a pair of float32. It has float32's exponent range (1e39
    # arrives as inf, 1e-300 as 0) and about 49 mantissa bits (ordinary
    # values come back within 1.8e-15 relative, not bit-exact; even
    # device_put followed by device_get changes them). The u64->f64
    # bitcast lowers but converts, so it is no bit cast either. Bits are
    # exact everywhere; convert with values_f64() on the host, or accept
    # that loss converting on-device.
    value_bits: jnp.ndarray  # [B, T] uint64
    valid: jnp.ndarray  # [B, T] bool
    n_points: jnp.ndarray  # [B] int32
    # True per series if a non-EOS special marker (annotation / time-unit
    # change) was hit: such streams carry host-path features and must be
    # decoded by the scalar decoder instead.
    error: jnp.ndarray  # [B] bool

    def values_f64(self) -> np.ndarray:
        """Decoded values as float64 (host-side bitcast; always exact)."""
        return np.asarray(jax.device_get(self.value_bits)).view(np.float64)


class DecodedValues(NamedTuple):
    """Decode result carrying materialized float values (int-optimized
    kernel, whose values are computed, not bit-copied)."""

    times: jnp.ndarray  # [B, T] int64
    values: jnp.ndarray  # [B, T] float64
    valid: jnp.ndarray  # [B, T] bool
    n_points: jnp.ndarray  # [B] int32
    error: jnp.ndarray  # [B] bool


def _sx(v: jnp.ndarray, n: int) -> jnp.ndarray:
    """Sign-extend the low n bits of a u32 to int64 (n <= 32, static)."""
    s = np.uint32(1 << (n - 1))
    m = np.uint32((1 << n) - 1) if n < 32 else np.uint32(0xFFFFFFFF)
    x = (v.astype(jnp.uint32) & m) ^ s
    return x.astype(I64) - jnp.int64(int(s))


def decode(
    words: jnp.ndarray,  # [B, W] uint64
    unit: TimeUnit = TimeUnit.SECOND,
    max_points: int = 1024,
    impl: str | None = None,
    n_live: int | None = None,
) -> DecodedBlocks:
    """Batched M3TSZ float-mode decode (platform dispatch; `impl` as in
    encode_bits). `n_live`: the first rows that hold streams, where the
    rest pad the batch to a shape bucket (all of them when None); the
    TPU lowering stops as soon as every live row is at its end."""
    return _decode_jit(words, unit, max_points, _resolve_impl(impl),
                       np.int32(words.shape[0] if n_live is None else n_live))


@functools.partial(jax.jit, static_argnames=("unit", "max_points", "impl"))
def _decode_jit(
    words: jnp.ndarray,
    unit: TimeUnit,
    max_points: int,
    impl: str,
    n_live: jnp.ndarray,
) -> DecodedBlocks:
    with jax.named_scope("m3.decode.scan"):
        if impl == "tree":
            return _decode_shift(words, unit, max_points, n_live)
        return _decode_gather(words, unit, max_points)


def _decode_gather(
    words: jnp.ndarray,  # [B, W] uint64
    unit: TimeUnit = TimeUnit.SECOND,
    max_points: int = 1024,
) -> DecodedBlocks:
    """CPU decode: scan over points, vmapped over series, with per-step
    read_window gathers (XLA:CPU handles these well; slow on the TPU, see
    _decode_shift)."""
    unit_ns = unit_value_ns(unit)
    default_bits = 32 if unit in (TimeUnit.SECOND, TimeUnit.MILLISECOND) else 64

    def decode_one(series_words: jnp.ndarray):
        start = sign_extend64(series_words[0], jnp.uint64(64))

        def step(carry, i):
            off, prev_time, prev_dt, prev_bits, prev_xor, done, err = carry
            win = read_window(series_words, off)

            # special marker: 9-bit opcode 0x100 at the cursor; value 0 is
            # end-of-stream, anything else (annotation/time-unit change) is
            # a host-path feature this kernel doesn't decode -> error.
            is_marker = shr(win, jnp.uint64(55)) == jnp.uint64(0x100)
            marker_val = shr(win, jnp.uint64(53)) & jnp.uint64(3)
            err = err | (is_marker & (marker_val != 0) & ~done)
            is_eos = is_marker

            # --- delta-of-delta ---
            dod_u, ts_len = _decode_ts_fields(series_words, off, win, default_bits)
            new_dt = prev_dt + dod_u * unit_ns
            new_time = prev_time + new_dt

            # --- value ---
            voff = off + ts_len
            vwin = read_window(series_words, voff)
            first = i == 0
            vb1 = shr(vwin, jnp.uint64(63))
            vb2 = shr(vwin, jnp.uint64(62)) & jnp.uint64(1)
            xz = vb1 == 0
            contained = (vb1 == 1) & (vb2 == 0)
            # Mantissas can extend past a 64-bit window anchored at the
            # opcode (fields reach 78 bits), so each is read from a window
            # anchored at its own start.
            pl, pt = clz64(prev_xor), ctz64(prev_xor)
            m_prev = jnp.uint64(64) - pl - pt
            c_mant = shr(read_window(series_words, voff + jnp.uint64(2)), jnp.uint64(64) - m_prev)
            c_xor = shl(c_mant, pt)
            c_len = jnp.uint64(2) + m_prev
            lead = shr(vwin, jnp.uint64(56)) & jnp.uint64(0x3F)
            mm = (shr(vwin, jnp.uint64(50)) & jnp.uint64(0x3F)) + jnp.uint64(1)
            u_mant = shr(read_window(series_words, voff + jnp.uint64(14)), jnp.uint64(64) - mm)
            trail = jnp.uint64(64) - lead - mm
            u_xor = shl(u_mant, trail)
            u_len = jnp.uint64(14) + mm
            xor = jnp.where(xz, jnp.uint64(0), jnp.where(contained, c_xor, u_xor))
            x_len = jnp.where(xz, jnp.uint64(1), jnp.where(contained, c_len, u_len))

            new_bits = jnp.where(first, vwin, prev_bits ^ xor)
            new_xor = jnp.where(first, vwin, xor)
            v_len = jnp.where(first, jnp.uint64(64), x_len)

            ok = ~done & ~is_eos
            out_t = jnp.where(ok, new_time, 0)
            out_v = jnp.where(ok, new_bits, jnp.uint64(0))
            carry = (
                jnp.where(ok, off + ts_len + v_len, off),
                jnp.where(ok, new_time, prev_time),
                jnp.where(ok, new_dt, prev_dt),
                jnp.where(ok, new_bits, prev_bits),
                jnp.where(ok, new_xor, prev_xor),
                done | is_eos,
                err,
            )
            return carry, (out_t, out_v, ok)

        init = (
            jnp.uint64(64),
            start,
            jnp.int64(0),
            jnp.uint64(0),
            jnp.uint64(0),
            jnp.bool_(False),
            jnp.bool_(False),
        )
        carry, (ts, vs, ok) = lax.scan(step, init, jnp.arange(max_points))
        return ts, vs, ok, carry[-1]

    ts, vs, ok, err = jax.vmap(decode_one)(words)
    return DecodedBlocks(
        times=ts,
        value_bits=vs,
        valid=ok,
        n_points=ok.sum(axis=1).astype(jnp.int32),
        error=err,
    )


def _decode_shift(
    words: jnp.ndarray,  # [B, W] uint64
    unit: TimeUnit,
    max_points: int,
    n_live: jnp.ndarray,  # int32 scalar: rows from here on are padding
) -> DecodedBlocks:
    """Batched M3TSZ float-mode decode via a shifting stream buffer.

    The format is sequential per stream, but per-step RANDOM ACCESS is not
    required: the scan carries the remaining stream as a [B, W] u32 limb
    register and consumes each datapoint from its top — static slices for
    the parse, then a log-decomposed left shift by the datapoint's length.
    This replaces the per-step `read_window` gathers of the original design
    (on the v5e the gather decoder ran 48x slower at [1024, 32 words,
    1024 steps]: 0.145 s against 0.003 s, one run each, PR 21) with pure
    elementwise work that XLA tiles; throughput comes from the batch axis
    and HBM bandwidth.

    The loop ends at the step where every live row has met its end of
    stream, not after `max_points` steps: the capacity is reckoned from
    the longest stream at 2 bits a point (hostpath._max_points: 2,048
    steps for an hour of 10 s readings, 360 points), and every step past
    the last point is device time, and a step's worth of events in a
    device trace, for nothing. Rows from `n_live` on (shape-bucket
    padding: zero words never reach an end marker) start out done.
    """
    from m3_tpu.ops import bits32 as b32

    unit_ns = unit_value_ns(unit)
    default_bits = 32 if unit in (TimeUnit.SECOND, TimeUnit.MILLISECOND) else 64
    B, W = words.shape  # noqa: N806

    start = sign_extend64(words[:, 0], jnp.uint64(64))  # [B] int64
    hi, lo = b32.u64_to_pair(words)
    limbs = jnp.stack([hi, lo], axis=-1).reshape(B, 2 * W)
    buf0 = limbs[:, 2:]  # the 64-bit start prefix is consumed up front
    if buf0.shape[1] < 8:  # parse window needs 8 limbs; tiny streams pad
        buf0 = b32.pad_limbs(buf0, 8)

    u32 = jnp.uint32

    def step(carry, i):
        buf, r, prev_time, prev_dt, pb_h, pb_l, px_h, px_l, done, err = carry

        # Align the next 224 bits at the cursor: funnel the first 8 limbs
        # by r (< 32). A datapoint spans <= 146 bits; with ts_len <= 68 the
        # value window needs bits [ts_len, ts_len + 96) <= 164 < 224.
        w = [buf[:, j] for j in range(8)]
        a = []
        for j in range(7):
            cur, nxt = w[j], w[j + 1]
            a.append(jnp.where(r == 0, cur, b32.shl32(cur, r) | b32.shr32(nxt, 32 - r)))
        a0, a1, a2 = a[0], a[1], a[2]

        # special marker: 9-bit opcode 0x100; value 0 = EOS, else a
        # host-path feature (annotation / time-unit change) -> error.
        is_marker = (a0 >> u32(23)) == u32(0x100)
        marker_val = (a0 >> u32(21)) & u32(3)
        err = err | (is_marker & (marker_val != 0) & ~done)
        is_eos = is_marker

        # --- delta-of-delta (static bit positions within a0..a2) ---
        zero_dod = (a0 >> u32(31)) == 0
        in7 = (a0 >> u32(30)) == u32(0b10)
        in9 = (a0 >> u32(29)) == u32(0b110)
        in12 = (a0 >> u32(28)) == u32(0b1110)
        d7 = _sx(a0 >> u32(23), 7)
        d9 = _sx(a0 >> u32(20), 9)
        d12 = _sx(a0 >> u32(16), 12)
        if default_bits == 32:
            ddef = _sx((a0 << u32(4)) | (a1 >> u32(28)), 32)
        else:
            ddef = sign_extend64(
                b32.pair_to_u64(
                    (a0 << u32(4)) | (a1 >> u32(28)),
                    (a1 << u32(4)) | (a2 >> u32(28)),
                ),
                jnp.uint64(64),
            )
        dod = jnp.where(
            zero_dod, jnp.int64(0),
            jnp.where(in7, d7, jnp.where(in9, d9, jnp.where(in12, d12, ddef))),
        )
        ts_len = jnp.where(
            zero_dod, u32(1),
            jnp.where(in7, u32(9),
                      jnp.where(in9, u32(12),
                                jnp.where(in12, u32(16), u32(4 + default_bits)))),
        )
        new_dt = prev_dt + dod * unit_ns
        new_time = prev_time + new_dt

        # --- value field at bit offset ts_len: word-select + funnel ---
        ws = ts_len >> u32(5)  # 0..2
        tb = ts_len & u32(31)
        v = []
        for j in range(3):
            c0 = jnp.where(ws == 0, a[j], jnp.where(ws == 1, a[j + 1], a[j + 2]))
            c1 = jnp.where(ws == 0, a[j + 1], jnp.where(ws == 1, a[j + 2], a[j + 3]))
            v.append(jnp.where(tb == 0, c0, b32.shl32(c0, tb) | b32.shr32(c1, 32 - tb)))
        v0, v1, v2 = v

        first = i == 0
        vb1 = v0 >> u32(31)
        vb2 = (v0 >> u32(30)) & u32(1)
        xz = vb1 == 0
        contained = (vb1 == 1) & (vb2 == 0)
        pl = b32.pair_clz(px_h, px_l)
        pt = b32.pair_ctz(px_h, px_l)
        m_prev = u32(64) - pl - pt
        # contained: mantissa window at field offset 2
        cw_h = (v0 << u32(2)) | (v1 >> u32(30))
        cw_l = (v1 << u32(2)) | (v2 >> u32(30))
        cm_h, cm_l = b32.pair_shr(cw_h, cw_l, u32(64) - m_prev)
        cx_h, cx_l = b32.pair_shl(cm_h, cm_l, pt)
        c_len = u32(2) + m_prev
        # uncontained: '11' + 6b lead + 6b (m-1) + m mantissa bits at offset 14
        lead = (v0 >> u32(24)) & u32(0x3F)
        mm = ((v0 >> u32(18)) & u32(0x3F)) + u32(1)
        uw_h = (v0 << u32(14)) | (v1 >> u32(18))
        uw_l = (v1 << u32(14)) | (v2 >> u32(18))
        um_h, um_l = b32.pair_shr(uw_h, uw_l, u32(64) - mm)
        trail = u32(64) - lead - mm
        ux_h, ux_l = b32.pair_shl(um_h, um_l, trail)
        u_len = u32(14) + mm

        xor_h = jnp.where(xz, u32(0), jnp.where(contained, cx_h, ux_h))
        xor_l = jnp.where(xz, u32(0), jnp.where(contained, cx_l, ux_l))
        x_len = jnp.where(xz, u32(1), jnp.where(contained, c_len, u_len))

        nb_h = jnp.where(first, v0, pb_h ^ xor_h)
        nb_l = jnp.where(first, v1, pb_l ^ xor_l)
        nx_h = jnp.where(first, v0, xor_h)
        nx_l = jnp.where(first, v1, xor_l)
        v_len = jnp.where(first, u32(64), x_len)

        ok = ~done & ~is_eos
        dp_len = ts_len + v_len
        r2 = r + jnp.where(ok, dp_len, u32(0))
        buf2 = b32.roll_left_words(buf, r2 >> u32(5), 6)
        r3 = r2 & u32(31)

        out_t = jnp.where(ok, new_time, jnp.int64(0))
        carry = (
            buf2,
            r3,
            jnp.where(ok, new_time, prev_time),
            jnp.where(ok, new_dt, prev_dt),
            jnp.where(ok, nb_h, pb_h),
            jnp.where(ok, nb_l, pb_l),
            jnp.where(ok, nx_h, px_h),
            jnp.where(ok, nx_l, px_l),
            done | is_eos,
            err,
        )
        return carry, (out_t, jnp.where(ok, nb_h, u32(0)),
                       jnp.where(ok, nb_l, u32(0)), ok)

    zb = jnp.zeros((B,), u32)
    init = (
        buf0,
        zb,
        start,
        jnp.zeros((B,), I64),
        zb, zb, zb, zb,
        jnp.arange(B, dtype=jnp.int32) >= n_live,
        jnp.zeros((B,), bool),
    )

    # a scan that stops early: the per-step outputs land in [P, B]
    # buffers at row i, as lax.scan stacks them, and rows past the last
    # step stay zero / not ok, which is what a done row's steps write
    def more(state):
        i, carry, _outs = state
        done = carry[8]
        return (i < max_points) & ~jnp.all(done)

    def advance(state):
        i, carry, outs = state
        carry, step_outs = step(carry, i)
        return (i + 1, carry, tuple(
            lax.dynamic_update_index_in_dim(buf, row, i, 0)
            for buf, row in zip(outs, step_outs)))

    outs0 = tuple(jnp.zeros((max_points, B), dt)
                  for dt in (I64, u32, u32, bool))
    _, carry, (ts, vh, vl, ok) = lax.while_loop(
        more, advance, (jnp.int32(0), init, outs0))
    err = carry[-1]
    return DecodedBlocks(
        times=ts.T,
        value_bits=b32.pair_to_u64(vh.T, vl.T),
        valid=ok.T,
        n_points=ok.T.sum(axis=1).astype(jnp.int32),
        error=err,
    )


def blocks_to_bytes(blocks: EncodedBlocks,
                    n_rows: int | None = None) -> list[bytes]:
    """Materialize encoded device blocks as per-series byte strings
    (host-side, for persistence/interop with the scalar codec); the
    first ``n_rows`` only when the batch carries shape-bucket pad rows."""
    words = np.asarray(jax.device_get(blocks.words))[:n_rows]
    bits = np.asarray(jax.device_get(blocks.bit_lengths))[:n_rows]
    # order="C": device_get may hand back the device's own layout
    raw = words.astype(">u8", order="C").view(np.uint8).reshape(
        len(words), -1)
    nbytes = ((bits.astype(np.int64) + 7) // 8).tolist()
    return [raw[i, :nb].tobytes() for i, nb in enumerate(nbytes)]


def bytes_to_words(streams: list[bytes], capacity_words: int | None = None) -> jnp.ndarray:
    """Pack byte streams into a [B, W] uint64 word tensor for decode."""
    if capacity_words is None:
        capacity_words = max((len(s) + 7) // 8 for s in streams) if streams else 1
    arr = np.zeros((len(streams), capacity_words), dtype=np.uint64)
    for i, s in enumerate(streams):
        padded = s + b"\x00" * (-len(s) % 8)
        arr[i, : len(padded) // 8] = np.frombuffer(padded, dtype=">u8").astype(np.uint64)
    return jnp.asarray(arr)
