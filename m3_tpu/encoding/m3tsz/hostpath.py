"""Per-platform codec dispatch for the storage serving paths.

The storage engine flushes and reads through exactly one of:
  - the batched XLA kernels (tpu.py / tpu_int.py) when an accelerator
    backend is live — the device path;
  - the native v2 batch codec (native/m3tsz.cpp, word-level bit I/O,
    threaded across cores) on CPU-only hosts, float mode, for both the
    flush encode and the read decode;
  - the pure-Python scalar codec as the always-available fallback (and the
    only decoder for int-optimized and marker-bearing streams host-side).

This mirrors the reference's role split where the Go hot loop IS the
serving path (/root/reference/src/dbnode/encoding/m3tsz/encoder.go): here
the hot loop is the native batch codec or the device kernel, chosen by
platform. utils/dispatch counters record which path served so tests and
/metrics can verify the production path (round-1 failure mode: device
kernels only tests invoked).
"""

from __future__ import annotations

import numpy as np

from m3_tpu.utils import dispatch
from m3_tpu.utils.xtime import TimeUnit


def _device_encode() -> bool:
    """Device encode when forced (M3_TPU_DEVICE_OPS=1, kernel-parity tests)
    or when an accelerator backend is live."""
    import os

    force = os.environ.get("M3_TPU_DEVICE_OPS")
    if force == "1":
        return True
    if force == "0":
        return False
    return bool(dispatch._accelerator_present())


# decode rides the same platform switch as encode
_device_decode = _device_encode


def encode_blocks(times, vbits, starts, n_points,
                  unit: TimeUnit, int_optimized: bool) -> list[bytes]:
    """Encode a sealed [B, T] window to per-series streams on the best
    path for this platform. Raises on overflow (caller bug: capacity)."""
    from m3_tpu.encoding.m3tsz import native

    times = np.asarray(times)
    vbits = np.asarray(vbits)
    if (not int_optimized and not _device_encode()
            and native.available()):
        dispatch.counters["m3tsz_encode_native"] += 1
        return native.encode_batch(times, vbits, np.asarray(starts), unit,
                                   n_points=np.asarray(n_points))
    import jax.numpy as jnp

    from m3_tpu.encoding.m3tsz import tpu as m3tsz_tpu

    if int_optimized:
        from m3_tpu.encoding.m3tsz import tpu_int

        encode_fn = tpu_int.encode_bits_int
        jitted = tpu_int._encode_bits_int_jit
    else:
        encode_fn = m3tsz_tpu.encode_bits
        jitted = m3tsz_tpu._encode_bits_jit
    dispatch.counters["m3tsz_encode_device"] += 1
    n_rows = times.shape[0]
    if n_rows == 0:
        return []
    times, vbits, starts, n_points = _pad_encode_batch(
        times, vbits, np.asarray(starts), np.asarray(n_points))
    # plan-cache attribution: did this shape bucket hit the jit cache or
    # pay a trace+compile? (compute.jit_* on /metrics); the sig keys the
    # per-program execute histogram on the batch rectangle
    sig = f"B{times.shape[0]}xT{times.shape[1]}" + \
        ("|int" if int_optimized else "")
    from m3_tpu.utils import trace

    # one stage around the encoder call AND the transfers that wait for
    # it (the overflow flag, then words and bit lengths), as the decoder's
    with trace.stage(trace.STAGE_ENCODE_WAIT):
        with dispatch.jit_tracker("m3tsz_encode", jitted, sig=sig):
            blocks = encode_fn(
                jnp.asarray(times), jnp.asarray(vbits),
                jnp.asarray(starts), jnp.asarray(n_points), unit,
            )
        if bool(blocks.overflow):
            raise OverflowError("batched encode overflow")
        return m3tsz_tpu.blocks_to_bytes(blocks, n_rows)


def _pad_encode_batch(times, vbits, starts, n_points):
    """Pad a sealed [B, T] window to half-octave shape buckets on both
    axes. Every shard's window is its own rectangle and every distinct
    rectangle is a compile (tens of seconds each on a TPU: an 8-shard
    flush paid 8 of them before this), so the device encoder sees
    bucketed shapes like the compiler and the index do. Pad rows carry
    zero points and a real row's (unit-aligned) start; pad lanes repeat
    the row's last timestamp (seal's monotone-tail rule). The encoder
    reads exactly n_points lanes per row, so streams are unchanged."""
    from m3_tpu.utils import compute_stats

    B, T = times.shape
    Bp, Tp = dispatch.next_bucket(B), dispatch.next_bucket(max(T, 1))
    compute_stats.record_waste("encode_batch", "series", B, Bp)
    compute_stats.record_waste("encode_batch", "points", T, Tp)
    if Tp > T:
        last = times[:, -1:] if T else starts[:, None]
        times = np.concatenate(
            [times, np.broadcast_to(last, (B, Tp - T))], axis=1)
        vbits = np.concatenate(
            [vbits, np.zeros((B, Tp - T), vbits.dtype)], axis=1)
    if Bp > B:
        times = np.concatenate(
            [times, np.full((Bp - B, Tp), starts[0], times.dtype)])
        vbits = np.concatenate(
            [vbits, np.zeros((Bp - B, Tp), vbits.dtype)])
        starts = np.concatenate(
            [starts, np.full(Bp - B, starts[0], starts.dtype)])
        n_points = np.concatenate(
            [n_points, np.zeros(Bp - B, n_points.dtype)])
    return times, vbits, starts, n_points


def encode_blocks_ragged(times, vbits, offsets, starts,
                         unit: TimeUnit, int_optimized: bool,
                         waste_site: str = "encode_ragged") -> list[bytes]:
    """Encode a RAGGED (CSR) sealed window to per-series streams without
    one global [B, max_T] rectangle (ROADMAP #3, the ingest-side padding
    tax): rows bucket by geometric length (ops.ragged.length_buckets) and
    each bucket pads only to ITS max before the ordinary batched encode —
    a window where one series wrote 10k points and a million wrote one no
    longer materializes a million 10k-wide padded lanes.  Streams are
    byte-identical to encode_blocks over the fully-padded window (the
    encoder reads exactly n_points lanes per row), pinned by the seeded
    parity sweep in tests/test_paged_memory.py.  Zero-length rows return
    b"".

    ``waste_site`` names the padding-waste ledger row: the ingest seal
    keeps the default, while the binary wire codec (utils/wire) passes
    its own site so compute_stats tells re-encode rectangles on the
    serving path apart from sealed-window encode rectangles."""
    from m3_tpu.ops import ragged

    offsets = np.asarray(offsets, np.int64)
    starts = np.asarray(starts)
    lens = np.diff(offsets)
    out: list[bytes] = [b""] * len(lens)
    from m3_tpu.utils import compute_stats

    for rows in ragged.length_buckets(lens):
        if lens[rows[0]] == 0:
            continue
        sub_t, sub_v, sub_n = ragged.csr_to_padded(
            np.asarray(times), np.asarray(vbits), offsets, rows)
        # padding-waste ledger: real points vs this bucket's rectangle
        compute_stats.record_waste(waste_site, "samples",
                                   int(lens[rows].sum()), sub_t.size)
        streams = encode_blocks(sub_t, sub_v, starts[rows], sub_n,
                                unit, int_optimized)
        for r, s in zip(rows.tolist(), streams):
            out[r] = s
    return out


def decode_stream(stream: bytes, unit: TimeUnit,
                  int_optimized: bool) -> tuple[np.ndarray, np.ndarray]:
    """Decode one stream to (times int64, value_bits uint64) on the best
    host path: the native v2 codec for plain float-mode streams, the
    scalar decoder for int-optimized streams (the native codec is
    float-mode only, same contract as the device kernels) and for streams
    carrying time-unit/annotation markers, which the native decoder
    rejects rather than misparses (e.g. repair-written scalar-Encoder
    streams whose block start is not unit-aligned)."""
    from m3_tpu.encoding.m3tsz import native

    if not int_optimized and native.available():
        try:
            t, v, ns = native.decode_batch([stream], unit)
        except ValueError:
            pass  # marker-bearing stream: scalar path below handles it
        else:
            dispatch.counters["m3tsz_decode_native"] += 1
            n = int(ns[0])
            return t[0, :n].copy(), v[0, :n].copy()
    from m3_tpu.encoding.m3tsz import decode as scalar_decode

    dispatch.counters["m3tsz_decode_scalar"] += 1
    dps = scalar_decode(stream, int_optimized=int_optimized,
                        default_time_unit=unit)
    if not dps:
        return np.empty(0, np.int64), np.empty(0, np.uint64)
    t = np.array([d.timestamp_ns for d in dps], np.int64)
    v = np.array([np.float64(d.value) for d in dps], np.float64).view(np.uint64)
    return t, v


def _forced_batch_path() -> str:
    """Test/diagnostic override for the decode_streams_batch ladder:
    M3_TPU_DECODE_BATCH_PATH in {native, device, scalar} pins one rung
    (parity tests force each rung against the per-series path)."""
    import os

    return os.environ.get("M3_TPU_DECODE_BATCH_PATH", "")


# most bytes the padded output of ONE decoder launch may take (rows x
# points x 17 B: an int64 time, a uint64 value and a valid flag a point).
# A read's whole miss set is one launch up to here, and beyond it is cut
# into launches of the largest row bucket that fits: a 20,000-series read
# of hour-long streams is 7 launches of 107 MB, not one of 700 MB.
_LAUNCH_OUT_BYTES = 128 << 20


def _max_points(maxlen: int) -> int:
    """Point capacity for streams of at most `maxlen` bytes: a datapoint
    costs >= 2 bits, so the longest stream bounds the points."""
    return dispatch.next_pow2(maxlen * 4 + 16)


def _launch_rows(maxlen: int) -> int:
    """Most streams one launch takes when the longest has `maxlen`
    bytes: the largest half-octave row bucket (so padding adds nothing)
    whose output stays within _LAUNCH_OUT_BYTES, and at least one."""
    cap = max(1, _LAUNCH_OUT_BYTES // (_max_points(maxlen) * 17))
    p = 1 << (cap.bit_length() - 1)
    return p + p // 2 if p + p // 2 <= cap else p


def _decode_streams_device(streams: list[bytes], unit: TimeUnit,
                           int_optimized: bool):
    """One vmapped XLA decode over the whole launch. Streams whose rows come
    back flagged (annotation/time-unit markers the kernels don't decode)
    fall back to the scalar decoder individually. Every axis is padded
    to a shape bucket (half-octave rows, power-of-two words and points)
    so repeated launches share compiled kernels: each read has its own
    miss count, and an unbucketed row axis compiled the scan once per
    count."""
    import jax
    import numpy as _np

    from m3_tpu.encoding.m3tsz import tpu as m3tsz_tpu

    from m3_tpu.utils import compute_stats

    maxlen = max(len(s) for s in streams)
    n_rows = dispatch.next_bucket(len(streams))
    compute_stats.record_waste("decode_batch", "series", len(streams),
                               n_rows)
    words = m3tsz_tpu.bytes_to_words(
        streams + [b""] * (n_rows - len(streams)),
        dispatch.next_pow2((maxlen + 7) // 8))
    max_points = _max_points(maxlen)
    # padding-waste ledger: real stream words vs the pow2 word rectangle
    compute_stats.record_waste(
        "decode_batch", "words",
        sum((len(s) + 7) // 8 for s in streams), int(words.size))
    sig = f"B{words.shape[0]}xW{words.shape[1]}xP{max_points}" + \
        ("|int" if int_optimized else "")
    from m3_tpu.utils import trace

    if int_optimized:
        from m3_tpu.encoding.m3tsz import tpu_int

        jitted = tpu_int.decode_int
    else:
        jitted = m3tsz_tpu._decode_jit
    # one stage and one tracked block around the decoder call AND the
    # one transfer that waits for it: dispatch, H2D, queue, device time
    # and D2H of the four results together
    with trace.stage(trace.STAGE_DECODE_WAIT) as fr:
        with dispatch.jit_tracker("m3tsz_decode", jitted,
                                  sig=sig) as tracker:
            if int_optimized:
                dec = tpu_int.decode_int(words, unit, max_points=max_points)
            else:
                dec = m3tsz_tpu.decode(words, unit, max_points=max_points,
                                       n_live=len(streams))
            vals, times, err, counts = jax.device_get(
                (dec.values if int_optimized else dec.value_bits,
                 dec.times, dec.error, dec.n_points))
            vbits = _np.asarray(
                vals, _np.float64 if int_optimized else _np.uint64
            ).view(_np.uint64)
            times = _np.asarray(times, _np.int64)
        if tracker.miss:
            fr.name = trace.STAGE_DECODE_COMPILE
    dispatch.counters["m3tsz_decode_device_batch"] += 1
    out = []
    for b, stream in enumerate(streams):
        if err[b]:
            out.append(decode_stream(stream, unit, int_optimized))
            continue
        n = int(counts[b])
        out.append((times[b, :n].copy(), vbits[b, :n].copy()))
    return out


def decode_streams_batch(streams: list[bytes | None], unit: TimeUnit,
                         int_optimized: bool, groups: int = 1
                         ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Decode MANY streams in a single batched dispatch — the read-path
    dual of encode_blocks. The unit of a call is everything one read
    missed in the block cache, across its ``groups`` (shard, block,
    volume) groups (Shard.decode_misses); it becomes one launch, or
    ceil(streams / _launch_rows) where the padded output of one would
    pass _LAUNCH_OUT_BYTES. Returns [(times int64, value_bits uint64)]
    aligned to the input; empty/None streams decode to empty arrays.

    Ladder (same platform dispatch as the flush encode): the vmapped XLA
    kernels when an accelerator is live/forced (float AND int-optimized —
    the batch surface removes the int-opt scalar cliff), else the native
    v2 batch decoder (float-mode only), else a scalar loop. Streams the
    fast rungs reject (annotation/time-unit markers) degrade per stream,
    never the whole launch.
    """
    from m3_tpu.utils import querystats

    empty = (np.empty(0, np.int64), np.empty(0, np.uint64))
    out: list = [empty] * len(streams)
    todo = [i for i, s in enumerate(streams) if s]
    if not todo:
        return out
    subset = [streams[i] for i in todo]
    rows = _launch_rows(max(map(len, subset)))
    decoded: list = []
    for lo in range(0, len(subset), rows):
        decoded += _decode_launch(subset[lo : lo + rows], unit,
                                  int_optimized)
    querystats.record(blocks_read=groups)
    for i, r in zip(todo, decoded):
        out[i] = r
    return out


def _decode_launch(subset: list[bytes], unit: TimeUnit,
                   int_optimized: bool
                   ) -> list[tuple[np.ndarray, np.ndarray]]:
    """One rung of the ladder over non-empty streams, with its stage,
    its per-rung metrics and its line of the query record."""
    from m3_tpu.utils import querystats, trace

    empty = (np.empty(0, np.int64), np.empty(0, np.uint64))
    # one counter bump per LAUNCH: tests bound the batched dispatches a
    # read_many issues from above
    dispatch.counters["m3tsz_decode_batch_groups"] += 1
    forced = _forced_batch_path()
    decoded = None
    rung = "scalar"
    use_device = forced == "device" or (not forced and _device_decode())
    use_native = forced == "native" or (not forced and not use_device)
    with trace.stage(trace.STAGE_DECODE_HOST, streams=len(subset)) as fr:
        if use_device:
            decoded = _decode_streams_device(subset, unit, int_optimized)
            rung = "device"
        if decoded is None and use_native and not int_optimized:
            from m3_tpu.encoding.m3tsz import native

            if native.available():
                try:
                    t, v, ns = native.decode_batch(subset, unit)
                except ValueError:
                    # a marker-bearing stream poisons the whole native
                    # batch: degrade per stream (decode_stream isolates
                    # the bad ones)
                    decoded = [decode_stream(s, unit, int_optimized)
                               for s in subset]
                else:
                    dispatch.counters["m3tsz_decode_native_batch"] += 1
                    decoded = [(t[b, : int(ns[b])].copy(),
                                v[b, : int(ns[b])].copy())
                               for b in range(len(subset))]
                    rung = "native"
        if decoded is None:
            from m3_tpu.encoding.m3tsz import decode as scalar_decode

            dispatch.counters["m3tsz_decode_scalar_batch"] += 1
            decoded = []
            for s in subset:
                dps = scalar_decode(s, int_optimized=int_optimized,
                                    default_time_unit=unit)
                if not dps:
                    decoded.append(empty)
                    continue
                t = np.array([d.timestamp_ns for d in dps], np.int64)
                v = np.array([np.float64(d.value) for d in dps],
                             np.float64).view(np.uint64)
                decoded.append((t, v))
        n_bytes = sum(len(s) for s in subset)
        fr.tag(path=rung, bytes=n_bytes)
    # device-op profiling: which rung served this launch (visible on
    # /metrics per rung), how long it took (the stage's whole time), how
    # many bytes it chewed — the per-query record gets the same
    # attribution
    sc = _decode_scope(rung)
    sc.observe("seconds", fr.wall_s)
    sc.counter("streams", len(subset))
    sc.counter("bytes", n_bytes)
    # batch-size DISTRIBUTION per rung (count-shaped bounds): whether
    # batches are big enough to amortize a dispatch is the question
    # the per-rung counters alone can't answer
    from m3_tpu.utils.instrument import COUNT_BUCKETS

    sc.observe("batch_size", float(len(subset)), bounds=COUNT_BUCKETS)
    querystats.record(bytes_decoded=n_bytes, decode_rung=rung)
    return decoded


_decode_scopes: dict = {}


def _decode_scope(rung: str):
    """Cached per-rung metrics scope (decode.batch{path=rung})."""
    sc = _decode_scopes.get(rung)
    if sc is None:
        from m3_tpu.utils.instrument import default_registry

        sc = default_registry().root_scope("decode").subscope("batch",
                                                              path=rung)
        _decode_scopes[rung] = sc
    return sc
