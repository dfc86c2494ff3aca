"""The write side of ``correct``: what a deployment acked is what it
reads back. The reference is the generator itself: the samples the
traffic sent (series, timestamp in ms, float64 bits) and whether each
request was acked. ``read_hosts`` asks the service for them through
``/api/v1/prom/remote/read``; ``acked_gap`` counts what differs;
``altered`` makes the control's record, which has to differ.
``on_disk`` reads what a restart would find once the service has
stopped (the commitlog files replayed, the newest snapshot volumes
under the scalar decoder) and ``durable_gap`` holds the same record
against it: an ack is a promise about the disk, not about the buffers.
They return numbers; the traffic kind prints each beside its limit.

Imports of the program: its prompb reader/writer and snappy codec, as
``tsbs.py`` does, and for ``on_disk`` its commitlog and fileset readers
and the scalar decoder, as ``compare.volume_streams_gap`` does.
"""

from __future__ import annotations

import os

import numpy as np

from . import tsbs


def read_hosts(client, hosts: list[int], start_ms: int,
               end_ms: int) -> dict[tuple[bytes, bytes], dict[int, int]]:
    """{(metric name, hostname): {timestamp ms: float64 bits}} of every
    series of `hosts` over [start_ms, end_ms], as remote-read serves it."""
    from m3_tpu.utils import protowire, snappy

    names = "|".join(f"host_{h}" for h in hosts).encode()
    body = snappy.compress(protowire.encode_read_request([(
        start_ms, end_ms,
        [protowire.PromMatcher(2, b"hostname", names)])]))
    raw = snappy.decompress(
        client.request("POST", "/api/v1/prom/remote/read", body))
    out: dict[tuple[bytes, bytes], dict[int, int]] = {}
    for result in protowire.decode_read_response(raw):
        for ts in result:
            labels = dict(ts.labels)
            got = out.setdefault(
                (labels.get(b"__name__", b""), labels.get(b"hostname", b"")),
                {})
            bits = np.array([v for _t, v in ts.samples],
                            np.float64).view(np.uint64).tolist()
            got.update(zip((int(t) for t, _v in ts.samples), bits))
    return out


def sent_samples(fleet: tsbs.Fleet, hosts: list[int], requests,
                 bits) -> dict[tuple[bytes, bytes], dict[int, tuple]]:
    """{(metric name, hostname): {timestamp ms: (float64 bits, acked)}}
    for the series of `hosts`, from the traffic's own record of what it
    sent: `requests` is [(round, host0, host1, timestamp ms, acked)],
    `bits` the uint64 view of the live values [series, round]. A sample
    sent twice (a request repeated on a fresh connection) is acked if
    either was."""
    n_f = len(tsbs.CPU_FIELDS)
    want = set(hosts)
    out: dict[tuple[bytes, bytes], dict[int, tuple]] = {}
    for rnd, h0, h1, t_ms, acked in requests:
        for h in range(h0, h1):
            if h not in want:
                continue
            for s in range(h * n_f, (h + 1) * n_f):
                row = out.setdefault(
                    (fleet.metric_name(s), f"host_{h}".encode()), {})
                was = row.get(t_ms)
                row[t_ms] = (int(bits[s, rnd]),
                             bool(acked or (was and was[1])))
    return out


def acked_gap(returned: dict, sent: dict):
    """(acked samples compared, missing, wrong, unasked for, first fault):
    an acked sample has to come back with its bits; nothing may come
    back that was not sent (a sent sample whose request failed may or
    may not: a partial write is a refusal, not a lie)."""
    n = missing = wrong = unasked = 0
    fault = None
    for key, row in sent.items():
        got = returned.get(key, {})
        for t_ms, (bits, acked) in row.items():
            if not acked:
                continue
            n += 1
            if t_ms not in got:
                missing += 1
                fault = fault or f"{key} at {t_ms} ms: acked, not read back"
            elif got[t_ms] != bits:
                wrong += 1
                fault = fault or (f"{key} at {t_ms} ms: read back "
                                  f"{got[t_ms]:#x}, sent {bits:#x}")
    for key, got in returned.items():
        row = sent.get(key, {})
        for t_ms in got:
            if t_ms not in row:
                unasked += 1
                fault = fault or f"{key} at {t_ms} ms: never sent"
    return n, missing, wrong, unasked, fault


def altered(sent: dict, every: int) -> dict:
    """`sent` with the bits of every `every`-th acked sample changed (its
    lowest bit turned): the ack record of a harness that misremembers,
    for the control. The read-back has to find each."""
    out, k = {}, 0
    for key in sorted(sent):
        row = out[key] = {}
        for t_ms in sorted(sent[key]):
            bits, acked = sent[key][t_ms]
            if acked:
                k += 1
                if k % every == 0:
                    bits ^= 1
            row[t_ms] = (bits, acked)
    return out


def on_disk(data_dir: str, namespace: str, n_shards: int, fleet: tsbs.Fleet,
            hosts: list[int]):
    """What a restart would find of every series of `hosts`, read after
    the service has stopped: (logged, snapshotted, entries replayed),
    the first two {(metric name, hostname): {timestamp ms: {float64
    bits}}}. `logged`: every commitlog file that is left, replayed (a
    retired one goes once a snapshot covers it). `snapshotted`: the
    newest complete snapshot volume of every (shard, block), its streams
    under the scalar decoder."""
    from m3_tpu.encoding.m3tsz.decoder import decode
    from m3_tpu.storage import commitlog
    from m3_tpu.storage.fileset import FilesetReader, list_filesets
    from m3_tpu.utils.ident import tags_to_id
    from m3_tpu.utils.xtime import TimeUnit

    n_f = len(tsbs.CPU_FIELDS)
    keys = {tags_to_id(fleet.metric_name(s), fleet.tags(s)):
            (fleet.metric_name(s), f"host_{h}".encode())
            for h in hosts for s in range(h * n_f, (h + 1) * n_f)}
    logged: dict = {}
    n_entries = 0
    for path in commitlog.log_files(
            os.path.join(data_dir, "commitlog", namespace)):
        entries, _report = commitlog.replay_salvage(path)
        n_entries += len(entries)
        for e in entries:
            key = keys.get(e.series_id)
            if key is not None:
                logged.setdefault(key, {}).setdefault(
                    e.time_ns // 1_000_000, set()).add(e.value_bits)
    snapshotted: dict = {}
    root = os.path.join(data_dir, "snapshots")
    for shard in range(n_shards):
        for bs, vol in list_filesets(root, namespace, shard):
            reader = FilesetReader(root, namespace, shard, bs, vol)
            try:
                for sid, key in keys.items():
                    stream = reader.read(sid)
                    if not stream:
                        continue
                    row = snapshotted.setdefault(key, {})
                    for d in decode(stream, int_optimized=False,
                                    default_time_unit=TimeUnit.SECOND):
                        row.setdefault(d.timestamp_ns // 1_000_000,
                                       set()).add(int(np.float64(
                                           d.value).view(np.uint64)))
            finally:
                reader.close()
    return logged, snapshotted, n_entries


def durable_gap(logged: dict, snapshotted: dict, sent: dict):
    """(acked samples compared, missing, wrong, snapshot streams, wrong
    snapshot streams, first fault): an acked sample has to be on the
    disk, in a commitlog or a snapshot, with its bits and no others; a
    snapshot stream may hold nothing that was not sent (it may be
    short: the logs hold what came after it)."""
    n = missing = wrong = bad_streams = 0
    fault = None
    for key, row in sent.items():
        log, snap = logged.get(key, {}), snapshotted.get(key, {})
        for t_ms, (bits, acked) in row.items():
            if not acked:
                continue
            n += 1
            found = log.get(t_ms, set()) | snap.get(t_ms, set())
            if not found:
                missing += 1
                fault = fault or (f"{key} at {t_ms} ms: acked, in no "
                                  "commitlog and no snapshot")
            elif found != {bits}:
                wrong += 1
                fault = fault or (f"{key} at {t_ms} ms: on disk "
                                  f"{sorted(found)}, sent {bits:#x}")
    for key, snap in snapshotted.items():
        row = sent.get(key, {})
        if any(t_ms not in row or found != {row[t_ms][0]}
               for t_ms, found in snap.items()):
            bad_streams += 1
            fault = fault or f"{key}: its snapshot holds what was not sent"
    return n, missing, wrong, len(snapshotted), bad_streams, fault
