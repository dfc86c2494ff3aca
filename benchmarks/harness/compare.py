"""The comparisons that decide ``correct``. Each returns numbers; run.py
prints every number beside its limit and sets ``correct`` from them.

``matrix_gap`` is chip_smoke.py's ``compare_matrix`` (PR 21) made to
return what it found: label sets, step timestamps and NaN masks must be
equal (a difference there makes the answer ``wrong``); values are
compared by relative error, whose maximum is the number held to the
cell's tolerance.
"""

from __future__ import annotations

import json

import numpy as np

from . import tsbs

NS = tsbs.NS


def matrix_gap(served: bytes | dict, ref_labels: list[dict],
               eval_ts: np.ndarray, ref_values: np.ndarray):
    """(wrong: str | None, max_rel_err: float, n_values: int) for one
    served Prometheus matrix against the reference's rows."""
    try:
        doc = json.loads(served) if isinstance(served, bytes) else served
    except ValueError as e:
        return f"not JSON: {e}", 0.0, 0
    if doc.get("status") != "success":
        return f"served {str(doc)[:200]}", 0.0, 0
    got: dict[tuple, list] = {}
    for row in doc["data"]["result"]:
        got.setdefault(tuple(sorted(row["metric"].items())), []).append(
            row["values"])
    want: dict[tuple, list] = {}
    for lb, vals in zip(ref_labels, ref_values):
        keep = ~np.isnan(vals)
        if keep.any():
            want.setdefault(tuple(sorted(lb.items())), []).append(
                (eval_ts[keep].astype(np.float64) / NS, vals[keep]))
    if not want:
        return "reference answer is empty", 0.0, 0
    missing = set(want) - set(got)
    if missing:
        return (f"series {sorted(missing)[0]} missing from answer, which "
                f"has {len(got)}, e.g. {sorted(got)[:1]}"), 0.0, 0
    extra = set(got) - set(want)
    if extra:
        return (f"{len(extra)} unexpected series, e.g. {sorted(extra)[0]}",
                0.0, 0)
    worst, n_values = 0.0, 0
    for key, rows in want.items():
        served_rows = got[key]
        if len(served_rows) != len(rows):
            return (f"{key}: {len(served_rows)} rows served, "
                    f"{len(rows)} wanted"), 0.0, 0
        parsed = [(np.array([r[0] for r in vs], np.float64),
                   np.array([float(r[1]) for r in vs], np.float64))
                  for vs in served_rows]
        if len(rows) > 1:
            # label sets that repeat: pair the rows in value order
            parsed.sort(key=lambda tv: tv[1].tolist())
            rows = sorted(rows, key=lambda tv: tv[1].tolist())
        for (t_got, v_got), (t_want, v_want) in zip(parsed, rows):
            if len(t_got) != len(t_want) or not np.array_equal(t_got, t_want):
                return (f"{key}: steps differ (NaN mask): got {len(t_got)}, "
                        f"want {len(t_want)}"), 0.0, 0
            err = np.abs(v_got - v_want)
            scale = np.abs(v_want)
            if (err[scale == 0] != 0).any():
                return f"{key}: nonzero where the reference is 0", 0.0, 0
            nz = scale != 0
            if nz.any():
                worst = max(worst, float((err[nz] / scale[nz]).max()))
            n_values += len(v_want)
    return None, worst, n_values


def volume_streams_gap(root: str, fleet, picks: list[int], expect,
                       n_shards: int, namespace: str):
    """A sample of the streams in the newest fileset volume of every
    shard under `root` (flushed blocks or snapshots), decoded by the
    scalar Python decoder. `expect(s, block_start)` gives (times_ns,
    value_bits) the stream has to hold, or None to skip the volume.
    Returns (n_streams, n_points, n_bytes, n_wrong, first_fault, volumes).
    The reader and the scalar decoder are the program's own (PERF.md, Open
    questions): the device encoder is what they check, not themselves."""
    from m3_tpu.encoding.m3tsz.decoder import decode
    from m3_tpu.storage.fileset import FilesetReader, list_filesets
    from m3_tpu.utils.ident import tags_to_id
    from m3_tpu.utils.xtime import TimeUnit

    readers = []
    for shard in range(n_shards):
        for bs, vol in list_filesets(root, namespace, shard):
            readers.append((bs, FilesetReader(root, namespace, shard, bs,
                                              vol)))
    n_streams = n_points = n_bytes = n_wrong = 0
    fault = None
    try:
        for s in picks:
            sid = tags_to_id(fleet.metric_name(s), fleet.tags(s))
            found = False
            for bs, r in readers:
                want = expect(s, bs)
                if want is None:
                    continue
                stream = r.read(sid)
                if not stream:
                    continue
                found = True
                dps = decode(stream, int_optimized=False,
                             default_time_unit=TimeUnit.SECOND)
                t = np.array([d.timestamp_ns for d in dps], np.int64)
                v = np.array([d.value for d in dps], np.float64)
                n_streams += 1
                n_points += len(t)
                n_bytes += len(stream)
                if not (np.array_equal(t, want[0]) and np.array_equal(
                        v.view(np.uint64), want[1])):
                    n_wrong += 1
                    fault = fault or (
                        f"series {s} block {bs}: stream decodes to "
                        f"{len(t)} points, want {len(want[0])}")
            if not found:
                n_wrong += 1
                fault = fault or f"series {s} in no volume under {root}"
    finally:
        for _, r in readers:
            r.close()
    return n_streams, n_points, n_bytes, n_wrong, fault, len(readers)
