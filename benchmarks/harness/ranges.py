"""TSBS's own time ranges over a loaded span of several blocks: where a
request's window may start, the grid it asks for, the plain reference at
that grid and the least bytes of its plan. Imports nothing of the program.

A query type with ``range_s`` (TSBS ``uses/devops``: 12 h for
``double-groupby-*`` and ``single-groupby-*-*-12``, 8 h for
``cpu-max-all-*``) asks for ``[start, start + range_s]`` in steps of
``step_s``: evaluations at ``start + step_s`` ... ``start + range_s``.
A window's start lies on a whole minute after the loaded span's first
sample, ``offset`` minutes on, and the window lies inside the span, so
a type has ``n_starts`` of them. The reference follows
``harness/reference.py``: ``fn`` over the left-open window ``(t -
window_s, t]``, NaN where it holds no sample, then the group's mean
under ``by``; it is written here against an explicit grid.
"""

from __future__ import annotations

import numpy as np

from . import reference, tsbs

NS = tsbs.NS
MINUTE_NS = 60 * NS


def span_ns(points: int) -> int:
    """First loaded sample to last."""
    return (points - 1) * tsbs.INTERVAL_NS


def n_starts(spec: dict, points: int) -> int:
    """Whole minutes after the first sample at which the type's window
    can start and still end at or before the last."""
    room = span_ns(points) - int(spec["range_s"]) * NS
    if room < 0:
        raise ValueError(f"{spec['name']}: a range of {spec['range_s']} s "
                         f"does not fit {points} loaded points")
    return room // MINUTE_NS + 1


def whole_span_s(spec: dict, points: int) -> int:
    """The range, in whole steps, that covers every loaded sample from
    the first on: what a request over the whole history asks for."""
    step = int(spec["step_s"]) * NS
    return -(-span_ns(points) // step) * step // NS


def grid(spec: dict, t_first: int, offset: int,
         range_s: int | None = None) -> tuple[int, int, int]:
    """(start, end, step) in ns of a window `offset` minutes after
    `t_first`, the loaded span's first sample."""
    step = int(spec["step_s"]) * NS
    start = t_first + offset * MINUTE_NS
    span = int(spec["range_s"] if range_s is None else range_s) * NS
    if span % step:
        raise ValueError(f"{spec['name']}: range is not whole steps")
    return start + step, start + span, step


def eval_ts(spec: dict, t_first: int, offset: int,
            range_s: int | None = None) -> np.ndarray:
    start, end, step = grid(spec, t_first, offset, range_s)
    return np.arange(start, end + 1, step, dtype=np.int64)


def evaluate(spec: dict, fleet: tsbs.Fleet, values: np.ndarray,
             times_ns: np.ndarray, hosts: list[int], at: np.ndarray,
             dtype=np.float64):
    """(labels [dict str->str], float64 [n_out, len(at)] with NaN where
    absent) at the evaluation times `at`; `dtype` is the arithmetic's
    precision, as in harness/reference.py."""
    rows = reference.series_of(spec, fleet, hosts)
    vals = values[rows].astype(dtype)
    window = int(spec["window_s"]) * NS
    # window i holds the columns [lo[i], hi[i]): times in (t - window, t]
    lo = np.searchsorted(times_ns, at - window, side="right")
    hi = np.searchsorted(times_ns, at, side="right")
    # reduceat over the pairs (lo, hi) of a padded copy, so that hi may
    # equal the number of columns; every other result is a window's
    padded = np.concatenate([vals, np.zeros((len(rows), 1), dtype)], axis=1)
    bounds = np.empty(2 * len(at), np.int64)
    bounds[0::2], bounds[1::2] = lo, hi
    if spec["fn"] == "max_over_time":
        out = np.maximum.reduceat(padded, bounds, axis=1)[:, 0::2]
    elif spec["fn"] == "avg_over_time":
        out = np.add.reduceat(padded, bounds, axis=1)[:, 0::2] \
            / np.maximum(hi - lo, 1).astype(dtype)
    else:
        raise ValueError(f"unknown fn {spec['fn']!r}")
    out = out.astype(dtype)
    out[:, hi <= lo] = np.nan
    labels = [{k.decode(): v.decode() for k, v in fleet.tags(s)}
              for s in rows]
    by = spec.get("by")
    if by is None:
        return labels, out.astype(np.float64)
    groups: dict[tuple, list[int]] = {}
    for i, lb in enumerate(labels):
        groups.setdefault(tuple((k, lb[k]) for k in by if k in lb),
                          []).append(i)
    g_labels, g_rows = [], []
    for key, members in groups.items():
        m = out[members]
        present = ~np.isnan(m)
        n = present.sum(axis=0)
        total = np.where(present, m, dtype(0)).sum(axis=0, dtype=dtype)
        with np.errstate(invalid="ignore", divide="ignore"):
            g_rows.append(np.where(n > 0, total / n.astype(dtype), np.nan))
        g_labels.append(dict(key))
    return g_labels, np.array(g_rows, np.float64)


def least_bytes(spec: dict, fleet_hosts: int) -> int:
    """The least bytes a plan over one window has to move, whatever
    implements it: every (timestamp, value) pair of the selected series
    inside the range, 16 B each, in; one float64 per result series and
    step, out."""
    n_hosts = fleet_hosts if spec["hosts"] == "all" else int(spec["hosts"])
    series_in = n_hosts * len(spec["fields"])
    points = int(spec["range_s"]) * NS // tsbs.INTERVAL_NS
    steps = int(spec["range_s"]) // int(spec["step_s"])
    by = spec.get("by")
    out_series = series_in if by is None else (
        n_hosts if "hostname" in by else 1)
    return series_in * points * 16 + out_series * steps * 8
