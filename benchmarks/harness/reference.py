"""The plain reference: the query types' semantics over the generated
arrays, in numpy, importing nothing of the program.

A query type (``queries/<type>.json``) states its meaning beside its
PromQL text: ``fn`` over a left-open window ``(t - window_s, t]`` at each
step of its grid (Prometheus' range-vector selection), for the series of
``fields`` x the drawn hosts, then ``by`` (the label kept; the group's
mean). A step with no sample in the window is absent (NaN). ``dtype`` is
the precision of the arithmetic: float64 is the reference; float32 is the
lower-precision control (contract, "How correct is decided", step 2).
"""

from __future__ import annotations

import numpy as np

from . import tsbs

NS = tsbs.NS


def grid(spec: dict, t_first: int, t_last: int) -> tuple[int, int, int]:
    """(start, end, step) in ns over data whose samples span
    [t_first, t_last]. ``steps``: every step_s from t_first + step_s up to
    the first step at or past t_last; ``end``: one evaluation at t_last."""
    step = int(spec["step_s"]) * NS
    if spec["grid"] == "end":
        return t_last, t_last, step
    if spec["grid"] != "steps":
        raise ValueError(f"unknown grid {spec['grid']!r}")
    n = -(-(t_last - t_first) // step)
    return t_first + step, t_first + n * step, step


def series_of(spec: dict, fleet: tsbs.Fleet, hosts: list[int]) -> list[int]:
    """Series indices the type selects for the drawn hosts (all hosts
    where the type says so), in (host, field) order."""
    fields = [tsbs.CPU_FIELDS.index(f) for f in spec["fields"]]
    if spec["hosts"] == "all":
        hosts = list(range(fleet.hosts))
    n_f = len(tsbs.CPU_FIELDS)
    return [h * n_f + f for h in hosts for f in fields]


def promql(spec: dict, hosts: list[int]) -> str:
    """The PromQL text sent: the template with the drawn hosts filled in
    (every host, where the type or a prime request says "all")."""
    names = [f"host_{h}" for h in hosts]
    if spec["hosts"] == "all":
        return spec["promql"].replace("{HOSTS}", ".+")
    return spec["promql"].replace("{HOST}", names[0]) \
        .replace("{HOSTS}", "|".join(names))


def evaluate(spec: dict, fleet: tsbs.Fleet, values: np.ndarray,
             times_ns: np.ndarray, hosts: list[int],
             dtype=np.float64):
    """(labels [dict str->str], eval_ts int64[n_steps],
    float64 [n_out, n_steps] with NaN where absent)."""
    start, end, step = grid(spec, int(times_ns[0]), int(times_ns[-1]))
    eval_ts = np.arange(start, end + 1, step, dtype=np.int64)
    rows = series_of(spec, fleet, hosts)
    vals = values[rows].astype(dtype)
    window = int(spec["window_s"]) * NS
    out = np.full((len(rows), len(eval_ts)), np.nan, dtype)
    for i, t in enumerate(eval_ts.tolist()):
        cols = np.nonzero((times_ns > t - window) & (times_ns <= t))[0]
        if not len(cols):
            continue
        w = vals[:, cols]
        if spec["fn"] == "max_over_time":
            out[:, i] = w.max(axis=1)
        elif spec["fn"] == "avg_over_time":
            out[:, i] = w.sum(axis=1, dtype=dtype) / dtype(len(cols))
        else:
            raise ValueError(f"unknown fn {spec['fn']!r}")
    labels = []
    for s in rows:
        lb = {k.decode(): v.decode() for k, v in fleet.tags(s)}
        labels.append(lb)
    by = spec.get("by")
    if by is None:
        # a range function drops the metric name, so a type that selects
        # several fields of one host answers with label sets that repeat
        # (Prometheus refuses that; this program serves it): compare.py
        # matches such rows as a multiset
        return labels, eval_ts, out.astype(np.float64)
    # the range function has dropped the metric name, so `__name__` in
    # `by` names a label no series carries and groups nothing apart
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
    return g_labels, eval_ts, np.array(g_rows, np.float64)


def least_bytes(spec: dict, fleet_hosts: int, loaded_points: int) -> int:
    """The least bytes the type's plan has to move, whatever implements
    it: every (timestamp, value) pair in reach of a window, 16 B each,
    in; one float64 per result series and step, out."""
    n_hosts = fleet_hosts if spec["hosts"] == "all" else int(spec["hosts"])
    series_in = n_hosts * len(spec["fields"])
    interval_s = tsbs.INTERVAL_NS // NS
    span_s = (loaded_points - 1) * interval_s
    if spec["grid"] == "end":
        steps = 1
        points = min(loaded_points, int(spec["window_s"]) // interval_s)
    else:
        steps = -(-span_s // int(spec["step_s"]))
        points = loaded_points     # the windows tile the loaded span
    by = spec.get("by")
    if by is None:
        out_series = series_in
    else:
        out_series = n_hosts if "hostname" in by else 1
    return series_in * points * 16 + out_series * steps * 8
