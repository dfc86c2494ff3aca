"""Readers of the per-layer metrics. A ``layer_metrics/<name>.json`` names
one of these and its arguments; run.py calls it with the run's
``Reading``. A reader that finds nothing to read returns None and the
metric is left out of the line: never 0 for a share.

What each takes:

- ``prom_counter_delta(keys)``: regexes over the parsed ``/metrics`` keys;
  the sum of their increase over the window.
- ``prom_hist_mean(family, scale)``: increase of ``<family>_sum`` over
  increase of ``<family>_count`` over the window, times ``scale``.
- ``prom_ratio(num, den, scale)``: two lists of key regexes; increase of
  the first over increase of the second, times ``scale``.
- ``fact_ratio(num, den)``: two facts the traffic recorded (counts or
  bytes), one over the other. ``files_bytes`` is how a fact is taken.
- ``trace_idle()``: 100 x (1 - device busy union / traced window).
- ``trace_roofline(program, bytes_fact, peak)``: least bytes (a fact the
  traffic reckons for the traced interval) over the peak, divided by the
  device time of the programs whose name matches ``program``.
"""

from __future__ import annotations

import dataclasses
import os
import re

from . import trace_reduce


@dataclasses.dataclass
class Reading:
    before: dict            # parsed /metrics at window open
    after: dict             # ... at window close
    window_s: float
    facts: dict             # what the traffic recorded (counts, bytes)
    peaks: dict             # this device's row of peaks.json
    trace: dict | None = None      # trace_reduce's extracted form
    trace_window_s: float = 0.0


def _delta(r: Reading, patterns: list[str]) -> float | None:
    rxs = [re.compile(p) for p in patterns]
    keys = [k for k in r.after if any(rx.fullmatch(k) for rx in rxs)]
    if not keys:
        return None
    return sum(r.after[k] - r.before.get(k, 0.0) for k in keys)


def prom_counter_delta(r: Reading, keys: list[str],
                       zero_if_absent: bool = False) -> float | None:
    d = _delta(r, keys)
    if d is None and zero_if_absent:
        return 0.0      # a counter nothing has incremented is not exposed
    return d


def prom_hist_mean(r: Reading, family: str,
                   scale: float = 1.0) -> float | None:
    n = _delta(r, [re.escape(family) + "_count"])
    total = _delta(r, [re.escape(family) + "_sum"])
    if not n or total is None:
        return None
    return scale * total / n


def prom_ratio(r: Reading, num: list[str], den: list[str],
               scale: float = 100.0) -> float | None:
    d = _delta(r, den)
    if not d:
        return None
    return scale * (_delta(r, num) or 0.0) / d


def fact_ratio(r: Reading, num: str, den: str) -> float | None:
    if not r.facts.get(den) or r.facts.get(num) is None:
        return None
    return r.facts[num] / r.facts[den]


def trace_idle(r: Reading) -> float | None:
    if r.trace is None:
        return None
    return trace_reduce.idle_pct(r.trace, r.trace_window_s)


def trace_roofline(r: Reading, program: str, bytes_fact: str,
                   peak: str = "hbm_bytes_per_s") -> float | None:
    if r.trace is None or not r.facts.get(bytes_fact) or peak not in r.peaks:
        return None
    device_s, _ = trace_reduce.program_s(r.trace, program)
    return trace_reduce.roofline_pct(r.facts[bytes_fact], device_s,
                                     r.peaks[peak])


def files_bytes(root: str, suffix: str) -> int:
    """Bytes of the files under `root` whose name ends in `suffix`."""
    total = 0
    for d, _, names in os.walk(root):
        for n in names:
            if n.endswith(suffix):
                total += os.path.getsize(os.path.join(d, n))
    return total


READERS = {f.__name__: f for f in (
    prom_counter_delta, prom_hist_mean, prom_ratio, fact_ratio,
    trace_idle, trace_roofline)}
