#!/usr/bin/env python3
"""From a profiler trace to numbers: the device's busy union and idle
share, device time per operation and per program, roofline arithmetic.

Two halves. ``extract`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (through ``jax.profiler.ProfileData``, so it imports jax and runs as
a process of its own, after the service has ended, pinned to the CPU) and
keeps the device planes' events as plain lists. Everything else works on
those lists and imports nothing, so the tests run it on a recorded trace.

    python trace_reduce.py <trace_dir> <out.json>

The extracted form:
``{"devices": [{"plane": "/device:TPU:0", "lines": {"XLA Modules":
[[name, start_ns, duration_ns], ...]}, "op_seconds": {name: seconds}}],
"interval_ns": [a, b] | null}``.
The per-operation line is kept as seconds per name only: a scan decoder's
while loop alone leaves a million events there in a third of a second.

The launcher marks the traced interval inside the trace (a program named
``bench_mark`` after the profiler's start and before its stop). Where
both marks are found, every event is clipped to the time between them,
``interval_ns``, on the trace's own clock: what ran while the profiler
started or stopped is left out, and no second clock comes in.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"          # one event per device operation
MODULES_LINE = "XLA Modules"  # one event per launched program
SANITY_PCT = 105.0
OP_NAME_CHARS = 80            # an operation's name is its whole HLO text
MARK = r"^jit_bench_mark\b"   # harness/serve.py's mark, as the trace names it


def extract(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(paths[-1])
    planes = [p for p in data.planes if p.name.startswith("/device:")]
    interval = mark_interval([
        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
        for p in planes for line in p.lines if line.name == MODULES_LINE
        for ev in line.events])
    devices = []
    for plane in planes:
        lines, op_seconds = {}, {}
        for line in plane.lines:
            events = clip(((ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events), interval)
            if line.name == OPS_LINE:
                for name, _, dur in events:     # a million of them: summed
                    key = name[:OP_NAME_CHARS]  # as they come, never held
                    op_seconds[key] = op_seconds.get(key, 0.0) + dur / 1e9
            else:
                lines.setdefault(line.name, []).extend(events)
        lines = {k: v for k, v in lines.items() if v}
        if lines or op_seconds:
            devices.append({"plane": plane.name, "lines": lines,
                            "op_seconds": op_seconds})
    return {"devices": devices, "interval_ns": interval,
            "source": os.path.basename(paths[-1])}


# -- the marked interval (no imports of jax from here on) ------------------


def mark_interval(module_events: list) -> list | None:
    """[end of the first mark, start of the last] over the programs'
    events of all devices; None where the trace holds fewer than two."""
    rx = re.compile(MARK)
    marks = sorted((start, start + dur) for name, start, dur in module_events
                   if rx.search(name))
    if len(marks) < 2 or marks[-1][0] <= marks[0][1]:
        return None
    return [marks[0][1], marks[-1][0]]


def clip(events, interval: list | None):
    """The events' parts inside the interval, as they come; all of them
    where there is none."""
    for name, start, dur in events:
        if interval is None:
            yield [name, start, dur]
            continue
        lo, hi = max(start, interval[0]), min(start + dur, interval[1])
        if hi > lo:
            yield [name, lo, hi - lo]


def interval_s(trace: dict) -> float | None:
    iv = trace.get("interval_ns")
    return None if not iv else (iv[1] - iv[0]) / 1e9


# -- reductions over the extracted form ------------------------------------


def union_ns(events: list) -> int:
    """Length of the union of the events' intervals."""
    total, cur_a, cur_b = 0, None, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_b is None or start > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = start, end
        elif end > cur_b:
            cur_b = end
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def _busy_events(device: dict) -> list:
    """A program's event spans its operations', so the programs' union is
    the device's busy time; a trace without that line: every event."""
    lines = device["lines"]
    if MODULES_LINE in lines:
        return lines[MODULES_LINE]
    return [ev for evs in lines.values() for ev in evs]


def busy_s(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over the devices that
    show any; None where the trace holds no device events."""
    per_device = [union_ns(_busy_events(d)) / 1e9 for d in trace["devices"]]
    per_device = [b for b in per_device if b > 0]
    if not per_device:
        return None
    return sum(per_device) / len(per_device)


def idle_pct(trace: dict, window_s: float) -> float | None:
    busy = busy_s(trace)
    if busy is None or window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / window_s)


def time_by_name(trace: dict, line: str) -> dict[str, float]:
    """Device seconds per event name on `line`, summed over devices."""
    out: dict[str, float] = {}
    for d in trace["devices"]:
        for name, _, dur in d["lines"].get(line, []):
            out[name] = out.get(name, 0.0) + dur / 1e9
        if line == OPS_LINE:
            for name, secs in d.get("op_seconds", {}).items():
                out[name] = out.get(name, 0.0) + secs
    return out


def program_s(trace: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, launches) of the programs whose name matches."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for d in trace["devices"]:
        for name, _, dur in d["lines"].get(MODULES_LINE, []):
            if rx.search(name):
                total += dur / 1e9
                n += 1
    return total, n


def roofline_pct(least_bytes: float, device_s: float,
                 peak_bytes_per_s: float) -> float | None:
    """The least time the chip could take for `least_bytes` over the time
    it took. None where nothing ran. A share over SANITY_PCT means the
    bytes are counted too high or the time leaves out part of the work:
    that is an error, never clipped."""
    if device_s <= 0 or least_bytes <= 0:
        return None
    pct = 100.0 * (least_bytes / peak_bytes_per_s) / device_s
    if pct > SANITY_PCT:
        raise ValueError(
            f"roofline share {pct:.1f}% is over {SANITY_PCT}%: "
            f"{least_bytes} B in {device_s} s")
    return pct


def _top(by: dict[str, float], n: int) -> list:
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]]: the programs that took most device time (the
    trace's own names, launch ids stripped), then the single operations
    (their HLO text, cut short)."""
    programs: dict[str, float] = {}
    for name, secs in time_by_name(trace, MODULES_LINE).items():
        key = "program " + re.sub(r"\(\d+\)$", "", name)
        programs[key] = programs.get(key, 0.0) + secs
    ops: dict[str, float] = {}
    for name, secs in time_by_name(trace, OPS_LINE).items():
        key = "op " + name[:OP_NAME_CHARS]
        ops[key] = ops.get(key, 0.0) + secs
    half = n // 2
    top = _top(programs, half)
    return top + _top(ops, n - len(top))


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[name, seconds]]: idle time of the first device, grouped by the
    program that ended the gap ('before <program>'). What the host was
    doing in a gap cannot be attributed yet (PERF.md, Open questions)."""
    if not trace["devices"]:
        return []
    lines = trace["devices"][0]["lines"]
    events = sorted(lines.get(MODULES_LINE) or _busy_events(
        trace["devices"][0]), key=lambda e: e[1])
    gaps: dict[str, float] = {}
    cur_end = None
    for name, start, dur in events:
        if cur_end is not None and start > cur_end:
            key = "before " + re.sub(r"\(\d+\)$", "", name)
            gaps[key] = gaps.get(key, 0.0) + (start - cur_end) / 1e9
        cur_end = max(cur_end or 0, start + dur)
    return [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:n]]


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace = extract(argv[0])
    with open(argv[1], "w") as f:
        json.dump(trace, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
