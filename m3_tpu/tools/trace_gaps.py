#!/usr/bin/env python3
"""What the host was doing while the device sat idle.

    python -m m3_tpu.tools.trace_gaps <trace_dir> [--top N]

Reads the ``.xplane.pb`` a device-trace session wrote (``POST
/debug/profile/device``, utils/backend.py) through
``jax.profiler.ProfileData``. The device plane's ``XLA Modules`` line is
the programs' line; the host plane holds, per thread, the stage clock's
spans as annotations (utils/trace.py ``stage()``) and JAX's own
``PjitFunction(<fn>)`` launch events, all on the trace's clock. The k-th
launch of ``fn`` is the k-th program ``jit_<fn>`` (one device runs its
queue in order), which names the thread that launched each program. An
idle gap on the programs' line is charged to the innermost stage open on
the thread that launched the program which ended it, piece by piece.
A trace without a device plane (the CPU backend) has no programs' line:
the launches themselves stand in, so the tool can be rehearsed. The
program keeps the same split always on, over the time in which no
launch is outstanding (``compute_launch_gap_seconds{stage}``,
utils/dispatch.py ``LaunchLedger``): a subset of the idle time read here.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import sys

from m3_tpu.utils import trace

MODULES_LINE = "XLA Modules"
NO_STAGE = trace.NO_STAGE
NO_LAUNCH = "(launch not in the trace)"


def load(trace_dir: str):
    """(programs, threads): [(name, start_ns, end_ns)] of the programs'
    line, and {thread line: [(name, start_ns, end_ns)]} of the host."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    from jax.profiler import ProfileData

    programs, threads = [], {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # the operations' line (a million events a second) and the
            # runtime's own pools (`tf_*`) hold nothing read here
            skip = (line.name != MODULES_LINE or programs) if device \
                else line.name.startswith("tf_")
            if skip:
                continue
            events = sorted(
                ((ev.name, int(ev.start_ns),
                  int(ev.start_ns + ev.duration_ns)) for ev in line.events),
                key=lambda e: (e[1], -e[2]))
            if device:
                programs = events
            elif events:
                threads[f"{line.name}#{i}"] = events
    return programs, threads


def _launches(threads: dict) -> dict:
    """{fn: [(start_ns, end_ns, thread)]} of the outermost
    ``PjitFunction(fn)`` events, in time order."""
    out: dict = {}
    for key, events in threads.items():
        last_end = -1
        for name, start, end in events:
            m = re.fullmatch(r"PjitFunction\((.*)\)", name)
            if m and start >= last_end:     # the inner twin is skipped
                out.setdefault(m.group(1), []).append((start, end, key))
                last_end = end
    for launches in out.values():
        launches.sort()
    return out


def _stages_in(events: list, stage_names: set, a: int, b: int) -> dict:
    """Seconds of [a, b) per innermost stage open on one thread: the
    stage clock's own rule (``trace.stages_in``), which the launch-gap
    ledger applies in process."""
    return trace.stages_in([ev for ev in events if ev[0] in stage_names],
                           a, b)


def reduce(trace_dir: str, top: int = 10) -> dict:
    stage_names = {v for k, v in vars(trace).items()
                   if k.startswith("STAGE_")} | {trace.PIPELINE_CONSUME}
    programs, threads = load(trace_dir)
    launches = _launches(threads)
    on_device = bool(programs)
    if not on_device:
        programs = sorted((f"jit_{fn}", s, e) for fn, ls in launches.items()
                          for s, e, _ in ls)
    taken = {fn: 0 for fn in launches}
    by_stage: dict = {}
    by_program: dict = {}
    gaps = []
    prev_end = None
    for name, start, end in programs:
        m = re.match(r"jit_(.+?)(?:\(|$)", name)
        fn = m.group(1) if m else name
        ls, i = launches.get(fn, []), taken.get(fn, 0)
        thread = None
        if i < len(ls) and ls[i][0] <= start:
            thread, taken[fn] = ls[i][2], i + 1
        if prev_end is not None and start > prev_end:
            split = _stages_in(threads[thread], stage_names, prev_end,
                               start) if thread else \
                {NO_LAUNCH: (start - prev_end) / 1e9}
            ended = by_program.setdefault(fn, {})
            for k, v in split.items():
                by_stage[k] = by_stage.get(k, 0.0) + v
                ended[k] = ended.get(k, 0.0) + v
            gaps.append({"seconds": (start - prev_end) / 1e9,
                         "ended_by": fn, "stages": split})
        prev_end = max(prev_end or end, end)
    idle = sum(by_stage.values())
    named = sum(v for k, v in by_stage.items() if k in stage_names)
    gaps.sort(key=lambda g: -g["seconds"])
    return {"on_device": on_device, "programs": len(programs),
            "idle_s": idle, "named_share": named / idle if idle else None,
            "by_stage": dict(sorted(by_stage.items(), key=lambda kv: -kv[1])),
            "by_program": by_program, "longest": gaps[:top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=10)
    opts = ap.parse_args(argv)
    doc = reduce(opts.trace_dir, opts.top)
    if not doc["on_device"]:
        print("no device plane in this trace: launches on the host stand "
              "in for the programs' line")
    print(f"{doc['programs']} programs, {doc['idle_s']:.6f} s idle between "
          "them" + ("" if doc["named_share"] is None else
                    f", {100 * doc['named_share']:.1f}% in named stages"))
    print(f"{'stage':<28}{'gap s':>12}")
    for name, secs in doc["by_stage"].items():
        print(f"{name:<28}{secs:>12.6f}")
    for fn, split in sorted(doc["by_program"].items(),
                            key=lambda kv: -sum(kv[1].values())):
        parts = ", ".join(f"{k} {v:.6f}" for k, v in sorted(
            split.items(), key=lambda kv: -kv[1])[:4])
        print(f"gaps ended by {fn}: {sum(split.values()):.6f} s ({parts})")
    print(f"longest {len(doc['longest'])} gaps:")
    for g in doc["longest"]:
        parts = ", ".join(f"{k} {v * 1e3:.3f}" for k, v in sorted(
            g["stages"].items(), key=lambda kv: -kv[1]))
        print(f"  {g['seconds'] * 1e3:9.3f} ms before {g['ended_by']}: {parts}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
