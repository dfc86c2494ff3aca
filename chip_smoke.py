#!/usr/bin/env python3
"""chip_smoke.py: the served path, once, on the chip.

Starts the single-node deployment the documented way
(``python -m m3_tpu.services.coordinator -f <cfg>``: coordinator with
embedded storage, one process, the only one that touches JAX) and drives
it over HTTP like a user would:

  1. Prometheus remote-write of the TSBS DevOps ``cpu-only`` shape
     (10 CPU gauges per host, 10 s interval, the ten host tags) at
     10,000 hosts = 100,000 series;
  2. tick-driven flush of the sealed block through the device encoder to
     fileset volumes;
  3. a read-back of flushed blocks (Prometheus remote-read; the blocks
     are not in the block cache yet, so the device decodes them);
  4. a set of ``query_range`` calls that run as compiled device programs:
     one narrow, one wide grouped reduce over every series of a metric,
     one extrapolated ``rate``, one sparse-table ``max_over_time`` and
     one matcher heavy enough for the device postings program.

Every answer is checked: acked samples are read back bit-for-bit,
device-written streams decode to the written points under the scalar
Python decoder, query answers equal the float64 numpy interpreter run in
this (JAX-free) parent, and the counters on /metrics must show the
device rungs served what was driven. Any failed phase exits non-zero.

The parent never imports JAX; the service is its one child. The smoke
fails when the service reports a platform other than ``tpu``, and then
prints no result. The line before the last of stdout is the summary (one
JSON object: versions, sizes, ``reduced``, the report of every phase,
``"claim": null``); the last line is the result, one JSON object with
exactly these keys, the device as JAX reported it to the service:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python3 chip_smoke.py [--seed N] [--hosts N] [--points N]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

NS = 1_000_000_000
BLOCK_NS = 2 * 3600 * NS          # the namespace's 2 h block
INTERVAL_NS = 10 * NS             # TSBS cpu-only: one reading per 10 s
FULL_BLOCK_POINTS = BLOCK_NS // INTERVAL_NS  # 720
BUFFER_PAST_NS = 10 * 60 * NS
N_SHARDS = 8
DEFAULT_HOSTS = 10_000            # x10 gauges = BASELINE.json config #1's 100k series
DEFAULT_POINTS = 120
MIN_POINTS = 40                   # the query grid starts 5 min into the block

# -- TSBS DevOps cpu-only (github.com/timescale/tsbs, use case
# `cpu-only`). No network here: the field and tag names are the suite's;
# `assumed` lists what this file sets from memory of its simulator.
CPU_FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
              "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
              "usage_guest", "usage_guest_nice")
REGIONS = {
    "us-east-1": 5, "us-west-1": 2, "us-west-2": 3, "eu-west-1": 3,
    "eu-central-1": 2, "ap-southeast-1": 2, "ap-southeast-2": 2,
    "ap-northeast-1": 2, "sa-east-1": 3,
}  # region -> number of datacenters (suffix a, b, c, ...)
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVIRONMENTS = ("production", "staging", "test")
ASSUMED = [
    "tag value pools (regions/datacenters, os, arch, team, 20 services, "
    "2 versions, 3 environments, racks 0-99) follow TSBS's host simulator "
    "as remembered; drawn uniformly from --seed",
    "each gauge is TSBS's clamped random walk (start uniform in [0,100), "
    "N(0,1) steps, clamped to [0,100]) truncated to an integer as its "
    "serializer does, sent as a float64 sample",
    "Prometheus metric name = 'cpu_' + field, one series per (field, host)",
]


class Workload:
    """The generated deployment: labels, sample times, values. Everything
    comes from (seed, hosts, points, block_start)."""

    def __init__(self, seed: int, hosts: int, points: int, block_start: int):
        self.seed, self.hosts, self.points = seed, hosts, points
        self.block_start = block_start
        rng = np.random.default_rng(seed)
        regions = list(REGIONS)
        self.host_tags: list[list[tuple[bytes, bytes]]] = []
        r_idx = rng.integers(0, len(regions), hosts)
        dc_u = rng.random(hosts)
        rack = rng.integers(0, 100, hosts)
        os_i = rng.integers(0, len(OSES), hosts)
        arch_i = rng.integers(0, len(ARCHES), hosts)
        team_i = rng.integers(0, len(TEAMS), hosts)
        svc = rng.integers(0, 20, hosts)
        ver = rng.integers(0, 2, hosts)
        env_i = rng.integers(0, len(ENVIRONMENTS), hosts)
        for h in range(hosts):
            region = regions[r_idx[h]]
            dc = region + "abcde"[int(dc_u[h] * REGIONS[region])]
            tags = [
                ("hostname", f"host_{h}"), ("region", region),
                ("datacenter", dc), ("rack", str(rack[h])),
                ("os", OSES[os_i[h]]), ("arch", ARCHES[arch_i[h]]),
                ("team", TEAMS[team_i[h]]), ("service", str(svc[h])),
                ("service_version", str(ver[h])),
                ("service_environment", ENVIRONMENTS[env_i[h]]),
            ]
            self.host_tags.append([(k.encode(), v.encode()) for k, v in tags])
        self.n_series = hosts * len(CPU_FIELDS)
        # at the default width the heavy matcher passes the index's work
        # threshold and the postings program must run; a debug size skips
        # that one check
        self.full_width = hosts >= DEFAULT_HOSTS
        # series s = host * 10 + field
        self.times_ns = block_start + np.arange(points, dtype=np.int64) \
            * INTERVAL_NS
        x = rng.uniform(0.0, 100.0, self.n_series)
        vals = np.empty((self.n_series, points), np.float64)
        for p in range(points):
            vals[:, p] = np.floor(x)
            x = np.clip(x + rng.standard_normal(self.n_series), 0.0, 100.0)
        self.values = vals

    def metric_name(self, s: int) -> bytes:
        return b"cpu_" + CPU_FIELDS[s % len(CPU_FIELDS)].encode()

    def tags(self, s: int) -> list[tuple[bytes, bytes]]:
        return self.host_tags[s // len(CPU_FIELDS)]

    def labels(self, s: int) -> dict[bytes, bytes]:
        return {b"__name__": self.metric_name(s), **dict(self.tags(s))}

    # -- Prometheus remote-write bodies --------------------------------

    def _label_bytes(self, s: int) -> bytes:
        from m3_tpu.utils.protowire import field_bytes

        out = bytearray()
        for k, v in sorted(self.labels(s).items()):
            out += field_bytes(1, field_bytes(1, k) + field_bytes(2, v))
        return bytes(out)

    def write_requests(self, hosts_per_request: int, points_per_request: int):
        """Yield (body, n_samples): snappy'd prompb.WriteRequest bodies,
        time-major (every series' first chunk of points, then the next),
        the order a fleet of scrapers produces. Sample submessages are
        laid out with numpy: value = field 1 (double), timestamp = field 2
        (int64 ms varint, 6 bytes until the year 2109)."""
        from m3_tpu.utils import snappy
        from m3_tpu.utils.protowire import _uvarint

        n_f = len(CPU_FIELDS)
        labels = [self._label_bytes(s) for s in range(self.n_series)]
        ts_ms = self.times_ns // 1_000_000
        assert (ts_ms >= 1 << 35).all() and (ts_ms < 1 << 42).all()
        for p0 in range(0, self.points, points_per_request):
            p1 = min(p0 + points_per_request, self.points)
            n_p = p1 - p0
            tpl = np.zeros((n_p, 18), np.uint8)
            tpl[:, 0], tpl[:, 1], tpl[:, 2], tpl[:, 11] = 0x12, 16, 0x09, 0x10
            t = ts_ms[p0:p1].astype(np.uint64)
            for b in range(6):
                byte = (t >> np.uint64(7 * b)) & np.uint64(0x7F)
                tpl[:, 12 + b] = byte | (0x80 if b < 5 else 0)
            for h0 in range(0, self.hosts, hosts_per_request):
                s0 = h0 * n_f
                s1 = min(h0 + hosts_per_request, self.hosts) * n_f
                block = np.broadcast_to(tpl, (s1 - s0, n_p, 18)).copy()
                block[:, :, 3:11] = self.values[s0:s1, p0:p1].astype(
                    "<f8").view(np.uint8).reshape(s1 - s0, n_p, 8)
                parts = []
                for i, s in enumerate(range(s0, s1)):
                    body = labels[s] + block[i].tobytes()
                    parts.append(b"\x0a" + _uvarint(len(body)) + body)
                yield snappy.compress(b"".join(parts)), (s1 - s0) * n_p


# ---------------------------------------------------------------------------
# references, independent of the code under test
# ---------------------------------------------------------------------------


def reference_engine(wl: Workload):
    """The float64 numpy interpreter (query/engine.py) over the generated
    arrays: selection by a plain regex walk over the generated labels, no
    index, no storage, no codec, no JAX (the device and native rungs are
    pinned off for this process by ``pin_reference_rungs``)."""
    from m3_tpu.index.query import MatchType
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.windows import RaggedSeries

    labels = [wl.labels(s) for s in range(wl.n_series)]

    class ReferenceEngine(Engine):
        def _fetch(self, sel, eval_ts, range_ns):
            shifted = self._resolve_ts(sel, eval_ts)
            t_min = int(shifted[0]) - max(range_ns, self.lookback_ns)
            t_max = int(shifted[-1]) + 1
            keep = np.ones(wl.n_series, bool)
            for m in sel.matchers:
                rx = re.compile(m.value) if m.match_type in (
                    MatchType.REGEXP, MatchType.NOT_REGEXP) else None
                for s in np.nonzero(keep)[0].tolist():
                    v = labels[s].get(m.name, b"")
                    hit = (rx.fullmatch(v) is not None) if rx is not None \
                        else v == m.value
                    if m.match_type in (MatchType.NOT_EQUAL,
                                        MatchType.NOT_REGEXP):
                        hit = not hit
                    keep[s] = hit
            rows = np.nonzero(keep)[0]
            cols = np.nonzero((wl.times_ns >= t_min)
                              & (wl.times_ns < t_max))[0]
            times = np.tile(wl.times_ns[cols], len(rows))
            values = wl.values[np.ix_(rows, cols)].reshape(-1)
            offsets = np.arange(len(rows) + 1, dtype=np.int64) * len(cols)
            return ([labels[s] for s in rows.tolist()],
                    RaggedSeries(times, values, offsets))

    return ReferenceEngine(None, "default", resolve_tiers=False)


def pin_reference_rungs() -> None:
    """This process computes references only: numpy, never a device or
    the native C++ kernels. (The child's environment is copied before.)"""
    os.environ["M3_TPU_DEVICE_OPS"] = "0"
    os.environ["M3_TPU_NATIVE_OPS"] = "0"
    os.environ["M3_TPU_QUERY_COMPILE"] = "0"


# query answers against the float64 interpreter. The chip has no f64
# unit: XLA's TPU x64 rewriter carries a float64 as a pair of float32
# (about 49 mantissa bits, float32 exponent range; measured on the v5e,
# see PERF.md). Sums and averages of these gauges came back within 6e-14
# there and get 1e-12. `rate` converts int64 nanosecond timestamps
# (1.7e18) to that float64 before it subtracts them, which leaves each
# about 1 us off; over a 5 min window that is 1e-8 of the duration (seen:
# 1.04e-8), so plans that read sample times get 1e-7. Step timestamps,
# label sets and NaN masks must be equal.
RTOL_VALUES = 1e-12
RTOL_SAMPLE_TIMES = 1e-7


def queries(wl: Workload) -> list[dict]:
    narrow = "|".join(f"host_{h % wl.hosts}"
                      for h in (11, 222, 3333, 4444, 5555))
    return [
        {"name": "narrow", "rtol": RTOL_VALUES,
         "q": f'cpu_usage_user{{hostname=~"{narrow}"}}'},
        {"name": "wide_grouped", "rtol": RTOL_VALUES,
         "q": "avg by (region) (avg_over_time(cpu_usage_idle[5m]))"},
        {"name": "rate", "rtol": RTOL_SAMPLE_TIMES,
         "q": "sum by (region) (rate(cpu_usage_iowait[5m]))"},
        {"name": "minmax", "rtol": RTOL_VALUES,
         "q": 'max_over_time(cpu_usage_steal{hostname=~"host_1.*"}[5m])'},
        {"name": "heavy_matcher", "rtol": RTOL_VALUES, "postings": True,
         "q": 'count by (os) (cpu_usage_guest{hostname=~"host_.*"})'},
    ]


def query_grid(wl: Workload) -> tuple[int, int, int]:
    step = 60 * NS
    start = wl.block_start + 5 * 60 * NS
    last = int(wl.times_ns[-1])
    end = last - (last - start) % step
    return start, end, step


def compare_matrix(served: dict, ref, eval_ts: np.ndarray, rtol: float,
                   what: str) -> dict:
    """Served Prometheus matrix JSON against a reference Vector."""
    if served.get("status") != "success":
        raise SmokeFailure(f"{what}: served {served}")
    got = {}
    for row in served["data"]["result"]:
        key = tuple(sorted(row["metric"].items()))
        if key in got:
            raise SmokeFailure(f"{what}: duplicate series {key}")
        got[key] = row["values"]
    n_values, worst = 0, 0.0
    want_keys = set()
    for lb, vals in zip(ref.labels, ref.values):
        keep = ~np.isnan(vals)
        if not keep.any():
            continue
        key = tuple(sorted((k.decode(), v.decode()) for k, v in lb.items()))
        want_keys.add(key)
        rows = got.get(key)
        if rows is None:
            raise SmokeFailure(f"{what}: series {key} missing from answer")
        t_got = np.array([r[0] for r in rows], np.float64)
        v_got = np.array([float(r[1]) for r in rows], np.float64)
        t_want = eval_ts[keep].astype(np.float64) / NS
        if len(t_got) != len(t_want) or not np.array_equal(t_got, t_want):
            raise SmokeFailure(
                f"{what}: {key}: steps differ (NaN mask): got "
                f"{len(t_got)}, want {len(t_want)}")
        v_want = vals[keep]
        err = np.abs(v_got - v_want)
        bad = err > rtol * np.abs(v_want)
        if bad.any():
            i = int(np.argmax(bad))
            raise SmokeFailure(
                f"{what}: {key} step {t_want[i]}: got {v_got[i]!r}, "
                f"want {v_want[i]!r} (rtol {rtol})")
        nz = v_want != 0
        if nz.any():
            worst = max(worst, float((err[nz] / np.abs(v_want[nz])).max()))
        n_values += len(v_want)
    extra = set(got) - want_keys
    if extra:
        raise SmokeFailure(f"{what}: {len(extra)} unexpected series, "
                           f"e.g. {sorted(extra)[0]}")
    if not n_values:
        raise SmokeFailure(f"{what}: reference answer is empty")
    return {"series": len(want_keys), "values": n_values,
            "max_rel_err": worst}


# ---------------------------------------------------------------------------
# HTTP client side
# ---------------------------------------------------------------------------


class SmokeFailure(Exception):
    pass


class Client:
    def __init__(self, base: str):
        self.base = base

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = 600.0) -> bytes:
        req = urllib.request.Request(self.base + path, data=body,
                                     method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path.split('?')[0]} -> HTTP {e.code}: "
                f"{e.read()[:400]!r}") from None

    def get_json(self, path: str) -> dict:
        return json.loads(self.request("GET", path))

    def counters(self) -> dict[str, float]:
        """/metrics as {'name{labels}': value}; the dispatch counters come
        back under their own keys: 'op' or 'op[path]'."""
        out: dict[str, float] = {}
        for line in self.request("GET", "/metrics").decode().splitlines():
            if not line or line.startswith("#"):
                continue
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                continue
            m = re.fullmatch(
                r'm3_dispatch_ops_total\{op="([^"]*)"(?:,path="([^"]*)")?\}',
                key)
            if m:
                op, path = m.groups()
                out[f"{op}[{path}]" if path else op] = float(val)
        return out

    def query_range(self, q: str, start: int, end: int, step: int) -> dict:
        qs = urllib.parse.urlencode({
            "query": q, "start": repr(start / NS), "end": repr(end / NS),
            "step": f"{step // NS}s"})
        return self.get_json("/api/v1/query_range?" + qs)

    def remote_read(self, matchers, start_ms: int, end_ms: int):
        """[(labels dict, ts_ms int64[n], value bits uint64[n])]."""
        from m3_tpu.utils import protowire, snappy

        body = snappy.compress(protowire.encode_read_request(
            [(start_ms, end_ms, matchers)]))
        raw = snappy.decompress(
            self.request("POST", "/api/v1/prom/remote/read", body))
        out = []
        for fno, _, result in protowire.iter_fields(raw):
            if fno != 1:
                continue
            for f2, _, ts in protowire.iter_fields(result):
                if f2 == 1:
                    out.append(_parse_timeseries(ts))
        return out


def _parse_timeseries(ts: bytes):
    from m3_tpu.utils import protowire

    labels, samples = {}, []
    for fno, _, val in protowire.iter_fields(ts):
        if fno == 1:
            kv = {f: v for f, _, v in protowire.iter_fields(val)}
            labels[kv.get(1, b"")] = kv.get(2, b"")
        elif fno == 2:
            samples.append(val)
    if samples and all(len(s) == 16 for s in samples):
        # the common layout (double, 6-byte varint): parse in bulk
        a = np.frombuffer(b"".join(samples), np.uint8).reshape(-1, 16)
        if (a[:, 0] == 0x09).all() and (a[:, 9] == 0x10).all() \
                and (a[:, 15] < 0x80).all():
            bits = a[:, 1:9].copy().view("<u8").reshape(-1)
            t = np.zeros(len(a), np.int64)
            for b in range(6):
                t |= (a[:, 10 + b].astype(np.int64) & 0x7F) << (7 * b)
            return labels, t, bits.astype(np.uint64)
    one = protowire.decode_write_request(protowire.field_bytes(1, ts))[0]
    t = np.array([s[0] for s in one.samples], np.int64)
    v = np.array([s[1] for s in one.samples], np.float64)
    return labels, t, v.view(np.uint64)


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------


def say(*a) -> None:
    print(*a, flush=True)


def phase_ingest(client: Client, wl: Workload, hosts_per_request: int,
                 points_per_request: int) -> dict:
    t0 = time.perf_counter()
    acked = sent = n_req = 0
    for body, n in wl.write_requests(hosts_per_request, points_per_request):
        resp = json.loads(client.request(
            "POST", "/api/v1/prom/remote/write", body))
        if resp.get("status") != "success" or resp.get("samples") != n:
            raise SmokeFailure(f"remote-write not fully acked: {resp}")
        acked += resp["samples"]
        sent += n
        n_req += 1
    dt = time.perf_counter() - t0
    if acked != wl.n_series * wl.points:
        raise SmokeFailure(f"acked {acked} of {wl.n_series * wl.points}")
    return {"requests": n_req, "samples_acked": acked,
            "wall_s": round(dt, 2), "samples_per_s": round(acked / dt)}


def phase_readback(client: Client, wl: Workload, hosts_per_read: int) -> dict:
    """Every acked sample, read back through Prometheus remote-read and
    compared bit-for-bit with what was sent."""
    from m3_tpu.utils.protowire import PromMatcher

    index = {}
    for s in range(wl.n_series):
        index[(wl.metric_name(s), wl.tags(s)[0][1])] = s
    want_bits = wl.values.view(np.uint64)
    want_ms = wl.times_ns // 1_000_000
    seen = np.zeros(wl.n_series, bool)
    t0 = time.perf_counter()
    for h0 in range(0, wl.hosts, hosts_per_read):
        hosts = "|".join(f"host_{h}" for h in range(
            h0, min(h0 + hosts_per_read, wl.hosts)))
        got = client.remote_read(
            [PromMatcher(2, b"hostname", hosts.encode())],
            int(want_ms[0]), int(want_ms[-1]))
        for labels, t_ms, bits in got:
            s = index.get((labels.get(b"__name__"), labels.get(b"hostname")))
            if s is None or labels != wl.labels(s):
                raise SmokeFailure(f"read-back: unknown series {labels}")
            if seen[s]:
                raise SmokeFailure(f"read-back: series {s} returned twice")
            seen[s] = True
            if not (np.array_equal(t_ms, want_ms)
                    and np.array_equal(bits, want_bits[s])):
                raise SmokeFailure(
                    f"read-back: series {labels} differs from what was "
                    f"acked ({len(t_ms)} of {wl.points} samples returned)")
    if not seen.all():
        raise SmokeFailure(
            f"read-back: {int((~seen).sum())} of {wl.n_series} series "
            "not returned")
    dt = time.perf_counter() - t0
    return {"series": wl.n_series, "samples": wl.n_series * wl.points,
            "bit_exact": True, "wall_s": round(dt, 2)}


def phase_filesets(data_dir: str, wl: Workload, sample: int) -> dict:
    """The fileset volumes the flush wrote, read here from disk; a seeded
    sample of their streams decoded by the scalar Python decoder."""
    from m3_tpu.encoding.m3tsz.decoder import decode
    from m3_tpu.storage.fileset import FilesetReader, list_filesets
    from m3_tpu.utils.ident import tags_to_id
    from m3_tpu.utils.xtime import TimeUnit

    root = os.path.join(data_dir, "data")
    readers = []
    for shard in range(N_SHARDS):
        for bs, vol in list_filesets(root, "default", shard):
            if bs != wl.block_start:
                raise SmokeFailure(f"unexpected fileset block {bs}")
            readers.append(FilesetReader(root, "default", shard, bs, vol))
    if not readers:
        raise SmokeFailure(f"no fileset volume under {root}")
    n_streams = sum(r.n_series for r in readers)
    n_bytes = 0
    if n_streams != wl.n_series:
        raise SmokeFailure(
            f"fileset volumes hold {n_streams} series, wrote {wl.n_series}")
    rng = np.random.default_rng(wl.seed + 1)
    picks = rng.choice(wl.n_series, min(sample, wl.n_series), replace=False)
    t0 = time.perf_counter()
    for s in picks.tolist():
        sid = tags_to_id(wl.metric_name(s), wl.tags(s))
        stream = next((st for st in (r.read(sid) for r in readers) if st),
                      None)
        if stream is None:
            raise SmokeFailure(f"series {sid!r} in no fileset volume")
        n_bytes += len(stream)
        dps = decode(stream, int_optimized=False,
                     default_time_unit=TimeUnit.SECOND)
        t = np.array([d.timestamp_ns for d in dps], np.int64)
        v = np.array([d.value for d in dps], np.float64)
        if not (np.array_equal(t, wl.times_ns) and np.array_equal(
                v.view(np.uint64), wl.values[s].view(np.uint64))):
            raise SmokeFailure(
                f"scalar decoder: device-written stream of {sid!r} does "
                "not decode to the written points")
    for r in readers:
        r.close()
    return {"volumes": len(readers), "series": n_streams,
            "scalar_decoded_series": len(picks),
            "scalar_decoded_bytes_per_point":
                round(n_bytes / (len(picks) * wl.points), 3),
            "wall_s": round(time.perf_counter() - t0, 2)}


def wait_for_flush(client: Client, log_path: str, timeout_s: float) -> dict:
    """Poll until the tick loop has flushed every shard's window."""
    t0 = time.perf_counter()
    flushed = 0.0
    while time.perf_counter() - t0 < timeout_s:
        check_log(log_path)
        flushed = client.counters().get("coordinator_blocks_flushed", 0.0)
        if flushed >= N_SHARDS:
            return {"blocks_flushed": int(flushed),
                    "wall_s": round(time.perf_counter() - t0, 2)}
        time.sleep(1.0)
    raise SmokeFailure(
        f"blocks_flushed={int(flushed)} after {timeout_s:.0f}s, "
        f"want {N_SHARDS}")


def _jit_misses(c: dict) -> dict[str, int]:
    return {k: int(v) for k, v in c.items()
            if k.startswith("jit_") and k.endswith("[miss]")}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def phase_queries(client: Client, wl: Workload, ref, label: str,
                  previous: dict | None) -> dict:
    start, end, step = query_grid(wl)
    eval_ts = np.arange(start, end + 1, step, dtype=np.int64)
    out = {"queries": {}}
    before_all = client.counters()
    t_all = time.perf_counter()
    for spec in queries(wl):
        before = client.counters()
        t0 = time.perf_counter()
        served = client.query_range(spec["q"], start, end, step)
        wall = time.perf_counter() - t0
        d = _delta(client.counters(), before)
        if d.get("query.compile[compiled]", 0) < 1 \
                or d.get("query.compile[fallback]", 0):
            raise SmokeFailure(
                f"{label} {spec['name']}: not served as a compiled plan: "
                f"{ {k: v for k, v in d.items() if 'compile' in k} }")
        if spec.get("postings") and wl.full_width and (
                d.get("index.postings[device]", 0) < 1
                or d.get("index.postings[host]", 0)):
            raise SmokeFailure(
                f"{label} {spec['name']}: the postings program did not "
                f"serve the heavy matcher: "
                f"{ {k: v for k, v in d.items() if 'postings' in k} }")
        vec, _ = ref.query_range(spec["q"], start, end, step)
        res = compare_matrix(served, vec, eval_ts, spec["rtol"],
                             f"{label} {spec['name']}")
        if previous is not None and \
                served["data"] != previous[spec["name"]]:
            raise SmokeFailure(
                f"{label} {spec['name']}: answer changed between passes")
        res["wall_s"] = round(wall, 3)
        # values only (no timestamps): equal across runs of one seed and
        # size, whichever block they were loaded into and on however
        # many chips
        res["answer_sha256"] = hashlib.sha256(json.dumps(sorted(
            (sorted(r["metric"].items()), [v for _, v in r["values"]])
            for r in served["data"]["result"])).encode()).hexdigest()[:16]
        res["served"] = served["data"]
        out["queries"][spec["name"]] = res
    out["wall_s"] = round(time.perf_counter() - t_all, 3)
    out["jit_misses"] = _jit_misses(_delta(client.counters(), before_all))
    return out


DEVICE_RUNGS = ("m3tsz_encode_device", "m3tsz_decode_device_batch",
                "query.compile[compiled]")
POSTINGS_RUNG = "index.postings[device]"
HOST_RUNGS = ("m3tsz_encode_native", "m3tsz_decode_native_batch",
              "m3tsz_decode_native", "m3tsz_decode_scalar",
              "m3tsz_decode_scalar_batch", "query.compile[fallback]")


def check_rungs(c: dict, full_width: bool) -> dict:
    """The counters on /metrics must show the device rungs served what was
    driven, and no host rung did. `index.postings[host]` is checked per
    query (phase_queries): a narrow matcher stays under the index's
    documented work threshold by design and takes the sorted-array walk."""
    seen = {k: int(c.get(k, 0)) for k in DEVICE_RUNGS + HOST_RUNGS
            + (POSTINGS_RUNG, "index.postings[host]")}
    hosted = [k for k in HOST_RUNGS if seen[k]]
    if hosted:
        raise SmokeFailure(f"a host rung served the smoke: "
                           f"{ {k: seen[k] for k in hosted} }")
    need = DEVICE_RUNGS + ((POSTINGS_RUNG,) if full_width else ())
    idle = [k for k in need if not seen[k]]
    if idle:
        raise SmokeFailure(f"device rung(s) never ran: {idle}")
    return seen


def check_log(log_path: str) -> None:
    with open(log_path, errors="replace") as f:
        for line in f:
            if "tick error" in line or "Traceback" in line:
                raise SmokeFailure(f"service log: {line.strip()[:600]}")


def check_mesh(client: Client, n_devices: int, c: dict,
               full_width: bool) -> dict:
    """More than one chip: the sharded plane must have armed by itself.
    Prepared slabs and (at full width, where the postings program runs)
    the postings column sit on every device, and the compiled plans were
    dispatched on the n-device mesh."""
    caches = client.get_json("/debug/compute")["device_caches"]
    names = ("hot_tier",) + (("postings_columns",) if full_width else ())
    placed = {name: caches.get(name, {}).get("devices", 0)
              for name in names}
    key = f'compute_mesh_dispatch{{devices="{n_devices}"}}'
    dispatched = int(c.get(key, 0))
    if any(v != n_devices for v in placed.values()) or not dispatched:
        raise SmokeFailure(
            f"mesh of {n_devices} not serving: cache placement {placed}, "
            f"{key}={dispatched}")
    return {"devices": n_devices, "cache_placement": placed,
            "mesh_dispatches": dispatched}


def run_phases(client: Client, wl: Workload, data_dir: str, log_path: str,
               device_count: int, *, hosts_per_request: int = 200,
               points_per_request: int = 60, hosts_per_read: int = 100,
               scalar_sample: int = 2000,
               flush_timeout_s: float = 400.0) -> dict:
    """Everything after the service answers; shared with the CPU test
    (which shrinks the request sizes with the workload)."""
    report: dict = {}
    ref = reference_engine(wl)
    # an operator's backfill: hold flush and snapshot (runtime options,
    # the documented valve) while the block loads, so the tick loop seals
    # it once, whole, instead of re-merging a volume at every tick
    client.request("POST", "/api/v1/runtime", json.dumps(
        {"flush_enabled": False, "snapshot_enabled": False}).encode())
    report["ingest"] = phase_ingest(client, wl, hosts_per_request,
                                    points_per_request)
    say("ingest", report["ingest"])
    client.request("POST", "/api/v1/runtime", json.dumps(
        {"flush_enabled": True, "snapshot_enabled": True}).encode())
    report["flush"] = wait_for_flush(client, log_path, flush_timeout_s)
    say("flush", report["flush"])
    report["filesets"] = phase_filesets(data_dir, wl, scalar_sample)
    say("filesets", report["filesets"])
    before = client.counters()
    report["readback"] = phase_readback(client, wl, hosts_per_read)
    d = _delta(client.counters(), before)
    report["readback"]["decode_groups_on_device"] = int(
        d.get("m3tsz_decode_device_batch", 0))
    say("readback", report["readback"])
    cold = phase_queries(client, wl, ref, "cold", None)
    served = {k: v.pop("served") for k, v in cold["queries"].items()}
    say("queries cold", cold)
    warm = phase_queries(client, wl, ref, "warm", served)
    for v in warm["queries"].values():
        v.pop("served")
    say("queries warm", warm)
    if any(warm["jit_misses"].values()):
        raise SmokeFailure(f"warm pass compiled: {warm['jit_misses']}")
    report["queries_cold"], report["queries_warm"] = cold, warm
    c = client.counters()
    report["rungs"] = check_rungs(c, wl.full_width)
    report["compile_cache"] = {
        k: int(c.get(f"compile_cache[{k}]", 0)) for k in ("hit", "miss")}
    report["jit_misses_total"] = _jit_misses(c)
    if device_count > 1:
        report["mesh"] = check_mesh(client, device_count, c, wl.full_width)
    check_log(log_path)
    return report


# ---------------------------------------------------------------------------
# the service child
# ---------------------------------------------------------------------------

CONFIG = """\
# chip_smoke.py: coordinator with embedded storage, one process, one chip
db:
  path: {data}
  n_shards: {n_shards}
  namespace: default
  options:
    retention:
      period: 48h
      block_size: 2h
      buffer_past: 10m
cluster:
  kv_path: {kv}
http:
  host: 127.0.0.1
  port: 0
carbon:
  enabled: false
tick_interval_s: 2
query:
  compile: true
"""


class Service:
    """The one child: `python -m m3_tpu.services.coordinator -f <cfg>`,
    data, KV, config and log under `work`."""

    def __init__(self, work: str, env: dict, checkout: str):
        self.data_dir = os.path.join(work, "m3data")
        self.log_path = os.path.join(work, "coordinator.log")
        cfg_path = os.path.join(work, "coordinator.yml")
        with open(cfg_path, "w") as f:
            f.write(CONFIG.format(data=self.data_dir, n_shards=N_SHARDS,
                                  kv=os.path.join(work, "kv.json")))
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "m3_tpu.services.coordinator", "-f",
                 cfg_path], cwd=checkout, env=env, stdout=log, stderr=log,
                start_new_session=True)

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def wait_listening(self, timeout_s: float) -> tuple[int, dict]:
        """(port, backend info) from the service's own start-up lines."""
        t0 = time.perf_counter()
        backend = None
        while time.perf_counter() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"service exited with {self.proc.returncode} before "
                    f"listening:\n{self.log_tail()}")
            with open(self.log_path, errors="replace") as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if not isinstance(rec, dict):
                        continue
                    if rec.get("msg") == "backend initialised":
                        backend = rec
                    if rec.get("msg") == "http listening":
                        if backend is None:
                            raise SmokeFailure("service listened before "
                                               "initialising a backend")
                        return int(rec["port"]), backend
            time.sleep(0.25)
        raise SmokeFailure(f"service not listening after {timeout_s:.0f}s")

    def stop(self) -> None:
        """Ctrl-C first (the service shuts down and closes its storage),
        then harder."""
        for sig, wait_s in ((signal.SIGINT, 30), (signal.SIGTERM, 10),
                            (signal.SIGKILL, 30)):
            if self.proc.poll() is not None:
                return
            os.killpg(self.proc.pid, sig)
            try:
                self.proc.wait(wait_s)
            except subprocess.TimeoutExpired:
                continue


def block_start_for(now_ns: int) -> int:
    """The newest 2 h block that is already past buffer_past: sealed by
    time, so the first tick with flush enabled writes it out."""
    end = now_ns - BUFFER_PAST_NS - 60 * NS
    return end - end % BLOCK_NS - BLOCK_NS


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly `ok` and `device`, the device
    exactly `platform`, `kind`, `count`. Everything else the run found is
    in the summary line before it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=DEFAULT_HOSTS)
    ap.add_argument("--points", type=int, default=DEFAULT_POINTS)
    ap.add_argument("--keep", action="store_true",
                    help="keep the data directory and the service log")
    opts = ap.parse_args(argv)
    if not MIN_POINTS <= opts.points <= FULL_BLOCK_POINTS:
        ap.error(f"--points must be in [{MIN_POINTS}, {FULL_BLOCK_POINTS}]")

    t_start = time.perf_counter()
    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "m3_tpu")):
        print(f"chip_smoke: no m3_tpu package beside {__file__}; run it "
              "from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, here)
    child_env = dict(os.environ)   # before this process pins its rungs
    child_env["PYTHONPATH"] = here + os.pathsep + child_env.get(
        "PYTHONPATH", "")
    pin_reference_rungs()

    reduced = []
    if opts.hosts != DEFAULT_HOSTS:
        reduced.append(f"hosts: {opts.hosts} of {DEFAULT_HOSTS}")
    if opts.points < FULL_BLOCK_POINTS:
        reduced.append(
            f"points per series: {opts.points} of {FULL_BLOCK_POINTS} (the "
            f"first {opts.points * 10 // 60} min of the 2 h block): "
            "remote-write ingest is host-bound Python, 54k samples/s on the "
            "chip's host (PR 21), so a full block's 72M samples alone "
            "would take 1340 s of the 1200 s limit")
    work = tempfile.mkdtemp(prefix="m3_chip_smoke_")
    say(f"chip_smoke: seed={opts.seed} hosts={opts.hosts} "
        f"series={opts.hosts * len(CPU_FIELDS)} points={opts.points} "
        f"datapoints={opts.hosts * len(CPU_FIELDS) * opts.points}")
    say("reduced:", reduced or "nothing")

    svc = None
    summary = None
    device = None   # set once the service runs on an accelerator
    try:
        svc = Service(work, child_env, here)
        t0 = time.perf_counter()
        wl = Workload(opts.seed, opts.hosts, opts.points,
                      block_start_for(time.time_ns()))
        say(f"workload generated in {time.perf_counter() - t0:.1f}s")
        port, backend = svc.wait_listening(300.0)
        client = Client(f"http://127.0.0.1:{port}")
        seen = client.get_json("/debug/compute")["backend"]
        found = {"platform": str(seen["platform"]),
                 "kind": str(seen["device_kind"]),
                 "count": len(seen["devices"])}
        say(f"service up in {time.perf_counter() - t0:.1f}s: "
            f"platform={found['platform']} device_kind={found['kind']} "
            f"devices={found['count']} jax={seen['jax']} "
            f"compile_cache={backend.get('compile_cache')}")
        if found["platform"] != "tpu":
            pinned = os.environ.get("JAX_PLATFORMS")
            raise SmokeFailure(
                f"the service runs on platform {found['platform']!r}, not "
                "'tpu'" + (f" (JAX_PLATFORMS={pinned!r} is inherited and "
                           "pins it; this script does not unset it)"
                           if pinned else ""))
        device = found
        report = run_phases(client, wl, svc.data_dir, svc.log_path,
                            device["count"])
        summary = {
            "versions": {"jax": seen["jax"], "numpy": np.__version__,
                         "python": sys.version.split()[0]},
            "sizes": {"hosts": opts.hosts, "series": wl.n_series,
                      "points_per_series": opts.points,
                      "datapoints": wl.n_series * opts.points,
                      "shards": N_SHARDS, "block": "2h", "seed": opts.seed},
            "reduced": reduced, "assumed": ASSUMED,
            "report": report,
            "wall_s": round(time.perf_counter() - t_start, 1),
            "claim": None,
        }
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
    finally:
        if svc is not None:
            svc.stop()
            if opts.keep or summary is None:
                sys.stderr.write("--- service log (tail) ---\n"
                                 + svc.log_tail() + "\n")
        if opts.keep:
            print(f"chip_smoke: kept {work}", file=sys.stderr)
        else:
            shutil.rmtree(work, ignore_errors=True)
    ok = summary is not None
    if ok and "jax" in sys.modules:
        print("chip_smoke: FAILED: the parent imported jax", file=sys.stderr)
        ok = False
    if device is None:
        return 1   # no accelerator: no result line
    if ok:
        say(json.dumps(summary))
    say(result_line(ok, device))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
