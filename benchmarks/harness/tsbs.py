"""TSBS DevOps ``cpu-only`` (github.com/timescale/tsbs, use case
``cpu-only``), generated from ``--seed``: the fleet's labels, the gauges'
values, and Prometheus remote-write bodies of any slice of them.

Copied from ``chip_smoke.py Workload`` (PR 21) and split: a ``Fleet`` is
labels only, ``walk`` makes the value matrix, ``write_body`` one request,
so a scrape of 100,000 series is made without the rest. No network here:
the field and tag names are the suite's; ``ASSUMED`` lists what this file
sets from memory of its simulator. Imports of the program: its prompb
field writer and snappy codec only (what the smoke's parent imports).
"""

from __future__ import annotations

import numpy as np

NS = 1_000_000_000
INTERVAL_NS = 10 * NS             # cpu-only: one reading per 10 s

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


class Fleet:
    """Hosts and their ten tags; series s = host * 10 + field."""

    def __init__(self, seed: int, hosts: int):
        self.seed, self.hosts = seed, hosts
        rng = np.random.default_rng(seed)
        regions = list(REGIONS)
        r_idx = rng.integers(0, len(regions), hosts)
        dc_u = rng.random(hosts)
        rack = rng.integers(0, 100, hosts)
        os_i = rng.integers(0, len(OSES), hosts)
        arch_i = rng.integers(0, len(ARCHES), hosts)
        team_i = rng.integers(0, len(TEAMS), hosts)
        svc = rng.integers(0, 20, hosts)
        ver = rng.integers(0, 2, hosts)
        env_i = rng.integers(0, len(ENVIRONMENTS), hosts)
        self.host_tags: list[list[tuple[bytes, bytes]]] = []
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
        self._label_bytes: list[bytes] | None = None

    def metric_name(self, s: int) -> bytes:
        return b"cpu_" + CPU_FIELDS[s % len(CPU_FIELDS)].encode()

    def tags(self, s: int) -> list[tuple[bytes, bytes]]:
        return self.host_tags[s // len(CPU_FIELDS)]

    def labels(self, s: int) -> dict[bytes, bytes]:
        return {b"__name__": self.metric_name(s), **dict(self.tags(s))}

    def label_bytes(self) -> list[bytes]:
        """Each series' sorted prompb Label submessages, built once."""
        if self._label_bytes is None:
            from m3_tpu.utils.protowire import field_bytes

            out = []
            for s in range(self.n_series):
                buf = bytearray()
                for k, v in sorted(self.labels(s).items()):
                    buf += field_bytes(1, field_bytes(1, k) + field_bytes(2, v))
                out.append(bytes(buf))
            self._label_bytes = out
        return self._label_bytes


def walk(seed: int, n_series: int, points: int) -> np.ndarray:
    """float64 [n_series, points]: the clamped walk, integer-truncated.
    Its own stream of the seed, so the fleet's tags do not shift it."""
    rng = np.random.default_rng([seed, 1])
    x = rng.uniform(0.0, 100.0, n_series)
    vals = np.empty((n_series, points), np.float64)
    for p in range(points):
        vals[:, p] = np.floor(x)
        x = np.clip(x + rng.standard_normal(n_series), 0.0, 100.0)
    return vals


def write_body(fleet: Fleet, values: np.ndarray, times_ns: np.ndarray,
               s0: int, s1: int, p0: int, p1: int) -> tuple[bytes, int]:
    """(snappy'd prompb.WriteRequest, n_samples) for series [s0, s1) x
    points [p0, p1). Sample submessages are laid out with numpy: value =
    field 1 (double), timestamp = field 2 (int64 ms varint, 6 bytes until
    the year 2109)."""
    from m3_tpu.utils import snappy
    from m3_tpu.utils.protowire import _uvarint

    labels = fleet.label_bytes()
    ts_ms = times_ns[p0:p1] // 1_000_000
    if not ((ts_ms >= 1 << 35).all() and (ts_ms < 1 << 42).all()):
        raise ValueError("timestamps outside the 6-byte varint range")
    n_p = p1 - p0
    tpl = np.zeros((n_p, 18), np.uint8)
    tpl[:, 0], tpl[:, 1], tpl[:, 2], tpl[:, 11] = 0x12, 16, 0x09, 0x10
    t = ts_ms.astype(np.uint64)
    for b in range(6):
        byte = (t >> np.uint64(7 * b)) & np.uint64(0x7F)
        tpl[:, 12 + b] = byte | (0x80 if b < 5 else 0)
    block = np.broadcast_to(tpl, (s1 - s0, n_p, 18)).copy()
    block[:, :, 3:11] = values[s0:s1, p0:p1].astype("<f8").view(
        np.uint8).reshape(s1 - s0, n_p, 8)
    parts = []
    for i, s in enumerate(range(s0, s1)):
        body = labels[s] + block[i].tobytes()
        parts.append(b"\x0a" + _uvarint(len(body)) + body)
    return snappy.compress(b"".join(parts)), (s1 - s0) * n_p
