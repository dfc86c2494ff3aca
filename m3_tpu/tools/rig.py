"""Production traffic rig: recorded-shape load + process-level chaos.

The role of the reference's dtest harness driven to production shape
(/root/reference/src/cmd/tools/dtest + the m3em agents): a seeded load
generator replays recorded-shape traffic — zipf-distributed tenants,
bursty batched writes through ``session.write_many``, mixed query sizes
through the coordinator API — against REAL spawned service processes
(tools/em.py agents), while a seeded, replayable chaos schedule SIGKILLs
processes (dbnode, kvd replica, aggregator) and partitions them
(restart with env-injected ``M3_TPU_FAULTS`` network-fault rules). The
rig then proves the contracts the platform claims:

- **zero acked-write loss**: every entry the client session acked at
  the write consistency level is readable after the schedule heals
  (the WriteLedger records acks, ``verify`` replays them);
- **partial-result reads**: during an outage window reads SUCCEED with
  the PR-2 ReadWarning contract (warnings in the response envelope),
  never silently drop data;
- **SLO-bounded p99**: latency quantiles come from the PR-4 request
  histograms scraped off /metrics (per-tenant families), compared
  pair-median-style across interleaved windows so a noisy host cannot
  fake a regression or mask one;
- **tenant isolation**: the noisy-tenant phase saturates one tenant
  until admission control sheds it with 429s while a steady tenant's
  p99 holds — proven WHILE nodes are being killed;
- **anti-entropy convergence**: after the schedule heals, the replica
  that slept through its outage window converges via the nodes' OWN
  repair daemons — every replica pair reaches per-(shard, block)
  rollup-digest equality within the configured cycle budget
  (``convergence_audit``; nothing in the rig invokes repair directly).

Determinism: the traffic sequence (tenant choice, batch sizes, series,
query shapes) and the chaos schedule derive from one seed — the same
seed replays the same run shape. Timestamps and wall-clock interleaving
are the only nondeterminism, which is exactly the part production owns.

CLI (the ops surface; `run_tests.sh rig` drives the pytest wrapper):

    python -m m3_tpu.tools.rig --workdir /tmp/rig --seconds 20 --seed 7
"""

from __future__ import annotations

import argparse
import json
import math
import random
import statistics
import threading
import time
import urllib.error
import urllib.request

NS = 1_000_000_000


# ---------------------------------------------------------------------------
# traffic generation (seeded, recorded-shape)


def zipf_weights(n: int, s: float) -> list[float]:
    """Normalized zipf(s) weights over n ranks — the tenant/series skew
    every production metrics platform sees (a few namespaces dominate)."""
    w = [1.0 / (k ** s) for k in range(1, n + 1)]
    total = sum(w)
    return [x / total for x in w]


class RigConfig:
    """Knobs for one rig run; everything defaults to a shape small
    enough for CI and scales up by multiplying rates/duration."""

    def __init__(self, seed: int = 0, tenants: tuple = ("tenant0", "tenant1"),
                 zipf_s: float = 1.2, series_per_tenant: int = 32,
                 batch_size: int = 24, burst_every: int = 8,
                 burst_mult: int = 4, write_interval_s: float = 0.05,
                 query_interval_s: float = 0.08, duration_s: float = 10.0,
                 slo_p99_ms: float = 2000.0, churn_per_batch: int = 0):
        self.seed = seed
        self.tenants = tuple(tenants)
        self.zipf_s = zipf_s
        self.series_per_tenant = series_per_tenant
        self.batch_size = batch_size
        self.burst_every = burst_every
        self.burst_mult = burst_mult
        self.write_interval_s = write_interval_s
        self.query_interval_s = query_interval_s
        self.duration_s = duration_s
        self.slo_p99_ms = slo_p99_ms
        # cardinality-explosion shape: this many entries of every batch
        # carry a monotonically-unique `churn` tag, so each one mints a
        # brand-NEW series (continuous index ingest + segment churn — the
        # episode that must not blow up read latency)
        self.churn_per_batch = churn_per_batch


class TrafficGen:
    """Seeded recorded-shape traffic. The SEQUENCE (tenants, batch
    sizes, series ids, values, query shapes) is fully determined by the
    seed; timestamps are assigned by the caller at send time."""

    QUERY_WINDOWS_S = (60, 600, 3600)  # mixed query sizes: S / M / L

    def __init__(self, cfg: RigConfig):
        self.cfg = cfg
        self.rng = random.Random(f"rig-traffic:{cfg.seed}")
        self._weights = zipf_weights(len(cfg.tenants), cfg.zipf_s)
        self._batches = 0
        self._minted = 0  # monotonic: a churn tag value never repeats

    def pick_tenant(self) -> str:
        i = self.rng.choices(range(len(self.cfg.tenants)),
                             weights=self._weights)[0]
        return self.cfg.tenants[i]

    def next_batch(self, t_ns: int):
        """(tenant, entries) for session.write_many/db.write_batch:
        entries are (metric_name, tags, t_ns, value). Bursty: every
        burst_every-th batch is burst_mult times the base size."""
        tenant = self.pick_tenant()
        self._batches += 1
        n = self.cfg.batch_size
        if self.cfg.burst_every and self._batches % self.cfg.burst_every == 0:
            n *= self.cfg.burst_mult
        jitter = n // 4
        if jitter:
            n += self.rng.randrange(-jitter, jitter + 1)
        n = max(1, n)
        entries = []
        for k in range(n):
            sid = self.rng.randrange(self.cfg.series_per_tenant)
            name = f"rig_metric_{sid}".encode()
            tags = ((b"tenant", tenant.encode()),
                    (b"sid", str(sid).encode()))
            if k < self.cfg.churn_per_batch:
                # cardinality explosion: a never-repeating tag value
                # makes this entry a brand-new series every time
                tags += ((b"churn", b"c%08d" % self._minted),)
                self._minted += 1
            # 1us spacing keeps timestamps unique inside one batch (LWW
            # dedup must never collapse two ledgered datapoints)
            entries.append((name, tags, t_ns + k * 1000,
                            round(self.rng.random() * 100.0, 6)))
        return tenant, entries

    def next_query(self, now_s: float):
        """(tenant, expr, start_s, end_s, step_s) — mixed window sizes,
        selector and aggregation shapes."""
        tenant = self.pick_tenant()
        window = self.rng.choice(self.QUERY_WINDOWS_S)
        sid = self.rng.randrange(self.cfg.series_per_tenant)
        if self.rng.random() < 0.5:
            expr = f"rig_metric_{sid}"
        else:
            expr = f"sum(rig_metric_{sid})"
        step = max(1, window // 30)
        return tenant, expr, int(now_s - window), int(now_s), step


# ---------------------------------------------------------------------------
# acked-write ledger


class WriteLedger:
    """Thread-safe record of every ACKED write: the zero-loss contract
    is 'everything in here is readable after the schedule heals'."""

    def __init__(self):
        self._lock = threading.Lock()
        # (tenant, name, tags) -> list[(t_ns, value)]
        self._acked: dict[tuple, list] = {}
        self.acked_count = 0
        self.failed_count = 0

    def record(self, tenant: str, entries, results) -> None:
        """results: per-entry None (acked) or error string (not acked) —
        the session.write_many / Database.write_batch contract."""
        with self._lock:
            for (name, tags, t_ns, value), err in zip(entries, results):
                if err is None:
                    key = (tenant, bytes(name), tuple(tags))
                    self._acked.setdefault(key, []).append((int(t_ns),
                                                            float(value)))
                    self.acked_count += 1
                else:
                    self.failed_count += 1

    def series(self) -> list[tuple]:
        with self._lock:
            return list(self._acked)

    def verify(self, fetch_fn, max_missing: int = 20) -> dict:
        """Replay every acked datapoint against `fetch_fn(tenant, name,
        tags, start_ns, end_ns) -> [(t_ns, value)]`. Returns a report;
        an empty `missing` list IS the zero-acked-write-loss proof."""
        with self._lock:
            acked = {k: list(v) for k, v in self._acked.items()}
        checked = 0
        missing = []
        for (tenant, name, tags), points in acked.items():
            lo = min(t for t, _ in points)
            hi = max(t for t, _ in points)
            have = {}
            for t, v in fetch_fn(tenant, name, tags, lo, hi + 1):
                have[int(t)] = float(v)
            for t, v in points:
                checked += 1
                got = have.get(t)
                if got is None or abs(got - v) > 1e-9:
                    if len(missing) < max_missing:
                        missing.append({"tenant": tenant,
                                        "name": name.decode(),
                                        "t_ns": t, "want": v, "got": got})
        return {"checked": checked, "missing": missing,
                "acked": self.acked_count, "failed": self.failed_count}


# ---------------------------------------------------------------------------
# chaos schedule (seeded, replayable)


class ChaosEvent:
    """One scheduled action against a managed service process."""

    __slots__ = ("t_s", "action", "agent", "service", "fault_spec")

    def __init__(self, t_s: float, action: str, agent: str, service: str,
                 fault_spec: str = ""):
        self.t_s = round(float(t_s), 3)
        self.action = action  # kill | restart | partition | heal
        self.agent = agent
        self.service = service
        self.fault_spec = fault_spec

    def __eq__(self, other):
        return isinstance(other, ChaosEvent) and self.to_doc() == other.to_doc()

    def __repr__(self):
        return f"ChaosEvent({self.to_doc()})"

    def to_doc(self) -> dict:
        return {"t_s": self.t_s, "action": self.action, "agent": self.agent,
                "service": self.service, "fault_spec": self.fault_spec}


# per-service-kind partition rules: env-injected network faults that make
# a live process drop most requests (the reachable-but-sick half of the
# failure space SIGKILL doesn't cover). Each plan also wedges the
# service's periodic loop with a delay fault — a partitioned process is
# typically also a STUCK process (blocked RPCs, wedged ticks), and the
# stall watchdog must observe exactly that from inside
PARTITION_SPECS = {
    "dbnode": "dbnode.handle=error:p0.7;dbnode.tick=delay:d2.0",
    "kvd": "consensus.append=error:p0.5;kvd.rpc=error:p0.3;"
           "kvd.tick=delay:d2.0",
    "aggregator": "msg.consumer.recv=error:p0.5;"
                  "aggregator.flush=delay:d4.0",
}

# the stall drill's fault plan: a live (serving) dbnode whose tick loop
# is wedged hard — /debug/profile is fault-exempt, so the watchdog's
# stall verdict is observable from outside WHILE the loop is stuck
STALL_DRILL_SPEC = "dbnode.tick=delay:d6.0"


class ChaosSchedule:
    """Seeded kill/partition schedule. Windows never overlap across
    targets — one failure domain at a time, so a majority/consistency
    claim is actually testable (two dead replicas of an RF=2 shard is an
    availability loss by design, not a bug the rig should manufacture)."""

    @staticmethod
    def generate(seed: int, duration_s: float, targets: list[tuple],
                 outage_s: float = 3.0,
                 partition_frac: float = 0.5) -> list[ChaosEvent]:
        """targets: [(agent, service, kind)] with kind in
        PARTITION_SPECS. Produces kill->restart / partition->heal pairs
        laid out in non-overlapping windows across [10%, 85%] of the
        run. Same (seed, args) -> identical schedule (replayable)."""
        rng = random.Random(f"rig-schedule:{seed}")
        n = len(targets)
        if n == 0 or duration_s <= 0:
            return []
        lo, hi = 0.10 * duration_s, 0.85 * duration_s
        slot = (hi - lo) / n
        outage = min(outage_s, max(0.5, slot * 0.6))
        events: list[ChaosEvent] = []
        order = list(targets)
        rng.shuffle(order)
        for i, (agent, service, kind) in enumerate(order):
            start = lo + i * slot + rng.uniform(0, max(slot - outage, 0.01))
            if rng.random() < partition_frac:
                spec = PARTITION_SPECS.get(kind, "dbnode.handle=error:p0.5")
                events.append(ChaosEvent(start, "partition", agent, service,
                                         spec))
                events.append(ChaosEvent(start + outage, "heal", agent,
                                         service))
            else:
                events.append(ChaosEvent(start, "kill", agent, service))
                events.append(ChaosEvent(start + outage, "restart", agent,
                                         service))
        events.sort(key=lambda e: (e.t_s, e.agent, e.service))
        return events


class ChaosRunner:
    """Executes a schedule against em agents on a background thread.
    `base_env` maps service name -> the env it was originally started
    with, so `heal` restores a partitioned process to clean faults."""

    def __init__(self, agents: dict, schedule: list[ChaosEvent],
                 base_env: dict[str, dict], seed: int = 0):
        self.agents = agents
        self.schedule = list(schedule)
        self.base_env = base_env
        self.seed = seed
        self.executed: list[dict] = []
        self.errors: list[str] = []
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def join(self, timeout_s: float = 120.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout_s)

    def _run(self) -> None:
        t0 = time.monotonic()
        for ev in self.schedule:
            delay = ev.t_s - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            try:
                self._execute(ev)
                self.executed.append({**ev.to_doc(),
                                      "at_s": round(time.monotonic() - t0, 3)})
            except Exception as e:  # noqa: BLE001 - a failed action is
                # part of the report, not a rig crash
                self.errors.append(f"{ev!r}: {e}")

    def _execute(self, ev: ChaosEvent) -> None:
        agent = self.agents[ev.agent]
        env = self.base_env.get(ev.service, {})
        if ev.action == "kill":
            agent.kill(ev.service)
        elif ev.action == "restart":
            agent.start(ev.service, grace_s=0.5)
        elif ev.action == "partition":
            # env is process-start state: a partition is a graceful stop
            # + relaunch under a fault plan that drops most requests
            agent.stop(ev.service)
            agent.start(ev.service, env={
                **env,
                "M3_TPU_FAULTS": ev.fault_spec,
                "M3_TPU_FAULTS_SEED": str(self.seed),
            }, grace_s=0.5)
        elif ev.action == "heal":
            agent.stop(ev.service)
            agent.start(ev.service, env=env, grace_s=0.5)
        else:
            raise ValueError(f"unknown chaos action {ev.action!r}")


# ---------------------------------------------------------------------------
# the rig: load loops + collection


class Rig:
    """Drives seeded write/query load through pluggable transports and
    collects per-tenant outcomes. `write_fn(tenant, entries)` returns
    per-entry results (None = acked); `query_fn(tenant, expr, start_s,
    end_s, step_s)` returns (status, doc_or_None, headers)."""

    MAX_LATENCIES = 20_000

    def __init__(self, cfg: RigConfig, write_fn, query_fn,
                 ledger: WriteLedger | None = None):
        self.cfg = cfg
        self.write_fn = write_fn
        self.query_fn = query_fn
        self.ledger = ledger if ledger is not None else WriteLedger()
        self.gen = TrafficGen(cfg)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.tenant_stats: dict[str, dict] = {
            t: {"writes_acked": 0, "writes_failed": 0, "write_errors": 0,
                "queries_ok": 0, "queries_shed": 0, "query_errors": 0,
                "warnings": 0, "latencies_ms": []}
            for t in cfg.tenants
        }
        self.retry_after_seen = 0

    def _writer_loop(self) -> None:
        while not self._stop.is_set():
            t_ns = time.time_ns()
            tenant, entries = self.gen.next_batch(t_ns)
            st = self.tenant_stats[tenant]
            try:
                results = self.write_fn(tenant, entries)
            except Exception:  # noqa: BLE001 - whole batch failed
                with self._lock:
                    st["write_errors"] += 1
                    st["writes_failed"] += len(entries)
            else:
                self.ledger.record(tenant, entries, results)
                acked = sum(1 for r in results if r is None)
                with self._lock:
                    st["writes_acked"] += acked
                    st["writes_failed"] += len(entries) - acked
            self._stop.wait(self.cfg.write_interval_s)

    def _query_loop(self) -> None:
        while not self._stop.is_set():
            tenant, expr, start_s, end_s, step_s = \
                self.gen.next_query(time.time())
            st = self.tenant_stats[tenant]
            t0 = time.perf_counter()
            try:
                status, doc, headers = self.query_fn(tenant, expr, start_s,
                                                     end_s, step_s)
            except Exception:  # noqa: BLE001 - transport failure
                with self._lock:
                    st["query_errors"] += 1
            else:
                ms = (time.perf_counter() - t0) * 1e3
                with self._lock:
                    if status == 200:
                        st["queries_ok"] += 1
                        if len(st["latencies_ms"]) < self.MAX_LATENCIES:
                            st["latencies_ms"].append(round(ms, 3))
                        if doc and doc.get("warnings"):
                            st["warnings"] += 1
                    elif status == 429:
                        st["queries_shed"] += 1
                        if headers and _header(headers, "Retry-After"):
                            self.retry_after_seen += 1
                    else:
                        st["query_errors"] += 1
            self._stop.wait(self.cfg.query_interval_s)

    def run(self, duration_s: float | None = None) -> dict:
        """Run the load loops for the configured duration; returns the
        per-tenant report (the chaos runner, if any, is driven by the
        caller alongside this)."""
        duration = duration_s if duration_s is not None else self.cfg.duration_s
        writer = threading.Thread(target=self._writer_loop, daemon=True)
        querier = threading.Thread(target=self._query_loop, daemon=True)
        writer.start()
        querier.start()
        time.sleep(duration)
        self._stop.set()
        writer.join(10.0)
        querier.join(10.0)
        return self.report()

    def report(self) -> dict:
        with self._lock:
            tenants = {
                t: {**{k: v for k, v in st.items() if k != "latencies_ms"},
                    "client_p99_ms": _p99(st["latencies_ms"])}
                for t, st in self.tenant_stats.items()
            }
        return {
            "seed": self.cfg.seed,
            "tenants": tenants,
            "acked_total": self.ledger.acked_count,
            "failed_total": self.ledger.failed_count,
            "retry_after_seen": self.retry_after_seen,
        }


def _p99(values: list[float]) -> float | None:
    if not values:
        return None
    ordered = sorted(values)
    return round(ordered[min(len(ordered) - 1,
                             int(math.ceil(0.99 * len(ordered))) - 1)], 3)


def _header(headers, name: str):
    get = getattr(headers, "get", None)
    if get is None:
        return None
    val = get(name)
    if val is None and isinstance(headers, dict):
        for k, v in headers.items():
            if str(k).lower() == name.lower():
                return v
    return val


# ---------------------------------------------------------------------------
# transports


def api_query_fn(api):
    """Query transport over an IN-PROCESS CoordinatorAPI (the tier-1
    smoke path: same handle() code, no sockets)."""

    def query(tenant, expr, start_s, end_s, step_s):
        status, _ctype, payload, headers = api.handle(
            "GET", "/api/v1/query_range",
            {"query": [expr], "start": [str(start_s)], "end": [str(end_s)],
             "step": [str(step_s)], "namespace": [tenant]}, b"")
        doc = json.loads(payload) if payload else None
        return status, doc, headers

    return query


def http_query_fn(port: int, timeout_s: float = 15.0):
    """Query transport over a real coordinator's HTTP API."""

    def query(tenant, expr, start_s, end_s, step_s):
        from urllib.parse import urlencode

        qs = urlencode({"query": expr, "start": start_s, "end": end_s,
                        "step": step_s, "namespace": tenant})
        url = f"http://127.0.0.1:{port}/api/v1/query_range?{qs}"
        try:
            with urllib.request.urlopen(url, timeout=timeout_s) as r:
                return r.status, json.loads(r.read().decode()), dict(r.headers)
        except urllib.error.HTTPError as e:
            body = e.read()
            try:
                doc = json.loads(body.decode())
            except ValueError:
                doc = None
            return e.code, doc, dict(e.headers)

    return query


def db_write_fn(db):
    """Write transport over an in-process Database (smoke path)."""
    return lambda tenant, entries: db.write_batch(tenant, entries)


def session_write_fn(session):
    """Write transport over the cluster client session — the bursty
    batched `write_many` path the tentpole names."""
    return lambda tenant, entries: session.write_many(tenant, entries)


def session_fetch_fn(session):
    """Ledger-verification reader over the same session."""
    from m3_tpu.utils.ident import tags_to_id

    def fetch(tenant, name, tags, start_ns, end_ns):
        sid = tags_to_id(name, list(tags))
        return session.fetch(tenant, sid, start_ns, end_ns)

    return fetch


def db_fetch_fn(db):
    from m3_tpu.utils.ident import tags_to_id

    def fetch(tenant, name, tags, start_ns, end_ns):
        sid = tags_to_id(name, list(tags))
        return [(d.timestamp_ns, d.value)
                for d in db.read(tenant, sid, start_ns, end_ns)]

    return fetch


# ---------------------------------------------------------------------------
# histogram scraping: p99 from the PR-4 /metrics families


def parse_histogram(text: str, family: str,
                    labels: dict | None = None):
    """(bounds, bucket_counts) from a Prometheus text exposition:
    cumulative `_bucket` lines of `family` whose labels are a superset
    of `labels`, converted to per-bucket counts (last slot = +Inf)."""
    import re as _re

    want = dict(labels or {})
    rows = []
    for line in text.splitlines():
        if not line.startswith(family + "_bucket"):
            continue
        m = _re.match(r"^[\w:]+\{(.*)\}\s+(\S+)$", line)
        if not m:
            continue
        labelstr, value = m.groups()
        parsed = dict(_re.findall(r'([\w.]+)="((?:[^"\\]|\\.)*)"', labelstr))
        if any(parsed.get(k) != str(v) for k, v in want.items()):
            continue
        le = parsed.get("le")
        if le is None:
            continue
        ub = math.inf if le == "+Inf" else float(le)
        rows.append((ub, float(value)))
    rows.sort(key=lambda r: r[0])
    bounds = [ub for ub, _ in rows if not math.isinf(ub)]
    cum = [c for _, c in rows]
    counts = [cum[0] if cum else 0.0] + [cum[i] - cum[i - 1]
                                         for i in range(1, len(cum))]
    return bounds, counts


def hist_delta(prev, cur):
    """Per-bucket counts accrued between two scrapes of one histogram."""
    bounds, prev_counts = prev
    _, cur_counts = cur
    n = max(len(prev_counts), len(cur_counts))
    prev_counts = list(prev_counts) + [0.0] * (n - len(prev_counts))
    cur_counts = list(cur_counts) + [0.0] * (n - len(cur_counts))
    return bounds, [max(0.0, c - p) for p, c in zip(prev_counts, cur_counts)]


def hist_p99_ms(hist, q: float = 0.99) -> float | None:
    """Interpolated quantile over (bounds, per-bucket counts), in ms —
    the same math utils/instrument._Histogram.quantile runs in-process."""
    bounds, counts = hist
    total = sum(counts)
    if total <= 0:
        return None
    rank = q * total
    running = 0.0
    prev_ub = 0.0
    for ub, c in zip(bounds, counts):
        if running + c >= rank:
            if c == 0:
                return ub * 1e3
            return (prev_ub + (ub - prev_ub) * (rank - running) / c) * 1e3
        running += c
        prev_ub = ub
    # rank lands in the +Inf bucket: report the top finite bound
    return (bounds[-1] if bounds else 0.0) * 1e3


def scrape_metrics(port: int, timeout_s: float = 10.0) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=timeout_s) as r:
        return r.read().decode()


def parse_counters(text: str, family: str) -> dict:
    """{sorted-label-tuple: value} for one Prometheus counter/gauge
    family (un-labelled samples key on the empty tuple)."""
    import re as _re

    out: dict = {}
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        m = _re.match(r"^([\w:]+)(?:\{(.*)\})?\s+(\S+)$", line)
        if not m or m.group(1) != family:
            continue
        labels = tuple(sorted(_re.findall(
            r'([\w.]+)="((?:[^"\\]|\\.)*)"', m.group(2) or "")))
        out[labels] = out.get(labels, 0.0) + float(m.group(3))
    return out


def _series_points(doc) -> dict:
    """{label-key-tuple: {ts_s: value}} from a query_range matrix doc,
    NaN points dropped (grid slots the engine left unfilled)."""
    out: dict = {}
    try:
        result = doc["data"]["result"]
    except (TypeError, KeyError):
        return out
    for series in result:
        key = tuple(sorted(series.get("metric", {}).items()))
        vals = {}
        for ts, v in series.get("values", []):
            try:
                fv = float(v)
            except (TypeError, ValueError):
                continue
            if not math.isnan(fv):
                vals[float(ts)] = fv
        out[key] = vals
    return out


def windowed_p99s_ms(scrape_fn, family: str, labels: dict,
                     run_window_fn, n_windows: int) -> list:
    """Per-window p99s from a CUMULATIVE server histogram: scrape at
    every window boundary, diff bucket counts, interpolate. The
    pair-median protocol (the noisy-host discipline): callers
    take the MEDIAN of the window p99s so one scheduler hiccup cannot
    fake an SLO breach."""
    out = []
    prev = parse_histogram(scrape_fn(), family, labels)
    for i in range(n_windows):
        run_window_fn(i)
        cur = parse_histogram(scrape_fn(), family, labels)
        out.append(hist_p99_ms(hist_delta(prev, cur)))
        prev = cur
    return out


def median_p99_ms(p99s: list) -> float | None:
    vals = [p for p in p99s if p is not None]
    return round(statistics.median(vals), 3) if vals else None


# ---------------------------------------------------------------------------
# convergence audit: per-(shard, block) rollup digests across replicas


def _http_post_ok(url: str, timeout_s: float = 30.0) -> None:
    req = urllib.request.Request(url, data=b"{}", method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        r.read()


def node_rollup(port: int, namespace: str, shard: int,
                timeout_s: float = 10.0) -> dict:
    """{block_start: (digest, n_series)} from one node's /blocks/rollup
    — the same packed wire format the repair daemons exchange."""
    import base64 as _b64
    from urllib.parse import urlencode

    from m3_tpu.storage.peers import unpack_rollup

    qs = urlencode({"namespace": namespace, "shard": shard})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/blocks/rollup?{qs}",
            timeout=timeout_s) as r:
        doc = json.loads(r.read().decode())
    return unpack_rollup(_b64.b64decode(doc.get("rollup_b64", "")))


def node_repair_cycles(port: int, timeout_s: float = 10.0) -> int:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/repair",
            timeout=timeout_s) as r:
        doc = json.loads(r.read().decode())
    return int(doc.get("totals", {}).get("cycles", 0))


def convergence_audit(cluster, namespaces, budget_cycles: int = 10,
                      interval_s: float = 1.0, poll_s: float = 0.5) -> dict:
    """The anti-entropy acceptance phase: after the chaos schedule heals,
    every replica pair must reach per-(shard, block) rollup-digest
    equality within `budget_cycles` repair cycles — the replica that
    slept through a kill/partition window converges via the daemons, not
    via test code invoking repair.

    Both replicas are flushed first (digests cover persisted volumes;
    the rig's short run otherwise leaves everything in the mutable
    buffer, making equality vacuous), then the audit POLLS — repair runs
    only inside the nodes."""
    from m3_tpu.cluster.placement import ShardState

    for port in cluster.node_ports.values():
        _http_post_ok(f"http://127.0.0.1:{port}/debug/flush")
    owners: dict[int, list[str]] = {}
    for nid, inst in cluster.placement.instances.items():
        for sh in inst.shards.values():
            if sh.state in (ShardState.AVAILABLE, ShardState.LEAVING):
                owners.setdefault(sh.id, []).append(nid)
    pairs = {s: sorted(nids) for s, nids in owners.items() if len(nids) >= 2}
    cycles0 = {nid: node_repair_cycles(port)
               for nid, port in cluster.node_ports.items()}

    def mismatches() -> list[dict]:
        out = []
        for shard, nids in sorted(pairs.items()):
            for namespace in namespaces:
                tables = {
                    nid: node_rollup(cluster.node_ports[nid], namespace,
                                     shard)
                    for nid in nids
                }
                base = tables[nids[0]]
                if any(tables[n] != base for n in nids[1:]):
                    out.append({
                        "namespace": namespace, "shard": shard,
                        "tables": {n: {str(bs): d for bs, (d, _c)
                                       in sorted(t.items())}
                                   for n, t in tables.items()},
                    })
        return out

    # budget in wall time: budget_cycles at the configured interval plus
    # the daemon's jitter headroom and one deadline-length straggler
    deadline = time.monotonic() + budget_cycles * interval_s * 1.5 + 5.0
    remaining = mismatches()
    initially_divergent = len(remaining)
    while remaining and time.monotonic() < deadline:
        time.sleep(poll_s)
        remaining = mismatches()
    cycles_used = max(
        (node_repair_cycles(port) - cycles0[nid]
         for nid, port in cluster.node_ports.items()), default=0)
    return {
        "converged": not remaining,
        "initially_divergent": initially_divergent,
        "replica_pairs": len(pairs),
        "namespaces": list(namespaces),
        "budget_cycles": budget_cycles,
        "cycles_used": cycles_used,
        "mismatches": remaining[:10],
    }


# ---------------------------------------------------------------------------
# soak trajectory: the first-class artifact of the profiling plane
# (ROADMAP #6(a) first leg) — QPS, p99, RSS, top contended locks and
# stall events over time, sampled off the live cluster's /metrics and
# fault-exempt /debug/profile surfaces


class TrajectoryRecorder:
    """Samples the running deployment's saturation plane on a background
    thread into one schema'd artifact. Every fetch is best-effort — a
    killed node yields a gap in that service's row, never a rig crash."""

    SCHEMA = "m3_tpu.trajectory.v1"

    def __init__(self, coord_port: int, profile_ports: dict[str, int],
                 rig: "Rig | None" = None, sample_s: float = 1.0):
        self.coord_port = coord_port
        # service name -> port serving /debug/profile (coordinator and
        # dbnodes on their APIs, aggregator/kvd on the shared debug
        # surface when armed)
        self.profile_ports = dict(profile_ports)
        self.rig = rig
        self.sample_s = sample_s
        self.samples: list[dict] = []
        self.topology_events: list[dict] = []       # annotate() rows
        self._events: dict[tuple, dict] = {}        # dedup key -> event
        self._locks: dict[tuple, dict] = {}         # (svc, site) -> doc
        self._compute_tops: dict[str, list] = {}    # svc -> top programs
        self._prev_hist = None
        self._prev_writes = 0
        self._prev_queries = 0
        self._t0 = time.monotonic()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # fetchers are methods so tests can stub the transport
    def _fetch_metrics(self) -> str:
        return scrape_metrics(self.coord_port, timeout_s=3.0)

    def _fetch_profile(self, port: int) -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile", timeout=3.0) as r:
            return json.loads(r.read().decode())

    def _fetch_compute(self, port: int) -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/compute?top=5",
                timeout=3.0) as r:
            return json.loads(r.read().decode())

    def _rig_totals(self) -> tuple[int, int]:
        if self.rig is None:
            return 0, 0
        with self.rig._lock:
            writes = sum(st["writes_acked"]
                         for st in self.rig.tenant_stats.values())
            queries = sum(st["queries_ok"]
                          for st in self.rig.tenant_stats.values())
        return writes, queries

    def sample_once(self) -> dict:
        now_s = round(time.monotonic() - self._t0, 3)
        row: dict = {"t_s": now_s, "p99_ms": None,
                     "qps_writes": 0.0, "qps_queries": 0.0,
                     "rss_bytes": {}, "stalls": {},
                     "net_bytes": {}, "device_compute": {}}
        writes, queries = self._rig_totals()
        row["qps_writes"] = round((writes - self._prev_writes)
                                  / max(self.sample_s, 1e-6), 1)
        row["qps_queries"] = round((queries - self._prev_queries)
                                   / max(self.sample_s, 1e-6), 1)
        self._prev_writes, self._prev_queries = writes, queries
        try:
            text = self._fetch_metrics()
            cur = parse_histogram(text, "coordinator_request_seconds")
            if self._prev_hist is not None:
                row["p99_ms"] = hist_p99_ms(hist_delta(self._prev_hist, cur))
            self._prev_hist = cur
            # bytes-on-wire ledger (utils/wire, ROADMAP #1): cumulative
            # per-flow totals off the coordinator scrape — a first-class
            # soak column, so a wire-format regression shows up as a
            # bytes/row slope change against the same QPS
            for direction in ("sent", "recv"):
                for labels, val in parse_counters(
                        text, f"net_bytes_{direction}").items():
                    flow = dict(labels).get("flow", "?")
                    row["net_bytes"][f"{flow}_{direction}"] = int(val)
        except Exception:  # noqa: BLE001 - coordinator briefly unreachable
            pass
        for svc, port in self.profile_ports.items():
            try:
                doc = self._fetch_profile(port)
            except Exception:  # noqa: BLE001 - killed/partitioned process
                continue
            row["rss_bytes"][svc] = doc.get("rss_bytes", 0)
            wd = doc.get("watchdog", {}) or {}
            row["stalls"][svc] = sum(lp.get("stalls", 0)
                                     for lp in wd.get("loops", ()))
            for ev in wd.get("recent_events", ()):
                if ev.get("kind") != "stall":
                    continue
                key = (svc, ev.get("loop"), ev.get("t_unix"))
                self._events.setdefault(key, {**ev, "service": svc,
                                               "rig_t_s": now_s})
            for cls in (doc.get("locks", {}) or {}).get("classes", ()):
                self._locks[(svc, cls.get("site"))] = {**cls, "service": svc}
            # device-compute columns (fault-exempt /debug/compute): per-
            # service device time, device-resident cache bytes, padding
            # waste — the soak's view of compute-plane pressure
            try:
                comp = self._fetch_compute(port)
            except Exception:  # noqa: BLE001 - pre-upgrade node or
                continue       # killed process: gap, never a crash
            progs = comp.get("programs", ()) or ()
            caches = comp.get("device_caches", {}) or {}
            self._compute_tops[svc] = [
                {"op": p.get("op"), "sig": p.get("sig"),
                 "execute_seconds_total":
                     round(p.get("execute_seconds_total", 0.0), 6)}
                for p in progs[:5]]
            row["device_compute"][svc] = {
                "execute_seconds_total": round(sum(
                    p.get("execute_seconds_total", 0.0)
                    for p in progs), 6),
                "compile_seconds_total": round(sum(
                    p.get("compile_seconds_total", 0.0)
                    for p in progs), 6),
                "jit_evictions": sum(
                    (comp.get("jit_evictions", {}) or {}).values()),
                "device_cache_bytes": sum(
                    int(c.get("bytes", 0)) for c in caches.values()),
                "device_mem_bytes": sum(
                    int(d.get("bytes_in_use", 0))
                    for d in comp.get("device_memory", ()) or ()),
            }
        self.samples.append(row)
        return row

    def annotate(self, action: str, **doc) -> None:
        """Topology/episode annotations on the trajectory timeline:
        t_s-aligned with the sampled rows, so a p99 excursion can be
        read against the add/drain/restart that caused it."""
        self.topology_events.append(
            {"action": action,
             "t_s": round(time.monotonic() - self._t0, 3), **doc})

    def artifact(self) -> dict:
        events = sorted(self._events.values(),
                        key=lambda e: e.get("t_unix", 0))
        locks = sorted(self._locks.values(),
                       key=lambda d: -d.get("wait_total_ms", 0.0))
        return {
            "schema": self.SCHEMA,
            "sample_interval_s": self.sample_s,
            "services": sorted(self.profile_ports),
            "samples": self.samples,
            "topology_events": list(self.topology_events),
            "stall_events": events,
            "contended_locks": locks[:32],
            "device_compute_top": dict(self._compute_tops),
        }

    def start(self) -> None:
        if self._thread is not None:
            return

        def loop():
            while not self._stop.wait(self.sample_s):
                try:
                    self.sample_once()
                except Exception:  # noqa: BLE001 - the recorder must
                    pass           # outlive anything it records

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="trajectory-recorder")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None


def stall_drill(cluster, recorder: "TrajectoryRecorder | None",
                timeout_s: float = 20.0) -> dict:
    """Deterministic stall-watchdog proof on a LIVE node: restart one
    dbnode under a tick-wedging fault plan, then poll its fault-exempt
    /debug/profile until the node's OWN watchdog reports the stalled
    tick loop (captured stack included); heal and hand the events to the
    trajectory. The detection is entirely in-process on the node — the
    drill only arranges the wedge and reads the verdict."""
    agent_name, service, _kind = cluster.chaos_targets()[0]
    agent = cluster.agents[agent_name]
    port = cluster.node_ports[service]
    base_env = cluster.base_service_env
    t_start = time.time()
    agent.stop(service)
    agent.start(service, env={**base_env, "M3_TPU_FAULTS": STALL_DRILL_SPEC,
                              "M3_TPU_FAULTS_SEED": "0"}, grace_s=0.5)
    from m3_tpu.tools.em import ClusterEnv

    ClusterEnv.wait_until(
        lambda: _http_ok(f"http://127.0.0.1:{port}/health"),
        timeout_s=60, desc="drill node serving")
    events: list[dict] = []

    def stalled():
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/profile",
                    timeout=3.0) as r:
                doc = json.loads(r.read().decode())
        except Exception:  # noqa: BLE001 - mid-restart
            return False
        evs = [e for e in doc.get("watchdog", {}).get("recent_events", ())
               if e.get("kind") == "stall" and e.get("loop") == "dbnode.tick"]
        if evs:
            events.extend(evs)
            return True
        return False

    try:
        ClusterEnv.wait_until(stalled, timeout_s=timeout_s, every_s=0.5,
                              desc="watchdog stall verdict")
    except TimeoutError:
        pass
    finally:
        agent.stop(service)
        agent.start(service, env=base_env, grace_s=0.5)
    if recorder is not None:
        for ev in events:
            key = (service, ev.get("loop"), ev.get("t_unix"))
            recorder._events.setdefault(key, {**ev, "service": service})
    return {"service": service, "window": [t_start, time.time()],
            "fault_spec": STALL_DRILL_SPEC,
            "events": events}


# ---------------------------------------------------------------------------
# full production deployment (real processes) — shared by the CLI and the
# chaos-lane pytest


NODE_CFG = """\
db:
  path: {workdir}/data
  n_shards: {n_shards}
  namespaces:
    - name: default
  # flush the WAL to the OS on every append: a SIGKILLed node must be
  # able to replay every write it acked — the zero-acked-write-loss
  # contract survives SEQUENTIAL outages of both replicas only if no
  # acked byte lives exclusively in a user-space buffer
  commitlog_flush_every_bytes: 1
cluster:
  instance_id: {node_id}
  kv_addr: {kv_addr}
http:
  host: 127.0.0.1
  port: {port}
tick_interval_s: 0.5
# continuous anti-entropy at rig tempo: production defaults are 30s
# cycles, but the convergence audit needs several cycles inside its
# budget, so the rig runs 1s cycles with the same pacing discipline
repair:
  interval_s: 1.0
  jitter_frac: 0.25
  cycle_deadline_s: 10.0
  rate_mbps: 8.0
"""

COORD_CFG = """\
db:
  namespace: {default_ns}
cluster:
  enabled: true
  kv_addr: {kv_addr}
  write_consistency: majority
  read_consistency: one
http:
  host: 127.0.0.1
  port: {port}
tick_interval_s: 0.5
tenants:
  tenants:
{tenant_quota_yaml}
"""

AGG_CFG = """\
instance_id: rig-agg
n_shards: 2
ingest:
  host: 127.0.0.1
  port: {port}
flush_interval_s: 1.0
# the aggregator has no HTTP API; the shared debug surface serves its
# /debug/profile (profiler top-N, contended locks, stall watchdog)
debug_port: {debug_port}
"""


class RigCluster:
    """A real multi-process deployment: N dbnodes (RF=replica_factor)
    + an R-replica quorum kvd metadata plane + coordinator + aggregator,
    every process spawned through em agents with M3_TPU_FAULTS_EXIT=1
    armed (crash-mode fault rules become REAL process deaths)."""

    def __init__(self, workdir: str, tenants: tuple,
                 tenant_quotas: dict[str, dict] | None = None,
                 n_dbnodes: int = 2, kvd_replicas: int = 3,
                 n_shards: int = 4, seed: int = 0):
        import os as _os
        import pathlib

        from m3_tpu.tools.em import AgentClient, ClusterEnv, EmAgent

        free_port = _free_port
        self.workdir = workdir
        self.tenants = tuple(tenants)
        self.seed = seed
        self.n_shards = n_shards
        self._agent_objs = []
        self.agents: dict[str, AgentClient] = {}
        repo_root = str(pathlib.Path(__file__).resolve().parents[2])
        self.base_service_env = {
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": repo_root,
            "M3_TPU_FAULTS_EXIT": "1",  # crash rules kill the process
            # the always-on profiling plane, armed fleet-wide: sampling
            # profiler + stall watchdog (M3_TPU_PROFILE) and per-class
            # lock-wait profiling (M3_TPU_LOCK_PROFILE) run for the
            # whole schedule — the trajectory artifact reads them back
            "M3_TPU_PROFILE": "1",
            "M3_TPU_LOCK_PROFILE": "1",
        }
        agent_names = ([f"kv{i}" for i in range(kvd_replicas)]
                       + [f"h{i}" for i in range(n_dbnodes)] + ["hc"])
        for name in agent_names:
            a = EmAgent(_os.path.join(workdir, name), "127.0.0.1:0",
                        agent_id=name)
            self._agent_objs.append(a)
            self.agents[name] = AgentClient(f"http://127.0.0.1:{a.port}")
        self.env = ClusterEnv(self.agents)
        self.node_ports = {f"node{i}": free_port() for i in range(n_dbnodes)}
        self.coord_port = free_port()
        self.agg_port = free_port()
        self.agg_debug_port = free_port()
        self.kvd_ports = {f"kv{i}": free_port() for i in range(kvd_replicas)}
        self.kv_addr = ""
        self.tenant_quotas = tenant_quotas or {}
        self.replica_factor = min(2, n_dbnodes)
        self._next_dbnode = n_dbnodes  # next node index for add_dbnode

    # -- deployment --

    def deploy(self, wait_s: float = 120.0) -> None:
        from m3_tpu.cluster import placement as pl
        from m3_tpu.cluster.kvd import KvdClient
        from m3_tpu.cluster.placement import Instance, initial_placement
        from m3_tpu.query.admin import store_namespace_registry
        from m3_tpu.tools.em import ClusterEnv

        # 1. quorum kvd metadata plane, one replica per kv* agent
        self.kv_addr = self.env.deploy_kvd_quorum(
            self.kvd_ports, env=self.base_service_env)
        kv = KvdClient(self.kv_addr, timeout_s=5.0)

        def plane_up():
            try:
                kv.keys()
                return True
            except Exception:  # noqa: BLE001
                return False

        ClusterEnv.wait_until(plane_up, timeout_s=wait_s, desc="kvd quorum up")

        # 2. placement (RF over the dbnodes) + the tenant namespaces in
        #    the registry (nodes and coordinator both sync from it)
        node_ids = sorted(self.node_ports)
        p = initial_placement(
            [Instance(nid, isolation_group=f"g{i}")
             for i, nid in enumerate(node_ids)],
            n_shards=self.n_shards, replica_factor=self.replica_factor)
        for nid in node_ids:
            p = pl.mark_available(p, nid)
            p.instances[nid].endpoint = \
                f"http://127.0.0.1:{self.node_ports[nid]}"
        pl.store_placement(kv, p)
        self.placement = p
        # nanosecond time unit: the rig writes irregular ns timestamps,
        # and the default SECOND unit would truncate them at every
        # snapshot/flush encode — collapsing datapoints that share a
        # wall second and breaking the exact-match loss audit
        store_namespace_registry(kv, {t: {"time_unit": "ns"}
                                      for t in self.tenants})
        self._kv = kv

        # 3. dbnodes
        for i, nid in enumerate(node_ids):
            agent = self.agents[f"h{i}"]
            agent.put_file("node.yml", NODE_CFG.format(
                workdir=f"{self.workdir}/h{i}",
                n_shards=self.n_shards, node_id=nid,
                kv_addr=self.kv_addr, port=self.node_ports[nid]))
            agent.start(nid, "m3_tpu.services.dbnode", "node.yml",
                        env=self.base_service_env)
        for nid, port in self.node_ports.items():
            ClusterEnv.wait_until(
                lambda p=port: _http_ok(f"http://127.0.0.1:{p}/health"),
                timeout_s=wait_s, desc=f"{nid} health")

        # 4. coordinator (admission quotas in config; runtime-tunable
        #    via the m3_tpu.tenants KV key) + aggregator
        # no quotas -> list each tenant with no limits: the hand-rolled
        # YAML parser has no flow syntax, so a literal `{}` won't parse
        quota_yaml = "".join(
            f"    {t}:\n" + "".join(f"      {k}: {v}\n"
                                    for k, v in (q or {}).items())
            for t, q in self.tenant_quotas.items()) \
            or "".join(f"    {t}:\n" for t in self.tenants)
        self.agents["hc"].put_file("coord.yml", COORD_CFG.format(
            default_ns=self.tenants[0], kv_addr=self.kv_addr,
            port=self.coord_port, tenant_quota_yaml=quota_yaml))
        self.agents["hc"].start("coord", "m3_tpu.services.coordinator",
                                "coord.yml", env=self.base_service_env)
        self.agents["hc"].put_file(
            "agg.yml", AGG_CFG.format(port=self.agg_port,
                                      debug_port=self.agg_debug_port))
        self.agents["hc"].start("agg", "m3_tpu.services.aggregator",
                                "agg.yml", env=self.base_service_env)
        ClusterEnv.wait_until(
            lambda: _http_ok(f"http://127.0.0.1:{self.coord_port}/ready",
                             key="ready"),
            timeout_s=wait_s, desc="coordinator ready")

    def session(self):
        """A fresh client session over the placement (the rig's write
        path — bursty batches through session.write_many)."""
        from m3_tpu.client.breaker import BreakerConfig
        from m3_tpu.client.http_conn import HTTPNodeConnection
        from m3_tpu.client.session import Session
        from m3_tpu.cluster.topology import ConsistencyLevel, TopologyMap

        connections = {
            iid: HTTPNodeConnection(inst.endpoint, timeout_s=5.0)
            for iid, inst in self.placement.instances.items() if inst.endpoint
        }
        return Session(
            TopologyMap(self.placement), connections,
            write_consistency=ConsistencyLevel.MAJORITY,
            read_consistency=ConsistencyLevel.ONE,
            # short cooldown: the rig WANTS to observe recovery inside
            # its budget, not wait out a production-shaped 5s shed window
            breaker_config=BreakerConfig(open_timeout_s=1.0,
                                         retry_jitter_frac=0.25),
        )

    def profile_ports(self) -> dict[str, int]:
        """Every port serving /debug/profile: coordinator + dbnodes on
        their APIs, the aggregator on its debug surface (kvd replicas
        arm the profiler too; their surface is config-opt-in)."""
        return {"coordinator": self.coord_port,
                "aggregator": self.agg_debug_port,
                **dict(self.node_ports)}

    def chaos_targets(self) -> list[tuple]:
        """Every killable process: dbnodes, one kvd replica, the
        aggregator. The coordinator is the measurement plane and stays
        up (its loss is a different drill)."""
        out = []
        for nid in sorted(self.node_ports):
            out.append((self._agent_of(nid), nid, "dbnode"))
        out.append((sorted(self.kvd_ports)[0], "kvd", "kvd"))
        out.append(("hc", "agg", "aggregator"))
        return out

    # -- elasticity verbs (ROADMAP #6(b)) ----------------------------------
    # The rig's only lever is the placement CAS: shard streaming, digest
    # verification, cutover, and the donor grace tick all run inside the
    # nodes (services/handoff.py controllers).

    def _agent_of(self, nid: str) -> str:
        """dbnode id -> its em agent name (node{i} lives on h{i})."""
        return "h" + nid.removeprefix("node")

    def refresh_placement(self) -> None:
        from m3_tpu.cluster import placement as pl

        loaded = pl.load_placement(self._kv)
        if loaded is not None:
            self.placement = loaded[0]

    def add_dbnode(self, wait_s: float = 120.0) -> str:
        """Scale-out verb: spawn a NEW dbnode process on a fresh em
        agent, wait for health, then CAS it into the live placement.
        Its fair share of shards lands INITIALIZING (sourced from the
        donors, which go LEAVING but keep serving); the nodes' handoff
        controllers do the rest."""
        import os as _os

        from m3_tpu.cluster import placement as pl
        from m3_tpu.cluster.placement import Instance
        from m3_tpu.tools.em import AgentClient, ClusterEnv, EmAgent

        i = self._next_dbnode
        self._next_dbnode += 1
        name, nid = f"h{i}", f"node{i}"
        a = EmAgent(_os.path.join(self.workdir, name), "127.0.0.1:0",
                    agent_id=name)
        self._agent_objs.append(a)
        self.agents[name] = AgentClient(f"http://127.0.0.1:{a.port}")
        port = _free_port()
        self.node_ports[nid] = port
        self.agents[name].put_file("node.yml", NODE_CFG.format(
            workdir=f"{self.workdir}/{name}", n_shards=self.n_shards,
            node_id=nid, kv_addr=self.kv_addr, port=port))
        self.agents[name].start(nid, "m3_tpu.services.dbnode", "node.yml",
                                env=self.base_service_env)
        ClusterEnv.wait_until(
            lambda: _http_ok(f"http://127.0.0.1:{port}/health"),
            timeout_s=wait_s, desc=f"{nid} health")
        endpoint = f"http://127.0.0.1:{port}"

        def add(cur):
            return pl.add_instance(
                cur, Instance(nid, isolation_group=f"g{i}",
                              endpoint=endpoint))

        pl.cas_update_placement(self._kv, add)
        self.refresh_placement()
        return nid

    def drain_dbnode(self, nid: str) -> None:
        """Paced-drain verb: CAS remove_instance — every shard the node
        holds goes LEAVING with a new owner INITIALIZING from it; the
        receiving nodes stream at the shared repair rate budget and cut
        over per shard. The process keeps serving until retired."""
        from m3_tpu.cluster import placement as pl

        pl.cas_update_placement(
            self._kv, lambda cur: pl.remove_instance(cur, nid))
        self.refresh_placement()

    def retire_dbnode(self, nid: str) -> None:
        """Stop a fully-drained node's process and forget its port (only
        after wait_placement_settled shows it out of the placement)."""
        agent = self.agents[self._agent_of(nid)]
        try:
            agent.stop(nid)
        except Exception:  # noqa: BLE001 - already dead is drained enough
            pass
        self.node_ports.pop(nid, None)

    def restart_dbnode(self, nid: str, wait_s: float = 120.0) -> None:
        """Rolling-restart verb: SIGKILL (crash consistency — WAL
        replay, no graceful flush) then relaunch and wait for health
        before the caller moves to the next node."""
        from m3_tpu.tools.em import ClusterEnv

        agent = self.agents[self._agent_of(nid)]
        agent.kill(nid)
        agent.start(nid, env=self.base_service_env, grace_s=0.5)
        port = self.node_ports[nid]
        ClusterEnv.wait_until(
            lambda: _http_ok(f"http://127.0.0.1:{port}/health"),
            timeout_s=wait_s, desc=f"{nid} back after restart")

    def wait_placement_settled(self, timeout_s: float = 120.0) -> None:
        """Poll KV until every shard everywhere is AVAILABLE — streamed,
        digest-verified, and cut over by the nodes themselves."""
        from m3_tpu.cluster.placement import ShardState
        from m3_tpu.tools.em import ClusterEnv

        def settled() -> bool:
            self.refresh_placement()
            return all(sh.state is ShardState.AVAILABLE
                       for inst in self.placement.instances.values()
                       for sh in inst.shards.values())

        ClusterEnv.wait_until(settled, timeout_s=timeout_s, every_s=0.5,
                              desc="placement settled (all AVAILABLE)")

    def set_tenant_quotas_kv(self, doc: dict) -> None:
        """Runtime quota update THROUGH the metadata plane: the
        coordinator's KV watch applies it live, no restart."""
        self._kv.set("m3_tpu.tenants", json.dumps(doc).encode())

    def wait_all_healthy(self, timeout_s: float = 120.0) -> None:
        from m3_tpu.tools.em import ClusterEnv

        for nid, port in self.node_ports.items():
            ClusterEnv.wait_until(
                lambda p=port: _http_ok(f"http://127.0.0.1:{p}/health"),
                timeout_s=timeout_s, desc=f"{nid} healthy after chaos")
        ClusterEnv.wait_until(
            lambda: _http_ok(f"http://127.0.0.1:{self.coord_port}/ready",
                             key="ready"),
            timeout_s=timeout_s, desc="coordinator healthy after chaos")

    def teardown(self) -> None:
        try:
            if getattr(self, "_kv", None) is not None:
                self._kv.close()
        except Exception:  # noqa: BLE001
            pass
        self.env.teardown()
        for a in self._agent_objs:
            try:
                a.close()
            except Exception:  # noqa: BLE001
                pass


def _http_ok(url: str, key: str = "ok", timeout_s: float = 5.0) -> bool:
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            return bool(json.loads(r.read().decode()).get(key))
    except Exception:  # noqa: BLE001
        return False


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def node_placement(port: int, timeout_s: float = 10.0) -> dict:
    """One node's /debug/placement: placement version, owned/grace
    shards, and the handoff controller's per-shard progress records."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/placement",
            timeout=timeout_s) as r:
        return json.loads(r.read().decode())


# ---------------------------------------------------------------------------
# the full run: chaos phase + verification + noisy-tenant phase


def run_production_rig(workdir: str, seconds: float = 20.0, seed: int = 7,
                       slo_p99_ms: float = 5000.0) -> dict:
    """Deploy the real cluster, run the seeded kill/partition schedule
    under live load, verify zero acked-write loss and the warning
    contract, then run the noisy-tenant isolation phase (runtime quota
    pushed through kvd; pair-median p99 from the server histograms).
    Returns the full report; raises AssertionError on contract breach
    only from the pytest wrapper — here every fact lands in the report."""
    tenants = ("steady", "noisy", "bulk0", "bulk1")
    cluster = RigCluster(
        workdir, tenants,
        # explicit (label-bounded) quotas; noisy starts UNLIMITED —
        # the KV push mid-run is what starts shedding it
        tenant_quotas={"steady": {"queries_per_sec": 500},
                       "noisy": {}},
        seed=seed)
    report: dict = {"seed": seed, "seconds": seconds}
    try:
        cluster.deploy()
        session = cluster.session()
        ledger = WriteLedger()

        # ---- phase 1: steady load + seeded kill/partition schedule ----
        chaos_s = max(6.0, seconds * 0.6)
        cfg = RigConfig(seed=seed, tenants=tenants, duration_s=chaos_s,
                        slo_p99_ms=slo_p99_ms)
        rig = Rig(cfg, session_write_fn(session),
                  http_query_fn(cluster.coord_port), ledger=ledger)
        # the soak-trajectory recorder runs across EVERY phase: QPS,
        # p99, RSS, contended locks and stall events over time — the
        # first-class artifact of the profiling & saturation plane
        recorder = TrajectoryRecorder(cluster.coord_port,
                                      cluster.profile_ports(), rig=rig)
        recorder.start()
        schedule = ChaosSchedule.generate(seed, chaos_s,
                                          cluster.chaos_targets())
        report["schedule"] = [e.to_doc() for e in schedule]
        runner = ChaosRunner(cluster.agents, schedule,
                             base_env={s: cluster.base_service_env
                                       for _a, s, _k in
                                       cluster.chaos_targets()},
                             seed=seed)
        runner.start()
        phase1 = rig.run(chaos_s)
        runner.join(60.0)
        report["phase1"] = phase1
        report["chaos_executed"] = runner.executed
        report["chaos_errors"] = runner.errors

        # ---- recovery + zero acked-write loss ----
        cluster.wait_all_healthy()
        verify_session = cluster.session()  # fresh breakers for the audit
        # /health answers a tick before a restarted node has re-synced
        # its tenant namespaces from the registry: gate the audit on
        # every tenant actually ANSWERING reads, not on liveness
        from m3_tpu.tools.em import ClusterEnv

        def _tenants_readable():
            try:
                for t in tenants:
                    verify_session.fetch(t, b"rig-readiness-probe", 0, 1)
                return True
            except Exception:  # noqa: BLE001 - not ready yet
                return False

        ClusterEnv.wait_until(_tenants_readable, timeout_s=90,
                              desc="tenant namespaces readable after chaos")
        report["verify"] = ledger.verify(session_fetch_fn(verify_session))

        # ---- convergence audit: anti-entropy actually converged ----
        # the replica that slept through its kill/partition window holds
        # less data than its partner; the nodes' OWN repair daemons must
        # reach per-(shard, block) rollup-digest equality within the
        # cycle budget — nothing here invokes repair
        report["convergence"] = convergence_audit(
            cluster, tenants, budget_cycles=10, interval_s=1.0)

        # ---- stall drill: the watchdog proves itself on a live node ----
        # one dbnode restarted under a tick-wedging fault plan; its OWN
        # watchdog must flag the stalled loop (with the wedged thread's
        # stack) on the fault-exempt /debug/profile before the heal
        report["stall_drill"] = stall_drill(cluster, recorder)
        cluster.wait_all_healthy()

        # ---- phase 2: noisy-tenant isolation under a node kill ----
        # runtime quota push through the kvd metadata plane: noisy goes
        # from unlimited to 3 qps LIVE; steady keeps its headroom
        cluster.set_tenant_quotas_kv({
            "tenants": {"steady": {"queries_per_sec": 500},
                        "noisy": {"queries_per_sec": 3.0,
                                  "burst_s": 1.0}}})
        time.sleep(1.5)  # watch delivery
        qfn = http_query_fn(cluster.coord_port)
        shed_counts = {"noisy": 0, "steady_shed": 0}
        kill_agent, kill_service, _ = cluster.chaos_targets()[0]

        def run_window(i: int) -> None:
            # kill a dbnode in the middle window: isolation must hold
            # WHILE nodes are dying
            if i == 1:
                cluster.agents[kill_agent].kill(kill_service)
            end = time.monotonic() + max(1.5, seconds * 0.08)
            k = 0
            while time.monotonic() < end:
                status, _doc, _h = qfn("noisy", "rig_metric_1",
                                       int(time.time()) - 60,
                                       int(time.time()), 10)
                if status == 429:
                    shed_counts["noisy"] += 1
                status, _doc, _h = qfn("steady", f"rig_metric_{k % 8}",
                                       int(time.time()) - 60,
                                       int(time.time()), 10)
                if status == 429:
                    shed_counts["steady_shed"] += 1
                k += 1
            if i == 1:
                cluster.agents[kill_agent].start(kill_service, grace_s=0.5)

        p99s = windowed_p99s_ms(
            lambda: scrape_metrics(cluster.coord_port),
            "coordinator_tenant_request_seconds", {"namespace": "steady"},
            run_window, n_windows=4)
        report["noisy_phase"] = {
            "steady_window_p99s_ms": p99s,
            "steady_pair_median_p99_ms": median_p99_ms(p99s),
            "noisy_sheds": shed_counts["noisy"],
            "steady_sheds": shed_counts["steady_shed"],
            "slo_p99_ms": slo_p99_ms,
        }
        cluster.wait_all_healthy()
        recorder.stop()
        try:
            recorder.sample_once()  # one final post-heal row
        except Exception:  # noqa: BLE001 - best-effort tail sample
            pass
        trajectory = recorder.artifact()
        report["trajectory"] = trajectory
        try:
            import os as _os

            with open(_os.path.join(workdir, "trajectory.json"), "w") as f:
                json.dump(trajectory, f, indent=2, default=str)
        except OSError:
            pass
        report["final_heartbeats"] = {
            name: ("ok" if "services" in hb else hb.get("error", "?"))
            for name, hb in cluster.env.heartbeats().items()
        }
    finally:
        cluster.teardown()
    return report


def run_elasticity_episode(workdir: str, seconds: float = 20.0,
                           seed: int = 7,
                           slo_p99_ms: float = 5000.0) -> dict:
    """ROADMAP #6(b), the elasticity episode: add-node -> paced drain ->
    rolling restart, all under live zipf load, overlapping a seeded
    chaos schedule on the metadata/aggregation planes (a kvd replica and
    the aggregator; the dbnodes' failures are the episode's own verbs).
    The placement CAS verbs are the ONLY lever the rig pulls — shard
    streaming, digest verification, cutover, and the donor grace tick
    all run inside the nodes (services/handoff.py). Proven at the end:
    zero acked-write loss, every shard AVAILABLE on the post-change
    owners, rollup convergence, and a client read p99 that stayed
    bounded while the topology churned (trajectory rows annotated with
    the topology events)."""
    from m3_tpu.client.http_conn import HTTPNodeConnection
    from m3_tpu.client.topology_watch import PlacementWatcher
    from m3_tpu.tools.em import ClusterEnv

    tenants = ("elastic0", "elastic1")
    cluster = RigCluster(workdir, tenants, n_dbnodes=2, n_shards=4,
                         seed=seed)
    report: dict = {"seed": seed, "seconds": seconds}
    watcher = None
    recorder = None
    try:
        cluster.deploy()
        session = cluster.session()
        # the hot-swap plane under test: the load session follows
        # placement changes through the watcher, never a rebuild
        watcher = PlacementWatcher(
            cluster._kv, session,
            connection_factory=lambda ep: HTTPNodeConnection(
                ep, timeout_s=5.0))
        watcher.poll()
        watcher.start(0.5)
        ledger = WriteLedger()
        cfg = RigConfig(seed=seed, tenants=tenants, duration_s=seconds,
                        slo_p99_ms=slo_p99_ms)
        rig = Rig(cfg, session_write_fn(session),
                  http_query_fn(cluster.coord_port), ledger=ledger)
        recorder = TrajectoryRecorder(cluster.coord_port,
                                      cluster.profile_ports(), rig=rig)
        recorder.start()
        targets = [t for t in cluster.chaos_targets() if t[2] != "dbnode"]
        schedule = ChaosSchedule.generate(seed, max(8.0, seconds), targets)
        report["schedule"] = [e.to_doc() for e in schedule]
        runner = ChaosRunner(cluster.agents, schedule,
                             base_env={s: cluster.base_service_env
                                       for _a, s, _k in targets},
                             seed=seed)
        # load loops driven directly (not rig.run): the episode's verbs
        # pace the run, and the loops stop when the last verb lands
        writer = threading.Thread(target=rig._writer_loop, daemon=True)
        querier = threading.Thread(target=rig._query_loop, daemon=True)
        writer.start()
        querier.start()
        runner.start()
        slice_s = max(2.0, seconds / 5.0)
        time.sleep(slice_s)  # baseline load on the 2-node deployment

        # ---- scale out: add-node, handoff streams onto it live ----
        new_nid = cluster.add_dbnode()
        recorder.annotate("add_node", node=new_nid)
        cluster.wait_placement_settled()
        recorder.annotate("handoff_settled", node=new_nid)
        report["handoff_status"] = {
            nid: node_placement(port)
            for nid, port in cluster.node_ports.items()}
        time.sleep(slice_s)

        # ---- paced drain of an original node ----
        drain_nid = sorted(cluster.node_ports)[0]
        recorder.annotate("drain", node=drain_nid)
        cluster.drain_dbnode(drain_nid)
        cluster.wait_placement_settled()
        time.sleep(1.5)  # the donor's grace tick: it still serves reads
        cluster.retire_dbnode(drain_nid)
        recorder.annotate("drained", node=drain_nid)
        report["drained_node"] = drain_nid
        time.sleep(slice_s)

        # ---- rolling restart (SIGKILL + WAL replay) of survivors ----
        for nid in sorted(cluster.node_ports):
            recorder.annotate("restart", node=nid)
            cluster.restart_dbnode(nid)
        time.sleep(slice_s)

        runner.join(60.0)
        rig._stop.set()
        writer.join(10.0)
        querier.join(10.0)
        report["phase"] = rig.report()
        report["chaos_executed"] = runner.executed
        report["chaos_errors"] = runner.errors

        # ---- verification on the post-change topology ----
        cluster.wait_all_healthy()
        verify_session = cluster.session()

        def _tenants_readable():
            try:
                for t in tenants:
                    verify_session.fetch(t, b"rig-readiness-probe", 0, 1)
                return True
            except Exception:  # noqa: BLE001 - not ready yet
                return False

        ClusterEnv.wait_until(_tenants_readable, timeout_s=90,
                              desc="tenants readable after elasticity")
        report["verify"] = ledger.verify(session_fetch_fn(verify_session))
        report["convergence"] = convergence_audit(
            cluster, tenants, budget_cycles=10, interval_s=1.0)
        report["final_placement"] = {
            iid: {str(sh.id): sh.state.value
                  for sh in inst.shards.values()}
            for iid, inst in cluster.placement.instances.items()}
        recorder.stop()
        report["trajectory"] = recorder.artifact()
        try:
            import os as _os

            with open(_os.path.join(workdir, "elasticity.json"), "w") as f:
                json.dump(report["trajectory"], f, indent=2, default=str)
        except OSError:
            pass
    finally:
        if watcher is not None:
            watcher.stop()
        if recorder is not None:
            recorder.stop()
        cluster.teardown()
    return report


def run_standing_rules_episode(workdir: str, seconds: float = 20.0,
                               seed: int = 11,
                               slo_p99_ms: float = 5000.0) -> dict:
    """ISSUE-18's standing-query episode: a standing-rules-only ruleset
    lands in KV mid-load; the coordinator's flush loop evaluates the
    rules against the quorum cluster while a seeded chaos schedule kills
    dbnodes, a kvd replica and the aggregator (the coordinator — the
    evaluation host — stays up, as in the production episode). Proven at
    the end: zero acked-write loss for the raw load, registry-sync of
    the rule-created namespace, rollup convergence over the tenants AND
    that namespace, standing outputs present and EQUAL across their
    aggregated/raw dual-write legs, every rule recovered to an
    error-free caught-up state (via /debug/standing — a flush that
    failed its output quorum holds the watermark and retries), bounded
    rule-eval lag (p99 of aggregator_standing_rule_eval_lag_seconds,
    annotated onto the trajectory per slice), and the misrouting
    honesty gate: standing rules alone never mark a tier complete, so
    cheapest-tier resolution must keep EVERY query of the episode on
    raw."""
    from m3_tpu.metrics import rules_store
    from m3_tpu.query.admin import load_namespace_registry
    from m3_tpu.tools.em import ClusterEnv

    tenants = ("rules0", "rules1")
    out_ns = "aggregated_1s_10m"  # StoragePolicy("1s:10m").namespace_name
    lag_bound_s = 30.0
    lag_family = "aggregator_standing_rule_eval_lag_seconds"
    ruleset_doc = {"standing": [
        # scalar aggregate over a hot metric
        {"name": "std:rig0:sum", "expr": "sum(rig_metric_0)",
         "policy": "1s:10m"},
        # grouped aggregate: the sid grouping label rides the output
        {"name": "std:rig1:by_sid", "expr": "sum by (sid) (rig_metric_1)",
         "policy": "1s:10m"},
        # avg + static rule labels on every output series
        {"name": "std:rig2:avg", "expr": "avg(rig_metric_2)",
         "policy": "1s:10m", "labels": {"plane": "standing"}},
        # absent input: must evaluate cleanly forever, writing nothing
        {"name": "std:absent", "expr": "sum(rig_metric_never)",
         "policy": "1s:10m"},
    ]}
    cluster = RigCluster(workdir, tenants, n_dbnodes=2, n_shards=4,
                         seed=seed)
    report: dict = {"seed": seed, "seconds": seconds, "out_ns": out_ns,
                    "lag_bound_s": lag_bound_s}
    recorder = None
    try:
        cluster.deploy()
        session = cluster.session()
        ledger = WriteLedger()
        chaos_s = max(8.0, seconds)
        cfg = RigConfig(seed=seed, tenants=tenants, duration_s=chaos_s,
                        slo_p99_ms=slo_p99_ms)
        rig = Rig(cfg, session_write_fn(session),
                  http_query_fn(cluster.coord_port), ledger=ledger)
        recorder = TrajectoryRecorder(cluster.coord_port,
                                      cluster.profile_ports(), rig=rig)
        recorder.start()
        # the ruleset lands through the same KV watch a live operator
        # uses; the coordinator builds its downsampler from the update
        version = rules_store.store_ruleset_doc(cluster._kv, ruleset_doc)
        report["ruleset_version"] = version
        recorder.annotate("ruleset_stored", version=version,
                          rules=len(ruleset_doc["standing"]))
        schedule = ChaosSchedule.generate(seed, chaos_s,
                                          cluster.chaos_targets())
        report["schedule"] = [e.to_doc() for e in schedule]
        runner = ChaosRunner(cluster.agents, schedule,
                             base_env={s: cluster.base_service_env
                                       for _a, s, _k in
                                       cluster.chaos_targets()},
                             seed=seed)
        writer = threading.Thread(target=rig._writer_loop, daemon=True)
        querier = threading.Thread(target=rig._query_loop, daemon=True)
        writer.start()
        querier.start()
        runner.start()

        # registry-sync leg: the first evaluation creates out_ns and the
        # coordinator lands it in the KV namespace registry, where the
        # dbnodes' sync_namespaces tick picks it up before quorum writes
        # can land — so chaos or not, the namespace must appear
        ClusterEnv.wait_until(
            lambda: out_ns in load_namespace_registry(cluster._kv),
            timeout_s=60, desc=f"{out_ns} in KV namespace registry")
        recorder.annotate("tier_namespace_registered", namespace=out_ns)
        report["registry_entry"] = \
            load_namespace_registry(cluster._kv).get(out_ns)

        # eval-lag trajectory: per-slice p99 of the coordinator's
        # rule-eval-lag histogram, annotated onto the soak trajectory
        slice_s = max(2.0, chaos_s / 4.0)
        prev = parse_histogram(scrape_metrics(cluster.coord_port),
                               lag_family)
        lag_slices = []
        deadline = time.monotonic() + chaos_s
        while time.monotonic() < deadline:
            time.sleep(min(slice_s, max(0.1, deadline - time.monotonic())))
            try:
                cur = parse_histogram(scrape_metrics(cluster.coord_port),
                                      lag_family)
            except Exception:  # noqa: BLE001 - scrape raced a fault
                continue
            p99_ms = hist_p99_ms(hist_delta(prev, cur))
            prev = cur
            p99_s = None if p99_ms is None else round(p99_ms / 1e3, 3)
            lag_slices.append(p99_s)
            recorder.annotate("rule_eval_lag", p99_s=p99_s)
        runner.join(60.0)
        rig._stop.set()
        writer.join(10.0)
        querier.join(10.0)
        report["phase"] = rig.report()
        report["chaos_executed"] = runner.executed
        report["chaos_errors"] = runner.errors
        report["rule_eval_lag_slices_s"] = lag_slices

        # ---- recovery: heal, then the standing plane must go clean ----
        cluster.wait_all_healthy()
        verify_session = cluster.session()

        def _readable():
            try:
                for t in (*tenants, out_ns):
                    verify_session.fetch(t, b"rig-readiness-probe", 0, 1)
                return True
            except Exception:  # noqa: BLE001 - not ready yet
                return False

        ClusterEnv.wait_until(_readable, timeout_s=90,
                              desc="tenants + tier readable after chaos")
        report["verify"] = ledger.verify(session_fetch_fn(verify_session))

        def _standing_status():
            url = (f"http://127.0.0.1:{cluster.coord_port}"
                   "/debug/standing")
            with urllib.request.urlopen(url, timeout=10) as r:
                return json.loads(r.read().decode())

        def _standing_clean():
            # every rule error-free, evaluated at least once, watermark
            # within the lag bound of now: an output write that failed
            # its quorum during chaos held last_end and retried — the
            # plane must close back up on its own after the heal
            try:
                doc = _standing_status()
            except Exception:  # noqa: BLE001 - surface racing the heal
                return False
            rules = doc.get("rules", {})
            if set(rules) != {r["name"] for r in ruleset_doc["standing"]}:
                return False
            now_ns = time.time_ns()
            return all(
                st["error"] is None and st["evals"] > 0
                and now_ns - st["last_end_ns"] <= lag_bound_s * 1e9
                for st in rules.values())

        ClusterEnv.wait_until(_standing_clean, timeout_s=90,
                              desc="standing rules error-free + caught up")
        report["standing_status"] = _standing_status()

        # convergence over the tenants AND the rule-created namespace:
        # standing outputs are replicated quorum writes like any other —
        # the repair daemons must converge them too
        report["convergence"] = convergence_audit(
            cluster, (*tenants, out_ns), budget_cycles=10, interval_s=1.0)

        # ---- output audit: presence + dual-write leg parity ----
        # each concrete rule's outputs, read back through the full query
        # path from BOTH legs: the aggregated namespace and the raw
        # write_raw leg in the source tenant. Values at common grid
        # points must be bitwise equal — the legs are one entries batch
        qfn = http_query_fn(cluster.coord_port)
        end_s = int(time.time())
        start_s = end_s - int(chaos_s) - 30
        audit = {}
        parity_ok = True
        total_points = 0
        for rule in ruleset_doc["standing"][:3]:
            name = rule["name"]
            agg = _series_points(qfn(out_ns, name, start_s, end_s, 1)[1])
            raw = _series_points(
                qfn(tenants[0], name, start_s, end_s, 1)[1])
            pts = sum(len(v) for v in agg.values())
            total_points += pts
            common = mismatched = 0
            for key, a_vals in agg.items():
                r_vals = raw.get(key, {})
                for ts, av in a_vals.items():
                    rv = r_vals.get(ts)
                    if rv is None:
                        continue
                    common += 1
                    if av != rv:
                        mismatched += 1
            if mismatched or not common or not pts:
                parity_ok = False
            audit[name] = {"agg_series": len(agg), "agg_points": pts,
                           "raw_series": len(raw),
                           "common_points": common,
                           "mismatched": mismatched}
        report["output_audit"] = audit
        report["output_points"] = total_points
        report["leg_parity_ok"] = parity_ok

        # ---- misrouting honesty gate ----
        text = scrape_metrics(cluster.coord_port)
        tier_reads = {dict(k).get("tier", "?"): v for k, v in
                      parse_counters(text, "query_tier_reads").items()}
        report["tier_reads"] = tier_reads
        report["no_misrouted_reads"] = not any(
            t.startswith("aggregated") for t in tier_reads)
        report["standing_counters"] = {
            leaf: sum(parse_counters(
                text, f"aggregator_standing_rules_{leaf}").values())
            for leaf in ("evaluated", "invalidated", "skipped", "errors")}
        p99 = hist_p99_ms(parse_histogram(text, lag_family))
        report["rule_eval_lag_p99_s"] = (None if p99 is None
                                         else round(p99 / 1e3, 3))
        recorder.stop()
        report["trajectory"] = recorder.artifact()
        try:
            import os as _os

            with open(_os.path.join(workdir, "standing_rules.json"),
                      "w") as f:
                json.dump(report["trajectory"], f, indent=2, default=str)
        except OSError:
            pass
    finally:
        if recorder is not None:
            recorder.stop()
        cluster.teardown()
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="production chaos/load rig")
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slo-p99-ms", type=float, default=5000.0)
    ap.add_argument("--episode",
                    choices=("production", "elasticity", "standing_rules"),
                    default="production",
                    help="production = kill/partition schedule; "
                         "elasticity = add/drain/restart under load; "
                         "standing_rules = recording rules + retention "
                         "tiers under chaos")
    args = ap.parse_args(argv)
    if args.episode == "standing_rules":
        report = run_standing_rules_episode(args.workdir, args.seconds,
                                            args.seed, args.slo_p99_ms)
        print(json.dumps(report, indent=2, default=str))
        lag = report.get("rule_eval_lag_p99_s")
        ok = (not report.get("verify", {}).get("missing")
              and report.get("convergence", {}).get("converged", False)
              and not report.get("chaos_errors")
              and report.get("output_points", 0) > 0
              and report.get("leg_parity_ok", False)
              and report.get("no_misrouted_reads", False)
              and lag is not None
              and lag <= report.get("lag_bound_s", 30.0))
        return 0 if ok else 1
    if args.episode == "elasticity":
        report = run_elasticity_episode(args.workdir, args.seconds,
                                        args.seed, args.slo_p99_ms)
        print(json.dumps(report, indent=2, default=str))
        ok = (not report.get("verify", {}).get("missing")
              and report.get("convergence", {}).get("converged", False)
              and not report.get("chaos_errors"))
        return 0 if ok else 1
    report = run_production_rig(args.workdir, args.seconds, args.seed,
                                args.slo_p99_ms)
    print(json.dumps(report, indent=2, default=str))
    traj = report.get("trajectory", {})
    ok = (not report.get("verify", {}).get("missing")
          and report.get("convergence", {}).get("converged", False)
          and report.get("noisy_phase", {}).get("noisy_sheds", 0) > 0
          and bool(traj.get("stall_events"))
          and bool(traj.get("contended_locks")))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
