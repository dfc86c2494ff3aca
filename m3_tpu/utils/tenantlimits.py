"""Per-tenant admission control: quotas keyed by namespace.

Role parity with the reference's tenant isolation seams
(/root/reference/src/dbnode/storage/limits — per-query/per-tenant
resource ceilings — and src/x/ratelimit): one hot namespace must degrade
*itself*, never the node. The coordinator consults this controller at
every ingest and query entrypoint (query/api.py):

- **datapoints/sec** and **queries/sec** token buckets per tenant
  (tenant == namespace, the reference's multi-tenancy key);
- a **live series-cardinality ceiling** checked against the storage
  layer's count (storage/limits.live_series) with a TTL cache so the
  hot path never scans shards per write;
- a **query-cost budget** in cost units/sec, charged POST-PAID from the
  finished query's QueryStats counters (series matched + blocks read +
  KiB decoded — the counters every read path already accrues): a tenant
  that just ran an expensive query is shed until its budget refills,
  which is the only honest way to bound cost you cannot know up front.

A shed decision raises :class:`TenantShedError`; the HTTP layer turns it
into ``429`` + ``Retry-After`` (client/breaker.py treats that as
backpressure, never as a breaker failure). Every decision point emits
per-tenant allow/shed counters into the metrics registry and the shed
path carries the ``tenant.admission.shed`` tracepoint — enforced
statically by m3lint's inv-admission-counted (``python -m tools.m3lint``).

Limits are runtime-updatable through the cluster KV (``m3_tpu.tenants``
key, same watch discipline as cluster/runtime.py) so an operator can
throttle a noisy tenant on a LIVE cluster without restarts. The clock is
injectable, so refill/burst/ceiling behavior is unit-testable in virtual
time.
"""

from __future__ import annotations

import json
import math
import threading
import time

# the kvconfig key operators write to retune tenant quotas live
# (reference kvconfig/keys.go discipline; see cluster/runtime.RUNTIME_KEY)
TENANTS_KEY = "m3_tpu.tenants"

# quota fields and their types; 0 means unlimited for every field
_QUOTA_FIELDS = {
    "datapoints_per_sec": float,
    "queries_per_sec": float,
    "max_series": int,
    "query_cost_per_sec": float,
    "burst_s": float,
}


class TenantShedError(Exception):
    """This tenant is over budget: shed THIS request (429), serve the
    rest of the node untouched."""

    def __init__(self, namespace: str, kind: str, retry_after_s: float):
        self.namespace = namespace
        self.kind = kind  # write | query | cardinality | cost
        self.retry_after_s = max(0.001, float(retry_after_s))
        super().__init__(
            f"tenant {namespace!r} over {kind} budget "
            f"(retry after {self.retry_after_s:.3f}s)"
        )


class TenantQuota:
    """One tenant's ceilings; every field 0 = unlimited. Immutable."""

    __slots__ = tuple(_QUOTA_FIELDS)

    def __init__(self, datapoints_per_sec: float = 0.0,
                 queries_per_sec: float = 0.0, max_series: int = 0,
                 query_cost_per_sec: float = 0.0, burst_s: float = 2.0):
        self.datapoints_per_sec = float(datapoints_per_sec)
        self.queries_per_sec = float(queries_per_sec)
        self.max_series = int(max_series)
        self.query_cost_per_sec = float(query_cost_per_sec)
        self.burst_s = float(burst_s)

    def __eq__(self, other):
        return isinstance(other, TenantQuota) and all(
            getattr(self, f) == getattr(other, f) for f in _QUOTA_FIELDS)

    def __repr__(self):
        body = ", ".join(f"{f}={getattr(self, f)!r}" for f in _QUOTA_FIELDS)
        return f"TenantQuota({body})"

    @classmethod
    def from_doc(cls, doc: dict) -> "TenantQuota":
        """Strictly-typed parse (the RuntimeOptions.from_json discipline):
        a mistyped KV payload must fail HERE, visibly, not inside a watch
        listener where errors are swallowed."""
        known = {}
        for k, v in (doc or {}).items():
            want = _QUOTA_FIELDS.get(k)
            if want is None:
                continue  # forward compatibility: ignore unknown keys
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"{k} must be a number, got {v!r}")
            known[k] = want(v)
        q = cls(**known)
        if q.burst_s <= 0:
            raise ValueError(f"burst_s must be > 0, got {q.burst_s!r}")
        return q


class TokenBucket:
    """Token bucket on an injectable clock. Supports both pre-paid
    (`try_take` — admission) and post-paid (`charge` — cost budgets)
    accounting; post-paid balances may go negative, which is how a
    single oversized query throttles its tenant's NEXT requests."""

    def __init__(self, rate_per_s: float, burst: float, clock=time.monotonic):
        self.rate = float(rate_per_s)
        self.burst = max(float(burst), 1.0)
        self._clock = clock
        self._tokens = self.burst  # start full: boot burst is free
        self._last = clock()
        self._lock = threading.Lock()

    def _refill_locked(self) -> None:
        now = self._clock()
        self._tokens = min(self.burst,
                           self._tokens + (now - self._last) * self.rate)
        self._last = now

    def try_take(self, n: float = 1.0) -> float:
        """Take n tokens if available; returns 0.0 on grant, else the
        seconds until the request becomes admittable (the Retry-After).

        A request LARGER than the whole burst capacity could never be
        admitted by waiting (tokens cap at burst), so — like
        cluster/runtime.PersistRateLimiter — it is granted while the
        bucket is solvent, driving the balance negative: the oversized
        batch throttles the tenant's NEXT requests instead of livelocking
        this one behind a Retry-After that can never come true."""
        with self._lock:
            self._refill_locked()
            if self._tokens >= n:
                self._tokens -= n
                return 0.0
            if self.rate <= 0:
                return math.inf
            if n > self.burst:
                if self._tokens >= 0:
                    self._tokens = max(self._tokens - n, -10.0 * self.burst)
                    return 0.0
                return -self._tokens / self.rate  # wait out the debt only
            return (n - self._tokens) / self.rate

    def charge(self, n: float) -> None:
        """Post-paid: subtract n unconditionally. Debt is capped at ten
        bursts so one pathological request cannot lock a tenant out
        forever — it throttles, it does not banish."""
        with self._lock:
            self._refill_locked()
            self._tokens = max(self._tokens - n, -10.0 * self.burst)

    def deficit_s(self) -> float:
        """Seconds until the balance is non-negative (0.0 = solvent)."""
        with self._lock:
            self._refill_locked()
            if self._tokens >= 0:
                return 0.0
            if self.rate <= 0:
                return math.inf
            return -self._tokens / self.rate

    def balance(self) -> float:
        with self._lock:
            self._refill_locked()
            return self._tokens


def query_cost(stats) -> float:
    """Cost units of one finished query, from the QueryStats counters the
    read path already accrues (utils/querystats): series matched + blocks
    read + KiB decoded. Linear and explainable — an operator can derive a
    tenant's budget from the envelope `stats` of their typical queries."""
    if stats is None:
        return 0.0
    return (float(getattr(stats, "series_matched", 0))
            + float(getattr(stats, "blocks_read", 0))
            + float(getattr(stats, "bytes_decoded", 0)) / 1024.0)


class _TenantState:
    """Per-tenant live accounting: one bucket per budgeted dimension,
    lazily built from the quota (None where unlimited)."""

    __slots__ = ("quota", "dp_bucket", "q_bucket", "cost_bucket",
                 "card_at", "card_value")

    def __init__(self, quota: TenantQuota, clock):
        self.quota = quota
        self.dp_bucket = (
            TokenBucket(quota.datapoints_per_sec,
                        quota.datapoints_per_sec * quota.burst_s, clock)
            if quota.datapoints_per_sec > 0 else None)
        self.q_bucket = (
            TokenBucket(quota.queries_per_sec,
                        quota.queries_per_sec * quota.burst_s, clock)
            if quota.queries_per_sec > 0 else None)
        self.cost_bucket = (
            TokenBucket(quota.query_cost_per_sec,
                        quota.query_cost_per_sec * quota.burst_s, clock)
            if quota.query_cost_per_sec > 0 else None)
        self.card_at = -math.inf  # cardinality cache stamp (clock units)
        self.card_value = 0


class TenantAdmission:
    """The per-tenant admission controller the coordinator consults.

    `quotas` maps namespace -> TenantQuota for explicitly configured
    tenants; `default` (optional) applies to every other namespace.
    `cardinality_source(namespace) -> int | None` supplies the live
    series count (None = unknown, e.g. remote cluster storage — the
    ceiling is then not enforced for that namespace)."""

    # bound on lazily-created tenant states: namespaces are operator-
    # created but the ?namespace= value is client-supplied (the same
    # bound discipline as CoordinatorAPI.MAX_ENGINES)
    MAX_TENANTS = 256

    def __init__(self, quotas: dict[str, TenantQuota] | None = None,
                 default: TenantQuota | None = None,
                 clock=time.monotonic, cardinality_source=None,
                 cardinality_ttl_s: float = 1.0):
        from m3_tpu.utils.instrument import default_registry

        self._clock = clock
        self._lock = threading.Lock()
        self._quotas = dict(quotas or {})
        self._default = default
        self._states: dict[str, _TenantState] = {}
        self._cardinality_source = cardinality_source
        self._cardinality_ttl_s = float(cardinality_ttl_s)
        self._scope = default_registry().root_scope("tenant")
        # cached per-(namespace, kind) counters: bounded by MAX_TENANTS x
        # the four shed kinds, and the hot path never rebuilds scopes
        self._counters: dict[tuple[str, str, str], object] = {}
        self._unwatch = None

    # -- configuration surface --

    def known_tenants(self) -> list[str]:
        with self._lock:
            return sorted(self._quotas)

    def has_quota(self, namespace: str) -> bool:
        with self._lock:
            return namespace in self._quotas or self._default is not None

    def is_configured(self, namespace: str) -> bool:
        """True only for EXPLICITLY configured tenants (metric-label
        bounding: default-quota namespaces are client-supplied strings)."""
        with self._lock:
            return namespace in self._quotas

    def set_quotas(self, quotas: dict[str, TenantQuota],
                   default: TenantQuota | None = None) -> None:
        """Swap the whole quota table (the KV watch path). Live bucket
        state is KEPT for tenants whose quota is unchanged — an operator
        tightening tenant A must not hand tenant B a fresh burst — and
        rebuilt (full) where the quota actually changed."""
        with self._lock:
            old_states = self._states
            self._quotas = dict(quotas)
            self._default = default
            self._states = {}
            for ns, st in old_states.items():
                new_q = self._quota_for_locked(ns)
                if new_q is not None and new_q == st.quota:
                    self._states[ns] = st

    def _quota_for_locked(self, namespace: str) -> TenantQuota | None:
        return self._quotas.get(namespace, self._default)

    def _state(self, namespace: str) -> _TenantState | None:
        with self._lock:
            st = self._states.get(namespace)
            if st is not None:
                return st
            quota = self._quota_for_locked(namespace)
            if quota is None:
                return None
            if len(self._states) >= self.MAX_TENANTS:
                # drop an arbitrary non-configured entry (same recycling
                # rule as the engine cache: correctness never depends on
                # accumulated bucket state)
                for key in list(self._states):
                    if key not in self._quotas:
                        del self._states[key]
                        break
            st = self._states[namespace] = _TenantState(quota, self._clock)
            return st

    # -- decision points --

    def _counter(self, namespace: str, verdict: str, kind: str):
        # metric-label bounding: only EXPLICITLY configured tenants get
        # their own label; namespaces admitted via the default quota are
        # client-supplied strings, and a scanner must not be able to
        # grow the registry (or this cache) without bound
        if not self.is_configured(namespace):
            namespace = "other"
        key = (namespace, verdict, kind)
        c = self._counters.get(key)
        if c is None:
            scope = self._scope.subscope("admission", namespace=namespace,
                                         kind=kind)
            c = self._counters[key] = (scope, verdict)
        return c

    def _allow(self, namespace: str, kind: str) -> None:
        scope, verdict = self._counter(namespace, "allowed", kind)
        scope.counter(verdict)

    def _shed(self, namespace: str, kind: str, retry_after_s: float):
        """The shed path: per-tenant counter + tracepoint, then the error
        the HTTP layer maps to 429 + Retry-After."""
        from m3_tpu.utils import trace

        scope, verdict = self._counter(namespace, "shed", kind)
        scope.counter(verdict)
        with trace.span(trace.TENANT_SHED, namespace=namespace, kind=kind,
                        retry_after_s=round(retry_after_s, 3)):
            pass  # the span IS the record: shed decisions join the trace
        raise TenantShedError(namespace, kind, retry_after_s)

    def admit_write(self, namespace: str, datapoints: int) -> None:
        """Gate one ingest batch: cardinality ceiling first (adding load
        to a tenant already over its live-series cap is strictly worse
        than rate-limiting it), then the datapoints/sec bucket."""
        st = self._state(namespace)
        if st is None:
            return  # no quota configured: unlimited
        if st.quota.max_series > 0:
            over = self._cardinality_over(namespace, st)
            if over:
                self._shed(namespace, "cardinality", self._cardinality_ttl_s)
        if st.dp_bucket is not None:
            wait = st.dp_bucket.try_take(float(datapoints))
            if wait > 0:
                self._shed(namespace, "write", wait)
        self._allow(namespace, "write")

    def admit_query(self, namespace: str) -> None:
        """Gate one query: the queries/sec bucket, then the post-paid
        cost budget (a tenant in cost debt is shed until it refills)."""
        st = self._state(namespace)
        if st is None:
            return
        if st.q_bucket is not None:
            wait = st.q_bucket.try_take(1.0)
            if wait > 0:
                self._shed(namespace, "query", wait)
        if st.cost_bucket is not None:
            wait = st.cost_bucket.deficit_s()
            if wait > 0:
                self._shed(namespace, "cost", wait)
        self._allow(namespace, "query")

    def charge_query_cost(self, namespace: str, stats) -> None:
        """Post-paid accounting from the finished query's QueryStats —
        called after the engine ran, never blocks, never raises."""
        st = self._state(namespace)
        if st is None or st.cost_bucket is None:
            return
        st.cost_bucket.charge(query_cost(stats))

    def _cardinality_over(self, namespace: str, st: _TenantState) -> bool:
        now = self._clock()
        if now - st.card_at >= self._cardinality_ttl_s:
            source = self._cardinality_source
            if source is None:
                return False
            try:
                val = source(namespace)
            except Exception:  # noqa: BLE001 - a storage hiccup must not
                return False   # turn the admission path into an outage
            if val is None:
                return False
            st.card_at = now
            st.card_value = int(val)
        return st.card_value >= st.quota.max_series

    # -- KV integration (runtime-updatable limits) --

    def watch_kv(self, kv, key: str = TENANTS_KEY):
        """Follow the tenants KV key; malformed payloads are ignored (the
        runtime.py watch discipline). Returns the unwatch callable."""

        def on_change(_key, vv):
            if vv is None:
                return  # deletion keeps the last applied quotas
            try:
                quotas, default = parse_quota_doc(json.loads(vv.data))
            except (ValueError, TypeError):
                return
            self.set_quotas(quotas, default)

        self._unwatch = kv.watch(key, on_change)
        return self._unwatch


def parse_quota_doc(doc: dict) -> tuple[dict[str, TenantQuota],
                                        TenantQuota | None]:
    """Shared doc shape for the config file `tenants:` section AND the
    `m3_tpu.tenants` KV payload:

        tenants:
          default: {queries_per_sec: 50}
          tenants:
            hot_ns: {datapoints_per_sec: 10000, max_series: 50000}
    """
    if not isinstance(doc, dict):
        raise ValueError(f"tenants doc must be a mapping, got {type(doc)}")
    default = None
    if doc.get("default"):
        default = TenantQuota.from_doc(doc["default"])
    quotas = {}
    for ns, sub in (doc.get("tenants") or {}).items():
        quotas[str(ns)] = TenantQuota.from_doc(sub or {})
    return quotas, default


def from_config(doc: dict | None, clock=time.monotonic,
                cardinality_source=None) -> TenantAdmission | None:
    """Controller from the coordinator config's `tenants:` section; None
    when the section is absent/empty (no controller, zero overhead)."""
    if not doc:
        return None
    quotas, default = parse_quota_doc(doc)
    if not quotas and default is None:
        return None
    return TenantAdmission(quotas, default, clock=clock,
                           cardinality_source=cardinality_source)
