"""Whole-query resource limits enforced at the storage layer.

Role parity with the reference storage/limits
(/root/reference/src/dbnode/storage/limits/types.go:37-57): budgets are
accounted where the data is read (Namespace.query_ids / Namespace.read),
so EVERY read path — PromQL, Graphite render, Prometheus remote read,
/api/v1/series — shares one per-request budget instead of each HTTP
handler opting in.
"""

from __future__ import annotations

import threading


class QueryLimitError(ValueError):
    """A query exceeded the configured resource limits."""


class QueryLimits:
    """Resource ceilings accumulated across a WHOLE query (every selector
    in the expression shares the budget); zero means unlimited. Accounting
    state is thread-local so one database can serve concurrent requests."""

    def __init__(self, max_series: int = 0, max_datapoints: int = 0,
                 max_steps: int = 0):
        self.max_series = max_series
        self.max_datapoints = max_datapoints
        self.max_steps = max_steps
        self._tl = threading.local()

    def start_query(self) -> None:
        self._tl.active = True
        self._tl.series = 0
        self._tl.datapoints = 0

    def end_query(self) -> None:
        self._tl.active = False

    def check_steps(self, n_steps: int) -> None:
        if self.max_steps and n_steps > self.max_steps:
            raise QueryLimitError(
                f"query spans {n_steps} steps, limit {self.max_steps}"
            )

    def charged(self) -> tuple[int, int]:
        """(series, datapoints) this thread's active query has been
        charged so far. The compiled query path takes the difference
        around a fetch, keeps it with the hot-tier entry the fetch
        prepares, and charges it again when the entry serves a repeat
        without reading (query/compiler.py _run_plan)."""
        return (getattr(self._tl, "series", 0),
                getattr(self._tl, "datapoints", 0))

    def add_series(self, n_series: int) -> None:
        # only count inside an active start_query..end_query scope: reads
        # from background work (repair, flush, direct library calls) are not
        # budgeted, and without the gate their counts would accumulate on a
        # long-lived thread until every read failed
        if not getattr(self._tl, "active", False):
            return
        total = getattr(self._tl, "series", 0) + n_series
        self._tl.series = total
        if self.max_series and total > self.max_series:
            raise QueryLimitError(
                f"query matched {total} series, limit {self.max_series}"
            )

    def add_datapoints(self, n: int) -> None:
        if not getattr(self._tl, "active", False):
            return
        total = getattr(self._tl, "datapoints", 0) + n
        self._tl.datapoints = total
        if self.max_datapoints and total > self.max_datapoints:
            raise QueryLimitError(
                f"query would read {total} datapoints, limit {self.max_datapoints}"
            )


def live_series(db, namespace: str) -> int | None:
    """Live (buffered) series count for one namespace — the storage-side
    source behind the per-tenant cardinality ceiling
    (utils/tenantlimits): the count is read where the series actually
    live, so the ceiling tracks reality instead of an ingest-side
    estimate. Returns None when the storage is remote (cluster facade:
    the nodes own the buffers) — the ceiling is then not enforceable
    from this process and the admission controller skips it."""
    ns = getattr(db, "namespaces", {}).get(namespace)
    shards = getattr(ns, "shards", None)
    if shards is None:
        return None
    return sum(s.buffer.n_series for s in shards.values())
