"""Per-query statistics + the slow-query log.

Role parity with the reference's query diagnostics (per-fetch result
metadata + slow-query logging in the coordinator): a `QueryStats` record
rides a thread-local through the engine -> resolver -> storage -> decode
call stack, so every layer can account what THIS query cost without
threading a parameter through a dozen signatures:

- the engine opens/finishes the record (query text, namespace, trace id,
  total duration) and exposes it per thread as `Engine.last_stats`;
- the resolver records series matched; per-stage durations are written
  by the stage clock (utils/trace.py ``stage()``: a metered stage adds
  its whole wall time to ``stages`` under its name on close; this module
  keeps no clock of its own for them);
- the block cache records hits/misses, the decode ladder records which
  rung served each (shard, block, volume) group and the bytes decoded.

Finished records land in a bounded ring served at /debug/slow_queries.
Admission is PERCENTILE-BASED when a request-latency histogram source is
registered (the coordinator registers its `request_seconds` histogram):
a query is slow when it exceeds the live p99 of that histogram —
`M3_TPU_SLOW_QUERY_MS` is the FLOOR under the adaptive bar, and the
fallback threshold while the histogram is still too thin to trust
(default 0: every query is kept — the ring IS the query log until the
p99 bar arms or an operator raises the floor). The HTTP layer embeds
the record in the response envelope under `stats`.

In cluster mode the storage/decode counters accrue on the STORAGE node
processes (their own /metrics histograms cover them); the coordinator's
record still carries matching, stage timing and duration.

Overhead when no query is active: each hook is one thread-local read.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field


@dataclass
class QueryStats:
    query: str = ""
    namespace: str = ""
    start_unix_ns: int = 0
    trace_id: str = ""
    series_matched: int = 0
    blocks_read: int = 0
    bytes_decoded: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    # decode rung -> groups served (device / native / scalar / cache)
    decode_rungs: dict = field(default_factory=dict)
    # stage name -> INCLUSIVE wall seconds, written by trace.stage() on
    # close (query_ids, read_many, eval and the other trace.STAGE_*
    # names; /metrics holds the same stages' SELF-time)
    stages: dict = field(default_factory=dict)
    # remote leg -> (calls, seconds, rows): one entry per storage node /
    # fanout zone this query touched (the cross-node half of EXPLAIN
    # ANALYZE — the coordinator's plan tree shows each node's share)
    node_legs: dict = field(default_factory=dict)
    # pipelined-dataflow overlap (storage/pipeline.py run_stages): how
    # many (shard, block) groups rode the executor, the wall time of the
    # pipelined pass, and its time sums: the `gather` and `consume` legs
    # (gather + consume > wall is overlap), the caller's `leg_wait` for
    # the pool and, of it, the `handover` after a leg had finished —
    # surfaced on ?explain=analyze
    pipeline_groups: int = 0
    pipeline_wall_s: float = 0.0
    pipeline_stage_s: dict = field(default_factory=dict)
    # device-compiled inverted index (index/device.py): segments the
    # postings walk visited, how many ran the fused device program vs
    # fell back to the scalar walk (and why), the term-dictionary scan
    # account (terms regex-scanned vs skipped by literal prefix/suffix
    # narrowing) and postings rows fed to the intersect legs — the
    # ?explain=analyze `index` block
    index_segments: int = 0
    index_device_segments: int = 0
    index_fallback: dict = field(default_factory=dict)  # reason -> segments
    index_terms_scanned: int = 0
    index_terms_prefiltered: int = 0
    index_postings_rows: int = 0
    duration_s: float = 0.0

    def to_dict(self) -> dict:
        out = {
            "query": self.query,
            "namespace": self.namespace,
            "start_unix_ns": self.start_unix_ns,
            "trace_id": self.trace_id,
            "duration_ms": round(self.duration_s * 1e3, 3),
            "series_matched": self.series_matched,
            "blocks_read": self.blocks_read,
            "bytes_decoded": self.bytes_decoded,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "decode_rungs": dict(self.decode_rungs),
            "stages_ms": {k: round(v * 1e3, 3) for k, v in self.stages.items()},
        }
        if self.node_legs:
            out["node_legs"] = {
                host: {"calls": c, "ms": round(s * 1e3, 3), "rows": r}
                for host, (c, s, r) in self.node_legs.items()
            }
        if self.index_segments:
            out["index"] = self.index_block()
        if self.pipeline_groups:
            stage_sum = pipeline_leg_sum(self.pipeline_stage_s)
            out["pipeline"] = {
                "groups": self.pipeline_groups,
                "wall_ms": round(self.pipeline_wall_s * 1e3, 3),
                "stage_ms": {k: round(v * 1e3, 3)
                             for k, v in self.pipeline_stage_s.items()},
                "stage_sum_ms": round(stage_sum * 1e3, 3),
                # >1.0 means the gather and consume legs overlapped
                "overlap": round(stage_sum / self.pipeline_wall_s, 3)
                if self.pipeline_wall_s > 0 else 0.0,
            }
        return out

    def index_block(self) -> dict:
        """The rendered ?explain=analyze / stats-envelope `index` doc."""
        return {
            "segments": self.index_segments,
            "device_segments": self.index_device_segments,
            "fallback": dict(self.index_fallback),
            "terms_scanned": self.index_terms_scanned,
            "terms_prefiltered": self.index_terms_prefiltered,
            "postings_rows": self.index_postings_rows,
        }


_tls = threading.local()
_ring_lock = threading.Lock()
_ring: deque[QueryStats] = deque(maxlen=256)

# the slow-query ring is a bounded buffer: its fill level rides the
# saturation plane (instrument.monitor_queue; m3lint inv-queue-gauge)
from m3_tpu.utils import instrument as _instrument  # noqa: E402

_instrument.monitor_queue("slow_query_ring", lambda: len(_ring),
                          _ring.maxlen)


def _env_threshold_s() -> float:
    try:
        return float(os.environ.get("M3_TPU_SLOW_QUERY_MS", "0")) / 1e3
    except ValueError:
        return 0.0


_threshold_s = _env_threshold_s()
# adaptive slow-query bar: (histogram_source_fn, quantile, min_count).
# The coordinator registers its request-latency histogram; while the
# histogram holds fewer than min_count observations the env/floor
# threshold governs alone (a 3-sample p99 is noise, not a bar).
_adaptive: tuple | None = None


def set_threshold_ms(ms: float) -> None:
    """FLOOR threshold for the slow-query ring (0 keeps everything until
    the adaptive p99 bar arms)."""
    global _threshold_s
    _threshold_s = max(0.0, float(ms)) / 1e3


def set_adaptive_source(source, quantile: float = 0.99,
                        min_count: int = 64) -> None:
    """Register (or clear, with None) the live histogram the slow-query
    bar derives from: `source()` returns an object with `.count` and
    `.quantile(q)` (utils/instrument._Histogram). Admission threshold
    becomes max(floor, histogram p99) once the histogram holds min_count
    observations."""
    global _adaptive
    _adaptive = None if source is None else (source, quantile, min_count)


def clear_adaptive_source(source) -> None:
    """Clear the adaptive bar ONLY if `source` is the currently
    registered one — a shutting-down CoordinatorAPI must not disarm a
    sibling instance's registration (the bar is process-global)."""
    global _adaptive
    if _adaptive is not None and _adaptive[0] is source:
        _adaptive = None


def threshold_s() -> float:
    """The CURRENT admission bar: the env/operator floor, raised to the
    registered histogram's live quantile once it has enough samples."""
    thr = _threshold_s
    if _adaptive is not None:
        source, q, min_count = _adaptive
        try:
            h = source()
            if h is not None and h.count >= min_count:
                p = h.quantile(q)
                if p == p:  # not NaN
                    thr = max(thr, p)
        except Exception:  # noqa: BLE001 - a broken source must never
            pass           # make finish() raise
    return thr


def current() -> QueryStats | None:
    return getattr(_tls, "current", None)


def start(query: str = "", namespace: str = "",
          clock=None) -> QueryStats:
    """Open a record for this thread's query. Nested engines (subqueries,
    front-ends compiling through the same engine, the engine under the
    HTTP handler's request-long record) keep the OUTER record: the inner
    call gets the same object back with a depth mark (and names the
    query where the outer could not), and only the matching outermost
    `finish` closes it. `clock` (seconds, default perf_counter) is
    injectable so admission tests run on virtual time."""
    cur = getattr(_tls, "current", None)
    if cur is not None:
        cur._depth = getattr(cur, "_depth", 0) + 1  # type: ignore[attr-defined]
        cur.query = cur.query or query
        cur.namespace = cur.namespace or namespace
        return cur
    st = QueryStats(query=query, namespace=namespace,
                    start_unix_ns=time.time_ns())
    st._clock = clock or time.perf_counter  # type: ignore[attr-defined]
    st._t0 = st._clock()  # type: ignore[attr-defined]
    st._depth = 0  # type: ignore[attr-defined]
    _tls.current = st
    return st


def finish(st: QueryStats) -> None:
    """Close the record, stamp duration, admit to the ring when it clears
    the threshold bar (env floor raised to the live p99 once the adaptive
    source arms). A nested finish (depth > 0) only pops one level and
    stamps the duration so far — the outer query keeps accruing; object
    identity alone can't tell owner from nested caller since start()
    hands the same record back."""
    if getattr(_tls, "current", None) is not st:
        return
    st.duration_s = st._clock() - st._t0  # type: ignore[attr-defined]
    depth = getattr(st, "_depth", 0)
    if depth > 0:
        st._depth = depth - 1  # type: ignore[attr-defined]
        return
    _tls.current = None
    if st.duration_s >= threshold_s():
        with _ring_lock:
            _ring.append(st)


def record(series_matched: int = 0, blocks_read: int = 0,
           bytes_decoded: int = 0, cache_hits: int = 0,
           cache_misses: int = 0, decode_rung: str | None = None) -> None:
    """Accrue deltas onto the active query's record (no-op outside one)."""
    st = getattr(_tls, "current", None)
    if st is None:
        return
    st.series_matched += series_matched
    st.blocks_read += blocks_read
    st.bytes_decoded += bytes_decoded
    st.cache_hits += cache_hits
    st.cache_misses += cache_misses
    if decode_rung is not None:
        st.decode_rungs[decode_rung] = st.decode_rungs.get(decode_rung, 0) + 1


def pipeline_leg_sum(stages: dict) -> float:
    """The legs' time of a pipeline block, gather + consume (the wait
    and hand-over keys are the caller's, not legs)."""
    return stages.get("gather", 0.0) + stages.get("consume", 0.0)


def record_pipeline(groups: int, wall_s: float, stages: dict) -> None:
    """Accrue one pipelined-dataflow pass (storage/pipeline run_stages)
    onto the active query's record: groups scheduled, wall time, and
    the pass's time sums (``StageStats.stages``). ?explain=analyze
    renders wall vs the legs' sum so the gather/consume overlap is
    visible per query. No-op outside one."""
    st = getattr(_tls, "current", None)
    if st is None or not groups:
        return
    st.pipeline_groups += groups
    st.pipeline_wall_s += wall_s
    for stage, dt in stages.items():
        st.pipeline_stage_s[stage] = st.pipeline_stage_s.get(stage, 0.0) + dt


def record_index(segments: int = 0, device_segments: int = 0,
                 fallback: str | None = None, terms_scanned: int = 0,
                 terms_prefiltered: int = 0,
                 postings_rows: int = 0) -> None:
    """Accrue one postings-walk account (index/executor.py) onto the
    active query's record: segments visited, device-program vs
    scalar-fallback outcomes (with the fallback reason), the term
    dictionary scan/prefilter split and postings rows intersected — the
    ?explain=analyze `index` block. No-op outside a query."""
    st = getattr(_tls, "current", None)
    if st is None:
        return
    st.index_segments += segments
    st.index_device_segments += device_segments
    if fallback is not None:
        st.index_fallback[fallback] = st.index_fallback.get(fallback, 0) + 1
    st.index_terms_scanned += terms_scanned
    st.index_terms_prefiltered += terms_prefiltered
    st.index_postings_rows += postings_rows


def record_node_leg(leg: str, seconds: float, rows: int = 0) -> None:
    """Accrue one remote leg (storage-node RPC, fanout zone) onto the
    active query's record: EXPLAIN ANALYZE shows each node's share of a
    fan-out stage. No-op outside a query."""
    st = getattr(_tls, "current", None)
    if st is None:
        return
    calls, total_s, total_rows = st.node_legs.get(leg, (0, 0.0, 0))
    st.node_legs[leg] = (calls + 1, total_s + seconds, total_rows + rows)


@contextmanager
def collect():
    """Scoped storage-counter collection WITHOUT slow-query-ring
    admission: the node half of the /read_batch stats envelope. Pushes a
    fresh record (shadowing any active one) so the yielded counters
    cover exactly the wrapped work; the previous record is restored on
    exit, unchanged — whoever reads the envelope decides to merge."""
    prev = getattr(_tls, "current", None)
    st = QueryStats()
    _tls.current = st
    try:
        yield st
    finally:
        _tls.current = prev


def storage_counters(st: QueryStats) -> dict:
    """The storage-side counters a node embeds in its /read_batch
    response envelope (merged coordinator-side via merge_storage)."""
    out = {"series": st.series_matched, "blocks": st.blocks_read,
           "bytes": st.bytes_decoded, "cache_hits": st.cache_hits,
           "cache_misses": st.cache_misses, "rungs": dict(st.decode_rungs)}
    if st.index_segments:
        out["index"] = st.index_block()
    if st.pipeline_groups:
        out["pipeline"] = {"groups": st.pipeline_groups,
                           "wall_s": st.pipeline_wall_s,
                           "stages": dict(st.pipeline_stage_s)}
    return out


def merge_storage(doc: dict | None) -> None:
    """Accrue a node's returned storage counters onto this thread's
    active record (the coordinator half; no-op outside a query) — so in
    cluster mode /debug/slow_queries and the response `stats` envelope
    carry the nodes' blocks/bytes/cache/rung counts, not zeros."""
    st = getattr(_tls, "current", None)
    if st is None or not doc:
        return
    st.series_matched += int(doc.get("series", 0))
    st.blocks_read += int(doc.get("blocks", 0))
    st.bytes_decoded += int(doc.get("bytes", 0))
    st.cache_hits += int(doc.get("cache_hits", 0))
    st.cache_misses += int(doc.get("cache_misses", 0))
    for rung, cnt in (doc.get("rungs") or {}).items():
        st.decode_rungs[rung] = st.decode_rungs.get(rung, 0) + int(cnt)
    idx = doc.get("index")
    if idx:
        st.index_segments += int(idx.get("segments", 0))
        st.index_device_segments += int(idx.get("device_segments", 0))
        for reason, cnt in (idx.get("fallback") or {}).items():
            st.index_fallback[reason] = \
                st.index_fallback.get(reason, 0) + int(cnt)
        st.index_terms_scanned += int(idx.get("terms_scanned", 0))
        st.index_terms_prefiltered += int(idx.get("terms_prefiltered", 0))
        st.index_postings_rows += int(idx.get("postings_rows", 0))
    pipe = doc.get("pipeline")
    if pipe:
        record_pipeline(int(pipe.get("groups", 0)),
                        float(pipe.get("wall_s", 0.0)),
                        {k: float(v)
                         for k, v in (pipe.get("stages") or {}).items()})


def slow_queries(limit: int = 50) -> list[dict]:
    """Ring contents, slowest first."""
    with _ring_lock:
        entries = list(_ring)
    entries.sort(key=lambda s: s.duration_s, reverse=True)
    return [s.to_dict() for s in entries[:limit]]


def clear() -> None:
    with _ring_lock:
        _ring.clear()
