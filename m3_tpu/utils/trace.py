"""Distributed tracing: named tracepoints, span identity, W3C propagation.

Role parity with the reference's OpenTracing plumbing
(/root/reference/src/dbnode/tracepoint/tracepoint.go named operation
constants, x/context StartSampledTraceSpan, x/opentracing/tracing.go),
upgraded from process-local span recording to real distributed traces:

- every recorded Span carries (trace_id, span_id, parent_span_id), so a
  fan-out query stitches into ONE tree across coordinator, client session
  and storage nodes;
- the context propagates across processes as a W3C-`traceparent`-style
  header (``00-<trace_id>-<span_id>-<flags>``) on HTTP requests, as gRPC
  metadata on remote-zone/kvd RPCs, and as an envelope field on m3msg
  frames;
- the sampling decision is HEAD-BASED: made once at ingress
  (``start_request``) and honored by every downstream hop via the
  propagated flags bit, so a trace is never half-recorded;
- spans land in a bounded per-process ring exposed at /debug/traces; the
  coordinator's handler additionally gathers matching spans from its
  storage nodes and returns the stitched cross-process tree.

Steady-state cost: an unsampled request pays one thread-local read per
tracepoint; a disabled tracer pays one attribute check. The sampler is a
lock-free ``itertools.count`` (atomic under CPython), replacing the old
documented-racy ``_counter % sample_every`` increment.

``M3_TPU_TRACE_SAMPLE`` overrides the default tracer's sampling: ``0``
disables tracing, ``N`` samples one root trace in N. Sampling governs the
ring only.

The stage clock. ``stage()`` opens a span that keeps time whatever the
sampling decision: wall (``perf_counter_ns``) and thread CPU
(``thread_time_ns``), and through the thread's span stack the part of
both that the stages beneath it covered. On close a metered stage
records its wall SELF-time (own minus covered) for
``query_stage_seconds{route,stage}`` and its CPU self-time for
``query_stage_cpu_seconds{route,stage}`` (published together when the
thread's outermost span closes) and writes its whole wall time to the
active ``QueryStats.stages``: the one clock the query path's
layer boundaries share (/metrics, the request's span tree,
/debug/slow_queries). ``route`` rides the SpanContext, set where the
HTTP handler routes. Plain spans between two stages are transparent to
that sum. While a device-trace session runs (utils/backend
``start_device_trace``), a sampled stage also enters a
``jax.profiler.TraceAnnotation`` of its name, so it lands in the xplane
on the device trace's clock.
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

from m3_tpu.utils import querystats

# tracepoint name constants (the tracepoint.go role). m3lint's
# inv-tracepoint-unique (python -m tools.m3lint) asserts these values
# stay unique.
DB_WRITE = "storage.db.write"
DB_WRITE_BATCH = "storage.db.write_batch"
DB_QUERY = "storage.db.query"
INDEX_QUERY = "index.query"
SHARD_FLUSH = "storage.shard.flush"
SESSION_FETCH = "client.session.fetch_many"
AGG_FLUSH = "aggregator.flush"
READ_MANY = "storage.ns.read_many"
DBNODE_HANDLE = "dbnode.handle"
FANOUT_READ = "query.fanout.read_many"
MSG_SEND = "msg.producer.send"
MSG_RECV = "msg.consumer.handle"
KVD_RPC = "kvd.client.rpc"
KVD_HANDLE = "kvd.server.handle"
PEER_HTTP = "storage.peer.http"
TENANT_SHED = "tenant.admission.shed"
REPAIR_CYCLE = "storage.repair.cycle"
QUERY_COMPILE_FALLBACK = "query.compile.fallback"
WATCHDOG_STALL = "watchdog.stall"
PLACEMENT_SYNC_DEFER = "placement.sync.defer"
WIRE_FALLBACK = "wire.fallback"
PIPELINE_CONSUME = "storage.pipeline.consume"

# stage names of the served paths (query, write, tick): one per layer
# boundary (PERF.md section 3), the `stage` label of query_stage_seconds
# and the keys of QueryStats.stages. A query stage whose jit_tracker
# reports a miss closes as the *.compile name instead.
STAGE_REQUEST = "request"
STAGE_PARSE_PLAN = "parse_plan"
STAGE_QUERY_IDS = "query_ids"
STAGE_READ_MANY = "read_many"
STAGE_GATHER = "read_many.gather"
STAGE_DECODE_HOST = "decode.host"
STAGE_DECODE_WAIT = "decode.device_wait"
STAGE_DECODE_COMPILE = "decode.compile"
# the device encoder's call and the transfers that wait for it
# (hostpath.encode_blocks), under whatever route seals a block: the
# tick's snapshot and flush, the wire codec's re-encode
STAGE_ENCODE_WAIT = "encode.device_wait"
STAGE_SLAB_PREP = "slab_prep"
STAGE_PLAN_DISPATCH = "plan.dispatch"
STAGE_PLAN_WAIT = "plan.device_wait"
STAGE_PLAN_COMPILE = "plan.compile"
STAGE_EVAL = "eval"
STAGE_RENDER = "render"
# the write route (query/api.py _remote_write under the `request` root)
STAGE_WRITE_DECODE = "write.decode"
STAGE_WRITE_BATCH = "write.batch"
STAGE_WRITE_COMMITLOG = "write.commitlog"
STAGE_WRITE_BUFFER = "write.buffer"
# the route `tick`: one root stage a cycle (storage/database.py tick),
# published when the cycle ends; what no stage beneath it covers
# (expiry, index persist and compaction) is its self-time
STAGE_TICK = "tick"
STAGE_TICK_SNAPSHOT = "tick.snapshot.host"   # less the encoder's wait
STAGE_TICK_FLUSH = "tick.flush"
STAGE_TICK_ROTATE = "tick.rotate"

# the `route` label's values (query/api.py route_of); a stage opened
# outside any routed request reports as ROUTE_OTHER
ROUTE_OTHER = "other"
ROUTE_TICK = STAGE_TICK   # the mediator's cycle: root stage and route

# a thread whose outermost span stays open publishes its closed stages
# at this many (a request closes some twenty)
_PENDING_CAP = 512

_ZERO_SPAN_ID = "0" * 16
# placeholder trace id carried by a negative head decision's context —
# never recorded, only propagated so descendants stay silent too
_UNSAMPLED_TRACE_ID = "f" * 32


@dataclass(frozen=True)
class SpanContext:
    """The propagated identity of the active span (or of the head sampling
    decision before any span opened: span_id == "" then)."""

    trace_id: str  # 32 hex chars (16 bytes)
    span_id: str   # 16 hex chars (8 bytes); "" = decision-only context
    sampled: bool = True
    # the HTTP route this request came in by (stage metrics' label);
    # process-local: never on the wire, "" past a remote hop
    route: str = ""

    def to_traceparent(self) -> str:
        return (f"00-{self.trace_id}-{self.span_id or _ZERO_SPAN_ID}-"
                f"{'01' if self.sampled else '00'}")


# ids come from a process-local generator seeded from the OS, not from
# os.urandom per id: that is a system call which gives the GIL away, and
# a request opens some forty spans (uniqueness is what an id needs here)
_ids = random.Random(os.urandom(16))
if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=lambda: _ids.seed(os.urandom(16)))


def new_trace_id() -> str:
    return f"{_ids.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def parse_traceparent(value: str | None) -> SpanContext | None:
    """``00-<32 hex>-<16 hex>-<2 hex flags>`` -> SpanContext, else None.
    Unknown versions parse leniently (same field layout), per the W3C
    forward-compat rule; malformed values are ignored, never raised on."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if version == "ff" or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        return None
    if trace_id == "0" * 32:
        return None
    return SpanContext(trace_id, span_id, sampled)


@dataclass
class Span:
    name: str
    start_ns: int
    duration_ns: int = 0
    parent: str | None = None  # parent tracepoint NAME (legacy surface)
    tags: dict = field(default_factory=dict)
    trace_id: str = ""
    span_id: str = ""
    parent_span_id: str | None = None
    # ring admission order, monotonic per process — the exporter's drain
    # cursor (utils/export.py) ships each recorded span exactly once
    seq: int = 0
    # stage spans only (cpu_ns < 0 marks a plain span): wall self-time
    # (own minus what the stages beneath covered), thread CPU time and
    # its self part
    self_ns: int = 0
    cpu_ns: int = -1
    cpu_self_ns: int = 0

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "start_unix_ns": self.start_ns,
            "duration_us": round(self.duration_ns / 1000, 1),
            "parent": self.parent,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            **({"tags": self.tags} if self.tags else {}),
        }
        if self.cpu_ns >= 0:
            out["self_us"] = round(self.self_ns / 1000, 1)
            out["cpu_us"] = round(self.cpu_ns / 1000, 1)
            out["cpu_self_us"] = round(self.cpu_self_ns / 1000, 1)
        return out


class StageFrame:
    """One open span on its thread's stack, and the handle ``stage()``
    yields: ``name`` may be changed until the close takes it (a compile
    miss renames its stage), ``tag()`` reaches the ring span when the
    request is sampled, ``wall_ns``/``cpu_ns`` are readable after the
    close. ``covered_*`` is what the metered stages beneath have
    covered of this frame's time, on this thread."""

    __slots__ = ("name", "span", "covered_ns", "covered_cpu_ns",
                 "wall_ns", "cpu_ns")

    def __init__(self, name: str, span: "Span | None" = None):
        self.name = name
        self.span = span
        self.covered_ns = 0
        self.covered_cpu_ns = 0
        self.wall_ns = 0
        self.cpu_ns = 0

    def tag(self, **tags) -> None:
        if self.span is not None:
            self.span.tags.update(tags)

    @property
    def wall_s(self) -> float:
        return self.wall_ns / 1e9


class Tracer:
    """Bounded recorder; one per process (default_tracer()).

    Sampling: a tracepoint hit with NO active context is a trace root and
    draws a head decision from the lock-free counter (1-in-sample_every).
    A hit under an active context follows that context's decision — the
    ingress decides once, everything below (including remote hops that
    propagated the flags bit) honors it.
    """

    def __init__(self, capacity: int = 2048, sample_every: int = 1):
        self.capacity = capacity
        self.sample_every = max(1, sample_every)
        # only the PROCESS tracer's ring rides the saturation plane (the
        # module-level monitor_queue below); privately-constructed
        # tracers are test fixtures whose rings gauge nothing
        # m3lint: disable=inv-queue-gauge
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._tl = threading.local()
        self._lock = threading.Lock()
        # lock-free sampler: next() on itertools.count is atomic in
        # CPython (a single C call), unlike the old racy `_counter += 1`
        self._count = itertools.count()
        # ring admission counter (under _lock): export_since cursors
        self._last_seq = 0
        self.enabled = True
        # jax.profiler.TraceAnnotation while a device-trace session runs
        # (utils/backend start_device_trace), else None: one attribute
        # check per stage
        self.annotate = None

    def _stack(self) -> list:
        st = getattr(self._tl, "stack", None)
        if st is None:
            st = self._tl.stack = []
        return st

    def _pending(self) -> list:
        """This thread's closed metered stages, (the series' tags, wall
        self seconds, CPU self seconds), not yet on the registry."""
        pending = getattr(self._tl, "pending", None)
        if pending is None:
            pending = self._tl.pending = []
        return pending

    def _publish(self) -> None:
        """The thread's outermost span has closed: its stages reach the
        two families in one acquisition of the registry's lock. From
        inside the request they cost two acquisitions a stage of a lock
        every metric of the process shares, which on the chip cost
        `double-groupby-1` 5% of its latency (PERF.md section 6)."""
        pending = getattr(self._tl, "pending", None)
        if not pending:
            return
        _instrument.default_registry().record_many(
            [("query.stage.seconds", tags, w) for tags, w, _c in pending],
            [("query.stage.cpu_seconds", tags, c) for tags, _w, c in pending])
        pending.clear()

    # -- context plumbing --

    def current(self) -> SpanContext | None:
        """The active SpanContext on this thread (propagated or opened by
        an enclosing span), or None outside any trace."""
        return getattr(self._tl, "ctx", None)

    def sample_head(self) -> bool:
        """One head-based sampling decision (root of a new trace)."""
        if not self.enabled:
            return False
        return next(self._count) % self.sample_every == 0

    def start_request(self, headers=None) -> SpanContext:
        """Ingress context: honor a propagated ``traceparent`` if present,
        else mint a new root trace with a head sampling decision. Always
        returns a context (so the response can echo the trace id);
        `sampled=False` contexts make every downstream tracepoint a no-op.

        `headers` is any case-insensitive-ish mapping (http.client
        HTTPMessage, dict, or None)."""
        tp = None
        if headers is not None:
            get = getattr(headers, "get", None)
            if get is not None:
                tp = get("traceparent") or get("Traceparent")
        ctx = parse_traceparent(tp)
        if ctx is not None:
            return ctx
        return SpanContext(new_trace_id(), "", self.sample_head())

    @contextmanager
    def activate(self, ctx: SpanContext | None):
        """Install `ctx` as this thread's active context for the scope
        (server-side of a propagated hop)."""
        tl = self._tl
        prev = getattr(tl, "ctx", None)
        tl.ctx = ctx
        try:
            yield ctx
        finally:
            tl.ctx = prev

    def inject_headers(self, extra: dict | None = None) -> dict:
        """Headers carrying the active context ({} when none/disabled)."""
        ctx = self.current()
        out = dict(extra) if extra else {}
        if ctx is not None and self.enabled:
            out["traceparent"] = ctx.to_traceparent()
        return out

    # -- spans --

    def _begin(self, name: str, tags: dict):
        """The ring half of opening a span: (Span or None, the context
        to put back on close, whether a context was installed). A hit
        with no active context is a trace root and draws the head
        decision; a NEGATIVE one still installs a not-sampled context
        for the span's extent — descendant tracepoints must follow this
        root's decision, not draw their own (which would record orphan
        bottom-half trees)."""
        tl = self._tl
        ctx = getattr(tl, "ctx", None)
        if not self.enabled:
            return None, ctx, False
        if ctx is None:
            if next(self._count) % self.sample_every:
                tl.ctx = SpanContext(_UNSAMPLED_TRACE_ID, "", False)
                return None, None, True
            trace_id, parent_sid, route = new_trace_id(), None, ""
        elif not ctx.sampled:
            return None, ctx, False
        else:
            trace_id, parent_sid, route = \
                ctx.trace_id, ctx.span_id or None, ctx.route
        sid = new_span_id()
        stack = self._stack()
        sp = Span(name, time.time_ns(),
                  parent=stack[-1].name if stack else None, tags=tags,
                  trace_id=trace_id, span_id=sid, parent_span_id=parent_sid)
        tl.ctx = SpanContext(trace_id, sid, True, route)
        return sp, ctx, True

    def _admit(self, sp: Span) -> None:
        with self._lock:
            self._last_seq += 1
            sp.seq = self._last_seq
            self._spans.append(sp)

    @contextmanager
    def span(self, name: str, **tags):
        """A plain tracepoint: recorded when the request is sampled, a
        no-op otherwise. Between two stages it is transparent to the
        stage clock: what the stages beneath it covered passes through
        to the frame above."""
        sp, prev_ctx, installed = self._begin(name, tags)
        if sp is None:
            try:
                yield None
            finally:
                if installed:
                    self._tl.ctx = prev_ctx
            return
        stack = self._stack()
        frame = StageFrame(name, sp)
        stack.append(frame)
        t0 = time.perf_counter_ns()
        try:
            yield sp
        finally:
            sp.duration_ns = time.perf_counter_ns() - t0
            stack.pop()
            if stack:
                stack[-1].covered_ns += frame.covered_ns
                stack[-1].covered_cpu_ns += frame.covered_cpu_ns
            self._tl.ctx = prev_ctx
            self._admit(sp)
            if not stack:
                self._publish()

    @contextmanager
    def stage(self, name: str, metered: bool = True, **tags):
        """A span that keeps time for EVERY request, sampled or not, with
        tracing enabled or not: wall and thread CPU, and what the stages
        beneath covered of each. Yields its StageFrame. A metered stage
        feeds query_stage_seconds / query_stage_cpu_seconds with its
        self-time and QueryStats.stages with its whole wall time on
        close; an unmetered one (a pipeline leg run inline) only keeps
        its own wall time for the caller (no CPU clock) and passes what
        was covered beneath it upward. The span enters the ring when the
        request is sampled."""
        tl = self._tl
        route = getattr(getattr(tl, "ctx", None), "route", "") or ROUTE_OTHER
        sp, prev_ctx, installed = self._begin(name, tags)
        stack = self._stack()
        frame = StageFrame(name, sp)
        stack.append(frame)
        ann = None
        if sp is not None and self.annotate is not None:
            ann = self.annotate(name)
            ann.__enter__()
        # the wall pair encloses the CPU pair, so CPU <= wall per stage;
        # an unmetered stage feeds no CPU counter and reads no CPU clock
        t0 = time.perf_counter_ns()
        c0 = time.thread_time_ns() if metered else 0
        try:
            yield frame
        finally:
            cpu = frame.cpu_ns = time.thread_time_ns() - c0 if metered else 0
            wall = frame.wall_ns = time.perf_counter_ns() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            stack.pop()
            parent = stack[-1] if stack else None
            if metered:
                if parent is not None:
                    parent.covered_ns += wall
                    parent.covered_cpu_ns += cpu
                self_ns = max(0, wall - frame.covered_ns)
                cpu_self_ns = max(0, cpu - frame.covered_cpu_ns)
                pending = self._pending()
                pending.append(
                    ((("route", route), ("stage", frame.name)),
                     self_ns / 1e9, cpu_self_ns / 1e9))
                if len(pending) >= _PENDING_CAP:
                    self._publish()
                st = querystats.current()
                if st is not None:
                    st.stages[frame.name] = \
                        st.stages.get(frame.name, 0.0) + wall / 1e9
            elif parent is not None:
                parent.covered_ns += frame.covered_ns
                parent.covered_cpu_ns += frame.covered_cpu_ns
            if installed:
                tl.ctx = prev_ctx
            if sp is not None:
                sp.name = frame.name
                sp.duration_ns = wall
                if metered:
                    sp.self_ns, sp.cpu_ns, sp.cpu_self_ns = \
                        self_ns, cpu, cpu_self_ns
                self._admit(sp)
            if not stack:
                self._publish()

    # -- ring access --

    def recent(self, limit: int = 200) -> list[dict]:
        with self._lock:
            spans = list(self._spans)[-limit:]
        return [s.to_dict() for s in spans]

    def find(self, trace_id: str) -> list[dict]:
        """Every ring span belonging to `trace_id`, oldest first."""
        with self._lock:
            spans = [s for s in self._spans if s.trace_id == trace_id]
        return [s.to_dict() for s in spans]

    def export_since(self, cursor: int) -> tuple[list[dict], int]:
        """Spans recorded after `cursor` (a prior call's returned cursor;
        0 = everything still in the ring) plus the new cursor. The
        exporter's drain surface: spans evicted from the bounded ring
        between drains are simply gone — the ring never grows to wait for
        a slow exporter (export must not backpressure recording)."""
        with self._lock:
            spans = [s for s in self._spans if s.seq > cursor]
            last = self._last_seq
        return [s.to_dict() for s in spans], last

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


def build_tree(spans: list[dict]) -> list[dict]:
    """Nest span dicts into parent->children trees by span id. Spans whose
    parent_span_id is absent from the set become roots (the cross-process
    gather may be partial); duplicates (same span_id, e.g. a span served
    by both the local ring and a node's) dedupe, first occurrence wins."""
    by_id: dict[str, dict] = {}
    ordered: list[dict] = []
    for s in spans:
        sid = s.get("span_id") or ""
        if sid and sid in by_id:
            continue
        node = {**s, "children": []}
        if sid:
            by_id[sid] = node
        ordered.append(node)
    roots = []
    for node in ordered:
        parent = node.get("parent_span_id")
        if parent and parent in by_id and by_id[parent] is not node:
            by_id[parent]["children"].append(node)
        else:
            roots.append(node)
    return roots


def _env_sample() -> tuple[int, bool]:
    """(sample_every, enabled) from M3_TPU_TRACE_SAMPLE (0 disables)."""
    raw = os.environ.get("M3_TPU_TRACE_SAMPLE", "")
    if not raw:
        return 1, True
    try:
        n = int(raw)
    except ValueError:
        return 1, True
    if n <= 0:
        return 1, False
    return n, True


_sample_every, _enabled = _env_sample()
_default = Tracer(sample_every=_sample_every)
_default.enabled = _enabled

# the process span ring is a bounded buffer like any other: its depth
# rides the saturation plane (a full ring means the exporter is losing
# spans between drains)
from m3_tpu.utils import instrument as _instrument  # noqa: E402

_instrument.monitor_queue("trace_ring", lambda: len(_default._spans),
                          _default.capacity)


def default_tracer() -> Tracer:
    return _default


def span(name: str, **tags):
    """Open a span on the process tracer: `with trace.span(trace.DB_WRITE):`"""
    return _default.span(name, **tags)


def stage(name: str, metered: bool = True, **tags):
    """Open a stage of the served query path on the process tracer:
    `with trace.stage(trace.STAGE_RENDER):`"""
    return _default.stage(name, metered, **tags)


def current() -> SpanContext | None:
    return _default.current()


def activate(ctx: SpanContext | None):
    return _default.activate(ctx)


def start_request(headers=None) -> SpanContext:
    return _default.start_request(headers)


def inject_headers(extra: dict | None = None) -> dict:
    return _default.inject_headers(extra)


def grpc_metadata() -> tuple | None:
    """The active context as gRPC metadata, or None outside a trace."""
    ctx = _default.current()
    if ctx is None or not _default.enabled:
        return None
    return (("traceparent", ctx.to_traceparent()),)


def from_grpc_context(grpc_ctx) -> SpanContext | None:
    """Extract a propagated context from a grpc.ServicerContext."""
    try:
        md = grpc_ctx.invocation_metadata()
    except Exception:  # noqa: BLE001 - non-grpc test doubles
        return None
    for key, value in md or ():
        if key == "traceparent":
            return parse_traceparent(value)
    return None
