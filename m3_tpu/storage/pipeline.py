"""Bounded-depth pipelined dataflow executor (ROADMAP #2).

The T3 pattern (PAPERS.md "Transparent Tracking & Triggering for
Fine-grained Overlap of Compute & Collectives") applied to the storage
engine's host dataflow: instead of `fetch whole group -> consume whole
group -> next group`, a small fixed worker pool runs the NEXT group's
fetch/RPC leg while the CALLER consumes the current one, with a bounded
prefetch depth so memory stays flat. The same executor serves both hot
paths:

  read side   `Shard`/`Namespace.read_many` push per-(shard, block)
              gather legs through ``run_stages`` so group N+1's fileset
              gather overlaps the caller landing group N's cache hits;
              what the groups missed decodes in ONE batch when the last
              gather has landed (`shard.run_read_groups`), and
              `Session.fetch_many` / the coordinator fanout put every
              node/zone RPC in flight at once instead of draining them
              serially.
  write side  `Database.write_batch` splits a big batch into WAL chunks
              on a per-namespace FIFO ``lane`` — the lane worker packs/
              flushes chunk N while the caller runs chunk N-1's buffer
              and index inserts. Ack (the call returning) still happens
              only after every chunk's WAL stage completed, so the
              acked => durably-logged contract is untouched.

Design rules (enforced by m3lint + the shadow-lock checker):

- every lock is taken through the standard ``with`` discipline;
- the task queue is bounded and registered with
  ``instrument.monitor_queue`` (inv-queue-gauge) — saturation is a
  gauge, not a mystery;
- the ``pipeline.task`` fault point fires at SUBMIT time on the caller
  thread, so injection schedules stay deterministic under the seeded
  chaos specs (worker-side execution order is not);
- a worker that catches ``SimulatedCrash`` escalates (armed chaos ==
  process death) before handing the exception to the consumer, which
  re-raises it in submission order — serial-path crash semantics.
- hand-rolled thread-pool/queue pipelines anywhere else in the tree are
  an m3lint finding (``conc-handrolled-pipeline``): one executor seam,
  one saturation story, one fault surface.

Hatches: ``M3_TPU_PIPELINE=0`` pins every caller to its serial path
(bisection); ``M3_TPU_PIPELINE_WORKERS`` / ``M3_TPU_PIPELINE_DEPTH`` /
``M3_TPU_PIPELINE_WAL_CHUNK`` size the pool, the prefetch depth and the
write-side WAL chunking. Tasks submitted FROM a pipeline worker run
inline (``active()`` is False there): a worker waiting on the pool that
must run its work is a deadlock, not a pipeline.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from m3_tpu.utils import faults
from m3_tpu.utils.instrument import default_registry, monitor_queue

_scope = default_registry().root_scope("pipeline")

# worker heartbeat cadence: long enough that a worker parked on a slow
# (but legitimate) RPC leg doesn't trip the stall watchdog, short enough
# that a genuinely wedged pool is flagged within a minute
_HEARTBEAT_S = 30.0
_IDLE_POLL_S = 1.0


# service-config overrides (configure()); env always wins, defaults last
_cfg: dict[str, int] = {}


def _env_int(name: str, default: int, floor: int = 1) -> int:
    raw = os.environ.get(name, "")
    if raw:
        try:
            return max(floor, int(raw))
        except ValueError:
            pass
    if name in _cfg:
        return max(floor, _cfg[name])
    return default


def enabled() -> bool:
    """The M3_TPU_PIPELINE hatch: unset/1 = on, 0 = serial everywhere."""
    return os.environ.get("M3_TPU_PIPELINE", "1") != "0"


_tl = threading.local()


def in_worker() -> bool:
    return getattr(_tl, "worker", False)


def active() -> bool:
    """True when callers should pipeline: the hatch is open AND this is
    not already a pipeline worker (nested submission would wait on the
    pool it occupies — run inline instead)."""
    return enabled() and not in_worker()


def wal_chunk_entries() -> int:
    """Write-side WAL chunk size: batches larger than this split into
    per-chunk lane appends so buffer/index inserts for chunk N-1 overlap
    the WAL pack/flush of chunk N."""
    return _env_int("M3_TPU_PIPELINE_WAL_CHUNK", 4096)


class _Future:
    """Single-shot result slot (Event-based; no cancellation races)."""

    __slots__ = ("_done", "_result", "_exc")

    def __init__(self) -> None:
        self._done = threading.Event()
        self._result = None
        self._exc: BaseException | None = None

    def run(self, fn) -> None:
        """Execute fn() capturing its outcome for the consumer. A
        SimulatedCrash escalates HERE (armed chaos kills the process at
        the point of injury) and is still handed to the consumer, which
        re-raises it in submission order — the serial path's semantics."""
        try:
            self._result = fn()
        except faults.SimulatedCrash as e:
            faults.escalate()
            self._exc = e
        except BaseException as e:  # delivered to the consumer's result()
            self._exc = e
        finally:
            self._done.set()

    def result(self):
        self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._result


class SerialLane:
    """Strict-FIFO execution lane over the shared pool: at most one lane
    task runs at a time, in submission order — the WAL discipline (the
    emitted commitlog byte stream must equal the serial path's)."""

    def __init__(self, executor: "PipelineExecutor", name: str):
        self._executor = executor
        self.name = name
        self._lock = threading.Lock()
        self._pending: deque = deque()
        self._draining = False
        # lane backlog on the saturation plane (depth only: the lane is
        # deliberately unbounded — WAL appends must never drop)
        self._unmonitor = monitor_queue(
            f"pipeline_lane_{name}", lambda: len(self._pending), None,
            owner=self)

    def submit(self, fn) -> _Future:
        faults.check("pipeline.task", lane=self.name)
        fut = _Future()
        with self._lock:
            self._pending.append((fn, fut))
            kick = not self._draining
            if kick:
                self._draining = True
        if kick:
            self._executor._enqueue(self._drain)
        return fut

    def _drain(self) -> None:
        while True:
            with self._lock:
                if not self._pending:
                    self._draining = False
                    return
                fn, fut = self._pending.popleft()
            fut.run(fn)


class PipelineExecutor:
    """Fixed worker pool + bounded task queue + named FIFO lanes."""

    def __init__(self, workers: int | None = None,
                 queue_cap: int | None = None, name: str = "storage"):
        self.workers = workers or _env_int(
            "M3_TPU_PIPELINE_WORKERS", min(8, max(2, os.cpu_count() or 2)))
        self.name = name
        cap = queue_cap or max(64, self.workers * 16)
        self._q: queue.Queue = queue.Queue(maxsize=cap)
        self._lanes: dict[str, SerialLane] = {}
        self._lock = threading.Lock()
        self._started = False
        self._heartbeat = None
        self._unmonitor = monitor_queue(
            f"pipeline_tasks_{name}", self._q.qsize, cap, owner=self)

    # -- pool plumbing --

    def _ensure_started(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
            from m3_tpu.utils import profiler

            self._heartbeat = profiler.register_heartbeat(
                f"pipeline.workers.{self.name}", _HEARTBEAT_S)
            for i in range(self.workers):
                t = threading.Thread(target=self._worker_loop,
                                     name=f"pipeline-{self.name}-{i}",
                                     daemon=True)
                t.start()

    def _worker_loop(self) -> None:
        _tl.worker = True
        hb = self._heartbeat
        while True:
            try:
                task = self._q.get(timeout=_IDLE_POLL_S)
            except queue.Empty:
                if hb is not None:
                    hb.beat()
                continue
            if hb is not None:
                hb.beat()
            fn, fut = task
            if fut is None:
                fn()  # lane drain: runs its own futures
            else:
                fut.run(fn)

    def _enqueue(self, drain_fn) -> None:
        self._ensure_started()
        self._q.put((drain_fn, None))

    def submit(self, fn, point_ctx: str = "") -> _Future:
        # same semantic seam as SerialLane.submit — ONE submit-time
        # injection schedule for "a pipeline task", whichever entry the
        # caller took (deterministic: both fire on the caller thread)
        # m3lint: disable=inv-fault-point-unique
        faults.check("pipeline.task", stage=point_ctx)
        self._ensure_started()
        fut = _Future()
        self._q.put((fn, fut))
        return fut

    def lane(self, name: str) -> SerialLane:
        with self._lock:
            ln = self._lanes.get(name)
            if ln is None:
                ln = self._lanes[name] = SerialLane(self, name)
            return ln

    def map_ordered(self, fns: list, depth: int):
        """Yield fn() results in input order with up to ``depth`` calls
        in flight ahead of the consumer — the bounded-depth prefetch the
        read path overlaps its gathers through. Falls back to a
        plain inline loop from worker context (no nested waits)."""
        if in_worker() or len(fns) <= 1:
            for fn in fns:
                yield fn()
            return
        depth = max(1, depth)
        futs: deque = deque()
        it = iter(fns)
        for fn in it:
            futs.append(self.submit(fn, point_ctx="map"))
            if len(futs) >= depth:
                break
        while futs:
            fut = futs.popleft()
            nxt = next(it, None)
            if nxt is not None:
                futs.append(self.submit(nxt, point_ctx="map"))
            yield fut.result()


_default_lock = threading.Lock()
_default: PipelineExecutor | None = None
_client: PipelineExecutor | None = None


def default_executor() -> PipelineExecutor:
    """The STORAGE pool: fileset gathers and WAL-lane appends — leaf
    tasks that never wait on another pipeline task."""
    global _default
    with _default_lock:
        if _default is None:
            _default = PipelineExecutor()
        return _default


def client_executor() -> PipelineExecutor:
    """The CLIENT pool: session/fanout RPC legs. Deliberately separate
    from the storage pool — a leg blocks on a downstream node whose read
    path needs STORAGE workers, so colocated processes (tests, the
    in-process rig) sharing one pool would form a wait cycle: legs hold
    every worker while the gathers that would unblock them queue behind
    them. Two pools with a strict leg->storage dependency direction
    cannot cycle. Sized for I/O (legs park on sockets, not cores)."""
    global _client
    with _default_lock:
        if _client is None:
            _client = PipelineExecutor(
                workers=_env_int("M3_TPU_PIPELINE_CLIENT_WORKERS",
                                 min(16, max(4, 2 * (os.cpu_count() or 2)))),
                name="client")
        return _client


def started() -> bool:
    """Whether the default pool ever spawned workers (hatch tests)."""
    with _default_lock:
        return _default is not None and _default._started


def configure(workers: int | None = None, depth: int | None = None,
              wal_chunk: int | None = None) -> None:
    """Service-config knobs (dbnode `pipeline:` section), recorded as
    module state: an explicit M3_TPU_PIPELINE_* env var still wins, the
    built-in defaults lose, and repeated calls last-write-win (an
    in-process multi-service harness gets the LAST service's sizing —
    depth/wal_chunk take effect immediately; worker counts bind when a
    pool first starts, so configure before first pipelined use)."""
    for name, value in (("M3_TPU_PIPELINE_WORKERS", workers),
                        ("M3_TPU_PIPELINE_DEPTH", depth),
                        ("M3_TPU_PIPELINE_WAL_CHUNK", wal_chunk)):
        if value is not None:
            _cfg[name] = int(value)


def prefetch_depth() -> int:
    return _env_int("M3_TPU_PIPELINE_DEPTH", 2)


def submit_client_leg(fn, tracer, ctx, point_ctx: str) -> _Future:
    """Submit ONE fan-out RPC leg to the client pool with the shared leg
    policy (session fetch_many and the coordinator fanout both ride
    this): the caller's trace context is re-activated on the worker
    (header injection and exemplar capture are thread-local), the leg is
    timed, and the outcome comes back AS A VALUE — ``(result, err,
    seconds)`` — so the consumer applies its own per-host/per-zone
    failure policy in submission order. A SimulatedCrash escalates on
    the worker (armed chaos == process death at the point of injury) and
    is still returned as ``err`` for the consumer to re-raise."""

    def leg():
        t0 = time.perf_counter()
        try:
            with tracer.activate(ctx):
                return fn(), None, time.perf_counter() - t0
        except faults.SimulatedCrash as e:
            faults.escalate()
            return None, e, time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 - delivered to the consumer
            return None, e, time.perf_counter() - t0

    return client_executor().submit(leg, point_ctx=point_ctx)


@dataclass
class StageStats:
    """Per-run overlap accounting: wall time vs sum-of-stage time. When
    ``sum(stages.values()) > wall_s`` the pipeline overlapped work; the
    ratio rides ``?explain=analyze`` via querystats.record_pipeline."""

    items: int = 0
    wall_s: float = 0.0
    stages: dict = field(default_factory=dict)

    def add_stage(self, name: str, dt: float) -> None:
        self.stages[name] = self.stages.get(name, 0.0) + dt


def run_stages(items: list, produce, consume, depth: int | None = None,
               produce_stage: str = "gather",
               consume_stage: str = "decode") -> StageStats:
    """The two-stage overlap primitive: ``produce(item)`` runs on the
    pool up to ``depth`` items ahead (thread-safe leg: fileset gather,
    node RPC) while ``consume(item, payload)`` runs on the CALLING
    thread in submission order (thread-local leg: decode rungs,
    querystats, cache fills). With the hatch closed (or from a worker)
    it degrades to the exact serial interleaving ``consume(produce())``
    — same work, same order, no threads. Per-leg seconds come from the
    legs' spans (utils/trace.py stage()); ``wall_s`` is this pass's own."""
    from m3_tpu.utils import trace

    stats = StageStats(items=len(items))
    t0 = time.perf_counter()
    pipelined = active() and len(items) > 1
    tracer = trace.default_tracer()
    ctx = tracer.current()

    def produce_leg(item):
        # the leg's seconds are its span's. On a pool worker the span is
        # the stage `read_many.gather`, under the caller's re-activated
        # context (so it hangs in the request's tree with the request's
        # route); run inline it is the caller's own time, and its stage
        # (read_many) keeps it as self-time
        with tracer.activate(ctx), \
                tracer.stage(trace.STAGE_GATHER, metered=pipelined) as fr:
            payload = produce(item)
        return item, payload, fr.wall_s

    if pipelined:
        ex = default_executor()
        results = ex.map_ordered(
            [lambda it=it: produce_leg(it) for it in items],
            depth or prefetch_depth())
    else:
        results = (produce_leg(it) for it in items)
    for item, payload, p_dt in results:
        stats.add_stage(produce_stage, p_dt)
        with tracer.stage(trace.PIPELINE_CONSUME, metered=False) as fr:
            consume(item, payload)
        stats.add_stage(consume_stage, fr.wall_s)
    stats.wall_s = time.perf_counter() - t0
    if stats.items:
        _scope.subscope("stage", stage=produce_stage).observe(
            "stage_seconds", stats.stages.get(produce_stage, 0.0))
        _scope.subscope("stage", stage=consume_stage).observe(
            "stage_seconds", stats.stages.get(consume_stage, 0.0))
    return stats
