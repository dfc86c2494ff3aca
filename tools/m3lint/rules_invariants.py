"""Project-invariant rules (rule family ``inv-*``).

Absorbs tools/check_observability.py (PR 4-7's five observability
invariants) as rules 1-5 and adds three new ones:

``inv-tracepoint-unique``   tracepoint constants in utils/trace.py unique
``inv-fault-instrumented``  every declared fault point's module carries a
                            metric scope or span at the seam
``inv-exemplar-capture``    the Scope histogram entry points capture
                            exemplars (p99 bucket -> stitched trace link)
``inv-exporter-registered`` every service entrypoint registers the
                            telemetry-exporter drainer
``inv-admission-counted``   tenant admission decisions counted, sheds
                            carry the TENANT_SHED tracepoint
``inv-fault-point-unique``  every fault-point NAME is declared at exactly
                            one code site — two seams sharing a name merge
                            their injection schedules and their stats
                            (deliberate shared seams carry a waiver)
``inv-histogram-catalog``   every literal histogram/timer name is listed
                            in utils/metric_catalog.py — the catalog is
                            what dashboards and the self-scrape contract
                            are written against
``inv-crash-swallow``       no bare/broad ``except`` around a fault seam
                            that would swallow ``SimulatedCrash`` without
                            re-raising or escalating: a swallowed crash
                            turns every chaos assertion into a lie. Seams
                            are found transitively through same-module
                            calls (the peers.py bug class: the broad
                            except wraps an RPC helper whose
                            ``faults.check`` lives one call down)
``inv-wire-frame-scope``    frame codec descriptors (``struct.Struct``,
                            ``np.dtype``) built once at module scope,
                            never per call — a per-request construction
                            re-parses the format string on the hot
                            handler path (the utils/wire.py idiom)

The fixed-project-file rules (tracepoints, exemplars, exporter,
admission) run in whole-tree mode only; the fault-seam, catalog, and
crash-swallow rules are per-module so fixture tests can exercise them.
"""

from __future__ import annotations

import ast
import os

from tools.m3lint.engine import attr_chain as _attr_chain
from tools.m3lint.engine import PKG, Finding, Module, Project

RULES = {
    "inv-tracepoint-unique": "duplicate tracepoint constant",
    "inv-fault-instrumented": "fault point with no observability at its seam",
    "inv-exemplar-capture": "histogram entry point without exemplar capture",
    "inv-exporter-registered": "service entrypoint missing the exporter",
    "inv-admission-counted": "admission decision without counters/tracepoint",
    "inv-fault-point-unique": "fault point name declared at more than one site",
    "inv-histogram-catalog": "histogram/timer name missing from the catalog",
    "inv-crash-swallow": "broad except around a fault seam swallows SimulatedCrash",
    "inv-queue-gauge": "bounded queue/ring without a monitor_queue registration",
    "inv-pagepool-gauge": "page pool/hot tier constructed without a "
                          "saturation-plane registration",
    "inv-wire-frame-scope": "frame codec struct/dtype constructed per call",
}

# modules whose fault-point mentions are documentation or test scaffolding
EXEMPT = {
    os.path.join("utils", "faults.py"),      # the registry itself (docs)
    os.path.join("tools", "race_check.py"),  # stress harness
}

# `stage` is the stage clock's span (utils/trace.py stage())
_OBS_ATTRS = {"span", "stage", "histogram", "observe", "counter", "timer",
              "gauge", "subscope", "root_scope"}

SERVICE_ENTRYPOINTS = (
    os.path.join("services", "coordinator.py"),
    os.path.join("services", "dbnode.py"),
    os.path.join("services", "aggregator.py"),
    os.path.join("cluster", "kvd.py"),
)

_HISTO_ATTRS = {"observe", "histogram", "histogram_handle", "timer"}


# ---------------------------------------------------------------------------
# shared scanners
# ---------------------------------------------------------------------------

class _SeamScanner(ast.NodeVisitor):
    """Fault points + instrumentation references in one module."""

    def __init__(self):
        self.fault_points: list[tuple[str, int]] = []
        self.instrumented = False

    def visit_Call(self, node: ast.Call):
        fn = node.func
        attr = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if attr in ("check", "torn_write", "wrap_io"):
            owner = getattr(fn, "value", None)
            owner_name = owner.id if isinstance(owner, ast.Name) else None
            if owner_name in ("faults", None) or attr == "check":
                for arg in node.args:
                    if isinstance(arg, ast.Constant) and \
                            isinstance(arg.value, str) and "." in arg.value:
                        self.fault_points.append((arg.value, node.lineno))
                        break
        if attr in _OBS_ATTRS:
            self.instrumented = True
        self.generic_visit(node)


def _function_references(tree: ast.AST, func_name: str, needle: str) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == func_name:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id == needle:
                    return True
                if isinstance(sub, ast.Attribute) and sub.attr == needle:
                    return True
    return False


def _project_tree(proj: Project, path: str) -> ast.AST | None:
    """Tree for a fixed project file — from the engine's already-parsed
    module table when present (whole-tree mode always has it; re-reading
    would also bypass the waiver/parse-error machinery), falling back to
    a direct parse only for paths outside the analyzed set."""
    mod = proj.by_path.get(os.path.abspath(path))
    if mod is not None:
        return mod.tree
    try:
        return ast.parse(open(path).read())
    except (OSError, SyntaxError):
        return None


# ---------------------------------------------------------------------------
# rules 1-5: the absorbed check_observability invariants
# ---------------------------------------------------------------------------

def _check_tracepoints(proj: Project):
    path = os.path.join(PKG, "utils", "trace.py")
    tree = _project_tree(proj, path)
    if tree is None:
        yield Finding("inv-tracepoint-unique", path, 1,
                      "utils/trace.py unreadable")
        return
    seen: dict[str, tuple[str, int]] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id.isupper() \
                and not node.targets[0].id.startswith("_") \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, str):
            name, value = node.targets[0].id, node.value.value
            if value in seen:
                prev, _line = seen[value]
                yield Finding(
                    "inv-tracepoint-unique", path, node.lineno,
                    f"tracepoint {name} duplicates {prev} (both {value!r}) "
                    f"— they would silently merge in every trace tree")
            else:
                seen[value] = (name, node.lineno)


def _check_fault_seams(proj: Project):
    """Rules 2 and 6 share the project-wide fault-point catalog."""
    catalog: dict[str, list[tuple[str, int]]] = {}
    for mod in proj.modules:
        if mod.rel in EXEMPT:
            continue
        sc = _SeamScanner()
        sc.visit(mod.tree)
        if not sc.fault_points:
            continue
        for point, lineno in sc.fault_points:
            catalog.setdefault(point, []).append((mod.path, lineno))
        if not sc.instrumented:
            pts = ", ".join(p for p, _ in sc.fault_points)
            yield Finding(
                "inv-fault-instrumented", mod.path, sc.fault_points[0][1],
                f"module declares fault point(s) [{pts}] but has no metric "
                f"scope or trace span at the seam — a seam we can break "
                f"but not see")
    for point, sites in sorted(catalog.items()):
        if len(sites) <= 1:
            continue
        first_path, first_line = sites[0]
        for path, line in sites[1:]:
            yield Finding(
                "inv-fault-point-unique", path, line,
                f"fault point {point!r} already declared at "
                f"{os.path.relpath(first_path, PKG)}:{first_line} — two "
                f"seams sharing a name merge their injection schedules "
                f"and stats (waive if the paths are one semantic seam)")


def _check_exemplar_capture(proj: Project):
    path = os.path.join(PKG, "utils", "instrument.py")
    tree = _project_tree(proj, path)
    if tree is None:
        yield Finding("inv-exemplar-capture", path, 1,
                      "utils/instrument.py unreadable")
        return
    if not _function_references(tree, "observe", "_active_exemplar_trace") \
            and not _function_references(tree, "observe", "_exemplar"):
        yield Finding(
            "inv-exemplar-capture", path, 1,
            "Scope.observe does not capture exemplars — seam histograms "
            "lose the p99-bucket -> trace link")
    if not (_function_references(tree, "histogram_handle",
                                 "_active_exemplar_trace")
            or _function_references(tree, "histogram_handle", "exemplars")):
        yield Finding(
            "inv-exemplar-capture", path, 1,
            "histogram_handle's hot-path closure does not capture exemplars")
    if not _function_references(tree, "observe_locked", "exemplars"):
        yield Finding(
            "inv-exemplar-capture", path, 1,
            "_Histogram.observe_locked has no exemplar storage")


def _check_exporter_registered(proj: Project):
    for rel in SERVICE_ENTRYPOINTS:
        path = os.path.join(PKG, rel)
        tree = _project_tree(proj, path)
        if tree is None:
            yield Finding("inv-exporter-registered", path, 1,
                          f"{rel}: unreadable/unparseable")
            continue
        found = any(isinstance(n, ast.Name) and n.id == "exporter_from_config"
                    for n in ast.walk(tree))
        if not found:
            yield Finding(
                "inv-exporter-registered", path, 1,
                f"service entrypoint {rel} does not register the telemetry "
                f"exporter (exporter_from_config) — a process outside the "
                f"export plane is a blind spot")


def _check_admission(proj: Project):
    path = os.path.join(PKG, "utils", "tenantlimits.py")
    tree = _project_tree(proj, path)
    if tree is None:
        yield Finding("inv-admission-counted", path, 1,
                      "utils/tenantlimits.py unreadable")
        return
    for fn in ("admit_write", "admit_query"):
        counted = (_function_references(tree, fn, "_allow")
                   and _function_references(tree, fn, "_shed")) \
            or _function_references(tree, fn, "counter")
        if not counted:
            yield Finding(
                "inv-admission-counted", path, 1,
                f"decision point {fn} does not emit per-tenant allow/shed "
                f"counters")
    if not _function_references(tree, "_shed", "counter"):
        yield Finding("inv-admission-counted", path, 1,
                      "the shed path does not emit a per-tenant counter")
    if not (_function_references(tree, "_shed", "span")
            and _function_references(tree, "_shed", "TENANT_SHED")):
        yield Finding("inv-admission-counted", path, 1,
                      "the shed path does not carry the TENANT_SHED "
                      "tracepoint")


# ---------------------------------------------------------------------------
# rule 7: histogram catalog
# ---------------------------------------------------------------------------

def _load_catalog(proj: Project) -> set[str] | None:
    path = os.path.join(PKG, "utils", "metric_catalog.py")
    tree = _project_tree(proj, path)
    if tree is None:
        return None
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id in ("HISTOGRAMS", "TIMERS"):
            try:
                val = ast.literal_eval(node.value)
            except ValueError:
                continue
            names.update(val)
    return names


def _check_histogram_catalog(proj: Project):
    catalog = _load_catalog(proj)
    if catalog is None:
        cat_path = os.path.join(PKG, "utils", "metric_catalog.py")
        yield Finding("inv-histogram-catalog", cat_path, 1,
                      "utils/metric_catalog.py missing or unparseable — "
                      "the histogram catalog is the exposition contract")
        return
    for mod in proj.modules:
        if mod.rel == os.path.join("utils", "metric_catalog.py"):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or \
                    not isinstance(node.func, ast.Attribute):
                continue
            if node.func.attr not in _HISTO_ATTRS:
                continue
            if not node.args or not isinstance(node.args[0], ast.Constant) \
                    or not isinstance(node.args[0].value, str):
                continue
            name = node.args[0].value
            if name not in catalog:
                yield Finding(
                    "inv-histogram-catalog", mod.path, node.lineno,
                    f"histogram/timer name {name!r} is not in "
                    f"utils/metric_catalog.py — add it to the catalog so "
                    f"dashboards and the self-scrape contract see it")


# ---------------------------------------------------------------------------
# rule 8: SimulatedCrash-swallowing excepts
# ---------------------------------------------------------------------------

_BROAD = {"Exception", "BaseException"}


def _handler_is_broad(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True
    types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
    for t in types:
        chain = _attr_chain(t)
        if chain and chain.rsplit(".", 1)[-1] in _BROAD:
            return True
    return False


def _mentions_crash(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id == "SimulatedCrash":
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in (
                "SimulatedCrash", "escalate"):
            return True
        if isinstance(sub, ast.Name) and sub.id == "escalate":
            return True
    return False


def _call_name(call: ast.Call) -> str | None:
    fn = call.func
    return fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else None)


def _is_direct_seam(call: ast.Call) -> bool:
    attr = _call_name(call)
    if attr not in ("check", "torn_write", "wrap_io"):
        return False
    owner = getattr(call.func, "value", None)
    if isinstance(owner, ast.Name) and owner.id == "faults":
        return True
    return attr in ("torn_write", "wrap_io")


# object-protocol names too generic to resolve by name: `q.get()`,
# `event.set()`, `channel.close()` would otherwise match any same-module
# seam-bearing `def get/set/close` (a queue is not a KV server). Calls to
# these are never chased; the direct-seam check still covers their
# bodies where it matters.
_GENERIC_NAMES = frozenset({
    "get", "set", "put", "pop", "close", "open", "read", "write", "flush",
    "send", "recv", "start", "stop", "run", "join", "wait", "clear", "add",
    "append", "update", "remove", "discard", "items", "keys", "values",
    "copy", "encode", "decode", "acquire", "release", "submit", "result",
    "cancel", "done", "next",
})


def _body_has_seam(stmts: list[ast.stmt],
                   seam_names: frozenset[str] = frozenset()) -> bool:
    """True when the statements reach a fault seam — directly
    (``faults.check``/``torn_write``/``wrap_io``) or through a call to a
    same-module callable whose body reaches one (``seam_names``, from
    `_seam_bearing_names`)."""
    for stmt in stmts:
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Call):
                if _is_direct_seam(sub):
                    return True
                if _call_name(sub) in seam_names:
                    return True
    return False


def _seam_bearing_names(mod: Module) -> frozenset[str]:
    """Names of this module's functions/methods whose bodies reach a
    fault seam, transitively through same-module calls (fixpoint — the
    concurrency family's intra-module call chasing, applied to crash
    propagation). Matching is by terminal name, so ``peer.block_starts()``
    resolves to any same-module ``def block_starts`` — the cross-function
    bug class where ``except Exception`` wraps an RPC helper whose seam
    lives one call down (storage/peers.py's bootstrap/metadata/stream
    loops around the ``peer.http`` seam)."""
    defs: dict[str, list[ast.AST]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name not in _GENERIC_NAMES:
            defs.setdefault(node.name, []).append(node)
    seam: set[str] = set()
    changed = True
    while changed:
        changed = False
        for name, fns in defs.items():
            if name in seam:
                continue
            for fn in fns:
                if _body_has_seam(fn.body, frozenset(seam)):
                    seam.add(name)
                    changed = True
                    break
    return frozenset(seam)


def _check_crash_swallow(proj: Project):
    for mod in proj.modules:
        if mod.rel in EXEMPT:
            continue
        seam_names = _seam_bearing_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Try):
                continue
            if not _body_has_seam(node.body, seam_names):
                continue
            crash_handled_earlier = False
            for h in node.handlers:
                if not _handler_is_broad(h):
                    if h.type is not None and _mentions_crash(h.type):
                        crash_handled_earlier = True
                    continue
                if crash_handled_earlier:
                    break
                reraises = any(isinstance(s, ast.Raise)
                               for s in ast.walk(ast.Module(
                                   body=h.body, type_ignores=[])))
                if reraises or _mentions_crash(ast.Module(
                        body=h.body, type_ignores=[])):
                    break
                label = "bare except:" if h.type is None else \
                    f"except {ast.unparse(h.type)}:"
                yield Finding(
                    "inv-crash-swallow", mod.path, h.lineno,
                    f"{label} around a fault seam swallows SimulatedCrash "
                    f"— re-raise it, call faults.escalate(e), or catch "
                    f"SimulatedCrash explicitly first (a swallowed crash "
                    f"falsifies every chaos assertion downstream)")
                break


# ---------------------------------------------------------------------------
# rule 9: bounded queues must register with the saturation plane
# ---------------------------------------------------------------------------

def _unbounding_const(node: ast.AST) -> bool:
    """A literal that makes the buffer unbounded (None maxlen, 0/negative
    maxsize)."""
    return isinstance(node, ast.Constant) and (
        node.value is None
        or (isinstance(node.value, (int, float)) and node.value <= 0))


def _is_bounded_queue_ctor(call: ast.Call) -> bool:
    """A ``deque(..., maxlen)`` (non-None) or ``queue.Queue(maxsize)``
    construction, keyword OR positional — a bounded buffer that can
    silently fill and drop."""
    name = _call_name(call)
    if name == "deque":
        for kw in call.keywords:
            if kw.arg == "maxlen":
                return not _unbounding_const(kw.value)
        return len(call.args) >= 2 and not _unbounding_const(call.args[1])
    if name == "Queue":
        for kw in call.keywords:
            if kw.arg == "maxsize":
                return not _unbounding_const(kw.value)
        return len(call.args) >= 1 and not _unbounding_const(call.args[0])
    return False


# memory-pool ctors held to the same registration discipline as bounded
# queues (ISSUE 15): a page pool or hot tier that fills/evicts with no
# occupancy gauges is the same invisible-saturation failure mode
_POOL_CTORS = {"PagePool", "HotTier"}
_POOL_REGISTERS = {"monitor_pool", "monitor_queue"}


class _QueueScanner(ast.NodeVisitor):
    """Bounded-queue + pool ctors and their registrations, per enclosing
    class.

    Scope key is the innermost ClassDef (None = module level): a class
    that builds bounded buffers must register at least one monitor; a
    module-level ring is satisfied by a module-level registration (the
    default-instance idiom, e.g. the tracer's span ring)."""

    def __init__(self):
        self._stack: list[ast.ClassDef | None] = [None]
        self.ctors: list[tuple[ast.ClassDef | None, int]] = []
        self.pool_ctors: list[tuple[ast.ClassDef | None, int]] = []
        self.monitored: set[ast.ClassDef | None] = set()
        self.pool_monitored: set[ast.ClassDef | None] = set()

    def visit_ClassDef(self, node: ast.ClassDef):
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        if name == "monitor_queue":
            self.monitored.add(self._stack[-1])
        if name in _POOL_REGISTERS:
            self.pool_monitored.add(self._stack[-1])
        if name in _POOL_CTORS \
                and self._stack[-1] is not None \
                and self._stack[-1].name != name:
            # the class DEFINING the pool is not a construction site
            self.pool_ctors.append((self._stack[-1], node.lineno))
        elif name in _POOL_CTORS and self._stack[-1] is None:
            self.pool_ctors.append((None, node.lineno))
        elif _is_bounded_queue_ctor(node):
            self.ctors.append((self._stack[-1], node.lineno))
        self.generic_visit(node)


def _check_queue_gauges(proj: Project):
    """Every bounded queue/ring must be registered with
    ``instrument.monitor_queue`` so its depth/capacity/drop gauges ride
    the saturation plane (a bounded queue with no gauge fills and drops
    invisibly — the failure mode this PR exists to kill). Deliberately
    unmonitored internals carry a same-line/line-above
    ``# m3lint: disable=inv-queue-gauge`` waiver."""
    for mod in proj.modules:
        if mod.rel in EXEMPT:
            continue
        sc = _QueueScanner()
        sc.visit(mod.tree)
        for cls, lineno in sc.pool_ctors:
            # pool discipline (inv-pagepool-gauge): a PagePool/HotTier
            # construction site must register it on the saturation plane
            # (monitor_pool / monitor_queue) in the SAME scope
            if cls in sc.pool_monitored:
                continue
            yield Finding(
                "inv-pagepool-gauge", mod.path, lineno,
                "page pool / hot tier constructed without a "
                "monitor_pool/monitor_queue registration in this scope "
                "— its occupancy and evictions are invisible to the "
                "saturation plane")
        if not sc.ctors:
            continue
        for cls, lineno in sc.ctors:
            # scope-matched blessing: a class's queues need a monitor in
            # THAT class; module-level rings need a module-level call —
            # one module-level registration must not silence every class
            # in the file
            if cls in sc.monitored:
                continue
            yield Finding(
                "inv-queue-gauge", mod.path, lineno,
                "bounded queue/ring is not registered with "
                "instrument.monitor_queue — it can saturate and drop "
                "with no depth/capacity/drop gauges on the saturation "
                "plane (waive only for intentionally unmonitored "
                "internals)")


# ---------------------------------------------------------------------------
# rule: frame codec objects built once at module scope
# ---------------------------------------------------------------------------

# constructor chains that COMPILE a wire-format descriptor: each call
# parses a format string / field spec, so one per request on a hot
# handler is pure re-parse overhead (the utils/wire.py + peers.py
# ROLLUP_DTYPE idiom is module scope, once per process). struct.pack /
# struct.unpack with a literal format are fine — the struct module
# caches compiled formats internally.
_FRAME_CTORS = {"struct.Struct", "np.dtype", "numpy.dtype"}


def _check_wire_frame_scope(proj: Project):
    """inv-wire-frame-scope: a ``struct.Struct(...)`` / ``np.dtype(...)``
    constructed inside a function or method body — frame descriptors
    belong at module scope (built once), not per call on a request
    handler. Waive for genuinely dynamic descriptors (a dtype computed
    from runtime shape)."""
    for mod in proj.modules:
        seen: set[int] = set()  # nested defs re-walk inner calls
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or node.lineno in seen:
                    continue
                chain = _attr_chain(node.func)
                if chain not in _FRAME_CTORS:
                    continue
                seen.add(node.lineno)
                yield Finding(
                    "inv-wire-frame-scope", mod.path, node.lineno,
                    f"{chain}(...) constructed inside {fn.name}() — frame "
                    f"codec descriptors are parsed at construction; build "
                    f"them ONCE at module scope (the utils/wire.py / "
                    f"peers.ROLLUP_DTYPE idiom), not per call")


def check(proj: Project):
    # per-module rules run in both whole-tree and explicit-paths mode
    yield from _check_fault_seams(proj)
    yield from _check_histogram_catalog(proj)
    yield from _check_crash_swallow(proj)
    yield from _check_queue_gauges(proj)
    yield from _check_wire_frame_scope(proj)
    if not proj.whole_tree:
        return
    # project-level rules reference fixed files; whole-tree mode only
    yield from _check_tracepoints(proj)
    yield from _check_exemplar_capture(proj)
    yield from _check_exporter_registered(proj)
    yield from _check_admission(proj)
