"""Standing-query engine: incremental recording-rule evaluation.

The standing plane (ROADMAP #2) turns recording rules into CONTINUOUS
queries: each rule's PromQL expression compiles through the SAME
query/compiler.py plan path as an ad-hoc request (one fused jit program
per plan signature via the lru_cache program factory; the bounded plan
cache keys evaluations like any query), but evaluation is INCREMENTAL
per ingest batch instead of per request:

- every evaluation is keyed by
  ``(Namespace.data_version(), selector matchers, evaluation grid)``:
  the shape of the key the compiled path uses for device-resident
  prepared slabs (storage/hottier.py), with the version of the whole
  namespace where a fetch takes that of its range's blocks (a rule
  reads the newest windows, so the head block's writes are what moves
  it). An unchanged key means the inputs cannot have changed: the rule
  is SKIPPED without touching storage.
- a changed namespace version is refined to shard granularity:
  ``Shard.data_version`` bumps tell the evaluator precisely WHICH
  shards' content moved, and a rule re-evaluates only when a bumped
  shard holds (or just received) series its selectors match. The
  matched-shard set comes from a cheap index probe (query_ids — no
  sample reads), so a steady-state batch re-evaluates only the rules it
  invalidated; everything else is counted ``rules_skipped``.
- a skipped rule emits no new output points; readers' lookback carries
  its last written value forward exactly as it would for the untouched
  input series, so skipping is value-preserving for staleness-bounded
  reads.

Output lands through the downsampler's per-policy write leg: the
policy's aggregated namespace (coarse resolution, long retention — what
cheapest-tier read resolution serves) and, by default, the unaggregated
namespace so fine-step reads inside raw retention see the outputs too.

Hosting: the aggregator's flush loop (aggregator/downsample.Downsampler
.flush) drives ``evaluate`` under the same leader/local-flush
discipline as aggregation output.
"""

from __future__ import annotations

import time

import numpy as np

from m3_tpu.query import promql
from m3_tpu.query.promql import Expr, VectorSelector

NS = 1_000_000_000

# catch-up bound: one evaluation never back-fills more than this many
# grid points (a stalled evaluator resumes bounded, not unbounded)
MAX_POINTS_PER_EVAL = 4096


def collect_selectors(e: Expr) -> list[VectorSelector]:
    """Every VectorSelector in the expression tree — the rule's input
    surface (what the invalidation probe matches against shards)."""
    out: list[VectorSelector] = []
    if isinstance(e, VectorSelector):
        out.append(e)
    for attr in ("expr", "selector", "lhs", "rhs", "param"):
        child = getattr(e, attr, None)
        if isinstance(child, Expr):
            out.extend(collect_selectors(child))
    for child in getattr(e, "args", ()) or ():
        if isinstance(child, Expr):
            out.extend(collect_selectors(child))
    return out


def _matcher_fp(selectors) -> tuple:
    """Stable fingerprint of every selector's matchers (the `selector`
    leg of the (data_version, selector, grid) evaluation key)."""
    return tuple(
        tuple(sorted((m.name, getattr(m.match_type, "value",
                                      str(m.match_type)), m.value)
                     for m in sel.matchers))
        for sel in selectors
    )


class _RuleState:
    """Per-rule incremental-evaluation bookkeeping."""

    __slots__ = ("selectors", "matcher_fp", "last_end", "shards", "key",
                 "evals", "skips", "last_error")

    def __init__(self, selectors):
        self.selectors = selectors
        self.matcher_fp = _matcher_fp(selectors)
        self.last_end = 0          # last evaluated grid point (ns)
        self.shards: set[int] = set()  # shards holding matched series
        self.key = None            # (data_version, selector, grid) id
        self.evals = 0
        self.skips = 0
        self.last_error: str | None = None


class StandingEvaluator:
    """Evaluates a set of StandingRules incrementally against one source
    namespace, writing outputs through the downsampler's namespace leg."""

    def __init__(self, db, rules, source_namespace: str = "default",
                 namespace_for=None, now_fn=None,
                 buffer_past_ns: int = 0, catchup_points: int = 2,
                 query_compile: bool = True, write_raw_namespace=None):
        from m3_tpu.query.engine import Engine
        from m3_tpu.utils.instrument import default_registry

        self.db = db
        self.source = source_namespace
        # rules always read the RAW tier: their own outputs must never
        # become their inputs through cheapest-tier resolution
        self.engine = Engine(db, source_namespace, resolve_tiers=False,
                             query_compile=query_compile, now_fn=now_fn)
        self.namespace_for = namespace_for  # StoragePolicy -> ns name
        self.now_fn = now_fn or time.time_ns
        self.buffer_past_ns = buffer_past_ns
        self.catchup_points = max(1, catchup_points)
        self.write_raw_namespace = (write_raw_namespace
                                    if write_raw_namespace is not None
                                    else source_namespace)
        self._scope = default_registry().root_scope("aggregator").subscope(
            "standing")
        self._states: dict[str, _RuleState] = {}
        self._rules: list = []
        self._last_shard_versions: dict[int, int] = {}
        self._last_placement_epoch: int | None = None
        # local mirrors of the registry counters (test + /debug surface)
        self.counts = {"evaluated": 0, "invalidated": 0, "skipped": 0,
                       "errors": 0}
        self.last_invalidated: set[str] = set()
        self.set_rules(rules)

    def set_rules(self, rules) -> None:
        """Swap the live rule list (KV reload); state for surviving rule
        names is kept so a reload does not force a full re-evaluation."""
        self._rules = list(rules)
        keep = {r.name for r in self._rules}
        self._states = {n: s for n, s in self._states.items() if n in keep}

    # -- input versioning ---------------------------------------------------

    def _source_ns(self):
        try:
            ns = self.db.namespaces[self.source]
        except Exception:  # noqa: BLE001 - facade without the map
            return None
        # same capability marker as the engine's fetch key: facades have
        # no local version truth, so incremental skip cannot apply
        if not getattr(ns, "has_version_truth", False):
            return None
        return ns

    def _shard_versions(self, ns) -> dict[int, int]:
        return {sid: s.data_version for sid, s in list(ns.shards.items())}

    # -- evaluation ---------------------------------------------------------

    def evaluate(self, now_ns: int | None = None) -> dict:
        """One incremental pass over every rule; returns the pass
        summary {evaluated, invalidated, skipped, errors, points}."""
        now_ns = now_ns if now_ns is not None else self.now_fn()
        ns = self._source_ns()
        summary = {"evaluated": 0, "invalidated": 0, "skipped": 0,
                   "errors": 0, "points": 0}
        self.last_invalidated = set()
        if ns is not None:
            versions = self._shard_versions(ns)
            bumped = {sid for sid, v in versions.items()
                      if self._last_shard_versions.get(sid) != v}
            bumped |= set(self._last_shard_versions) - set(versions)
            epoch = ns._placement_epoch
            if self._last_placement_epoch != epoch:
                # shards moved: version sums alias across placements, so
                # every cached shard set is suspect — probe everything
                bumped |= set(versions) | {
                    s for st in self._states.values() for s in st.shards}
            self._last_shard_versions = versions
            self._last_placement_epoch = epoch
            ns_version = ns.data_version()
        else:
            bumped = None  # no local truth: every rule re-evaluates
            ns_version = None
        for rule in self._rules:
            self._evaluate_rule(rule, ns, ns_version, bumped, now_ns,
                                summary)
        for k in ("evaluated", "invalidated", "skipped", "errors"):
            if summary[k]:
                self._scope.counter(f"rules_{k}", summary[k])
                self.counts[k] += summary[k]
        return summary

    def _evaluate_rule(self, rule, ns, ns_version, bumped, now_ns: int,
                       summary: dict) -> None:
        state = self._states.get(rule.name)
        if state is None:
            try:
                selectors = collect_selectors(promql.parse(rule.expr))
            except Exception as e:  # noqa: BLE001 - out-of-band bad expr
                # (the KV store validates; only a bypassing writer lands
                # here) must not kill the flush loop — the rule keeps a
                # state slot so /debug shows its error, and retries next
                # flush (last_end stays 0 -> bootstrap)
                summary["errors"] += 1
                self._states.setdefault(rule.name, _RuleState([]))
                self._record_error(rule.name, str(e))
                return
            state = self._states[rule.name] = _RuleState(selectors)
        res = rule.policy.resolution_ns
        watermark = ((now_ns - self.buffer_past_ns) // res) * res
        if watermark <= 0:
            return
        # the hot tier's evaluation identity: (data_version, selector,
        # grid) — unchanged means the inputs and the requested grid are
        # byte-identical to the last pass, skip without touching storage
        key = (ns_version, state.matcher_fp, watermark, res)
        invalid, reason = self._invalidation(state, ns, bumped, watermark,
                                             key)
        if not invalid:
            state.skips += 1
            summary["skipped"] += 1
            return
        self.last_invalidated.add(rule.name)
        summary["invalidated"] += 1
        prev_end = state.last_end
        lag_s = (now_ns - (prev_end if prev_end else watermark)) / 1e9
        self._scope.observe("rule_eval_lag_seconds", max(0.0, lag_s))
        if prev_end:
            # re-evaluate the last emitted point too: a late write lands
            # in the current window and last-write-wins absorbs the
            # overwrite downstream
            start_pt = prev_end
        else:
            start_pt = watermark - (self.catchup_points - 1) * res
        start_pt = max(start_pt, res,
                       watermark - (MAX_POINTS_PER_EVAL - 1) * res)
        try:
            points = self._run(rule, state, ns, start_pt, watermark, res)
        except Exception as e:  # noqa: BLE001 - one broken rule must not
            # starve the rest of the flush
            summary["errors"] += 1
            self._record_error(rule.name, str(e))
            return
        state.last_end = watermark
        state.key = key
        state.evals += 1
        state.last_error = None
        if ns is not None:
            self._probe_shards(state, ns, start_pt, watermark)
        summary["evaluated"] += 1
        summary["points"] += points

    def _invalidation(self, state, ns, bumped, watermark: int, key):
        """(invalid?, reason). Exactness contract (pinned by tests): a
        batch touching shard S invalidates exactly the rules whose
        selectors match series now living in S."""
        if state.last_end == 0:
            return True, "bootstrap"
        if key == state.key:
            return False, "identity_unchanged"
        if ns is None or bumped is None:
            return True, "no_version_truth"
        if not bumped:
            return False, "unchanged"
        if state.shards & bumped:
            return True, "shard_version"
        # content moved somewhere this rule never matched — but a NEW
        # matching series may have landed there: one index probe (no
        # sample reads) refreshes the matched-shard set exactly
        self._probe_shards(state, ns, state.last_end, watermark)
        if state.shards & bumped:
            return True, "new_series"
        return False, "unchanged"

    def _probe_shards(self, state, ns, start_pt: int, end_pt: int) -> None:
        """Refresh the rule's matched-shard set from the index: matched
        series ids route to shards in one vectorized lookup."""
        from m3_tpu.index.query import matchers_to_query

        t_lo = start_pt - self.engine.lookback_ns
        t_hi = end_pt + 1
        shards: set[int] = set()
        for sel in state.selectors:
            docs = ns.query_ids(matchers_to_query(sel.matchers), t_lo, t_hi)
            ids = [d.series_id for d in docs]
            if ids:
                shards.update(ns.shards_of(ids))
        state.shards = shards

    def _run(self, rule, state, ns, start_pt: int, end_pt: int,
             res: int) -> int:
        """Evaluate the rule over [start_pt, end_pt] on its grid and
        write the outputs. The engine call compiles through
        query/compiler.py exactly like an ad-hoc query — one fused
        program per plan signature, plan-cache keyed — so a thousand
        flushes of the same rule trace and compile once."""
        from m3_tpu.query.engine import Vector

        expr = promql.parse(rule.expr)
        out, eval_ts = self.engine.query_range_expr(
            expr, int(start_pt), int(end_pt), int(res),
            query_text=f"standing:{rule.name}")
        if not isinstance(out, Vector) or not len(out.labels):
            return 0
        name = rule.name.encode()
        extra = dict(rule.labels)
        entries = []
        for li, lab in enumerate(out.labels):
            tags = {k: v for k, v in lab.items() if k != b"__name__"}
            tags.update(extra)
            tag_items = sorted(tags.items())
            row = out.values[li]
            ok = ~np.isnan(row)
            for ti in np.nonzero(ok)[0]:
                entries.append((name, tag_items, int(eval_ts[ti]),
                                float(row[ti])))
        if not entries:
            return 0
        out_ns = (self.namespace_for(rule.policy) if self.namespace_for
                  else rule.policy.namespace_name)
        self._write_outputs(out_ns, entries)
        if rule.write_raw and self.write_raw_namespace:
            self._write_outputs(self.write_raw_namespace, entries)
            if ns is not None:
                self._absorb_self_writes(ns, entries)
        return len(entries)

    def _write_outputs(self, namespace: str, entries) -> None:
        """Output writes are acked-or-retried: both write_batch surfaces
        (Database and the quorum ClusterDatabase facade) report per-entry
        failures as aligned strings instead of raising, so a partially
        dropped batch must fail the pass HERE — otherwise the watermark
        advances past grid points that never landed and the standing
        output silently loses them (no later flush re-covers the window)."""
        results = self.db.write_batch(namespace, entries)
        bad = [r for r in results or () if r is not None]
        if bad:
            raise RuntimeError(
                f"standing output write to {namespace!r}: "
                f"{len(bad)}/{len(entries)} entries failed "
                f"(first: {bad[:3]})")

    def _absorb_self_writes(self, ns, entries) -> None:
        """The evaluator's own raw-namespace output writes bump source
        shard versions; re-snapshot exactly those shards POST-write so
        the next pass does not self-invalidate every rule sharing a
        shard with an output series. (An external write racing into the
        same shard inside this tiny window is masked once; the next
        write to that shard re-invalidates.) A standing rule chained on
        another rule's raw output therefore does not re-fire from the
        output write alone — compose the upstream expr instead."""
        from m3_tpu.utils.ident import tags_to_id

        ids = list({tags_to_id(name, tags) for name, tags, _t, _v in entries})
        for sid in set(ns.shards_of(ids)):
            shard = ns.shards.get(sid)
            if shard is not None:
                self._last_shard_versions[sid] = shard.data_version

    def _record_error(self, name: str, err: str) -> None:
        st = self._states.get(name)
        if st is not None:
            st.last_error = err

    # -- introspection ------------------------------------------------------

    def status(self) -> dict:
        """Per-rule evaluation state for /debug surfaces and the rig."""
        return {
            "source": self.source,
            "totals": dict(self.counts),
            "rules": {
                name: {"last_end_ns": st.last_end, "evals": st.evals,
                       "skips": st.skips, "shards": sorted(st.shards),
                       "error": st.last_error}
                for name, st in self._states.items()
            },
        }
