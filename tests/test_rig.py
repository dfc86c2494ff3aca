"""The production traffic rig (tools/rig.py).

Tier-1 (fast) half: seeded-traffic determinism, replayable chaos
schedules, the /metrics histogram parser, and an IN-PROCESS rig smoke
run — real CoordinatorAPI + Database + admission controller, zero
subprocesses — proving the ledger/shed/isolation machinery end to end.

Chaos half (`run_tests.sh rig`, marked `chaos` -> never tier-1): real
spawned processes — 2 dbnodes (RF=2) + a 3-replica quorum kvd metadata
plane + coordinator + aggregator — under a seeded kill/partition
schedule with live load: zero acked-write loss, stitched-warning reads
during the outage, runtime quota push through kvd, and the noisy-tenant
isolation SLO from the server-side per-tenant histograms."""

from __future__ import annotations

import json
import os
import time

import pytest

from m3_tpu.tools import rig as rigmod
from m3_tpu.tools.rig import (
    ChaosSchedule,
    Rig,
    RigConfig,
    TrafficGen,
    WriteLedger,
)


# ---------------------------------------------------------------------------
# determinism (tier-1)


class TestTrafficDeterminism:
    def test_same_seed_same_sequence(self):
        cfg = RigConfig(seed=11, tenants=("a", "b", "c"))
        g1, g2 = TrafficGen(cfg), TrafficGen(cfg)
        for _ in range(50):
            assert g1.next_batch(0) == g2.next_batch(0)
            assert g1.next_query(1000.0) == g2.next_query(1000.0)

    def test_different_seed_differs(self):
        a = TrafficGen(RigConfig(seed=1, tenants=("a", "b", "c")))
        b = TrafficGen(RigConfig(seed=2, tenants=("a", "b", "c")))
        seq_a = [a.next_batch(0) for _ in range(20)]
        seq_b = [b.next_batch(0) for _ in range(20)]
        assert seq_a != seq_b

    def test_zipf_skew(self):
        """Recorded-shape traffic: the head tenant dominates."""
        g = TrafficGen(RigConfig(seed=3, tenants=("hot", "warm", "cold"),
                                 zipf_s=1.5))
        picks = [g.pick_tenant() for _ in range(600)]
        assert picks.count("hot") > picks.count("warm") > picks.count("cold")


class TestChaosSchedule:
    TARGETS = [("h0", "node0", "dbnode"), ("h1", "node1", "dbnode"),
               ("kv0", "kvd", "kvd"), ("hc", "agg", "aggregator")]

    def test_replayable(self):
        s1 = ChaosSchedule.generate(7, 30.0, self.TARGETS)
        s2 = ChaosSchedule.generate(7, 30.0, self.TARGETS)
        assert s1 == s2
        assert s1 != ChaosSchedule.generate(8, 30.0, self.TARGETS)

    def test_every_outage_has_a_closing_pair(self):
        events = ChaosSchedule.generate(7, 30.0, self.TARGETS)
        opens = {"kill": "restart", "partition": "heal"}
        by_target: dict[tuple, list] = {}
        for e in events:
            by_target.setdefault((e.agent, e.service), []).append(e)
        assert len(by_target) == len(self.TARGETS)
        for pair in by_target.values():
            assert len(pair) == 2
            assert opens[pair[0].action] == pair[1].action
            assert pair[1].t_s > pair[0].t_s

    def test_outage_windows_never_overlap(self):
        """One failure domain at a time: overlapping windows would kill
        both replicas of an RF=2 shard and turn an availability-by-design
        gap into a fake data-loss signal."""
        events = ChaosSchedule.generate(7, 30.0, self.TARGETS)
        windows = []
        open_at: dict[tuple, float] = {}
        for e in events:
            key = (e.agent, e.service)
            if e.action in ("kill", "partition"):
                open_at[key] = e.t_s
            else:
                windows.append((open_at.pop(key), e.t_s))
        windows.sort()
        for (s1, e1), (s2, _e2) in zip(windows, windows[1:]):
            assert e1 <= s2

    def test_partition_events_carry_fault_specs(self):
        events = ChaosSchedule.generate(123, 60.0, self.TARGETS,
                                        partition_frac=1.0)
        parts = [e for e in events if e.action == "partition"]
        assert parts and all(e.fault_spec for e in parts)


# ---------------------------------------------------------------------------
# histogram parsing (tier-1): the rig's p99s come from /metrics text


class TestHistogramParsing:
    def test_parse_matches_inprocess_quantile(self):
        from m3_tpu.utils.instrument import MetricsRegistry

        reg = MetricsRegistry()
        scope = reg.root_scope("coordinator").subscope(
            "tenant", namespace="parse_t")
        import random

        rng = random.Random(5)
        values = [rng.uniform(0.001, 0.2) for _ in range(500)]
        for v in values:
            scope.observe("request_seconds", v)
        text = reg.render_prometheus().decode()
        hist = rigmod.parse_histogram(
            text, "coordinator_tenant_request_seconds",
            {"namespace": "parse_t"})
        assert sum(hist[1]) == 500
        key = ("coordinator.tenant.request_seconds",
               (("namespace", "parse_t"),))
        want_ms = reg.histograms[key].quantile(0.99) * 1e3
        got_ms = rigmod.hist_p99_ms(hist)
        assert got_ms == pytest.approx(want_ms, rel=1e-6)

    def test_delta_windows(self):
        bounds = [0.1, 1.0]
        prev = (bounds, [5.0, 1.0, 0.0])
        cur = (bounds, [9.0, 1.0, 2.0])
        b, d = rigmod.hist_delta(prev, cur)
        assert b == bounds and d == [4.0, 0.0, 2.0]
        assert rigmod.hist_p99_ms((bounds, [0.0, 0.0, 0.0])) is None

    def test_label_filter_excludes_other_series(self):
        text = (
            'coordinator_tenant_request_seconds_bucket{namespace="x",le="1"} 3\n'
            'coordinator_tenant_request_seconds_bucket{namespace="x",le="+Inf"} 3\n'
            'coordinator_tenant_request_seconds_bucket{namespace="y",le="1"} 9\n'
            'coordinator_tenant_request_seconds_bucket{namespace="y",le="+Inf"} 9\n'
        )
        _b, counts = rigmod.parse_histogram(
            text, "coordinator_tenant_request_seconds", {"namespace": "x"})
        assert sum(counts) == 3


class TestNamespaceTimeUnit:
    """The registry knob the rig depends on: a namespace ingesting
    irregular ns timestamps must be able to declare a fine time unit, or
    snapshot/flush encode truncates to seconds and a restart silently
    collapses datapoints (the loss mode the rig's audit caught)."""

    def test_parse_time_unit(self):
        from m3_tpu.encoding.m3tsz.constants import TimeUnit
        from m3_tpu.services.coordinator import (
            namespace_options,
            parse_time_unit,
        )

        assert parse_time_unit("ns") is TimeUnit.NANOSECOND
        assert parse_time_unit("MS") is TimeUnit.MILLISECOND
        with pytest.raises(ValueError):
            parse_time_unit("fortnights")
        assert namespace_options(
            {"time_unit": "ns"}).write_time_unit is TimeUnit.NANOSECOND
        assert namespace_options({}).write_time_unit is TimeUnit.SECOND

    def test_ns_unit_snapshot_restore_roundtrip(self, tmp_path):
        """Irregular ns timestamps survive a snapshot -> restart ->
        restore cycle exactly when the namespace declares time_unit ns
        (with the WAL already reclaimed, the snapshot IS durability)."""
        from m3_tpu.services.coordinator import namespace_options
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        opts = namespace_options({"time_unit": "ns"})
        base = 1_785_754_950_000_000_000
        points = [(base + i * 997_001, float(i)) for i in range(40)]

        db = Database(str(tmp_path / "d"), DatabaseOptions(n_shards=2))
        db.create_namespace("t", opts)
        db.open(now_ns=base)
        for t, v in points:
            db.write_tagged("t", b"m", [(b"k", b"v")], t, v)
        db.snapshot(base + 1)
        # simulate the WAL being reclaimed: durability rests on snapshots
        import glob
        import os

        for f in glob.glob(str(tmp_path / "d" / "commitlog" / "t" / "*")):
            os.remove(f)
        db.close()

        db2 = Database(str(tmp_path / "d"), DatabaseOptions(n_shards=2))
        db2.create_namespace("t", opts)
        db2.open(now_ns=base + 2)
        try:
            from m3_tpu.utils.ident import tags_to_id

            sid = tags_to_id(b"m", [(b"k", b"v")])
            got = {(d.timestamp_ns, d.value)
                   for d in db2.read("t", sid, 0, 1 << 62)}
            assert got == set(points)  # ns-exact, nothing collapsed
        finally:
            db2.close()


# ---------------------------------------------------------------------------
# in-process rig smoke (tier-1): the whole loop, no subprocesses


class TestInProcessRigSmoke:
    @pytest.fixture
    def smoke(self, tmp_path):
        from m3_tpu.query.api import CoordinatorAPI
        from m3_tpu.storage import limits as storage_limits
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions
        from m3_tpu.utils.tenantlimits import TenantAdmission, TenantQuota

        db = Database(str(tmp_path / "data"), DatabaseOptions(n_shards=2))
        for t in ("smokeA", "smokeB"):
            db.create_namespace(t)
        db.open()
        api = CoordinatorAPI(db, "smokeA")
        api.admission = TenantAdmission(
            {"smokeA": TenantQuota(queries_per_sec=3, burst_s=1.0),
             "smokeB": TenantQuota(queries_per_sec=10_000)},
            cardinality_source=lambda ns: storage_limits.live_series(db, ns))
        yield db, api
        db.close()

    def test_smoke_run(self, smoke):
        db, api = smoke
        cfg = RigConfig(seed=42, tenants=("smokeA", "smokeB"), zipf_s=1.0,
                        series_per_tenant=8, batch_size=8,
                        write_interval_s=0.02, query_interval_s=0.02,
                        duration_s=2.0)
        ledger = WriteLedger()
        rig = Rig(cfg, rigmod.db_write_fn(db), rigmod.api_query_fn(api),
                  ledger=ledger)
        report = rig.run()

        # load actually flowed and every acked write reads back
        assert report["acked_total"] > 100
        verify = ledger.verify(rigmod.db_fetch_fn(db))
        assert verify["checked"] == report["acked_total"]
        assert verify["missing"] == []

        # the saturated tenant was shed with Retry-After; the steady
        # tenant was never shed
        a = report["tenants"]["smokeA"]
        b = report["tenants"]["smokeB"]
        assert a["queries_shed"] > 0
        assert report["retry_after_seen"] > 0
        assert b["queries_shed"] == 0
        assert b["queries_ok"] > 0

        # server-side per-tenant histogram (the PR-4 family) carries
        # B's latency; p99 parsed from the exposition text
        from m3_tpu.utils.instrument import default_registry

        text = default_registry().render_prometheus().decode()
        hist = rigmod.parse_histogram(
            text, "coordinator_tenant_request_seconds",
            {"namespace": "smokeB"})
        assert sum(hist[1]) >= b["queries_ok"]
        p99 = rigmod.hist_p99_ms(hist)
        assert p99 is not None and p99 < 5000.0

    def test_ledger_detects_loss(self, smoke):
        """The verifier is only evidence if it can FAIL: a datapoint the
        reader does not return must be reported missing."""
        db, _api = smoke
        ledger = WriteLedger()
        entries = [(b"rig_metric_0", ((b"tenant", b"smokeA"),), 10**9, 1.5)]
        ledger.record("smokeA", entries, [None])
        report = ledger.verify(lambda *a: [])
        assert report["checked"] == 1
        assert len(report["missing"]) == 1
        report2 = ledger.verify(lambda *a: [(10**9, 1.5)])
        assert report2["missing"] == []


# ---------------------------------------------------------------------------
# cardinality-explosion episode (tier-1, in-process): index churn under
# live reads — the ISSUE-16 rig lane for the device-compiled index


class TestCardinalityChurnEpisode:
    """A tenant whose writes keep minting brand-new series (the
    ``churn_per_batch`` knob: monotonically-unique churn tags) drives
    continuous index ingest and segment churn. The episode's claim: the
    read path stays bounded — client p99 holds under the explosion, no
    query errors — while the live-series population multiplies."""

    def test_churn_minting_deterministic_and_unique(self):
        cfg = RigConfig(seed=9, tenants=("a", "b"), batch_size=8,
                        churn_per_batch=4)
        g1, g2 = TrafficGen(cfg), TrafficGen(cfg)
        seen = set()
        minted = 0
        for _ in range(30):
            batch = g1.next_batch(0)
            assert batch == g2.next_batch(0)  # same seed, same sequence
            for name, tags, _t, _v in batch[1]:
                if b"churn" in dict(tags):
                    minted += 1
                    seen.add((name, tags))
        # every churn entry is a NEW series identity, never a repeat
        assert minted >= 30 * cfg.churn_per_batch
        assert len(seen) == minted

    def test_bounded_read_p99_under_index_churn(self, tmp_path):
        from m3_tpu.query.api import CoordinatorAPI
        from m3_tpu.storage import limits as storage_limits
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path / "data"), DatabaseOptions(n_shards=2))
        db.create_namespace("churnT")
        db.open()
        api = CoordinatorAPI(db, "churnT")
        try:
            before = storage_limits.live_series(db, "churnT")
            cfg = RigConfig(seed=77, tenants=("churnT",), zipf_s=1.0,
                            series_per_tenant=8, batch_size=16,
                            churn_per_batch=12, write_interval_s=0.01,
                            query_interval_s=0.02, duration_s=2.5)
            rig = Rig(cfg, rigmod.db_write_fn(db), rigmod.api_query_fn(api))
            report = rig.run()
            after = storage_limits.live_series(db, "churnT")

            # the explosion actually happened: the live-series population
            # grew by hundreds of freshly minted identities
            assert after - before > 300
            st = report["tenants"]["churnT"]
            assert st["writes_acked"] > 500 and st["write_errors"] == 0

            # and reads stayed healthy THROUGH the churn: all served, no
            # errors, client p99 inside the default SLO bound
            assert st["queries_ok"] > 20
            assert st["query_errors"] == 0
            assert st["client_p99_ms"] is not None
            assert st["client_p99_ms"] < cfg.slo_p99_ms
        finally:
            db.close()


# ---------------------------------------------------------------------------
# process-level chaos lane (`run_tests.sh rig`; marked chaos -> not tier-1)


def _cpu_env():
    import pathlib

    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(pathlib.Path(__file__).resolve().parents[1]),
    }


@pytest.mark.chaos
class TestProcessRig:
    def test_production_rig_full(self, tmp_path):
        """The acceptance run: seeded kill/partition schedule against
        real spawned processes under live load — zero acked-write loss,
        warning-bearing reads during the outage, runtime quota push via
        kvd, noisy tenant shed with 429 while steady tenant's
        pair-median p99 (server histograms) holds the SLO."""
        seconds = float(os.environ.get("M3_TPU_RIG_SECONDS", "20"))
        seed = int(os.environ.get("M3_TPU_RIG_SEED", "7"))
        report = rigmod.run_production_rig(
            str(tmp_path / "rig"), seconds=seconds, seed=seed,
            slo_p99_ms=5000.0)

        # chaos actually happened, and every action round-tripped
        assert report["chaos_executed"], report.get("chaos_errors")
        assert not report["chaos_errors"], report["chaos_errors"]

        # zero acked-write loss across SIGKILLs and partitions
        assert report["verify"]["acked"] > 0
        assert report["verify"]["missing"] == [], report["verify"]
        assert report["verify"]["checked"] == report["verify"]["acked"]

        # the ReadWarning contract surfaced during the outage windows
        warnings = sum(t["warnings"]
                       for t in report["phase1"]["tenants"].values())
        assert warnings >= 1, report["phase1"]

        # anti-entropy convergence: every replica pair reached
        # per-(shard, block) rollup-digest equality within the repair
        # cycle budget — driven by the nodes' continuous daemons, not by
        # the rig invoking repair
        conv = report["convergence"]
        assert conv["converged"], conv
        assert conv["replica_pairs"] > 0, conv
        assert conv["cycles_used"] <= conv["budget_cycles"] * 2, conv

        # noisy-tenant isolation under a node kill: quota pushed through
        # the kvd metadata plane mid-run started shedding the noisy
        # tenant; the steady tenant held its SLO (pair-median p99 from
        # the per-tenant server histograms)
        noisy = report["noisy_phase"]
        assert noisy["noisy_sheds"] > 0, noisy
        assert noisy["steady_sheds"] == 0, noisy
        assert noisy["steady_pair_median_p99_ms"] is not None, noisy
        assert noisy["steady_pair_median_p99_ms"] <= noisy["slo_p99_ms"], noisy

        # the soak trajectory artifact (profiling & saturation plane):
        # sampled rows with QPS/p99/RSS, a NON-EMPTY contended-lock
        # table from the armed lock-wait profiler, and >= 1 watchdog
        # stall event (the drill wedges a live dbnode's tick loop; its
        # own watchdog must report it with the wedged thread's stack)
        traj = report["trajectory"]
        assert traj["schema"] == rigmod.TrajectoryRecorder.SCHEMA
        assert len(traj["samples"]) >= 3, traj["samples"]
        assert any(s["rss_bytes"] for s in traj["samples"]), traj["samples"]
        assert traj["contended_locks"], "no contended locks recorded"
        assert traj["stall_events"], report.get("stall_drill")
        drill = report["stall_drill"]
        assert drill["events"], drill
        assert any("dbnode.py" in (e.get("stack") or "")
                   for e in drill["events"]), drill

        # every process is back at the end
        assert all(v == "ok" for v in report["final_heartbeats"].values())

    def test_elasticity_episode(self, tmp_path):
        """ROADMAP #6(b): add-node -> paced drain -> rolling restart
        under live load with a chaos schedule on the kvd/aggregator
        planes. The placement CAS is the rig's only lever — the nodes'
        handoff controllers stream, digest-verify, and cut over. Budget
        rides M3_TPU_RIG_SECONDS like the production run."""
        seconds = float(os.environ.get("M3_TPU_RIG_SECONDS", "20"))
        seed = int(os.environ.get("M3_TPU_RIG_SEED", "7"))
        report = rigmod.run_elasticity_episode(
            str(tmp_path / "rig"), seconds=max(10.0, seconds), seed=seed,
            slo_p99_ms=5000.0)

        # the topology actually churned: every verb ran and landed on
        # the trajectory timeline
        acts = [e["action"]
                for e in report["trajectory"]["topology_events"]]
        for want in ("add_node", "handoff_settled", "drain", "drained",
                     "restart"):
            assert want in acts, acts
        assert not report["chaos_errors"], report["chaos_errors"]

        # zero acked-write loss through add/drain/restart
        assert report["verify"]["acked"] > 0
        assert report["verify"]["missing"] == [], report["verify"]
        assert report["verify"]["checked"] == report["verify"]["acked"]

        # the handoff controllers did the work, observable on the new
        # /debug/placement surface (per-shard records, cutover totals)
        completed = sum(
            doc.get("handoff", {}).get("totals", {}).get("completed", 0)
            for doc in report["handoff_status"].values())
        assert completed > 0, report["handoff_status"]

        # the drained node is GONE and every shard ended AVAILABLE on
        # the post-change owners
        final = report["final_placement"]
        assert report["drained_node"] not in final, final
        assert final, final
        assert all(st == "AVAILABLE" for shards in final.values()
                   for st in shards.values()), final

        # bounded read p99 while the topology churned
        for t, st in report["phase"]["tenants"].items():
            if st["client_p99_ms"] is not None:
                assert st["client_p99_ms"] < 5000.0, (t, st)

        # anti-entropy convergence on the post-change replica pairs
        conv = report["convergence"]
        assert conv["converged"], conv
        assert conv["replica_pairs"] > 0, conv

    def test_standing_rules_episode(self, tmp_path):
        """ISSUE-18: standing recording rules + retention tiers under
        the full chaos schedule. The ruleset lands through KV mid-load;
        the coordinator evaluates against the quorum cluster while
        dbnodes, a kvd replica and the aggregator die and heal."""
        seconds = float(os.environ.get("M3_TPU_RIG_SECONDS", "20"))
        seed = int(os.environ.get("M3_TPU_RIG_SEED", "11"))
        report = rigmod.run_standing_rules_episode(
            str(tmp_path / "rig"), seconds=max(10.0, seconds), seed=seed,
            slo_p99_ms=5000.0)

        assert report["chaos_executed"], report.get("chaos_errors")
        assert not report["chaos_errors"], report["chaos_errors"]

        # zero acked-write loss for the raw load under chaos
        assert report["verify"]["acked"] > 0
        assert report["verify"]["missing"] == [], report["verify"]
        assert report["verify"]["checked"] == report["verify"]["acked"]

        # registry-sync: the rule-created tier namespace landed in KV
        # with its resolution (and WAL-replayable retention) recorded
        entry = report["registry_entry"]
        assert entry and entry["resolution"] == "1s", entry
        assert "complete" not in entry, entry  # standing-only: never

        # every rule recovered error-free with a caught-up watermark,
        # including the absent-input rule (evaluates, writes nothing)
        rules = report["standing_status"]["rules"]
        assert set(rules) == {"std:rig0:sum", "std:rig1:by_sid",
                              "std:rig2:avg", "std:absent"}, rules
        assert all(st["error"] is None and st["evals"] > 0
                   for st in rules.values()), rules

        # outputs exist and the aggregated/raw dual-write legs agree
        # point-for-point after the repair daemons converged
        assert report["output_points"] > 0, report["output_audit"]
        assert report["leg_parity_ok"], report["output_audit"]
        by_sid = report["output_audit"]["std:rig1:by_sid"]
        assert by_sid["agg_series"] >= 1, by_sid

        # convergence covered the tenants AND the rule-created namespace
        conv = report["convergence"]
        assert conv["converged"], conv
        assert conv["replica_pairs"] > 0, conv

        # bounded rule-eval lag, annotated onto the trajectory
        assert report["rule_eval_lag_p99_s"] is not None
        assert report["rule_eval_lag_p99_s"] <= report["lag_bound_s"]
        lag_events = [e for e in report["trajectory"]["topology_events"]
                      if e["action"] == "rule_eval_lag"]
        assert lag_events, report["trajectory"]["topology_events"]

        # misrouting honesty gate: an incomplete tier is never read
        assert report["no_misrouted_reads"], report["tier_reads"]
        assert report["tier_reads"], "no tier-routing decisions recorded"

    def test_crash_rule_kills_real_process(self, tmp_path):
        """The M3_TPU_FAULTS_EXIT satellite end to end: a crash-mode
        fault rule firing inside a REAL dbnode makes the process exit
        137 (observable death), not a 500 from a process that lives on."""
        import urllib.request

        from m3_tpu.tools.em import AgentClient, ClusterEnv, EmAgent

        agent = EmAgent(str(tmp_path / "host"), "127.0.0.1:0",
                        agent_id="host")
        client = AgentClient(f"http://127.0.0.1:{agent.port}")
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        try:
            client.put_file("node.yml", (
                f"db:\n  path: {tmp_path}/host/data\n  n_shards: 2\n"
                f"  namespaces:\n    - name: default\n"
                f"http:\n  host: 127.0.0.1\n  port: {port}\n"
                f"tick_interval_s: 5.0\n"))
            client.start("node", "m3_tpu.services.dbnode", "node.yml", env={
                **_cpu_env(),
                "M3_TPU_FAULTS": "dbnode.handle=crash:n3",
                "M3_TPU_FAULTS_EXIT": "1",
            })
            ClusterEnv.wait_until(
                lambda: rigmod._http_ok(f"http://127.0.0.1:{port}/health"),
                timeout_s=60, desc="node up")

            def read_once():
                url = (f"http://127.0.0.1:{port}/read?namespace=default"
                       f"&series_id=c2lk&start_ns=0&end_ns=1")
                try:
                    urllib.request.urlopen(url, timeout=5).read()
                except Exception:  # noqa: BLE001 - the 3rd request dies
                    pass           # mid-flight: torn connection expected

            for _ in range(3):
                read_once()
            ClusterEnv.wait_until(
                lambda: not client.status("node")["running"],
                timeout_s=30, desc="process death from crash rule")
            assert client.status("node")["returncode"] == 137

            # restart with a clean plan: the node serves again
            client.start("node", env=_cpu_env())
            ClusterEnv.wait_until(
                lambda: rigmod._http_ok(f"http://127.0.0.1:{port}/health"),
                timeout_s=60, desc="node back after crash")
        finally:
            try:
                client.stop("node", sig="SIGKILL")
            except Exception:  # noqa: BLE001
                pass
            agent.close()

    def test_start_surfaces_death_diagnostics(self, tmp_path):
        """The em satellite: a child dying inside the startup grace
        window raises AgentError WITH the log tail (today's alternative
        is wait_until timing out blind)."""
        from m3_tpu.tools.em import AgentClient, AgentError, EmAgent

        agent = EmAgent(str(tmp_path / "host"), "127.0.0.1:0",
                        agent_id="host")
        client = AgentClient(f"http://127.0.0.1:{agent.port}")
        try:
            client.put_file("bad.yml", "db: [unclosed\n  nonsense")
            with pytest.raises(AgentError) as ei:
                client.start("svc", "m3_tpu.services.dbnode", "bad.yml",
                             env=_cpu_env(), grace_s=90.0)
            msg = str(ei.value)
            assert "exited rc=" in msg
            assert "log tail" in msg
            # the tail carries the actual failure (yaml/config traceback)
            assert "Traceback" in msg or "Error" in msg
        finally:
            agent.close()
