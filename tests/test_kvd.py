"""m3kvd metadata plane: push watches, linearizable CAS, leases,
kill-the-leader failover (VERDICT r2 "Next round" #5).

Reference semantics being matched: the etcd-backed cluster KV
(/root/reference/src/cluster/kv/types.go:113 — watchable versioned store,
src/cluster/etcd/, src/cluster/services/leader elections)."""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from m3_tpu.cluster.kv import KeyNotFound, VersionMismatch
from m3_tpu.cluster.kvd import KvdClient, KvdServer, LeaseElection


@pytest.fixture
def server(tmp_path):
    s = KvdServer("127.0.0.1:0", journal_path=str(tmp_path / "kvd.json"))
    yield s
    s.close()


@pytest.fixture
def client(server):
    c = KvdClient(f"127.0.0.1:{server.port}")
    yield c
    c.close()


def wait_for(fn, timeout_s=10.0, desc="condition"):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.05)
    raise TimeoutError(desc)


class TestKvdCore:
    def test_crud_and_versioning(self, client):
        assert client.set("a", b"1") == 1
        assert client.set("a", b"2") == 2
        vv = client.get("a")
        assert (vv.version, vv.data) == (2, b"2")
        with pytest.raises(KeyNotFound):
            client.get("missing")
        client.delete("a")
        with pytest.raises(KeyNotFound):
            client.get("a")
        with pytest.raises(KeyNotFound):
            client.delete("a")

    def test_cas_is_linearizable_across_clients(self, server):
        """Two clients racing CAS on one key: exactly one winner per
        version — the single-writer server serializes them."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        b = KvdClient(f"127.0.0.1:{server.port}")
        try:
            a.set("ctr", b"0")
            wins = {"a": 0, "b": 0}
            errs = {"a": 0, "b": 0}

            def bump(client, name, n=30):
                for _ in range(n):
                    vv = client.get("ctr")
                    try:
                        client.check_and_set(
                            "ctr", vv.version,
                            str(int(vv.data) + 1).encode())
                        wins[name] += 1
                    except VersionMismatch:
                        errs[name] += 1

            ta = threading.Thread(target=bump, args=(a, "a"))
            tb = threading.Thread(target=bump, args=(b, "b"))
            ta.start(); tb.start(); ta.join(); tb.join()
            final = int(a.get("ctr").data)
            # every win incremented exactly once; no lost updates
            assert final == wins["a"] + wins["b"]
            assert a.get("ctr").version == final + 1
        finally:
            a.close()
            b.close()

    def test_set_if_not_exists(self, client):
        assert client.set_if_not_exists("once", b"x") == 1
        with pytest.raises(VersionMismatch):
            client.set_if_not_exists("once", b"y")

    def test_keys_prefix(self, client):
        client.set("p/one", b"1")
        client.set("p/two", b"2")
        client.set("q/three", b"3")
        assert client.keys("p/") == ["p/one", "p/two"]

    def test_journal_survives_restart(self, tmp_path):
        path = str(tmp_path / "kvd.json")
        s1 = KvdServer("127.0.0.1:0", journal_path=path)
        c1 = KvdClient(f"127.0.0.1:{s1.port}")
        c1.set("durable", b"v")
        c1.close()
        s1.close()
        s2 = KvdServer("127.0.0.1:0", journal_path=path)
        c2 = KvdClient(f"127.0.0.1:{s2.port}")
        try:
            assert c2.get("durable").data == b"v"
        finally:
            c2.close()
            s2.close()


class TestKvdWatchPush:
    def test_cross_client_watch_is_pushed_not_polled(self, server):
        """Client A learns of client B's write via the server's push
        stream — A never calls refresh() (which is a no-op anyway)."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        b = KvdClient(f"127.0.0.1:{server.port}")
        got = []
        try:
            a.watch("cfg", lambda k, vv: got.append(vv))
            assert a.refresh() == 0  # push store: nothing to poll
            b.set("cfg", b"v1")
            wait_for(lambda: any(vv and vv.data == b"v1" for vv in got),
                     desc="push of set")
            b.delete("cfg")
            wait_for(lambda: got and got[-1] is None, desc="push of delete")
        finally:
            a.close()
            b.close()

    def test_watch_bootstrap_delivers_current_value(self, server):
        a = KvdClient(f"127.0.0.1:{server.port}")
        b = KvdClient(f"127.0.0.1:{server.port}")
        try:
            b.set("pre", b"existing")
            got = []
            a.watch("pre", lambda k, vv: got.append(vv))
            wait_for(lambda: any(vv and vv.data == b"existing" for vv in got),
                     desc="bootstrap delivery")
        finally:
            a.close()
            b.close()


class TestKvdLeases:
    def test_ephemeral_key_vanishes_without_keepalive(self, server, client):
        """A key attached to a lease that never gets keep-alives is
        reaped and its deletion pushed to watchers."""
        from m3_tpu.cluster import kvd as kvdmod

        dying = KvdClient(f"127.0.0.1:{server.port}")
        # grant a short lease but DO NOT start the keepalive thread —
        # simulates a process that stopped breathing
        resp = dying._stub("LeaseGrant")(kvdmod._enc_req(ttl_ms=700))
        _v, _d, _e, lease_id, _k = kvdmod._dec_resp(resp)
        dying._lease_id = lease_id
        dying.set("ephemeral", b"alive", ephemeral=True)

        events = []
        client.watch("ephemeral", lambda k, vv: events.append(vv))
        wait_for(lambda: any(vv and vv.data == b"alive" for vv in events),
                 desc="ephemeral visible")
        wait_for(lambda: events and events[-1] is None, timeout_s=10,
                 desc="lease expiry pushed")
        with pytest.raises(KeyNotFound):
            client.get("ephemeral")
        dying._lease_id = 0
        dying.close()

    def test_stale_lease_cannot_reap_recreated_key(self, server, client):
        """Ownership handover: A's ephemeral key is deleted and re-created
        by B under B's lease; when A's lease later dies, B's key must
        survive (every write re-resolves the key's single lease owner)."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        b = KvdClient(f"127.0.0.1:{server.port}")
        try:
            a.start_session(ttl_ms=600)
            a.set("handover", b"A", ephemeral=True)
            a.delete("handover")  # A resigns
            b.start_session(ttl_ms=60_000)
            b.set("handover", b"B", ephemeral=True)  # B takes over under its own lease
            # kill A without revoke: stop its keepalives and wait > TTL
            a._closed.set()
            time.sleep(2.0)
            assert client.get("handover").data == b"B"
        finally:
            a.close()
            b.close()

    def test_rev_dedupe_survives_delete_recreate_replay(self, server):
        """A key deleted and re-created restarts at version 1; a client
        replaying the bootstrap after a stream gap must still apply the
        new value (revision-based dedupe, not version-based)."""
        c = KvdClient(f"127.0.0.1:{server.port}")
        try:
            got = []
            c.watch("flappy", lambda k, vv: got.append(vv))
            # simulate a prior life of the key at a high version
            c._apply_event("flappy", 5, b"old", deleted=False, rev=10)
            assert c._versions["flappy"] == 5
            # stream gap: the delete event was lost; the reconnect
            # bootstrap replays the RE-CREATED key at version 1, rev 12
            c._apply_event("flappy", 1, b"new", deleted=False, rev=12)
            assert c._data["flappy"].data == b"new"
            assert any(vv and vv.data == b"new" for vv in got)
            # replayed duplicates (rev <= last) stay dropped
            c._apply_event("flappy", 1, b"stale", deleted=False, rev=12)
            assert c._data["flappy"].data == b"new"
            # reconcile: a cached key absent from the bootstrap snapshot
            # is a deletion that happened during the gap
            c._reconcile_deletions({"otherkey"})
            assert "flappy" not in c._data
            assert got[-1] is None
        finally:
            c.close()

    def test_keepalive_preserves_key(self, server, client):
        holder = KvdClient(f"127.0.0.1:{server.port}")
        try:
            holder.start_session(ttl_ms=600)
            holder.set("held", b"x", ephemeral=True)
            time.sleep(1.5)  # several TTLs with keepalives running
            assert client.get("held").data == b"x"
        finally:
            holder.close()

    # the loud thread death IS the assertion: unarmed, the crash
    # re-raises out of the keepalive thread instead of being swallowed
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_keepalive_crash_escalates_not_swallowed(self, server,
                                                     monkeypatch):
        """A SimulatedCrash _call re-raises (chaos at the kvd.rpc seam)
        must reach faults.escalate and terminate the keepalive loop —
        the broad transport-retry except must not eat it, or an armed
        chaos run observes no process death."""
        from m3_tpu.utils import faults

        a = KvdClient(f"127.0.0.1:{server.port}")
        try:
            a.start_session(ttl_ms=400)
            escalated = threading.Event()
            orig_escalate = faults.escalate

            def recording_escalate(exc=None):
                escalated.set()
                orig_escalate(exc)  # unarmed: no-op, crash then re-raises

            monkeypatch.setattr(faults, "escalate", recording_escalate)
            orig_call = a._call

            def crashing(name, req):
                if name == "LeaseKeepAlive":
                    raise faults.SimulatedCrash("kvd.rpc")
                return orig_call(name, req)

            monkeypatch.setattr(a, "_call", crashing)
            assert escalated.wait(5), \
                "keepalive swallowed the SimulatedCrash"
            a._lease_thread.join(5)
            assert not a._lease_thread.is_alive(), \
                "crash did not terminate the keepalive loop"
        finally:
            a._closed.set()
            a.close()

    def test_regrant_mid_loop_teardown_grants_no_new_lease(self, server,
                                                           monkeypatch):
        """end_session racing INTO _regrant's re-assert loop: once the
        lease id is zeroed the loop must stop, and critically must not
        auto-grant a fresh lease via set()/_session_lease (which would
        leave a ghost session alive for a full TTL)."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        try:
            lease = a.start_session(ttl_ms=60_000)
            a.set("mid-loop", b"A", ephemeral=True)
            orig_get = a.get

            def get_then_teardown(key):
                vv = orig_get(key)
                with a._lease_lock:  # end_session wins mid-loop
                    a._lease_id = 0
                return vv

            monkeypatch.setattr(a, "get", get_then_teardown)
            a._regrant(lease)
            assert a._lease_id == 0, \
                "regrant granted a new lease for a session being ended"
        finally:
            a._closed.set()
            a.close()

    def test_regrant_refuses_after_end_session(self, server):
        """The keepalive's re-grant path must not resurrect a session
        end_session() is tearing down: if the stale id it observed has
        been zeroed, _regrant bails instead of re-asserting ephemeral
        keys (which would grant a brand-new lease via _session_lease)."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        try:
            lease = a.start_session(ttl_ms=60_000)
            a.set("regrant-guard", b"A", ephemeral=True)
            # freeze end_session mid-flight: id zeroed under the lock,
            # revoke not yet landed, _ephemeral not yet cleared — the
            # exact window a keepalive's "notfound" answer races into
            with a._lease_lock:
                a._lease_id = 0
            a._regrant(lease)
            assert a._lease_id == 0, \
                "regrant resurrected a session being ended"
        finally:
            a._closed.set()
            a.close()


KILLABLE_LEADER = r"""
import sys, time
sys.path.insert(0, {repo!r})
from m3_tpu.cluster.kvd import KvdClient, LeaseElection
c = KvdClient("127.0.0.1:{port}")
e = LeaseElection(c, "flush", "doomed-leader", ttl_ms=800)
assert e.is_leader()
print("LEADING", flush=True)
time.sleep(300)
"""


class TestLeaseExpiryRollback:
    """A write whose lease expires between the liveness check and the
    attach must roll back to the key's PRIOR VersionedValue (value,
    version, lease attachment) — not delete it (which destroyed version
    history and pushed a spurious delete event to every watcher)."""

    def _dead_lease(self, server) -> int:
        with server._lock:
            server._lease_seq += 1
            return server._lease_seq  # never registered => not live

    def _force_past_liveness_check(self, server):
        """Simulate the lease dying BETWEEN _lease_live and _attach_lease
        (the reaper window) by letting the pre-check pass."""
        server._lease_live = lambda lid: True

    def test_set_rollback_restores_prior_value_and_version(self, server,
                                                           client):
        from m3_tpu.cluster.kvd import _dec_resp, _enc_req

        client.set("k", b"v1")
        client.set("k", b"v2")
        events = []
        orig_notify = server.store._notify
        server.store._notify = lambda key, vv: (
            events.append((key, None if vv is None else vv.data)),
            orig_notify(key, vv))
        self._force_past_liveness_check(server)
        resp = server._set(
            _enc_req(key="k", data=b"v3", lease_id=self._dead_lease(server)),
            None)
        assert _dec_resp(resp)[2] == "nolease"
        vv = server.store.get("k")
        assert (vv.version, vv.data) == (2, b"v2")  # exact prior restored
        assert ("k", None) not in events  # no spurious delete event
        # and the key is NOT silently lease-attached to anything
        with server._lock:
            assert "k" not in server._key_lease

    def test_cas_rollback_restores_prior_value(self, server, client):
        from m3_tpu.cluster.kvd import _dec_resp, _enc_req

        client.set("k", b"v1")
        self._force_past_liveness_check(server)
        resp = server._cas(
            _enc_req(key="k", data=b"v2", expect_version=1,
                     lease_id=self._dead_lease(server)), None)
        assert _dec_resp(resp)[2] == "nolease"
        vv = server.store.get("k")
        assert (vv.version, vv.data) == (1, b"v1")

    def test_rollback_deletes_only_previously_absent_keys(self, server,
                                                          client):
        from m3_tpu.cluster.kvd import _dec_resp, _enc_req

        self._force_past_liveness_check(server)
        resp = server._set(
            _enc_req(key="fresh", data=b"x",
                     lease_id=self._dead_lease(server)), None)
        assert _dec_resp(resp)[2] == "nolease"
        with pytest.raises(KeyNotFound):
            server.store.get("fresh")

    def test_grace_attach_never_steals_a_live_owner(self, server, client):
        """only_if_unowned attach (the grace-lease restore) is atomic with
        the ownership check: a key a live owner re-attached is left alone."""
        owner = client.start_session(ttl_ms=30_000)
        client.set("eph", b"mine", ephemeral=True)
        with server._lock:
            server._lease_seq += 1
            from m3_tpu.cluster.kvd import _Lease

            grace = _Lease(server._lease_seq, 10_000)
            server._leases[grace.lease_id] = grace
        assert not server._attach_lease("eph", grace.lease_id, persist=False,
                                        only_if_unowned=True)
        with server._lock:
            assert server._key_lease.get("eph") == owner

    def test_rollback_preserves_prior_lease_attachment(self, server, client):
        from m3_tpu.cluster.kvd import _dec_resp, _enc_req

        owner = client.start_session(ttl_ms=30_000)
        client.set("eph", b"mine", ephemeral=True)
        with server._lock:
            assert server._key_lease.get("eph") == owner
        self._force_past_liveness_check(server)
        resp = server._set(
            _enc_req(key="eph", data=b"stolen",
                     lease_id=self._dead_lease(server)), None)
        assert _dec_resp(resp)[2] == "nolease"
        vv = server.store.get("eph")
        assert vv.data == b"mine"
        # the ORIGINAL owner still holds the key: its expiry still reaps it
        with server._lock:
            assert server._key_lease.get("eph") == owner


class TestKvdElection:
    def test_kill_the_leader_failover(self, server, tmp_path):
        """The VERDICT's required scenario: SIGKILL the leader process;
        the follower is promoted by lease expiry + watch push alone."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = KILLABLE_LEADER.format(repo=repo, port=server.port)
        leader_proc = subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        try:
            assert leader_proc.stdout.readline().strip() == "LEADING", \
                leader_proc.stdout.read()

            follower_client = KvdClient(f"127.0.0.1:{server.port}")
            follower = LeaseElection(
                follower_client, "flush", "follower", ttl_ms=800)
            assert not follower.is_leader()
            assert follower.leader() == "doomed-leader"

            leader_proc.send_signal(signal.SIGKILL)
            leader_proc.wait(timeout=10)

            # no polling in sight: lease reaper deletes the ephemeral
            # key, the delete event is pushed, the follower re-campaigns
            wait_for(follower.is_leader, timeout_s=15,
                     desc="follower promoted after leader SIGKILL")
            assert follower.leader() == "follower"
            follower.close()
            follower_client.close()
        finally:
            if leader_proc.poll() is None:
                leader_proc.kill()

    def test_resign_hands_over(self, server):
        ca = KvdClient(f"127.0.0.1:{server.port}")
        cb = KvdClient(f"127.0.0.1:{server.port}")
        try:
            ea = LeaseElection(ca, "tick", "a", ttl_ms=2_000)
            eb = LeaseElection(cb, "tick", "b", ttl_ms=2_000)
            assert ea.is_leader() and not eb.is_leader()
            ea.resign()
            wait_for(eb.is_leader, desc="b promoted after resign")
            ea.close()
            eb.close()
        finally:
            ca.close()
            cb.close()


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


class TestKvdRestartSurvivability:
    """The metadata plane must survive a kvd restart (round-4 VERDICT #3):
    monotonic revisions, orphan-grace reaping of journaled ephemeral keys,
    session re-grant + re-assert, and standby failover."""

    def test_client_sees_updates_after_server_restart(self, tmp_path):
        """The epoch-based revision counter stays monotonic across a
        restart; a surviving client's watch must deliver post-restart
        updates instead of dropping them as replays."""
        port = _free_port()
        journal = str(tmp_path / "kvd.json")
        s1 = KvdServer(f"127.0.0.1:{port}", journal_path=journal)
        c = KvdClient(f"127.0.0.1:{port}")
        w = KvdClient(f"127.0.0.1:{port}")
        try:
            got = []
            w.watch("k", lambda k, vv: got.append(vv))
            c.set("k", b"v1")
            wait_for(lambda: any(vv and vv.data == b"v1" for vv in got),
                     desc="pre-restart watch")
            s1.close()
            s2 = KvdServer(f"127.0.0.1:{port}", journal_path=journal)
            try:
                # _call retries through the reconnect
                c.set("k", b"v2")
                wait_for(lambda: any(vv and vv.data == b"v2" for vv in got),
                         timeout_s=15, desc="post-restart watch delivery")
            finally:
                s2.close()
        finally:
            c.close()
            w.close()

    def test_dead_leaders_journaled_key_is_grace_reaped(self, tmp_path):
        """An election key restored from the journal whose owner is dead
        must be reaped after the orphan grace, unwedging failover."""
        port = _free_port()
        journal = str(tmp_path / "kvd.json")
        s1 = KvdServer(f"127.0.0.1:{port}", journal_path=journal)
        dead = KvdClient(f"127.0.0.1:{port}")
        dead.start_session(ttl_ms=60_000)
        dead.set("_election/agg", b"dead-leader", ephemeral=True)
        dead._closed.set()  # the process dies with the server outage
        s1.close()

        s2 = KvdServer(f"127.0.0.1:{port}", journal_path=journal,
                       orphan_grace_ms=1_000)
        cb = KvdClient(f"127.0.0.1:{port}")
        try:
            assert cb.get("_election/agg").data == b"dead-leader"
            el = LeaseElection(cb, "agg", "successor", ttl_ms=800)
            assert not el.is_leader()
            wait_for(el.is_leader, timeout_s=15,
                     desc="successor elected after orphan grace")
            el.close()
        finally:
            cb.close()
            s2.close()

    def test_live_leader_keeps_leadership_across_restart(self, tmp_path):
        """A LIVE leader re-grants its session on the restarted server and
        re-asserts its election key before the orphan grace expires."""
        port = _free_port()
        journal = str(tmp_path / "kvd.json")
        s1 = KvdServer(f"127.0.0.1:{port}", journal_path=journal)
        ca = KvdClient(f"127.0.0.1:{port}")
        try:
            el = LeaseElection(ca, "agg", "survivor", ttl_ms=600)
            assert el.is_leader()
            s1.close()
            s2 = KvdServer(f"127.0.0.1:{port}", journal_path=journal,
                           orphan_grace_ms=4_000)
            try:
                # give the keepalive time to re-grant + re-assert, then
                # outlive the grace window
                time.sleep(5.0)
                assert s2.store.get("_election/agg").data == b"survivor"
                assert el.is_leader()
                # and the key is lease-attached again (ephemeral)
                assert "_election/agg" in s2._key_lease
            finally:
                s2.close()
        finally:
            ca.close()

    def test_persistent_keys_survive_campaigner_death(self, server):
        """Plain sets from a process that also campaigned must NOT ride
        its lease: placements/rules stay after the process dies."""
        a = KvdClient(f"127.0.0.1:{server.port}")
        check = KvdClient(f"127.0.0.1:{server.port}")
        try:
            a.start_session(ttl_ms=600)
            a.set("_election/x", b"a", ephemeral=True)
            a.set("placement/prod", b"shards...")  # persistent
            a._closed.set()  # dies without revoking
            wait_for(lambda: not _has(check, "_election/x"), timeout_s=10,
                     desc="ephemeral reaped")
            assert check.get("placement/prod").data == b"shards..."
        finally:
            a.close()
            check.close()

def _quorum_plane(tmp_path, n=3, **kw):
    """An n-node replicated kvd plane; returns ({node_id: server}, peers)."""
    ports = [_free_port() for _ in range(n)]
    peers = {f"n{i}": f"127.0.0.1:{p}" for i, p in enumerate(ports)}
    kw.setdefault("election_timeout_s", (0.4, 0.8))
    kw.setdefault("heartbeat_s", 0.1)
    servers = {
        nid: KvdServer(addr, journal_path=str(tmp_path / f"{nid}.raft"),
                       node_id=nid, peers=peers, **kw)
        for nid, addr in peers.items()
    }
    wait_for(lambda: any(s.is_leader for s in servers.values()),
             desc="initial leader election")
    return servers, peers


class TestKvdQuorum:
    """The raft-replicated metadata plane (ISSUE 3): writes commit on a
    majority, followers hint clients to the leader, leader death fails
    over without ever opening a dual-write window, and every existing kvd
    consumer (elections, placements, runtime options) runs unchanged."""

    def test_write_survives_leader_kill(self, tmp_path):
        servers, peers = _quorum_plane(tmp_path)
        c = KvdClient(",".join(peers.values()))
        try:
            el = LeaseElection(c, "agg", "leader-1", ttl_ms=800)
            assert el.is_leader()
            assert c.set("placement/prod", b"v1") == 1
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            servers[lead].close()
            # client follows notleader hints to the new leader; the acked
            # write survives (it was majority-committed)
            assert c.get("placement/prod").data == b"v1"
            c.set("placement/prod", b"v2")
            assert c.get("placement/prod").data == b"v2"
            # the client's session lease re-arms on the new leader and
            # the ephemeral election key survives the failover
            wait_for(el.is_leader, timeout_s=15,
                     desc="leadership survives kvd failover")
            survivors = [s for nid, s in servers.items() if nid != lead]
            wait_for(lambda: any(
                _store_has(s, "placement/prod", b"v2") for s in survivors),
                desc="replicated to a survivor")
        finally:
            c.close()
            for s in servers.values():
                if not s._closed.is_set():
                    s.close()

    def test_follower_rejects_with_leader_hint(self, tmp_path):
        from m3_tpu.cluster.kvd import _dec_resp, _enc_req

        servers, peers = _quorum_plane(tmp_path)
        try:
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            follower = next(s for nid, s in servers.items() if nid != lead)

            # the follower learns the leader from the first heartbeat;
            # _quorum_plane only waits for the leader itself, so wait for
            # the hint rather than racing the heartbeat
            def rejected_with_hint():
                err = _dec_resp(follower._set(
                    _enc_req(key="k", data=b"v"), None))[2]
                return err.startswith("notleader:") \
                    and err.partition(":")[2] == peers[lead]

            wait_for(rejected_with_hint, desc="follower knows the leader")
            # reads are leader-only too (linearizable by construction)
            err = _dec_resp(follower._get(_enc_req(key="k"), None))[2]
            assert err.startswith("notleader:")
        finally:
            for s in servers.values():
                s.close()

    def test_minority_cannot_promote_or_commit(self, tmp_path):
        """THE dual-write test: with 2 of 3 nodes dead, the survivor —
        leader or not — must neither win an election nor commit a write.
        The old standby mode failed exactly this."""
        servers, peers = _quorum_plane(tmp_path)
        try:
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            for nid in list(servers):
                if nid != lead:
                    servers[nid].close()
            survivor = servers[lead]
            t = survivor._raft.submit(b'{"op":"set","k":"x","d":"00","l":0}')
            with pytest.raises(TimeoutError):
                survivor._raft.wait(t, timeout_s=2.0)
            assert survivor._raft.commit_index < t.index
            # and a client write fails loudly instead of forking state
            c = KvdClient(peers[lead], timeout_s=1.0)
            try:
                with pytest.raises(Exception):
                    c.set("fork", b"never")
            finally:
                c.close()
        finally:
            for s in servers.values():
                if not s._closed.is_set():
                    s.close()

    def test_no_promotion_without_majority(self, tmp_path):
        """A follower cut off with the leader dead stays a follower: no
        single node ever becomes writable alone."""
        servers, peers = _quorum_plane(tmp_path)
        try:
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            followers = [nid for nid in servers if nid != lead]
            # kill the leader AND one follower: the last node lacks quorum
            servers[lead].close()
            servers[followers[0]].close()
            last = servers[followers[1]]
            time.sleep(3.0)  # several election timeouts
            assert not last.is_leader, \
                "minority node promoted itself — dual-write hazard"
        finally:
            for s in servers.values():
                if not s._closed.is_set():
                    s.close()

    def test_restarted_replica_catches_up(self, tmp_path):
        servers, peers = _quorum_plane(tmp_path)
        c = KvdClient(",".join(peers.values()))
        try:
            c.set("a", b"1")
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            victim = next(nid for nid in servers if nid != lead)
            addr = peers[victim]
            servers[victim].close()
            c.set("b", b"2")  # committed by the remaining majority
            servers[victim] = KvdServer(
                addr, journal_path=str(tmp_path / f"{victim}.raft"),
                node_id=victim, peers=peers,
                election_timeout_s=(0.4, 0.8), heartbeat_s=0.1)
            wait_for(lambda: _store_has(servers[victim], "b", b"2"),
                     desc="restarted replica replayed the log")
            assert _store_has(servers[victim], "a", b"1")
        finally:
            c.close()
            for s in servers.values():
                if not s._closed.is_set():
                    s.close()

    def test_existing_consumers_run_unchanged(self, tmp_path):
        """Services discovery, LeaderService CAS elections, runtime
        options and placement records — the PR-0..2 kvd consumers — all
        pass against the 3-node plane through the stock KvdClient."""
        from m3_tpu.cluster.services import LeaderService, Services

        servers, peers = _quorum_plane(tmp_path)
        c = KvdClient(",".join(peers.values()))
        try:
            # service discovery
            sd = Services(c, heartbeat_ttl_s=10.0)
            sd.advertise("dbnode", "node-1", "127.0.0.1:9000")
            sd.advertise("dbnode", "node-2", "127.0.0.1:9001")
            assert [a.instance_id for a in sd.instances("dbnode")] == \
                ["node-1", "node-2"]
            # CAS-record leader election (the non-lease recipe)
            la = LeaderService(c, "flush", "inst-a", lease_ttl_s=10.0)
            lb = LeaderService(c, "flush", "inst-b", lease_ttl_s=10.0)
            assert la.campaign()
            assert not lb.campaign()
            assert lb.leader() == "inst-a"
            la.resign()
            assert lb.campaign()
            # runtime options + placement-style persistent records
            c.set("runtime/options", b'{"write_new_series_async": true}')
            assert c.get("runtime/options").version == 1
            c.check_and_set("runtime/options", 1, b'{"x": 1}')
            with pytest.raises(VersionMismatch):
                c.check_and_set("runtime/options", 1, b'{"y": 2}')
            keys = c.keys("runtime/")
            assert keys == ["runtime/options"]
        finally:
            c.close()
            for s in servers.values():
                s.close()

    def test_revoke_reroutes_from_follower(self, tmp_path):
        """end_session through a client currently pointed at a FOLLOWER:
        the revoke follows the notleader hint and the ephemeral key is
        reaped by the committed revoke — graceful resign stays graceful
        across failover, never a TTL wait."""
        servers, peers = _quorum_plane(tmp_path)
        c = KvdClient(",".join(peers.values()))
        probe = KvdClient(",".join(peers.values()))
        try:
            c.start_session(ttl_ms=60_000)  # long TTL: expiry can't help
            c.set("_election/x", b"me", ephemeral=True)
            lead = next(nid for nid, s in servers.items() if s.is_leader)
            follower_addr = next(a for nid, a in peers.items()
                                 if nid != lead)
            c._redirect(follower_addr)  # point the client off-leader
            c.end_session()
            wait_for(lambda: not _has(probe, "_election/x"), timeout_s=10,
                     desc="revoke committed via leader hint")
        finally:
            c.close()
            probe.close()
            for s in servers.values():
                s.close()

    def test_watch_push_across_replicas(self, tmp_path):
        """A watch on one replica sees writes committed via the leader;
        revisions (raft indices) dedupe across failover."""
        servers, peers = _quorum_plane(tmp_path)
        writer = KvdClient(",".join(peers.values()))
        lead = next(nid for nid, s in servers.items() if s.is_leader)
        follower_addr = next(a for nid, a in peers.items() if nid != lead)
        watcher = KvdClient(",".join(peers.values()))
        watcher._targets = [follower_addr] + [
            a for a in peers.values() if a != follower_addr]
        got = []
        try:
            watcher.watch("cfg", lambda k, vv: got.append(vv))
            writer.set("cfg", b"v1")
            wait_for(lambda: any(vv and vv.data == b"v1" for vv in got),
                     desc="committed write pushed through a follower")
        finally:
            writer.close()
            watcher.close()
            for s in servers.values():
                s.close()


def _has(client, key) -> bool:
    try:
        client.get(key)
        return True
    except KeyNotFound:
        return False


def _store_has(server, key, data) -> bool:
    try:
        return server.store.get(key).data == data
    except KeyNotFound:
        return False
