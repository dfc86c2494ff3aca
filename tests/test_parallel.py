"""Multi-chip collective kernel tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax

from m3_tpu.parallel import collectives as C
from m3_tpu.parallel.mesh import build_mesh


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return build_mesh(n_shard=8, n_replica=1)


@pytest.fixture(scope="module")
def mesh4x2():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return build_mesh(n_shard=4, n_replica=2)


class TestShardedGroupSum:
    def test_matches_local(self, rng, mesh8):
        import jax.numpy as jnp

        S, T, G = 64, 16, 5
        values = rng.normal(size=(S, T))
        gids = rng.integers(0, G, S).astype(np.int32)
        total, count = C.sharded_group_sum(
            jnp.asarray(values), jnp.asarray(gids), G, mesh8
        )
        want = np.zeros((G, T))
        for s in range(S):
            want[gids[s]] += values[s]
        np.testing.assert_allclose(np.asarray(total), want, rtol=1e-12)
        np.testing.assert_array_equal(
            np.asarray(count), np.bincount(gids, minlength=G)
        )

    def test_replicated_mesh_divides_out(self, rng, mesh4x2):
        import jax.numpy as jnp

        S, T, G = 32, 8, 3
        values = rng.normal(size=(S, T))
        gids = rng.integers(0, G, S).astype(np.int32)
        total, _ = C.sharded_group_sum(jnp.asarray(values), jnp.asarray(gids), G, mesh4x2)
        want = np.zeros((G, T))
        for s in range(S):
            want[gids[s]] += values[s]
        np.testing.assert_allclose(np.asarray(total), want, rtol=1e-12)


class TestReplicaDivergence:
    def test_clean_replicas_not_flagged(self, mesh4x2):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        S = 16
        cs = np.arange(S, dtype=np.uint64)
        # identical data on every replica: nothing should be flagged
        sharding = NamedSharding(mesh4x2, P("shard"))
        clean = jax.device_put(jnp.asarray(cs), sharding)
        out = C.replica_divergence(clean, mesh4x2)
        assert not np.asarray(out).any()

    def test_diverged_replica_flagged(self, mesh4x2):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        S = 16
        per_dev = S // 4
        # build a GLOBAL array whose replica copies differ for series 5:
        # device layout is (shard, replica); we hand-place buffers
        base = np.arange(S, dtype=np.uint64)
        bufs = []
        for si in range(4):
            for ri in range(2):
                chunk = base[si * per_dev : (si + 1) * per_dev].copy()
                if ri == 1 and si == 1:
                    chunk[1] ^= np.uint64(0xDEAD)  # series 5 diverges on replica 1
                bufs.append(jax.device_put(jnp.asarray(chunk),
                                           mesh4x2.devices[si, ri]))
        sharding = NamedSharding(mesh4x2, P("shard"))
        global_arr = jax.make_array_from_single_device_arrays(
            (S,), sharding, bufs
        )
        out = np.asarray(C.replica_divergence(global_arr, mesh4x2))
        assert out[5]
        assert out.sum() == 1


class TestTimeSharded:
    def test_window_sums_across_boundaries(self, rng, mesh8):
        import jax.numpy as jnp

        S, T, W = 4, 64, 16  # windows of 16 columns over 8 devices (8 cols each)
        values = rng.normal(size=(S, T))
        out = C.time_sharded_window_sums(jnp.asarray(values), mesh8, W)
        want = values.reshape(S, T // W, W).sum(axis=2)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-12)

    def test_ring_boundary_shift(self, rng, mesh8):
        import jax.numpy as jnp

        S, T = 3, 32  # 4 cols per device
        values = rng.normal(size=(S, T))
        out = np.asarray(C.ring_shift_boundary(jnp.asarray(values), mesh8))
        # device d receives left neighbor's last column
        per = T // 8
        want = np.stack(
            [values[:, ((d - 1) % 8 + 1) * per - 1] for d in range(8)], axis=1
        )
        np.testing.assert_allclose(out, want)


class TestMeshFromPlacement:
    def test_replica_axis_carries_rf(self):
        from m3_tpu.cluster import placement as pl
        from m3_tpu.cluster.placement import Instance
        from m3_tpu.parallel.mesh import mesh_from_placement

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        p = pl.initial_placement(
            [Instance(f"n{i}") for i in range(8)], n_shards=8, replica_factor=2
        )
        mesh = mesh_from_placement(p)
        assert mesh.shape["shard"] == 4 and mesh.shape["replica"] == 2

    def test_window_misalignment_rejected(self, rng, mesh8):
        import jax.numpy as jnp
        from m3_tpu.parallel import collectives as C

        with pytest.raises(ValueError, match="multiple"):
            C.time_sharded_window_sums(jnp.asarray(rng.normal(size=(2, 16))), mesh8, 5)


class TestComputeMeshPlumbing:
    def test_next_bucket_pads_to_mesh_multiple(self):
        from m3_tpu.utils.dispatch import next_bucket

        for n in (1, 2, 3, 5, 7, 8, 9, 24, 100, 1000):
            for m in (1, 2, 4, 8):
                b = next_bucket(n, multiple=m)
                assert b >= n and b % m == 0, (n, m, b)
        # without a multiple the half-octave ladder is unchanged
        assert next_bucket(5) == 6 and next_bucket(7) == 8
        # a 2/3-smooth multiple stays on the ladder
        assert next_bucket(5, multiple=8) == 8
        assert next_bucket(9, multiple=8) == 16

    def test_active_mesh_env_hatch(self, monkeypatch):
        from m3_tpu.parallel import mesh as mesh_mod

        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        assert mesh_mod.active_compute_mesh() is None
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "8")
        m8 = mesh_mod.active_compute_mesh()
        assert m8 is not None and int(m8.devices.size) == 8
        # identity-stable: the cached factory hands back the SAME object
        assert mesh_mod.active_compute_mesh() is m8
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "1")
        m1 = mesh_mod.active_compute_mesh()
        assert m1 is not None and int(m1.devices.size) == 1
        # a count past the device pool clamps (device-count independence)
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "4096")
        assert int(mesh_mod.active_compute_mesh().devices.size) == 8
        # unset + CPU backend: the plane stays off
        monkeypatch.delenv("M3_TPU_QUERY_SHARD")
        assert mesh_mod.active_compute_mesh() is None


class TestShardedQueryPlane:
    """Engine-path coverage for the series-sharded compute plane (PR 12,
    ROADMAP #1): the SAME compiled plan, on a seeded random-plan sweep,
    must agree with the interpreter exactly on NaN masks and within 1e-9
    relative on values at BOTH 1 and 8 mesh devices."""

    NS = 1_000_000_000
    MIN = 60 * NS
    START = 1_599_998_400_000_000_000

    PLANS = [
        "reqs",
        "sum by (host) (rate(reqs[5m]))",
        "avg by (job) (avg_over_time(reqs[4m]))",
        "max_over_time(reqs[3m])",
        "quantile by (job) (0.9, sum_over_time(reqs[2m]))",
        "min by (job) (irate(reqs[5m]) ^ 2)",
        "count without (host) (present_over_time(reqs[3m])) * 3",
    ]

    @pytest.fixture(scope="class")
    def engine(self, tmp_path_factory):
        from m3_tpu.query.engine import Engine
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path_factory.mktemp("shardq") / "db"),
                      DatabaseOptions(n_shards=4))
        db.create_namespace("default")
        db.open(self.START)
        rng = np.random.default_rng(7)
        hosts = [b"h%02d" % i for i in range(5)]
        jobs = [b"api", b"web", b"batch"]
        for i in range(40):
            tags = [(b"host", hosts[i % 5]), (b"job", jobs[i % 3])]
            t = self.START
            acc = float(rng.integers(0, 50))
            for _ in range(40):
                t += int(rng.integers(5, 40)) * self.NS
                if rng.random() < 0.06:
                    acc = 0.0
                acc += float(rng.integers(0, 9))
                if rng.random() < 0.9:
                    db.write_tagged("default", b"reqs", tags, t, acc)
        yield Engine(db, resolve_tiers=False)
        db.close()

    def _run(self, engine, monkeypatch, q, compiled, shard):
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1" if compiled else "0")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", str(shard))
        v, _ = engine.query_range(q, self.START, self.START + 14 * self.MIN,
                                  self.MIN)
        return v

    @staticmethod
    def _assert_parity(a, b, q):
        assert a.labels == b.labels, q
        assert a.values.shape == b.values.shape, q
        assert np.array_equal(np.isnan(a.values), np.isnan(b.values)), q
        assert np.allclose(a.values, b.values, rtol=1e-9, atol=0,
                           equal_nan=True), q

    def test_sharded_vs_single_device_sweep(self, engine, monkeypatch):
        from m3_tpu.utils import dispatch

        for q in self.PLANS:
            vi = self._run(engine, monkeypatch, q, compiled=False, shard=0)
            sharded0 = dispatch.counters["query.compile[sharded]"]
            v1 = self._run(engine, monkeypatch, q, compiled=True, shard=1)
            v8 = self._run(engine, monkeypatch, q, compiled=True, shard=8)
            assert dispatch.counters["query.compile[sharded]"] == \
                sharded0 + 2, f"plan not sharded: {q}"
            self._assert_parity(vi, v1, f"{q} @1dev")
            self._assert_parity(vi, v8, f"{q} @8dev")
            self._assert_parity(v1, v8, f"{q} 1dev-vs-8dev")

    def test_plan_cache_key_carries_mesh(self, engine, monkeypatch):
        from m3_tpu.query import compiler

        compiler.clear_plan_cache()
        self._run(engine, monkeypatch, "sum by (host) (rate(reqs[5m]))",
                  compiled=True, shard=8)
        # the key tuple grows (n_dev, cap) components under a mesh, so a
        # sharded plan can never collide with its single-device twin
        assert any(k.split("|")[-2] == "8"
                   for k in compiler.plan_cache_info()), \
            compiler.plan_cache_info()

    def test_explain_reports_mesh_and_stage_shardings(self, engine,
                                                      monkeypatch):
        from m3_tpu.query import explain

        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "8")
        with explain.collect(analyze=True) as col:
            engine.query_range("sum by (host) (max_over_time(reqs[3m]))",
                               self.START, self.START + 10 * self.MIN,
                               self.MIN)
        doc = col.to_dict()
        assert doc["compiled"]["mesh"] == {"axis": "series", "devices": 8}
        stages = {s["stage"]: s["spec"] for s in doc["compiled"]["sharding"]}
        assert stages["base:max_over_time"] == "P('series', None)"
        assert stages["agg:sum"] == "P()"
        assert "|M8x" in doc["compiled"]["cache_key"]

    def test_aggregate_groups_device_path_rides_the_mesh(self, monkeypatch):
        """The interpreter's m3_agg_groups rollup/quantile path places
        its padded sample triples across the active mesh — numerics
        unchanged vs the numpy host path."""
        from m3_tpu.ops import windowed_agg
        from m3_tpu.utils import dispatch

        rng = np.random.default_rng(3)
        n = 4096
        e = rng.integers(0, 257, n)
        w = rng.integers(0, 6, n)
        v = rng.normal(100, 10, n)
        t = rng.integers(0, 10**9, n)
        ge, gw, stats, vq, off = windowed_agg.aggregate_groups(
            e, w, v, times=t)
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "8")
        before = dispatch.counters["windowed_agg.aggregate_groups[mesh]"]
        de, dw, dstats, dvq, doff = windowed_agg.aggregate_groups(
            e, w, v, times=t)
        assert dispatch.counters["windowed_agg.aggregate_groups[mesh]"] == \
            before + 1
        np.testing.assert_array_equal(ge, de)
        np.testing.assert_array_equal(gw, dw)
        np.testing.assert_array_equal(off, doff)
        np.testing.assert_allclose(dvq, vq, rtol=0)
        for k in stats:
            np.testing.assert_allclose(dstats[k], stats[k], rtol=1e-9,
                                       err_msg=k)


class TestTimeShardedResetAdjust:
    def test_matches_host_monotonization(self, rng, mesh8):
        """Sequence-parallel reset adjustment == the single-host numpy
        path, including resets that straddle shard boundaries."""
        import jax.numpy as jnp

        from m3_tpu.query.windows import NS, RaggedSeries, _reset_adjusted

        S, T = 6, 64  # 8 columns per device; resets land on boundaries too
        vals = rng.integers(0, 10, (S, T)).astype(np.float64).cumsum(axis=1)
        # force resets at device boundaries (cols 8, 16, ...) and inside
        for s in range(S):
            for c in (8, 16, 24, 37, 55):
                vals[s, c:] -= vals[s, c] - rng.random() * 3
        got = np.asarray(C.time_sharded_reset_adjust(jnp.asarray(vals), mesh8))
        # host reference: per-series ragged monotonization
        per = [(np.arange(T, dtype=np.int64) * NS, vals[s]) for s in range(S)]
        raws = RaggedSeries.from_lists(per)
        want = _reset_adjusted(raws).reshape(S, T)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        # monotone non-decreasing everywhere
        assert (np.diff(got, axis=1) >= -1e-9).all()

    def test_increase_over_cross_device_window(self, rng, mesh8):
        import jax.numpy as jnp

        T = 64
        vals = np.arange(T, dtype=np.float64)[None, :].copy()
        vals[0, 40:] -= vals[0, 40]  # reset inside device 5
        adj = np.asarray(C.time_sharded_reset_adjust(jnp.asarray(vals), mesh8))
        # increase over the whole range = last - first on adjusted values
        assert adj[0, -1] - adj[0, 0] == pytest.approx(39 + 1 + 22)
