"""Paged ragged columnar memory + device-resident hot tier (ISSUE 15,
ROADMAP #3).

Contracts under test:
- the paged page pool / PagedColumnLog are operation-for-operation
  equivalent to a plain numpy model, concatenate and slice (seeded
  property sweep incl. page-boundary-straddling windows and prefix
  drops);
- `ops.ragged.merge_csr` / `assemble_rows` are row-for-row identical to
  the per-series `merge_dedup` reference (exact uint64 bit patterns),
  including empty, singleton, duplicated and unsorted rows;
- the ragged seal equals a per-series model of the writes, and the
  length-bucketed encode produces BYTE-identical streams to the encode
  of the fully padded rectangle;
- the batched read finalize (buffer + filesets, pipelined and serial)
  returns exactly the samples of the per-series read, and compiled and
  interpreted engine results agree to exact NaN masks + 1e-9;
- the device-resident hot tier serves repeated identical queries from
  warm prepared slabs, invalidates on any data-version bump, and the
  bf16 mirror engages only under the per-query precision grant.
"""

import numpy as np
import pytest

from m3_tpu.ops import ragged
from m3_tpu.query import explain
from m3_tpu.query.engine import Engine
from m3_tpu.storage import hottier, pagepool
from m3_tpu.storage.buffer import ShardBuffer, merge_dedup
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import (
    DatabaseOptions, IndexOptions, NamespaceOptions, RetentionOptions,
)

NS = 10**9
HOUR = 3600 * NS
START = 1_600_000_000 * NS


def bits(v: float) -> int:
    return int(np.float64(v).view(np.uint64))


def _random_rows(rng, n_rows, max_len=40, sorted_frac=0.5):
    """Random per-row (times, vbits) sets: empty rows, singletons,
    duplicate timestamps, unsorted rows, ties resolved by append order."""
    rows = []
    for _ in range(n_rows):
        kind = rng.random()
        if kind < 0.12:
            rows.append((np.empty(0, np.int64), np.empty(0, np.uint64)))
            continue
        m = 1 if kind < 0.25 else int(rng.integers(1, max_len))
        t = rng.integers(0, 50, m).astype(np.int64) * NS + START
        if rng.random() < sorted_frac:
            t = np.sort(t)
        v = rng.integers(0, 2**63, m).astype(np.uint64)
        rows.append((t, v))
    return rows


class TestPagePool:
    def test_alloc_free_reuse_and_eviction(self):
        pool = pagepool.PagePool(max_free_pages=64)
        pages = [pool.alloc() for _ in range(130)]  # spans 3 slabs
        assert pool.pages_in_use == 130
        assert pool.total_pages >= 130
        pool.free(pages)
        assert pool.pages_in_use == 0
        # free list over bound: whole all-free slabs released to the OS
        assert pool.evicted_pages > 0
        before = pool.total_pages
        p = pool.alloc()  # reuse, no new slab
        assert pool.total_pages == before
        pool.free([p])

    def test_page_views_are_stable_across_growth(self):
        pool = pagepool.PagePool()
        p0 = pool.alloc()
        s0, t0, v0 = pool.columns(p0)
        t0[0] = 1234
        for _ in range(200):  # force new slabs
            pool.alloc()
        assert pool.columns(p0)[1][0] == 1234

    def test_monitor_pool_feeds_aggregate(self):
        pool = pagepool.monitor_pool(pagepool.PagePool())
        pool.alloc()
        used, total, _ev, nbytes = pagepool._aggregate()
        assert used >= 1 and total >= used and nbytes > 0


class TestPagedColumnLog:
    def test_property_parity_with_numpy_model(self):
        rng = np.random.default_rng(7)
        pool = pagepool.PagePool()
        for _ in range(10):
            paged = pagepool.PagedColumnLog(pool)
            # the model: three plain columns, concatenate and slice
            model = (np.empty(0, np.int32), np.empty(0, np.int64),
                     np.empty(0, np.uint64))
            for _ in range(int(rng.integers(2, 8))):
                op = rng.random()
                total = len(model[0])
                if op < 0.55:
                    # bulk extend, sized to straddle page boundaries
                    m = int(rng.integers(1, 3000))
                    s = rng.integers(0, 50, m).astype(np.int32)
                    t = rng.integers(0, 10**6, m).astype(np.int64)
                    v = rng.integers(0, 2**63, m).astype(np.uint64)
                    paged.extend(s, t, v)
                    model = tuple(np.concatenate([a, b])
                                  for a, b in zip(model, (s, t, v)))
                elif op < 0.85 or total == 0:
                    paged.append(3, 17, 99)
                    model = tuple(np.concatenate([a, np.array([x], a.dtype)])
                                  for a, x in zip(model, (3, 17, 99)))
                else:
                    k = int(rng.integers(0, total + 1))
                    paged.drop_prefix(k)
                    model = tuple(a[k:] for a in model)
                assert paged.n == len(model[0])
                for a, b in zip(paged.view(), model):
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(a, b)
            paged.release()
        assert pool.pages_in_use == 0

    def test_view_cache_invalidated_across_drop_refill(self):
        """Regression (review finding): (n, head) is not unique over a
        log's lifetime — a drop_prefix followed by a refill landing on a
        previously-cached (n, head) pair must NOT serve the stale view
        (pre-flush rows; the lost-write class)."""
        pool = pagepool.PagePool()
        log = pagepool.PagedColumnLog(pool)
        R = pagepool.PAGE_ROWS
        log.extend(np.zeros(R, np.int32), np.arange(R, dtype=np.int64),
                   np.zeros(R, np.uint64))
        assert log.view()[1][0] == 0  # populate the cache at (R, 0)
        # 10 concurrent appends land after the seal copy...
        log.extend(np.zeros(10, np.int32),
                   np.full(10, 7_000_000, np.int64), np.zeros(10, np.uint64))
        # ...flush drops exactly the sealed prefix: head wraps back to 0
        log.drop_prefix(R)
        assert (log.n, log.head) == (10, 0)
        log.extend(np.zeros(R - 10, np.int32),
                   np.arange(R - 10, dtype=np.int64) + R,
                   np.zeros(R - 10, np.uint64))
        # (n, head) == (R, 0) again — the cached pre-flush rows must NOT
        # be served
        got = log.view()[1]
        np.testing.assert_array_equal(got[:10], np.full(10, 7_000_000))
        np.testing.assert_array_equal(got[10:],
                                      np.arange(R - 10, dtype=np.int64) + R)

    def test_drop_prefix_frees_pages(self):
        pool = pagepool.PagePool()
        log = pagepool.PagedColumnLog(pool)
        m = 5 * pagepool.PAGE_ROWS + 7
        log.extend(np.zeros(m, np.int32), np.arange(m, dtype=np.int64),
                   np.zeros(m, np.uint64))
        held = pool.pages_in_use
        log.drop_prefix(3 * pagepool.PAGE_ROWS + 1)
        assert pool.pages_in_use == held - 3
        np.testing.assert_array_equal(
            log.view()[1][:3], np.arange(3) + 3 * pagepool.PAGE_ROWS + 1)
        log.drop_prefix(log.n)
        assert pool.pages_in_use == 0


class TestRaggedKernels:
    def test_merge_csr_matches_merge_dedup_rowwise(self):
        rng = np.random.default_rng(11)
        for trial in range(30):
            rows = _random_rows(rng, int(rng.integers(0, 12)))
            t, v, offs = ragged.pairs_to_csr(rows)
            lo = START + int(rng.integers(0, 30)) * NS \
                if rng.random() < 0.6 else None
            hi = START + int(rng.integers(20, 60)) * NS \
                if rng.random() < 0.6 else None
            mt, mv, moffs = ragged.merge_csr(t.copy(), v.copy(),
                                             offs.copy(), lo, hi)
            for i, (rt, rv) in enumerate(rows):
                et, ev = merge_dedup(rt.copy(), rv.copy(), lo, hi)
                a, b = moffs[i], moffs[i + 1]
                np.testing.assert_array_equal(mt[a:b], et,
                                              err_msg=f"trial {trial} row {i}")
                np.testing.assert_array_equal(mv[a:b], ev)

    def test_assemble_rows_multi_part_order(self):
        # later parts win timestamp ties — the filesets-then-buffer rule
        rng = np.random.default_rng(5)
        for _ in range(15):
            n_rows = int(rng.integers(1, 8))
            parts_rows = []
            for _ in range(n_rows):
                parts_rows.append(
                    [(r[0], r[1]) for r in
                     _random_rows(rng, int(rng.integers(0, 4)), 12)])
            t, v, offs = ragged.assemble_rows(
                [list(p) for p in parts_rows], START, START + 100 * NS)
            for i, parts in enumerate(parts_rows):
                ct = np.concatenate([p[0] for p in parts]) if parts \
                    else np.empty(0, np.int64)
                cv = np.concatenate([p[1] for p in parts]) if parts \
                    else np.empty(0, np.uint64)
                et, ev = merge_dedup(ct, cv, START, START + 100 * NS)
                a, b = offs[i], offs[i + 1]
                np.testing.assert_array_equal(t[a:b], et)
                np.testing.assert_array_equal(v[a:b], ev)

    def test_length_buckets_cover_and_bound_waste(self):
        rng = np.random.default_rng(3)
        lens = rng.integers(0, 10_000, 200)
        lens[:5] = 0
        groups = ragged.length_buckets(lens)
        seen = np.concatenate(groups)
        assert sorted(seen.tolist()) == list(range(200))
        for g in groups:
            sub = lens[g]
            if sub.max() == 0:
                continue
            assert sub[sub > 0].min() * 2 >= sub.max()

    def test_bf16_pack_matches_jax_astype(self):
        """The numpy pack (the wire-format seam) and the hot tier's
        device conversion (astype(jnp.bfloat16)) must round identically
        — two bf16 implementations that drift would make the mirror's
        tolerance audit read the wrong code."""
        jnp = pytest.importorskip("jax.numpy")
        rng = np.random.default_rng(17)
        v = np.concatenate([rng.normal(0, 1e6, 300),
                            rng.normal(0, 1e-6, 300), [np.nan, 0.0, -0.0]])
        via_np = ragged.bf16_unpack(ragged.bf16_pack(v))
        via_jax = np.asarray(
            jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float64))
        assert np.array_equal(np.isnan(via_np), np.isnan(via_jax))
        ok = ~np.isnan(v)
        np.testing.assert_array_equal(via_np[ok], via_jax[ok])

    def test_bf16_roundtrip_bound_and_nan_mask(self):
        rng = np.random.default_rng(9)
        v = rng.normal(0, 1e6, 500)
        v[::17] = np.nan
        back = ragged.bf16_unpack(ragged.bf16_pack(v))
        assert np.array_equal(np.isnan(v), np.isnan(back))
        ok = ~np.isnan(v)
        # bf16 keeps ~8 mantissa bits: relative error < 2^-8
        assert np.all(np.abs(back[ok] - v[ok])
                      <= np.abs(v[ok]) * 2.0**-8 + 1e-300)


class TestRaggedSealEncode:
    def test_seal_csr_and_ragged_encode_byte_parity(self):
        from m3_tpu.encoding.m3tsz import hostpath
        from m3_tpu.utils.xtime import TimeUnit

        rng = np.random.default_rng(21)
        buf = ShardBuffer(2 * HOUR)
        sids = [b"s%03d" % i for i in range(40)]
        written: dict[bytes, list] = {}
        for _ in range(600):
            i = int(rng.integers(0, 40))
            # skewed: one series gets most points (the padding-tax shape)
            if rng.random() < 0.5:
                i = 0
            t = START + int(rng.integers(0, 3600)) * NS
            v = bits(float(rng.integers(0, 1000)))
            buf.write(sids[i], t, v)
            written.setdefault(sids[i], []).append((t, v))
        bs0 = START - START % (2 * HOUR)  # window the writes landed in
        csr = buf.seal_csr(bs0, drop=False)
        # the seal against a per-series model of the writes: rows in
        # series-index order, each merge_dedup of its appends
        assert [buf.series_ids[k] for k in csr.series_indices] \
            == sorted(written, key=buf.series_index)
        B = csr.n_series
        T = int(csr.n_points.max())
        pad_t = np.zeros((B, T), np.int64)
        pad_v = np.zeros((B, T), np.uint64)
        for r, k in enumerate(csr.series_indices):
            rows = written[buf.series_ids[k]]
            et, ev = merge_dedup(np.array([x[0] for x in rows], np.int64),
                                 np.array([x[1] for x in rows], np.uint64))
            a, b = csr.offsets[r], csr.offsets[r + 1]
            np.testing.assert_array_equal(csr.times[a:b], et)
            np.testing.assert_array_equal(csr.value_bits[a:b], ev)
            # the fully padded rectangle, tail repeating the last time
            pad_t[r, :len(et)] = et
            pad_t[r, len(et):] = et[-1]
            pad_v[r, :len(ev)] = ev
        starts = np.full(B, bs0, np.int64)
        s_pad = hostpath.encode_blocks(pad_t, pad_v, starts, csr.n_points,
                                       TimeUnit.SECOND, False)
        s_rag = hostpath.encode_blocks_ragged(
            csr.times, csr.value_bits, csr.offsets, starts,
            TimeUnit.SECOND, False)
        assert s_pad == s_rag


def _build_db(root, rng, n_series=64, n_blocks=3, with_flush=True):
    db = Database(root, DatabaseOptions(n_shards=4))
    ns = db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * HOUR,
                                   block_size_ns=HOUR),
        index=IndexOptions(enabled=True, block_size_ns=HOUR),
        writes_to_commitlog=False, snapshot_enabled=False))
    db.open(START)
    ids = [b"m,host=h%02d,i=%03d" % (i % 8, i) for i in range(n_series)]
    tags = [[(b"__name__", b"m"), (b"host", b"h%02d" % (i % 8)),
             (b"i", b"%03d" % i)] for i in range(n_series)]
    for b in range(n_blocks):
        bs = START + b * HOUR
        for i in range(n_series):
            if rng.random() < 0.15:
                continue  # gaps: some series empty in some blocks
            for _ in range(int(rng.integers(1, 6))):
                t = bs + int(rng.integers(0, 3600)) * NS
                db.write_tagged("default", ids[i], tags[i], t,
                                float(rng.integers(0, 100)))
        if with_flush and b < n_blocks - 1:
            # START is not block-aligned: a round of writes straddles two
            # windows, so the next round lands partly on a flushed volume
            # (buffer rows over fileset rows of the same window)
            for shard in ns.shards.values():
                for w in shard.buffer.block_starts():
                    shard.flush(w)
    return db, ns, ids


class TestPagedReadParity:
    def test_read_many_exact_parity_batched_vs_per_series(self, tmp_path,
                                                          monkeypatch):
        """The acceptance property: buffer+fileset reads through the
        batched ragged finalize, pipelined and serial, are SAMPLE-exact
        (uint64 bit patterns) against the per-series read
        (Namespace.read -> Shard.read: concatenate + merge_dedup)."""
        rng = np.random.default_rng(31)
        db, ns, _names = _build_db(str(tmp_path / "p"),
                                   np.random.default_rng(31))
        ids = sorted(ns.series_ids())
        assert any(sh._filesets and sh.buffer.block_starts()
                   for sh in ns.shards.values())
        n_samples = 0
        for pipe in ("1", "0", "1", "0"):
            monkeypatch.setenv("M3_TPU_PIPELINE", pipe)
            lo = START + int(rng.integers(0, 30)) * 60 * NS
            hi = START + 3 * HOUR - int(rng.integers(0, 30)) * 60 * NS
            got = ns.read_many(ids, lo, hi)
            assert len(got) == len(ids)
            for sid, (gt, gv) in zip(ids, got):
                wt, wv = ns.read(sid, lo, hi)
                np.testing.assert_array_equal(gt, wt)
                np.testing.assert_array_equal(gv, wv)
                n_samples += len(wt)
        assert n_samples > 0
        db.close()

    def test_read_many_ragged_matches_views(self, tmp_path, monkeypatch):
        monkeypatch.setenv("M3_TPU_PIPELINE", "1")
        rng = np.random.default_rng(41)
        db, ns, _names = _build_db(str(tmp_path / "r"), rng)
        ids = sorted(ns.series_ids())
        pairs = ns.read_many(ids, START, START + 3 * HOUR)
        t, v, offs = ns.read_many_ragged(ids, START, START + 3 * HOUR)
        assert len(offs) == len(ids) + 1 and offs[-1] > 0
        for i, (pt, pv) in enumerate(pairs):
            a, b = offs[i], offs[i + 1]
            np.testing.assert_array_equal(t[a:b], pt)
            np.testing.assert_array_equal(v[a:b], pv)
        db.close()

    def test_engine_parity_compiled_vs_interpreted(self, tmp_path,
                                                   monkeypatch):
        """Ragged decode/aggregate parity through the ENGINE: the
        compiled plans (which consume the ragged CSR) and the float64
        interpreter agree to exact NaN masks + 1e-9 values."""
        queries = [
            "m",
            "sum by (host) (sum_over_time(m[30m]))",
            "rate(m[10m])",
            "max_over_time(m[20m])",
        ]
        db, ns, ids = _build_db(str(tmp_path / "e"),
                                np.random.default_rng(55))
        eng = Engine(db, resolve_tiers=False)
        out = {}
        for compile_ in ("0", "1"):
            monkeypatch.setenv("M3_TPU_QUERY_COMPILE", compile_)
            for q in queries:
                with explain.collect(True) as col:
                    vec, _ = eng.query_range(
                        q, START + 30 * 60 * NS, START + 3 * HOUR,
                        10 * 60 * NS)
                out[(compile_, q)] = vec, bool(col.compiled
                                               and col.compiled["ran"])
        db.close()
        for q in queries:
            (a, a_compiled), (b, b_compiled) = out[("1", q)], out[("0", q)]
            assert not b_compiled, q
            assert a.labels == b.labels, q
            assert np.array_equal(np.isnan(a.values),
                                  np.isnan(b.values)), q
            assert np.allclose(a.values, b.values, rtol=1e-9, atol=0,
                               equal_nan=True), q
        assert any(out[("1", q)][1] for q in queries)


@pytest.fixture
def small_tier(monkeypatch):
    hottier.reset_default()
    monkeypatch.setenv("M3_TPU_HOT_TIER_MB", "64")
    yield
    hottier.reset_default()


class TestHotTier:
    def _db(self, tmp_path, monkeypatch):
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
        rng = np.random.default_rng(77)
        return _build_db(str(tmp_path / "h"), rng)

    def test_repeat_query_hits_and_write_invalidates(self, tmp_path,
                                                     monkeypatch,
                                                     small_tier):
        db, ns, ids = self._db(tmp_path, monkeypatch)
        eng = Engine(db, resolve_tiers=False)
        tier = hottier.default()
        q = "sum by (host) (sum_over_time(m[30m]))"

        def run():
            with explain.collect(True) as col:
                vec, _ = eng.query_range(q, START + 30 * 60 * NS,
                                         START + 3 * HOUR, 10 * 60 * NS)
            return vec, col.compiled

        v1, info1 = run()
        assert info1["ran"] and info1["hot_tier"]["hit"] is False
        v2, info2 = run()
        assert info2["hot_tier"]["hit"] is True
        assert v1.labels == v2.labels
        np.testing.assert_array_equal(v1.values, v2.values)
        assert tier.hits >= 1 and len(tier) >= 1
        # any write bumps the namespace data version: warm pages for the
        # old content stop matching
        db.write_tagged("default", ids[0],
                        [(b"__name__", b"m"), (b"host", b"h00"),
                         (b"i", b"000")], START + 2 * HOUR + NS, 5.0)
        _v3, info3 = run()
        assert info3["hot_tier"]["hit"] is False
        db.close()

    def test_bf16_mirror_negotiated_per_query(self, tmp_path, monkeypatch,
                                              small_tier):
        db, ns, ids = self._db(tmp_path, monkeypatch)
        # values with real mantissa so quantization is observable
        rng = np.random.default_rng(3)
        for i in range(16):
            db.write_tagged("default", ids[i],
                            [(b"__name__", b"m"), (b"host",
                              b"h%02d" % (i % 8)), (b"i", b"%03d" % i)],
                            START + 2 * HOUR + 100 * NS + i,
                            float(rng.normal(100, 13)))
        eng = Engine(db, resolve_tiers=False)
        q = "max_over_time(m[30m])"

        def run(precision=None):
            with hottier.negotiated_precision(precision):
                with explain.collect(True) as col:
                    vec, _ = eng.query_range(q, START + 30 * 60 * NS,
                                             START + 3 * HOUR,
                                             10 * 60 * NS)
            return vec, col.compiled

        vf, info_f = run()
        assert info_f["hot_tier"]["precision"] == "f64"
        vb, info_b = run("bf16")
        assert info_b["hot_tier"]["precision"] == "bf16"
        # separate keys: the bf16 run was a MISS, not a hit on f64 pages
        assert info_b["hot_tier"]["hit"] is False
        assert np.array_equal(np.isnan(vf.values), np.isnan(vb.values))
        ok = ~np.isnan(vf.values)
        assert np.allclose(vb.values[ok], vf.values[ok], rtol=1e-2)
        assert not np.array_equal(vb.values[ok], vf.values[ok])
        # full-precision repeat still hits ITS OWN warm entry, bit-exact
        vf2, info_f2 = run()
        assert info_f2["hot_tier"]["hit"] is True
        np.testing.assert_array_equal(vf.values, vf2.values)
        # rate bases never quantize, grant or not
        with hottier.negotiated_precision("bf16"):
            with explain.collect(True) as col:
                eng.query_range("rate(m[10m])", START + 30 * 60 * NS,
                                START + 3 * HOUR, 10 * 60 * NS)
        assert col.compiled["hot_tier"]["precision"] == "f64"
        db.close()

    # query -> fused plans it runs (a vector-vector binop runs one a side)
    _REPEATS = {
        "aggregated": ("sum by (host) (sum_over_time(m[30m]))", 1),
        "no_aggregation": ("max_over_time(m[30m])", 1),
        "vector_binop": ("sum_over_time(m[30m]) / count_over_time(m[30m])",
                         2),
    }

    @staticmethod
    def _count_fetches(monkeypatch):
        """Counts of the namespace's index matches and batched reads."""
        from m3_tpu.storage.namespace import Namespace

        calls = {"query_ids": 0, "read_many": 0}

        def counting(name, counted_as):
            inner = getattr(Namespace, name)

            def wrapper(self, *a, **kw):
                calls[counted_as] += 1
                return inner(self, *a, **kw)
            monkeypatch.setattr(Namespace, name, wrapper)

        counting("query_ids", "query_ids")
        counting("read_many", "read_many")
        counting("read_many_ragged", "read_many")
        return calls

    @staticmethod
    def _tier_counters():
        from m3_tpu.utils.instrument import default_registry

        counters, _g, _t, hists = default_registry().snapshot()
        out = {k: counters.get((f"storage.hot_tier.{k}", ()), 0.0)
               for k in ("hit", "miss", "fetch_skipped")}
        out["reads_timed"] = hists.get(("db.read_many_seconds", ()),
                                       (None, None, 0.0, 0))[3]
        return out

    @pytest.mark.parametrize("case", sorted(_REPEATS))
    def test_warm_repeat_reads_nothing(self, case, tmp_path, monkeypatch,
                                       small_tier):
        """Probe before fetch: an identical repeat is served from the
        warm entry with no index match and no read, bit for bit what
        the first run and the interpreter answer; a write and a flush
        each make the next run a miss that fetches; `fetch_skipped`
        counts exactly the hits."""
        q, plans = self._REPEATS[case]
        db, ns, ids = self._db(tmp_path, monkeypatch)
        eng = Engine(db, resolve_tiers=False)
        calls = self._count_fetches(monkeypatch)
        at_start = self._tier_counters()

        def run():
            before = dict(calls), self._tier_counters()
            with explain.collect(True) as col:
                vec, _ = eng.query_range(q, START + 30 * 60 * NS,
                                         START + 3 * HOUR, 10 * 60 * NS)
            after = self._tier_counters()
            delta = {k: calls[k] - before[0][k] for k in calls}
            delta.update({k: after[k] - before[1][k] for k in after})
            sides = (col.compiled or {}).get("sides") or [col.compiled]
            return vec, delta, [side["hot_tier"]["hit"]
                                for side in sides if side]

        fetched = {"query_ids": plans, "read_many": plans,
                   "reads_timed": plans, "hit": 0, "miss": plans,
                   "fetch_skipped": 0}
        skipped = {"query_ids": 0, "read_many": 0, "reads_timed": 0,
                   "hit": plans, "miss": 0, "fetch_skipped": plans}

        def same_bits(a, b):
            assert a.labels == b.labels
            assert a.values.shape == b.values.shape
            np.testing.assert_array_equal(a.values.view(np.uint64),
                                          b.values.view(np.uint64))

        v1, d1, hits1 = run()
        assert d1 == fetched and hits1 == [False] * plans
        assert len(v1.labels) > 0 and np.isfinite(v1.values).any()
        v2, d2, hits2 = run()
        assert d2 == skipped and hits2 == [True] * plans
        same_bits(v2, v1)
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "0")
        vi, di, interpreted = run()
        assert di["query_ids"] == plans and di["hit"] == di["miss"] == 0
        assert interpreted == []
        same_bits(v2, vi)
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")

        # any write bumps the namespace's data version: the next run
        # misses, fetches and answers with the new sample
        db.write_tagged("default", ids[0],
                        [(b"__name__", b"m"), (b"host", b"h00"),
                         (b"i", b"000")], START + 2 * HOUR + NS, 5.0)
        v3, d3, hits3 = run()
        assert d3 == fetched and hits3 == [False] * plans
        assert not np.array_equal(v3.values.view(np.uint64),
                                  v1.values.view(np.uint64))
        v4, d4, _ = run()
        assert d4 == skipped
        same_bits(v4, v3)
        # so does a flush, which leaves every answer as it was
        version = ns.data_version()
        assert sum(bool(shard.flush(w)) for shard in ns.shards.values()
                   for w in shard.buffer.block_starts()) > 0
        assert ns.data_version() != version
        v5, d5, hits5 = run()
        assert d5 == fetched and hits5 == [False] * plans
        same_bits(v5, v3)
        _v6, d6, _ = run()
        assert d6 == skipped

        total = self._tier_counters()
        assert total["fetch_skipped"] - at_start["fetch_skipped"] \
            == total["hit"] - at_start["hit"] == 3 * plans
        db.close()

    def test_query_limits_refuse_a_warm_repeat(self, tmp_path, monkeypatch,
                                               small_tier):
        """A hit charges the query limits with what the fetch that
        prepared its entry was charged: a repeat over a limit is refused
        without a read, exactly as a first run is."""
        from m3_tpu.storage.limits import QueryLimitError, QueryLimits

        db, ns, ids = self._db(tmp_path, monkeypatch)
        limits = QueryLimits()
        eng = Engine(db, limits=limits, resolve_tiers=False)
        calls = self._count_fetches(monkeypatch)
        q = "max_over_time(m[30m])"

        def run():
            return eng.query_range(q, START + 30 * 60 * NS,
                                   START + 3 * HOUR, 10 * 60 * NS)[0]

        v1 = run()
        series, datapoints = limits.charged()
        assert series == 64 and datapoints > series
        assert calls == {"query_ids": 1, "read_many": 1}
        run()
        assert limits.charged() == (series, datapoints)  # the hit's charge
        assert calls == {"query_ids": 1, "read_many": 1}

        for name, charged in (("max_datapoints", datapoints),
                              ("max_series", series)):
            setattr(limits, name, charged - 1)
            with pytest.raises(QueryLimitError) as warm:
                run()
            assert calls == {"query_ids": 1, "read_many": 1}  # no read
            hottier.default().clear()
            with pytest.raises(QueryLimitError) as cold:
                run()
            assert calls["query_ids"] == 2      # the first run's refusal
            assert str(warm.value).split(",")[1] \
                == str(cold.value).split(",")[1]
            # at the limit itself both pass, and the entry is warm again
            setattr(limits, name, charged)
            np.testing.assert_array_equal(run().values, v1.values)
            fetches = dict(calls)
            np.testing.assert_array_equal(run().values, v1.values)
            assert calls == fetches
            setattr(limits, name, 0)
            calls.update({"query_ids": 1, "read_many": 1})
        db.close()

    def test_lru_stays_under_byte_cap(self):
        tier = hottier.HotTier(max_bytes=1000)
        for i in range(20):
            tier.put(("k", i), {"x": i}, 300)
        assert tier.bytes_used <= 1000
        assert tier.evictions > 0
        assert len(tier) == 3

    def test_oversized_entry_never_admitted(self):
        tier = hottier.HotTier(max_bytes=100)
        tier.put(("big",), {}, 101)
        assert len(tier) == 0 and tier.bytes_used == 0


class TestFetchKey:
    def test_fetch_key_tracks_data_version(self, tmp_path):
        rng = np.random.default_rng(13)
        db, ns, ids = _build_db(str(tmp_path / "fk"), rng)
        eng = Engine(db, resolve_tiers=False)
        from m3_tpu.query.promql import parse

        sel = parse("m").expr if hasattr(parse("m"), "expr") else parse("m")
        grid = np.array([START + HOUR], np.int64)
        _lbl, raws1 = eng._fetch(sel, grid, 0)
        _lbl, raws2 = eng._fetch(sel, grid, 0)
        assert raws1.fetch_key is not None
        assert raws1.fetch_key == raws2.fetch_key
        db.write_tagged("default", ids[0],
                        [(b"__name__", b"m"), (b"host", b"h00"),
                         (b"i", b"000")], START + HOUR - NS, 1.0)
        _lbl, raws3 = eng._fetch(sel, grid, 0)
        assert raws3.fetch_key != raws1.fetch_key
        db.close()
