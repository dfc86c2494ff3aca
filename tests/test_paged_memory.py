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


def _build_db(root, rng, n_series=64, n_blocks=3, with_flush=True,
              index_block=HOUR):
    db = Database(root, DatabaseOptions(n_shards=4))
    ns = db.create_namespace("default", NamespaceOptions(
        retention=RetentionOptions(retention_ns=1000 * HOUR,
                                   block_size_ns=HOUR),
        index=IndexOptions(enabled=True, block_size_ns=index_block),
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


def _same_bits(a, b):
    assert a.labels == b.labels
    assert a.values.shape == b.values.shape
    np.testing.assert_array_equal(a.values.view(np.uint64),
                                  b.values.view(np.uint64))


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

        v1, d1, hits1 = run()
        assert d1 == fetched and hits1 == [False] * plans
        assert len(v1.labels) > 0 and np.isfinite(v1.values).any()
        v2, d2, hits2 = run()
        assert d2 == skipped and hits2 == [True] * plans
        _same_bits(v2, v1)
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "0")
        vi, di, interpreted = run()
        assert di["query_ids"] == plans and di["hit"] == di["miss"] == 0
        assert interpreted == []
        _same_bits(v2, vi)
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
        _same_bits(v4, v3)
        # so does a flush, which leaves every answer as it was
        version = ns.data_version()
        assert sum(bool(shard.flush(w)) for shard in ns.shards.values()
                   for w in shard.buffer.block_starts()) > 0
        assert ns.data_version() != version
        v5, d5, hits5 = run()
        assert d5 == fetched and hits5 == [False] * plans
        _same_bits(v5, v3)
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


# `_build_db`'s data blocks: START lies 1,600 s into an hour, so the
# three rounds of writes fill the windows BLOCK0 .. BLOCK0 + 3 h. The
# first two are sealed (a volume, nothing buffered), the third holds a
# volume and buffered cold rows, the last is the head: buffered only.
BLOCK0 = START - 1600 * NS
HEAD = BLOCK0 + 3 * HOUR
# query_range(start, end) of a `[10m]` plan at a step of 10 m: SEALED
# reads [START, START + 80 m], the two sealed blocks and no other;
# AT_HEAD reads [HEAD + 400 s, START + 3 h], the head block alone
SEALED = (START + 10 * 60 * NS, START + 80 * 60 * NS)
AT_HEAD = (START + 2 * HOUR + 50 * 60 * NS, START + 3 * HOUR)


class TestRangeScopedVersion:
    """The version in a fetch key is that of the blocks the fetch's
    range touches (ISSUE 38): what changes another block leaves an
    entry warm, what changes a block of the range makes it miss, and
    the fingerprint never returns to a value it had."""

    _PLANS = {"by": "sum by (host) (sum_over_time(m[10m]))",
              "no_by": "max_over_time(m[10m])"}

    def _warm(self, tmp_path, monkeypatch, span=SEALED, plan="by",
              index_block=HOUR, name="r"):
        """A database, and `run()` -> (vector, was it a hit, fetches it
        made); the entry of `plan` over `span` is warm on return."""
        monkeypatch.setenv("M3_TPU_QUERY_COMPILE", "1")
        db, ns, ids = _build_db(str(tmp_path / name),
                                np.random.default_rng(77),
                                index_block=index_block)
        eng = Engine(db, resolve_tiers=False)
        calls = TestHotTier._count_fetches(monkeypatch)

        def run():
            before = dict(calls)
            with explain.collect(True) as col:
                vec, _ = eng.query_range(self._PLANS[plan], span[0],
                                         span[1], 10 * 60 * NS)
            run.last = vec
            return (vec, col.compiled["hot_tier"]["hit"],
                    {k: calls[k] - before[k] for k in calls})

        v1, hit1, _ = run()
        v2, hit2, fetches = run()
        assert (hit1, hit2) == (False, True)
        assert fetches == {"query_ids": 0, "read_many": 0}
        assert len(v1.labels) > 0 and np.isfinite(v1.values).any()
        _same_bits(v1, v2)
        return db, ns, ids, run

    @staticmethod
    def _write(db, t_ns, value=5.0, host=0, i=0):
        """One sample of `_build_db`'s series `i` (or of a new one)."""
        db.write_tagged("default", b"m,host=h%02d,i=%03d" % (host, i),
                        [(b"__name__", b"m"), (b"host", b"h%02d" % host),
                         (b"i", b"%03d" % i)], t_ns, value)

    # -- (a) another block's changes leave the entry warm --

    @staticmethod
    def _outside_write(db, ns, tmp_path):
        TestRangeScopedVersion._write(db, START + 3 * HOUR - 10 * NS)

    @staticmethod
    def _outside_flush(db, ns, tmp_path):
        # the cold flush of BLOCK0 + 2 h and the head's first volume
        assert sum(bool(shard.flush(w)) for shard in ns.shards.values()
                   for w in shard.buffer.block_starts()
                   if w >= BLOCK0 + 2 * HOUR) > 4

    @staticmethod
    def _outside_snapshot(db, ns, tmp_path):
        assert sum(shard.snapshot(HEAD, str(tmp_path / "snap"), 1)
                   for shard in ns.shards.values()) == 4

    @staticmethod
    def _outside_tick(db, ns, tmp_path):
        # a mediator cycle while the head is open: the cold flush of
        # BLOCK0 + 2 h, no expiry, index persist and compaction
        out = db.tick(HEAD + 30 * 60 * NS)
        assert out["cold_flushed"] > 0 and out["expired"] == 0

    @pytest.mark.parametrize("event", ["write", "flush", "snapshot", "tick"])
    def test_other_blocks_leave_the_entry_warm(self, event, tmp_path,
                                               monkeypatch, small_tier):
        db, ns, ids, run = self._warm(tmp_path, monkeypatch)
        key = ns.data_version_in(SEALED[0] - 10 * 60 * NS, SEALED[1] + 1)
        whole = ns.data_version()
        getattr(self, "_outside_" + event)(db, ns, tmp_path)
        assert ns.data_version_in(SEALED[0] - 10 * 60 * NS,
                                  SEALED[1] + 1) == key
        # the namespace's own version moves as before (query/standing.py)
        assert (ns.data_version() != whole) == (event != "snapshot")
        warm, hit, fetches = run()
        assert hit and fetches == {"query_ids": 0, "read_many": 0}
        hottier.default().clear()
        fresh, hit, fetches = run()
        assert not hit and fetches == {"query_ids": 1, "read_many": 1}
        _same_bits(warm, fresh)
        db.close()

    # -- (b) a change to a block of the range makes the next run miss --

    @staticmethod
    def _inside_warm_write(db, ns, run, tmp_path):
        shards = list(ns.shards.values())
        before = sum(s.warm_writes for s in shards)
        TestRangeScopedVersion._write(db, START + 3 * HOUR - 10 * NS)
        assert sum(s.warm_writes for s in shards) == before + 1
        return "changed"

    @staticmethod
    def _inside_cold_write(db, ns, run, tmp_path):
        shards = list(ns.shards.values())
        before = sum(s.cold_writes for s in shards)
        TestRangeScopedVersion._write(db, START + 20 * 60 * NS)
        assert sum(s.cold_writes for s in shards) == before + 1
        return "changed"

    @staticmethod
    def _inside_flush(db, ns, run, tmp_path):
        TestRangeScopedVersion._write(db, START + 20 * 60 * NS)
        assert not run()[1] and run()[1]  # warm over the buffered row
        assert sum(bool(shard.flush(BLOCK0))
                   for shard in ns.shards.values()) == 1
        return "same"  # the volume swap moves no sample

    @staticmethod
    def _inside_bootstrap(db, ns, run, tmp_path):
        assert sum(shard.bootstrap_from_fs()
                   for shard in ns.shards.values()) >= 8
        return "same"

    @staticmethod
    def _inside_repair(db, ns, run, tmp_path):
        from m3_tpu.storage import peers

        peer, pns, _ids = _build_db(str(tmp_path / "peer"),
                                    np.random.default_rng(77))
        TestRangeScopedVersion._write(peer, START + 20 * 60 * NS)
        repaired = 0
        for sid, shard in pns.shards.items():
            if shard.flush(BLOCK0):
                repaired += peers.repair_shard_block(
                    db, "default", sid, BLOCK0,
                    [peers.InProcessPeer(peer)]).repaired
        peer.close()
        assert repaired == 1
        return "changed"

    @staticmethod
    def _inside_expiry(db, ns, run, tmp_path):
        # the retention cutoff passes BLOCK0 and no other block
        assert ns.expire(BLOCK0 + HOUR + 1000 * HOUR) == 4
        return "changed"

    # a placement change moves every range's version, whatever the shard
    # holds (a fifth shard, which no series routes to, holds nothing)
    @staticmethod
    def _inside_add_shard(db, ns, run, tmp_path):
        ns.add_shard(4)
        return "same"

    @staticmethod
    def _inside_remove_shard(db, ns, run, tmp_path):
        ns.add_shard(4)
        assert not run()[1] and run()[1]  # warm over five shards
        ns.remove_shard(4)
        return "same"

    @pytest.mark.parametrize("event,span", [
        ("warm_write", AT_HEAD), ("cold_write", SEALED), ("flush", SEALED),
        ("bootstrap", SEALED), ("repair", SEALED), ("expiry", SEALED),
        ("remove_shard", SEALED), ("add_shard", SEALED)])
    def test_a_block_of_the_range_makes_it_miss(self, event, span, tmp_path,
                                                monkeypatch, small_tier):
        db, ns, ids, run = self._warm(tmp_path, monkeypatch, span=span)
        answer = getattr(self, "_inside_" + event)(db, ns, run, tmp_path)
        before = run.last
        after, hit, fetches = run()
        assert not hit and fetches == {"query_ids": 1, "read_many": 1}
        same = np.array_equal(after.values.view(np.uint64),
                              before.values.view(np.uint64))
        assert same == (answer == "same")
        again, hit, fetches = run()
        assert hit and fetches == {"query_ids": 0, "read_many": 0}
        _same_bits(after, again)
        db.close()

    # -- (c) a range over two blocks misses on a write to either --

    @pytest.mark.parametrize("block", [0, 1])
    def test_two_block_range_misses_on_either(self, block, tmp_path,
                                              monkeypatch, small_tier):
        db, ns, ids, run = self._warm(tmp_path, monkeypatch)
        first = ns.opts.retention.block_start(SEALED[0] - 10 * 60 * NS)
        assert first == BLOCK0
        assert ns.opts.retention.block_start(SEALED[1]) == BLOCK0 + HOUR
        self._write(db, BLOCK0 + block * HOUR + 1700 * NS)
        after, hit, fetches = run()
        assert not hit and fetches == {"query_ids": 1, "read_many": 1}
        db.close()

    # -- (d) a series first seen in the head block, entry warm --

    @pytest.mark.parametrize("index_block", [HOUR, 4 * HOUR],
                             ids=["index_1h", "index_4h"])
    @pytest.mark.parametrize("plan", sorted(_PLANS))
    def test_new_series_at_the_head_keeps_the_answer(
            self, plan, index_block, tmp_path, monkeypatch, small_tier):
        """Under a 4 h index block the new series is matched by the
        sealed range's index query too (it holds no sample there, so the
        fetch drops it): warm and fresh agree in labels, rows and NaN
        masks."""
        db, ns, ids, run = self._warm(tmp_path, monkeypatch, plan=plan,
                                      index_block=index_block)
        from m3_tpu.index.query import Matcher, MatchType, matchers_to_query

        iq = matchers_to_query([Matcher(MatchType.EQUAL, b"__name__", b"m")])
        matched = len(ns.index.query(iq, SEALED[0], SEALED[1]))
        self._write(db, START + 3 * HOUR - 10 * NS, host=99, i=999)
        assert len(ns.index.query(iq, SEALED[0], SEALED[1])) \
            == matched + (index_block > HOUR)
        warm, hit, fetches = run()
        assert hit and fetches == {"query_ids": 0, "read_many": 0}
        hottier.default().clear()
        fresh, hit, _ = run()
        assert not hit
        assert not any(lb.get("host") == "h99" for lb in fresh.labels)
        _same_bits(warm, fresh)
        db.close()

    # -- (e) the fingerprint never returns to a value it had --

    def _fingerprints_across_expiry(self, db, ns):
        span = (SEALED[0] - 10 * 60 * NS, SEALED[1] + 1)
        seen = [ns.data_version_in(*span)]
        for k in range(3):
            self._write(db, START + (20 + k) * 60 * NS)
            seen.append(ns.data_version_in(*span))
        # everything expires: the blocks' volumes and buffered rows go
        assert ns.expire(HEAD + 2000 * HOUR) > 0
        seen.append(ns.data_version_in(*span))
        for k in range(6):
            self._write(db, START + (20 + k) * 60 * NS)
            seen.append(ns.data_version_in(*span))
        return seen

    def _fingerprints_across_placement(self, db, ns):
        span = (SEALED[0] - 10 * 60 * NS, SEALED[1] + 1)
        seen = [ns.data_version_in(*span)]
        for _ in range(3):
            # a shard that comes back is a new object with new counters:
            # the sum alone returns to what it was, the epoch does not
            ns.remove_shard(0)
            seen.append(ns.data_version_in(*span))
            ns.add_shard(0)
            seen.append(ns.data_version_in(*span))
        assert len({fp[2] for fp in seen}) < len(seen)
        return seen

    @pytest.mark.parametrize("across", ["expiry", "placement"])
    def test_fingerprint_does_not_alias(self, across, tmp_path):
        db, ns, ids = _build_db(str(tmp_path / "e"),
                                np.random.default_rng(77))
        seen = getattr(self, "_fingerprints_across_" + across)(db, ns)
        assert len(set(seen)) == len(seen)
        db.close()

    @pytest.mark.parametrize("path", ["write_tagged", "write_batch",
                                      "namespace_write_tagged"])
    def test_series_is_indexed_before_its_version_moves(self, path, tmp_path,
                                                        monkeypatch):
        """The fingerprint that holds a series' first sample also matches
        the series: every write path indexes before the shard's buffer
        append bumps the block's version, or a fetch in between would
        keep an answer without the series under the version that has
        it."""
        from m3_tpu.index.query import Matcher, MatchType, matchers_to_query
        from m3_tpu.storage.shard import Shard
        from m3_tpu.utils.ident import encode_tags, tags_to_id

        db, ns, ids = _build_db(str(tmp_path / "o"),
                                np.random.default_rng(77))
        iq = matchers_to_query([Matcher(MatchType.EQUAL, b"host", b"h99")])
        t = START + 3 * HOUR - 10 * NS
        matched_at_bump = []

        def bump(shard, block_start):
            matched_at_bump.append(len(ns.index.query(iq, t, t + 1)))
            return inner(shard, block_start)
        inner = Shard._bump_locked
        monkeypatch.setattr(Shard, "_bump_locked", bump)
        name = b"m,host=h99,i=999"
        tags = [(b"__name__", b"m"), (b"host", b"h99"), (b"i", b"999")]
        if path == "write_tagged":
            db.write_tagged("default", name, tags, t, 1.0)
        elif path == "write_batch":
            assert db.write_batch("default", [(name, tags, t, 1.0)]) == [None]
        else:
            fields = [(b"__name__", name), *tags]
            ns.write_tagged(tags_to_id(name, tags), fields, t, bits(1.0),
                            encode_tags(fields))
        assert matched_at_bump == [1]
        db.close()

    @pytest.mark.parametrize("swap,span", [("repair", SEALED),
                                           ("peer_bootstrap", AT_HEAD)])
    def test_swap_moves_the_version_though_its_index_phase_raises(
            self, swap, span, tmp_path, monkeypatch, small_tier):
        """`storage/peers.py` indexes what a peer sent AFTER the volume
        swap, and a peer's tags may not decode: the swap's own bump must
        not wait for that, or the entry over the block stays warm over
        the old volume for good (a retry skips a block it has)."""
        from m3_tpu.storage import peers
        from m3_tpu.utils import ident

        db, ns, ids, run = self._warm(tmp_path, monkeypatch, span=span)
        peer, pns, _ids = _build_db(str(tmp_path / "peer"),
                                    np.random.default_rng(77))
        block = BLOCK0 if swap == "repair" else HEAD
        # to a series the block's index has: the index phase will add none
        i = next(i for i in range(len(ids)) for sid in ns.series_ids()
                 if sid.endswith(b"|i=%03d" % i)
                 and len(ns.read(sid, block, block + HOUR)[0]))
        self._write(peer, START + 20 * 60 * NS if swap == "repair"
                    else START + 3 * HOUR - 10 * NS, host=i % 8, i=i)
        flushed = [sid for sid, shard in pns.shards.items()
                   if shard.flush(block)]
        assert flushed and not any(block in shard._filesets
                                   for shard in ns.shards.values()) \
            == (swap == "peer_bootstrap")

        def undecodable(blob):
            raise ValueError("tags do not decode")
        raised = 0
        with monkeypatch.context() as m:
            m.setattr(ident, "decode_tags", undecodable)
            for sid in flushed:
                with pytest.raises(ValueError, match="do not decode"):
                    if swap == "repair":
                        peers.repair_shard_block(
                            db, "default", sid, block,
                            [peers.InProcessPeer(peer)])
                    else:
                        peers.bootstrap_shard_from_peers(
                            db, "default", sid, [peers.InProcessPeer(peer)])
                raised += 1
        peer.close()
        assert raised == len(flushed)
        before = run.last
        after, hit, fetches = run()
        assert not hit and fetches == {"query_ids": 1, "read_many": 1}
        assert not np.array_equal(after.values.view(np.uint64),
                                  before.values.view(np.uint64))
        db.close()

    def test_untouched_block_reads_zero(self, tmp_path):
        """A block never written or loaded is version 0 and part of the
        fingerprint; a range from time 0 reads every touched block."""
        db, ns, ids = _build_db(str(tmp_path / "z"),
                                np.random.default_rng(77))
        far = HEAD + 500 * HOUR
        assert ns.data_version_in(far, far + HOUR) == (0, 4, 0)
        self._write(db, far + NS)
        assert ns.data_version_in(far, far + HOUR) == (0, 4, 1)
        for shard in ns.shards.values():
            assert len(shard._block_versions) <= 5
            assert shard.data_version_in(BLOCK0 - 90 * HOUR, HEAD) == sum(
                v for bs, v in shard._block_versions.items() if bs <= HEAD)
            assert shard.data_version_in(0, far) == sum(
                shard._block_versions.values())
        db.close()


class TestFetchKey:
    @pytest.mark.parametrize("where,moves", [("head", False),
                                             ("inside", True)])
    def test_fetch_key_is_scoped_to_the_range(self, where, moves, tmp_path):
        rng = np.random.default_rng(13)
        db, ns, ids = _build_db(str(tmp_path / "fk"), rng)
        eng = Engine(db, resolve_tiers=False)
        from m3_tpu.query.promql import parse

        sel = parse("m").expr if hasattr(parse("m"), "expr") else parse("m")
        grid = np.array([START + 20 * 60 * NS], np.int64)
        key = eng._resolve_fetch(sel, grid, 0)[-1]
        t = {"head": START + 3 * HOUR - 10 * NS,
             "inside": START + 18 * 60 * NS}[where]
        TestRangeScopedVersion._write(db, t, 1.0)
        assert (eng._resolve_fetch(sel, grid, 0)[-1] != key) == moves
        db.close()

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
