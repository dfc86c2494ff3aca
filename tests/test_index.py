"""Inverted index tests: segments, query algebra, namespace index, and the
tagged write -> query path through the database.

Mirrors the reference m3ninx test strategy (SURVEY.md §4): exhaustive
cross-checks of the boolean algebra against brute-force evaluation over
random documents (the search/proptest role).
"""

import numpy as np
import pytest

from m3_tpu.index import postings as P
from m3_tpu.index.executor import search, search_segment
from m3_tpu.index.index import NamespaceIndex
from m3_tpu.index.query import (
    AllQuery,
    ConjunctionQuery,
    DisjunctionQuery,
    FieldQuery,
    Matcher,
    MatchType,
    NegationQuery,
    RegexpQuery,
    TermQuery,
    matchers_to_query,
)
from m3_tpu.index.segment import MutableSegment, Segment, merge_segments

HOUR = 3600 * 10**9
START = 1_599_998_400_000_000_000


def build_docs(rng, n=200):
    docs = []
    for i in range(n):
        fields = [
            (b"host", f"host-{i % 17}".encode()),
            (b"dc", [b"us-east", b"us-west", b"eu"][i % 3]),
            (b"service", f"svc{i % 5}".encode()),
        ]
        if i % 4 == 0:
            fields.append((b"canary", b"true"))
        docs.append((f"series-{i}".encode(), fields))
    return docs


def brute_force(docs, pred):
    return {sid for sid, fields in docs if pred(dict(fields))}


@pytest.fixture
def seg(rng):
    m = MutableSegment()
    for sid, fields in build_docs(rng):
        m.insert(sid, fields)
    return m.seal(), build_docs(rng)


class TestPostings:
    def test_set_algebra(self):
        a = P.from_list([1, 3, 5, 7])
        b = P.from_list([3, 4, 5])
        assert list(P.intersect(a, b)) == [3, 5]
        assert list(P.union(a, b)) == [1, 3, 4, 5, 7]
        assert list(P.difference(a, b)) == [1, 7]

    def test_bitmap_roundtrip(self, rng):
        ids = np.unique(rng.integers(0, 1000, 300)).astype(np.uint32)
        words = P.to_bitmap(ids, 1000)
        np.testing.assert_array_equal(P.from_bitmap(words), ids)

    def test_device_bitmap_ops(self, rng):
        from m3_tpu.ops import bitmaps as BM
        import jax.numpy as jnp

        n = 512
        sets = [np.unique(rng.integers(0, n, 100)).astype(np.uint32) for _ in range(4)]
        masks = np.stack([P.to_bitmap(s, n) for s in sets])
        both = P.from_bitmap(np.asarray(BM.conjunct(jnp.asarray(masks))))
        expected = sets[0]
        for s in sets[1:]:
            expected = np.intersect1d(expected, s)
        np.testing.assert_array_equal(both, expected)
        any_ = P.from_bitmap(np.asarray(BM.disjunct(jnp.asarray(masks))))
        exp_any = np.unique(np.concatenate(sets))
        np.testing.assert_array_equal(any_, exp_any)
        cards = np.asarray(BM.cardinality(jnp.asarray(masks)))
        np.testing.assert_array_equal(cards, [len(s) for s in sets])


class TestSegmentSearch:
    def test_term(self, seg):
        s, docs = seg
        got = {s.docs[int(i)].series_id for i in search_segment(s, TermQuery(b"dc", b"eu"))}
        assert got == brute_force(docs, lambda f: f.get(b"dc") == b"eu")

    def test_regexp(self, seg):
        s, docs = seg
        q = RegexpQuery(b"host", r"host-1[0-3]")
        got = {s.docs[int(i)].series_id for i in search_segment(s, q)}
        import re

        rx = re.compile(rb"host-1[0-3]")
        assert got == brute_force(docs, lambda f: rx.fullmatch(f.get(b"host", b"")))

    def test_conjunction_with_negation(self, seg):
        s, docs = seg
        q = ConjunctionQuery(
            (
                TermQuery(b"dc", b"us-east"),
                NegationQuery(TermQuery(b"service", b"svc0")),
            )
        )
        got = {s.docs[int(i)].series_id for i in search_segment(s, q)}
        assert got == brute_force(
            docs, lambda f: f.get(b"dc") == b"us-east" and f.get(b"service") != b"svc0"
        )

    def test_disjunction(self, seg):
        s, docs = seg
        q = DisjunctionQuery((TermQuery(b"dc", b"eu"), TermQuery(b"canary", b"true")))
        got = {s.docs[int(i)].series_id for i in search_segment(s, q)}
        assert got == brute_force(
            docs, lambda f: f.get(b"dc") == b"eu" or f.get(b"canary") == b"true"
        )

    def test_field_exists(self, seg):
        s, docs = seg
        got = {s.docs[int(i)].series_id for i in search_segment(s, FieldQuery(b"canary"))}
        assert got == brute_force(docs, lambda f: b"canary" in f)

    def test_all_and_pure_negation(self, seg):
        s, docs = seg
        assert len(search_segment(s, AllQuery())) == len(docs)
        q = ConjunctionQuery((NegationQuery(TermQuery(b"dc", b"eu")),))
        got = {s.docs[int(i)].series_id for i in search_segment(s, q)}
        assert got == brute_force(docs, lambda f: f.get(b"dc") != b"eu")

    def test_random_algebra_vs_brute_force(self, rng, seg):
        s, docs = seg
        leaves = [
            TermQuery(b"dc", b"us-west"),
            TermQuery(b"service", b"svc3"),
            RegexpQuery(b"host", r"host-\d"),
            FieldQuery(b"canary"),
        ]
        preds = [
            lambda f: f.get(b"dc") == b"us-west",
            lambda f: f.get(b"service") == b"svc3",
            lambda f: __import__("re").compile(rb"host-\d").fullmatch(f.get(b"host", b"")) is not None,
            lambda f: b"canary" in f,
        ]
        for _ in range(30):
            k = rng.integers(2, 5)
            pick = rng.integers(0, len(leaves), k)
            neg = rng.random(k) < 0.4
            use_or = rng.random() < 0.5
            qs = tuple(
                NegationQuery(leaves[i]) if n else leaves[i] for i, n in zip(pick, neg)
            )
            if use_or and not any(neg):
                q = DisjunctionQuery(qs)

                def pred(f, pick=pick):
                    return any(preds[i](f) for i in pick)
            else:
                q = ConjunctionQuery(qs)

                def pred(f, pick=pick, neg=neg):
                    return all(
                        (not preds[i](f)) if n else preds[i](f)
                        for i, n in zip(pick, neg)
                    )
            got = {s.docs[int(i)].series_id for i in search_segment(s, q)}
            assert got == brute_force(docs, pred)


class TestSegmentLifecycle:
    def test_persist_roundtrip(self, seg):
        s, _ = seg
        raw = s.to_bytes()
        s2 = Segment.from_bytes(raw)
        assert s2.n_docs == s.n_docs
        q = TermQuery(b"dc", b"eu")
        np.testing.assert_array_equal(search_segment(s2, q), search_segment(s, q))
        assert s2.docs[5].fields == s.docs[5].fields

    def test_merge_dedupes_series(self):
        m1, m2 = MutableSegment(), MutableSegment()
        m1.insert(b"a", [(b"x", b"1")])
        m1.insert(b"b", [(b"x", b"2")])
        m2.insert(b"b", [(b"x", b"2")])
        m2.insert(b"c", [(b"x", b"3")])
        merged = merge_segments([m1.seal(), m2.seal()])
        assert merged.n_docs == 3
        got = {merged.docs[int(i)].series_id for i in search_segment(merged, FieldQuery(b"x"))}
        assert got == {b"a", b"b", b"c"}

    def test_multi_segment_search_dedupes(self):
        m1, m2 = MutableSegment(), MutableSegment()
        m1.insert(b"a", [(b"x", b"1")])
        m2.insert(b"a", [(b"x", b"1")])
        docs = search([m1.seal(), m2.seal()], TermQuery(b"x", b"1"))
        assert [d.series_id for d in docs] == [b"a"]


class TestNamespaceIndex:
    def test_time_partitioned_query(self):
        idx = NamespaceIndex(2 * HOUR)
        idx.insert(b"early", [(b"k", b"v")], START)
        idx.insert(b"late", [(b"k", b"v")], START + 4 * HOUR)
        q = TermQuery(b"k", b"v")
        assert {d.series_id for d in idx.query(q, START, START + HOUR)} == {b"early"}
        assert {d.series_id for d in idx.query(q, START, START + 6 * HOUR)} == {
            b"early",
            b"late",
        }

    def test_compact_and_expire(self):
        idx = NamespaceIndex(2 * HOUR)
        for i in range(50):
            idx.insert(f"s{i}".encode(), [(b"k", b"v")], START)
        idx.compact()
        assert len(idx._blocks[START].sealed) == 1
        assert idx._blocks[START].mutable.n_docs == 0
        assert len(idx.query(TermQuery(b"k", b"v"), START, START + HOUR)) == 50
        assert idx.expire_before(START + 3 * HOUR) == 1
        assert idx.n_blocks == 0

    def test_aggregate_queries(self):
        idx = NamespaceIndex(2 * HOUR)
        idx.insert(b"a", [(b"host", b"h1"), (b"dc", b"eu")], START)
        idx.insert(b"b", [(b"host", b"h2")], START)
        assert idx.aggregate_field_names(START, START + HOUR) == [b"dc", b"host"]
        assert idx.aggregate_field_values(b"host", START, START + HOUR) == [b"h1", b"h2"]
        assert idx.aggregate_field_values(b"host", START, START + HOUR, r"h1") == [b"h1"]


class TestInsertsRaceCompaction:
    def test_no_doc_is_lost_while_the_block_compacts(self):
        """Request threads insert while the tick thread compacts and
        persists: every inserted series must stay findable. Before the
        block lock, a doc landing between compaction's snapshot of the
        mutable segment and its swap for a fresh one was gone for good
        (chip_smoke's read-back lost 273 of 20,000 series to it)."""
        import sys
        import threading
        import time

        idx = NamespaceIndex(2 * HOUR)
        n_writers, per_writer = 12, 400
        stop = threading.Event()
        # a block big enough that each merge takes a while: the window
        # the race needs
        base = [b"base-%d" % i for i in range(20_000)]
        idx.insert_many(base, [[(b"writer", b"base"), (b"sid", sid)]
                               for sid in base],
                        np.full(len(base), START, np.int64))

        def writer(w):
            for i in range(0, per_writer, 8):
                sids = [b"w%d-s%d" % (w, j) for j in range(i, i + 8)]
                idx.insert_many(
                    sids, [[(b"writer", b"%d" % w), (b"sid", sid)]
                           for sid in sids],
                    np.full(8, START, np.int64))

        def compactor():
            full = False
            while not stop.is_set():
                idx.compact(full=full)
                full = not full
                list(idx.query(AllQuery(), START, START + 1))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            c = threading.Thread(target=compactor)
            c.start()
            ws = [threading.Thread(target=writer, args=(w,))
                  for w in range(n_writers)]
            t0 = time.time()
            for t in ws:
                t.start()
            for t in ws:
                t.join(120)
            stop.set()
            c.join(120)
        finally:
            sys.setswitchinterval(old)
        assert not c.is_alive() and not any(t.is_alive() for t in ws)
        assert time.time() - t0 < 120
        found = {d.series_id for d in idx.query(AllQuery(), START, START + 1)}
        assert len(found) == len(base) + n_writers * per_writer
        hits = list(idx.query(TermQuery(b"writer", b"7"), START, START + 1))
        assert len(hits) == per_writer


    def test_an_insert_during_a_merge_lands_after_it(self, monkeypatch):
        """The interleaving itself, forced: an insert that arrives while
        compaction is merging must wait for the swap and land in the
        fresh mutable segment (it used to land in the one being thrown
        away)."""
        import threading

        from m3_tpu.index import packed

        idx = NamespaceIndex(2 * HOUR)
        idx.insert(b"early", [(b"host", b"a")], START)
        late = threading.Thread(
            target=idx.insert, args=(b"late", [(b"host", b"b")], START))
        real_merge = packed.merge

        def merge_with_a_visitor(segs):
            late.start()
            late.join(0.3)  # blocked on the block lock until the swap
            return real_merge(segs)

        monkeypatch.setattr(packed, "merge", merge_with_a_visitor)
        idx.compact()
        late.join(30)
        assert not late.is_alive()
        for sid, host in ((b"early", b"a"), (b"late", b"b")):
            hits = list(idx.query(TermQuery(b"host", host), START, START + 1))
            assert [d.series_id for d in hits] == [sid]


class TestDatabaseTaggedPath:
    def test_write_tagged_query(self, tmp_path):
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db.create_namespace("default")
        db.open()
        for i in range(10):
            db.write_tagged(
                "default", b"cpu",
                [(b"host", f"h{i}".encode()), (b"dc", b"eu" if i % 2 else b"us")],
                START + 10**9 * (i + 1), float(i),
            )
        matchers = [
            Matcher(MatchType.EQUAL, b"__name__", b"cpu"),
            Matcher(MatchType.EQUAL, b"dc", b"eu"),
        ]
        res = db.query("default", matchers, START, START + HOUR)
        assert len(res) == 5
        for sid, fields, dps in res:
            assert (b"dc", b"eu") in fields
            assert len(dps) == 1
        # regex + negation matchers
        matchers = [
            Matcher(MatchType.REGEXP, b"host", b"h[0-3]"),
            Matcher(MatchType.NOT_EQUAL, b"dc", b"eu"),
        ]
        res = db.query("default", matchers, START, START + HOUR)
        got = {dict(f).get(b"host") for _, f, _ in res}
        assert got == {b"h0", b"h2"}
        db.close()

    def test_query_survives_flush_and_restart(self, tmp_path):
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db.create_namespace("default")
        db.open()
        db.write_tagged("default", b"mem", [(b"host", b"h1")], START + 10**9, 1.5)
        db.flush_all()
        db.close()

        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=4))
        db2.create_namespace("default")
        db2.open(START + HOUR)
        res = db2.query(
            "default", [Matcher(MatchType.EQUAL, b"__name__", b"mem")], START, START + HOUR
        )
        assert len(res) == 1
        assert res[0][2][0].value == 1.5
        db2.close()

    def test_matchers_to_query_shapes(self):
        q = matchers_to_query([])
        assert isinstance(q, AllQuery)
        q = matchers_to_query([Matcher(MatchType.EQUAL, b"a", b"b")])
        assert isinstance(q, TermQuery)


class TestIndexPersistence:
    def test_persist_and_restore(self, tmp_path):
        from m3_tpu.index import persist as ip
        from m3_tpu.index.index import NamespaceIndex

        idx = NamespaceIndex(2 * HOUR)
        for i in range(30):
            idx.insert(f"s{i}".encode(), [(b"k", b"v"), (b"i", str(i).encode())],
                       START + (i % 2) * 2 * HOUR)
        assert ip.persist_index(idx, str(tmp_path), "ns") == 2
        # second persist with no new docs is a no-op
        assert ip.persist_index(idx, str(tmp_path), "ns") == 0
        idx2 = NamespaceIndex(2 * HOUR)
        restored = ip.load_index(idx2, str(tmp_path), "ns")
        assert restored == {START, START + 2 * HOUR}
        got = idx2.query(TermQuery(b"k", b"v"), START, START + 4 * HOUR)
        assert len(got) == 30

    def test_corrupt_segment_skipped(self, tmp_path):
        from m3_tpu.index import persist as ip
        from m3_tpu.index.index import NamespaceIndex
        import os

        idx = NamespaceIndex(2 * HOUR)
        idx.insert(b"a", [(b"k", b"v")], START)
        ip.persist_index(idx, str(tmp_path), "ns")
        seg_dir = os.path.join(str(tmp_path), "ns", "_index")
        f = os.path.join(seg_dir, os.listdir(seg_dir)[0])
        with open(f, "r+b") as fh:
            fh.seek(10)
            fh.write(b"XX")
        idx2 = NamespaceIndex(2 * HOUR)
        assert ip.load_index(idx2, str(tmp_path), "ns") == set()

    def test_database_persists_index_through_restart(self, tmp_path):
        from m3_tpu.storage.database import Database
        from m3_tpu.storage.options import DatabaseOptions

        db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
        db.create_namespace("default")
        db.open(START)
        for i in range(20):
            db.write_tagged("default", b"m", [(b"i", str(i).encode())],
                            START + (i + 1) * 10**9, float(i))
        db.tick(START + 4 * HOUR)  # flush + index persist
        import os

        seg_dir = os.path.join(str(tmp_path / "db"), "data", "default", "_index")
        assert os.path.isdir(seg_dir) and os.listdir(seg_dir)
        db.close()
        db2 = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
        db2.create_namespace("default")
        db2.open(START + 4 * HOUR)
        # the restore path actually ran (not just the fileset rebuild
        # fallback): restored blocks carry a non-default persisted_docs
        idx = db2.namespaces["default"].index
        assert any(blk.persisted_docs >= 0 for blk in idx._blocks.values())
        res = db2.query("default", [Matcher(MatchType.EQUAL, b"__name__", b"m")],
                        START, START + HOUR)
        assert len(res) == 20
        db2.close()
