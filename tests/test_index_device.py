"""Device-compiled inverted index (index/device.py): exact parity of the
fused postings programs against the scalar walk, literal prefix/suffix
regex narrowing soundness on adversarial patterns, union_many parity with
the old pairwise reduce, and the ?explain=analyze `index` accounting."""

from __future__ import annotations

import functools
import re

import numpy as np
import pytest

from m3_tpu.index import device, packed
from m3_tpu.index import postings as P
from m3_tpu.index.executor import search, search_segment
from m3_tpu.index.query import (
    AllQuery,
    ConjunctionQuery,
    DisjunctionQuery,
    FieldQuery,
    NegationQuery,
    RegexpQuery,
    TermQuery,
)
from m3_tpu.index.segment import Document, MutableSegment
from m3_tpu.metrics import filters
from m3_tpu.utils import querystats


def _documents(n=4000, base=0):
    docs = []
    for i in range(n):
        fields = [
            (b"host", b"web-%03d" % (i % 41)),
            (b"dc", b"dc%d" % (i % 5)),
            (b"app", b"app-%03d" % (i % 97)),
        ]
        if i % 3 == 0:  # a field most docs lack
            fields.append((b"opt", b"v%d" % (i % 7)))
        if i % 997 == 0:  # high-byte terms for prefix upper-bound edges
            fields.append((b"odd", b"\xff\xff-%d" % (i % 3)))
        docs.append(Document(i, b"series-%06d" % (base + i), sorted(fields)))
    return docs


@pytest.fixture(scope="module")
def seg():
    return packed.build(_documents())


def _brute(seg_, q):
    """Reference evaluation by Python set algebra over brute-forced
    leaves (no narrowing, no batching, no device)."""
    alldocs = set(range(seg_.n_docs))
    if isinstance(q, AllQuery):
        return alldocs
    if isinstance(q, TermQuery):
        return set(seg_.postings_term(q.field_name, q.value).tolist())
    if isinstance(q, RegexpQuery):
        rx = q.compiled()
        hits = set()
        for fi, name in enumerate(seg_.field_names()):
            if name != q.field_name:
                continue
            lo, hi = seg_._term_range(fi)
            for i in range(lo, hi):
                if rx.fullmatch(seg_._term_at(i)):
                    hits |= set(seg_._postings_at(i).tolist())
        return hits
    if isinstance(q, FieldQuery):
        return set(seg_.postings_field(q.field_name).tolist())
    if isinstance(q, NegationQuery):
        return alldocs - _brute(seg_, q.inner)
    if isinstance(q, ConjunctionQuery):
        acc = alldocs
        for c in q.queries:
            acc = acc & _brute(seg_, c)
        return acc
    acc = set()
    for c in q.queries:
        acc = acc | _brute(seg_, c)
    return acc


class TestLiteralAffixes:
    """metrics/filters literal prefix/suffix extraction: sound (never
    excludes a true match) and useful on the common shapes."""

    @pytest.mark.parametrize("src,want", [
        (b"abc", b"abc"),
        (b"abc.*", b"abc"),
        (b"ab?c", b"a"),          # ? makes the b optional
        (b"ab*c", b"a"),
        (b"ab{0,2}c", b"a"),
        (b"a|b", b""),            # top-level alternation: no prefix
        (b"abc(d|e)", b""),
        (b"\\d+", b""),
        (b"", b""),
    ])
    def test_prefix(self, src, want):
        assert filters.literal_prefix(src) == want

    @pytest.mark.parametrize("src,want", [
        (b"abc", b"abc"),
        (b".*bar", b"bar"),
        (b"foo\\dbar", b"bar"),   # escape swallows the escaped byte
        (b"foo\\\\bar", b"ar"),   # literal backslash
        (b"a|bar", b""),          # alternation: suffix unsound
        (b"(?i)bar", b""),        # inline flags: suffix unsound
        (b"bar.*", b""),
        (b"bar$", b""),
        (b"web-\\.x", b"x"),
    ])
    def test_suffix(self, src, want):
        assert filters.literal_suffix(src) == want

    def test_prefix_upper_bound(self):
        assert filters.prefix_upper_bound(b"ab") == b"ac"
        assert filters.prefix_upper_bound(b"a\xff") == b"b"
        assert filters.prefix_upper_bound(b"\xff\xff") == b""


ADVERSARIAL = [
    rb".*",
    rb"web-.*",
    rb"web-0\d\d",
    rb"web-001|app-0.*",
    rb"(web|app)-00[13]",
    rb".*-001",
    rb"\d+",
    rb"",
    rb"web-0[0-9]{2}",
    rb"w.b-00.",
    rb"web-00\d$",
    rb"\xff.*",
    rb"(?i)WEB-00.*",
    rb"app-.*7",
    rb"[a-z]+-\d+",
]


class TestRegexNarrowingParity:
    """Satellite: literal prefix/suffix narrowing must be invisible —
    exact parity with unnarrowed per-term fullmatch on adversarial
    patterns, for both segment tiers."""

    @pytest.mark.parametrize("src", ADVERSARIAL)
    def test_packed(self, seg, src):
        for field in (b"host", b"app", b"odd", b"missing"):
            want = sorted(_brute(seg, RegexpQuery(field, src)))
            got = seg.postings_regexp(field, re.compile(src))
            assert got.tolist() == want, (field, src)

    @pytest.mark.parametrize("src", ADVERSARIAL)
    def test_mutable_sealed(self, src):
        m = MutableSegment()
        for d in _documents(600):
            m.insert(d.series_id, d.fields)
        s = m.seal()
        for field in (b"host", b"app", b"odd"):
            vocab = s.terms(field)
            rx = re.compile(src)
            want = set()
            for v in vocab:
                if rx.fullmatch(v):
                    want |= set(s.postings_term(field, v).tolist())
            got = s.postings_regexp(field, rx)
            assert got.tolist() == sorted(want), (field, src)

    def test_compile_time_flags(self, seg):
        rx = re.compile(rb"WEB-00[12]", re.IGNORECASE)
        want = sorted(
            set(seg.postings_regexp(b"host", re.compile(rb"web-00[12]"))
                .tolist()))
        assert seg.postings_regexp(b"host", rx).tolist() == want
        # same source, different flags: distinct cache entries
        rx2 = re.compile(rb"WEB-00[12]")
        assert seg.postings_regexp(b"host", rx2).tolist() == []


class TestUnionMany:
    """Satellite: union_many (one concatenate + unique pass) is exactly
    the old pairwise reduce."""

    def test_randomized_parity(self):
        rng = np.random.default_rng(42)
        for _ in range(50):
            n_lists = int(rng.integers(0, 8))
            lists = []
            for _ in range(n_lists):
                k = int(rng.integers(0, 200))
                lists.append(np.unique(
                    rng.integers(0, 500, k).astype(np.uint32)))
            got = P.union_many(lists)
            want = functools.reduce(P.union, lists, P.EMPTY)
            np.testing.assert_array_equal(got, want)
            assert got.dtype == np.uint32

    def test_empty_and_single(self):
        assert P.union_many([]).tolist() == []
        one = np.asarray([3, 9], np.uint32)
        np.testing.assert_array_equal(P.union_many([one]), one)
        assert P.union_many([P.EMPTY, P.EMPTY]).tolist() == []


def _sweep_queries(seed=1234, n=40):
    rng = np.random.default_rng(seed)
    hosts = [b"web-%03d" % i for i in range(0, 45, 3)] + [b"nope"]
    regexes = [rb"web-0[0-3].", rb"app-.*1", rb"dc[123]", rb".*-007",
               rb"web-00\d|app-00\d"]
    fields = [b"host", b"dc", b"app", b"opt", b"ghost"]
    out = []
    for _ in range(n):
        legs = []
        conj = bool(rng.integers(0, 2))
        for _ in range(int(rng.integers(2, 5))):
            kind = int(rng.integers(0, 4 if conj else 3))
            f = fields[int(rng.integers(0, len(fields)))]
            if kind == 0:
                leg = TermQuery(f, hosts[int(rng.integers(0, len(hosts)))])
            elif kind == 1:
                leg = RegexpQuery(
                    f, regexes[int(rng.integers(0, len(regexes)))].decode())
            elif kind == 2:
                leg = FieldQuery(f)
            else:
                leg = NegationQuery(
                    TermQuery(f, hosts[int(rng.integers(0, len(hosts)))]))
            legs.append(leg)
        out.append(ConjunctionQuery(tuple(legs)) if conj
                   else DisjunctionQuery(tuple(legs)))
    return out


class TestDeviceParity:
    """The fused postings programs return doc-id sets EXACTLY equal to
    the scalar walk — seeded random matcher sweep, pinned at 1 and 8
    virtual devices (pure boolean algebra: bit-identical on any mesh)."""

    def _device_ids(self, seg_, q, monkeypatch, shard):
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", shard)
        ids, reason = device.match(seg_, q)
        assert reason is None, (q, reason)
        return ids

    @pytest.mark.parametrize("shard", ["0", "8"])
    def test_matcher_sweep(self, seg, monkeypatch, shard):
        for q in _sweep_queries():
            want = np.asarray(sorted(_brute(seg, q)), np.uint32)
            got = self._device_ids(seg, q, monkeypatch, shard)
            np.testing.assert_array_equal(got, want)

    def test_executor_dispatches_device(self, seg, monkeypatch):
        from m3_tpu.utils import dispatch

        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        q = ConjunctionQuery((TermQuery(b"host", b"web-001"),
                              RegexpQuery(b"app", "app-0.*"),
                              NegationQuery(TermQuery(b"dc", b"dc3"))))
        before = dispatch.counters["index.postings[device]"]
        got = search_segment(seg, q)
        assert dispatch.counters["index.postings[device]"] > before
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "0")
        np.testing.assert_array_equal(got, search_segment(seg, q))

    def test_not_over_empty_postings(self, seg, monkeypatch):
        q = ConjunctionQuery((TermQuery(b"host", b"web-001"),
                              NegationQuery(TermQuery(b"app", b"absent"))))
        want = np.asarray(sorted(_brute(seg, q)), np.uint32)
        got = self._device_ids(seg, q, monkeypatch, "0")
        np.testing.assert_array_equal(got, want)
        # pure negation over a missing term: everything matches
        q2 = ConjunctionQuery((NegationQuery(TermQuery(b"app", b"absent")),))
        got2 = self._device_ids(seg, q2, monkeypatch, "0")
        assert len(got2) == seg.n_docs

    def test_missing_field_matcher(self, seg, monkeypatch):
        q = ConjunctionQuery((TermQuery(b"ghost", b"x"),
                              TermQuery(b"dc", b"dc1")))
        assert len(self._device_ids(seg, q, monkeypatch, "0")) == 0
        q2 = DisjunctionQuery((TermQuery(b"ghost", b"x"),
                               TermQuery(b"dc", b"dc1"),
                               FieldQuery(b"alsoghost")))
        want = np.asarray(sorted(_brute(seg, q2)), np.uint32)
        np.testing.assert_array_equal(
            self._device_ids(seg, q2, monkeypatch, "0"), want)

    def test_fallback_reasons(self, seg, monkeypatch):
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        nested = ConjunctionQuery((
            TermQuery(b"dc", b"dc1"),
            DisjunctionQuery((TermQuery(b"host", b"web-001"),
                              TermQuery(b"host", b"web-002"))),
        ))
        assert device.match(seg, nested) == (None, "nested_boolean")
        sealed = MutableSegment()
        sealed.insert(b"s", [(b"a", b"b")])
        assert device.match(sealed.seal(), nested)[1] == "unpacked_segment"
        allq = ConjunctionQuery((AllQuery(),))
        assert device.match(seg, allq) == (None, "trivial_query")
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "0")
        small = ConjunctionQuery((TermQuery(b"dc", b"dc1"),
                                  TermQuery(b"dc", b"dc2")))
        assert device.match(seg, small) == (None, "small_work")

    def test_duplicate_series_across_segments(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        a = packed.build(_documents(2500))
        b = packed.build(_documents(2500))  # same series ids: all dupes
        q = DisjunctionQuery((TermQuery(b"dc", b"dc1"),
                              TermQuery(b"dc", b"dc2")))
        docs = search([a, b], q)
        sids = [d.series_id for d in docs]
        assert len(sids) == len(set(sids)) == 1000
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "0")
        host_docs = search([a, b], q)
        assert [d.series_id for d in host_docs] == sids

    def test_limit_early_exit(self, seg, monkeypatch):
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        q = DisjunctionQuery((FieldQuery(b"host"), TermQuery(b"dc", b"dc0")))
        docs = search([seg], q, limit=7)
        assert len(docs) == 7
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "0")
        assert [d.series_id for d in search([seg], q, limit=7)] == \
            [d.series_id for d in docs]


class TestExplainIndexBlock:
    """Satellite: the ?explain=analyze `index` block — segments visited,
    device vs counted-and-explained fallback, term scan/prefilter split,
    postings rows intersected."""

    def test_device_and_fallback_accounting(self, monkeypatch):
        import jax  # noqa: F401

        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        pk = packed.build(_documents(3000))
        m = MutableSegment()
        for d in _documents(200, base=90000):
            m.insert(d.series_id, d.fields)
        legacy = m.seal()
        q = ConjunctionQuery((RegexpQuery(b"host", "web-00.*"),
                              TermQuery(b"dc", b"dc1")))
        with querystats.collect() as st:
            search([pk, legacy], q)
        blk = st.index_block()
        assert blk["segments"] == 2
        assert blk["device_segments"] == 1
        assert blk["fallback"] == {"unpacked_segment": 1}
        assert blk["terms_scanned"] > 0
        # literal prefix web-00 excludes the web-01x..web-04x vocab tail
        assert blk["terms_prefiltered"] > 0
        assert blk["postings_rows"] > 0

    def test_envelope_round_trip(self, monkeypatch):
        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        pk = packed.build(_documents(3000))
        q = ConjunctionQuery((TermQuery(b"host", b"web-001"),
                              TermQuery(b"dc", b"dc1")))
        with querystats.collect() as node_side:
            search([pk], q)
        env = querystats.storage_counters(node_side)
        assert "index" in env
        st = querystats.start("coordinator")
        try:
            querystats.merge_storage(env)
            assert st.index_block() == node_side.index_block()
            assert "index" in st.to_dict()
        finally:
            querystats.finish(st)

    def test_explain_node_attribution(self, monkeypatch):
        from m3_tpu.query import explain

        monkeypatch.setenv("M3_TPU_DEVICE_OPS", "1")
        monkeypatch.setenv("M3_TPU_QUERY_SHARD", "0")
        pk = packed.build(_documents(3000))
        q = ConjunctionQuery((RegexpQuery(b"host", "web-00.*"),
                              TermQuery(b"dc", b"dc1")))
        with querystats.collect(), explain.collect(analyze=True) as col:
            with col.node(object()) as entry:
                search([pk], q)
            with col.node(object()) as other:
                pass
        idx = entry["index"]
        assert idx["segments"] == 1 and idx["device_segments"] == 1
        assert idx["postings_rows"] > 0
        # the walk is attributed to the node that ran it, not siblings
        assert "index" not in other

    def test_no_block_outside_index_queries(self):
        st = querystats.QueryStats()
        assert "index" not in st.to_dict()
        assert "index" not in querystats.storage_counters(st)
