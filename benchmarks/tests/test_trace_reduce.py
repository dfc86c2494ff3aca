"""trace_reduce.py on a synthetic trace with a known answer and on a small
trace recorded on the v5e (tests/data/, extracted form: the 0.43 s between
the launcher's two marks in one traced run).

    python -m pytest benchmarks/tests -q
"""

import gzip
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace_reduce as tr  # noqa: E402

MS = 1_000_000

SYNTHETIC = {"devices": [{"plane": "/device:TPU:0", "lines": {
    tr.MODULES_LINE: [["jit_run(1)", 0, 10 * MS], ["jit_run(1)", 5 * MS, 10 * MS],
                      ["jit_other(2)", 30 * MS, 5 * MS],
                      ["jit_run(1)", 100 * MS, 20 * MS]],
}, "op_seconds": {"fusion.1": 0.030, "fusion.2": 0.010, "copy.3": 0.005}}]}


class TestSynthetic:
    def test_busy_union_counts_overlap_once(self):
        # [0,15) + [30,35) + [100,120) = 40 ms
        assert tr.union_ns(SYNTHETIC["devices"][0]["lines"][tr.MODULES_LINE]) \
            == 40 * MS
        assert tr.busy_s(SYNTHETIC) == pytest.approx(0.040)

    def test_idle_share(self):
        assert tr.idle_pct(SYNTHETIC, 0.2) == pytest.approx(80.0)
        assert tr.idle_pct({"devices": []}, 0.2) is None

    def test_time_by_name_and_program(self):
        by = tr.time_by_name(SYNTHETIC, tr.OPS_LINE)
        assert by["fusion.1"] == pytest.approx(0.030)
        assert tr.program_s(SYNTHETIC, r"^jit_run\b") == (
            pytest.approx(0.040), 3)
        top = tr.top_ops(SYNTHETIC, 4)
        assert top[0] == ["program jit_run", pytest.approx(0.040)]
        assert top[2] == ["op fusion.1", pytest.approx(0.030)]

    def test_idle_gaps_are_named_by_the_program_that_ends_them(self):
        gaps = dict(tr.idle_gaps(SYNTHETIC))
        assert gaps["before jit_other"] == pytest.approx(0.015)
        assert gaps["before jit_run"] == pytest.approx(0.065)

    def test_events_are_clipped_to_the_time_between_the_marks(self):
        mods = [["jit_run(1)", 0, 10 * MS],              # before the mark
                ["jit_bench_mark(9)", 12 * MS, 1 * MS],
                ["jit_run(1)", 12 * MS, 4 * MS],         # straddles it
                ["jit__decode_jit(3)", 20 * MS, 10 * MS],
                ["jit_run(1)", 48 * MS, 6 * MS],         # straddles the end
                ["jit_bench_mark(9)", 50 * MS, 1 * MS],
                ["jit_run(1)", 60 * MS, 5 * MS]]         # after it
        iv = tr.mark_interval(mods)
        assert iv == [13 * MS, 50 * MS]
        kept = list(tr.clip(mods, iv))
        assert kept == [["jit_run(1)", 13 * MS, 3 * MS],
                        ["jit__decode_jit(3)", 20 * MS, 10 * MS],
                        ["jit_run(1)", 48 * MS, 2 * MS]]
        trace = {"devices": [{"plane": "/device:TPU:0",
                              "lines": {tr.MODULES_LINE: kept}}],
                 "interval_ns": iv}
        assert tr.interval_s(trace) == pytest.approx(0.037)
        assert tr.idle_pct(trace, tr.interval_s(trace)) == pytest.approx(
            100 * (1 - 15 / 37))
        # fewer than two marks: nothing to clip to, every event stands
        assert tr.mark_interval(mods[:3]) is None
        assert list(tr.clip(mods, None)) == mods
        assert tr.interval_s({"devices": [], "interval_ns": None}) is None

    def test_roofline_arithmetic_and_the_sanity_limit(self):
        # 8.19 MB at 819 GB/s is 10 us; in 1 ms of device time: 1%
        assert tr.roofline_pct(8.19e6, 1e-3, 819e9) == pytest.approx(1.0)
        assert tr.roofline_pct(8.19e6, 0.0, 819e9) is None
        assert tr.roofline_pct(0, 1e-3, 819e9) is None
        with pytest.raises(ValueError):
            tr.roofline_pct(8.19e6, 9e-6, 819e9)   # 111%: never clipped


RECORDED = os.path.join(BENCH, "tests", "data", "dash_v5e_trace.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace committed")
class TestRecorded:
    @pytest.fixture(scope="class")
    def doc(self):
        with gzip.open(RECORDED) as f:
            return json.load(f)

    def test_reductions_repeat_what_was_recorded(self, doc):
        trace, want = doc["trace"], doc["expect"]
        assert tr.busy_s(trace) == pytest.approx(want["busy_s"])
        assert tr.idle_pct(trace, want["window_s"]) == pytest.approx(
            want["idle_pct"])
        secs, n = tr.program_s(trace, want["program"])
        assert n == want["program_launches"]
        assert secs == pytest.approx(want["program_s"])
        assert 0 < tr.busy_s(trace) < want["window_s"]
        assert tr.interval_s(trace) == pytest.approx(want["window_s"])
        # every event lies between the marks
        a, b = trace["interval_ns"]
        for dev in trace["devices"]:
            for events in dev["lines"].values():
                assert all(a <= s and s + d <= b for _, s, d in events)

    def test_rooflines_of_the_recorded_run(self, doc):
        trace, want = doc["trace"], doc["expect"]
        for prog, n, secs, nbytes, pct in (
                ("program", "program_launches", "program_s",
                 "plan_least_bytes", "plan_roofline"),
                ("decoder", "decoder_launches", "decoder_s",
                 "decode_least_bytes", "decode_roofline")):
            got_s, got_n = tr.program_s(trace, want[prog])
            assert (got_n, got_s) == (want[n], pytest.approx(want[secs]))
            assert tr.roofline_pct(want[nbytes], got_s, 819e9) == \
                pytest.approx(want[pct])
        assert 1.0 < want["plan_roofline"] < 3.0
        assert 0 < want["decode_roofline"] < 0.01

    def test_busy_is_the_sum_of_programs_that_do_not_overlap(self, doc):
        # one TPU core runs one program at a time: counted by hand, the
        # union is the plain sum, and the operations lie inside programs
        mods = sorted(doc["trace"]["devices"][0]["lines"][tr.MODULES_LINE],
                      key=lambda e: e[1])
        for (_, s0, d0), (_, s1, _) in zip(mods, mods[1:]):
            assert s0 + d0 <= s1
        assert tr.busy_s(doc["trace"]) == pytest.approx(
            sum(d for _, _, d in mods) / 1e9)
        ops = tr.time_by_name(doc["trace"], tr.OPS_LINE)
        assert ops and sum(ops.values()) > 0
        assert any(name.startswith("program jit__decode_jit")
                   for name, _ in tr.top_ops(doc["trace"]))
