"""The warm-up's rule of ``promql_closed_loop``, with the loop and the
program's counters played by hand: rounds go on until
``warm_clean_rounds`` of them in a row compiled nothing, ``warm_rounds``
at the most; a round with a compile starts the count again; a cell file
that does not say ``warm_clean_rounds`` stops at its first clean round, as
every cell did before the parameter was there.

    python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from run import load_traffic  # noqa: E402

KIND = load_traffic("promql_closed_loop")
GLOBALS = KIND.__init__.__globals__


class PlayedRun:
    """What `warm_rounds` sees of a run: the cell's parameters and a
    `/metrics` whose `[miss]` counters rise by `script[i]` in round i."""

    port = 0

    def __init__(self, params: dict, script: list[int]):
        self.params, self.script = params, script
        self.scrapes = 0
        self.said: list[str] = []

    def say(self, line: str) -> None:
        self.said.append(line)

    def metrics(self) -> dict:
        # two scrapes a round: before it and after it
        done = self.script[:self.scrapes // 2 + self.scrapes % 2]
        self.scrapes += 1
        return {"jit_m3tsz_decode[miss]": float(sum(done)),
                "jit_m3tsz_decode[hit]": 100.0 * self.scrapes,
                "coordinator_blocks_flushed": 8.0}


class PlayedLoop:
    """A ClosedLoop that sends nothing and answers three requests."""

    def __init__(self, port, workers, make, keep_answers):
        self.records = [(i, "t", 0.0, 0.1, True, b"") for i in range(3)]

    def start(self, seconds):
        pass

    def join(self):
        pass


def _rounds(params: dict, script: list[int]) -> tuple[list[int], str]:
    traffic = object.__new__(KIND)
    traffic.run = PlayedRun({"warm_s": 0.0, **params}, script)
    traffic.workers = 2
    traffic.warm_requests = None
    old = GLOBALS["ClosedLoop"]
    GLOBALS["ClosedLoop"] = PlayedLoop
    try:
        return traffic.warm_rounds(), traffic.run.said[-1]
    finally:
        GLOBALS["ClosedLoop"] = old


@pytest.mark.parametrize("params,script,want", [
    # two clean rounds in a row end it
    ({"warm_clean_rounds": 2, "warm_rounds": 10}, [3, 0, 0, 5], [3, 0, 0]),
    # a round with a compile starts the count again
    ({"warm_clean_rounds": 2, "warm_rounds": 10}, [2, 0, 1, 0, 0, 9],
     [2, 0, 1, 0, 0]),
    ({"warm_clean_rounds": 2, "warm_rounds": 10}, [0, 0, 7], [0, 0]),
    # the limit ends it where the rounds never come clean
    ({"warm_clean_rounds": 2, "warm_rounds": 4}, [1, 0, 1, 0, 0, 0],
     [1, 0, 1, 0]),
    ({"warm_clean_rounds": 3, "warm_rounds": 5}, [1] * 9, [1] * 5),
    # a cell file without the parameter: the first clean round, as before
    ({"warm_rounds": 6}, [4, 1, 0, 2, 0], [4, 1, 0]),
    ({"warm_rounds": 6}, [0, 3], [0]),
    ({"warm_rounds": 2}, [1, 1, 0], [1, 1]),
], ids=["two-clean", "miss-resets", "clean-at-once", "limit", "never-clean",
        "old-file", "old-file-clean-at-once", "old-file-limit"])
def test_rounds_until_clean_in_a_row_or_the_limit(params, script, want):
    misses, said = _rounds(params, script)
    assert misses == want
    # the log: every round's requests and compiles, and what ended it
    assert f"{3 * len(want)} queries in {len(want)} rounds" in said
    assert f"compiles a round {want}" in said
    clean = params.get("warm_clean_rounds", 1)
    assert said.endswith(f"ended by {clean} clean in a row"
                         if not any(want[-clean:]) and len(want) >= clean
                         else f"ended by the limit of "
                              f"{params['warm_rounds']} rounds")


@pytest.mark.parametrize("misses,want_clean,done", [
    ([], 1, False), ([0], 1, True), ([2], 1, False), ([0], 2, False),
    ([0, 0], 2, True), ([0, 1, 0], 2, False), ([5, 0, 0], 2, True),
    ([0, 0, 1], 2, False),
])
def test_warm_done(misses, want_clean, done):
    assert GLOBALS["warm_done"](misses, want_clean) is done


def test_the_measured_cell_asks_for_three_clean_rounds_of_twelve():
    import json

    with open(os.path.join(BENCH, "workloads", "tsbs-cpu-2k.dash.json")) as f:
        params = json.load(f)["traffic_params"]
    assert (params["warm_clean_rounds"], params["warm_rounds"]) == (3, 12)
