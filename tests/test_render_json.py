"""The JSON answer of a PromQL query (query/api.py `_render_json`).

The renderer writes Prometheus' envelope a row at a time: one NaN mask and
one `tolist` an answer, one repr a value, one join a row. Its bytes are
held to those of the per-value renderer it replaced, which built the
answer's lists value by value and handed them to ``json.dumps``; that
renderer is kept below, verbatim, as the oracle. The last test drives the
query routes through ``CoordinatorAPI.handle`` and holds them to one
`render` stage a request.
"""

import json
import types

import numpy as np
import pytest

from m3_tpu.query.api import CoordinatorAPI
from m3_tpu.query.engine import Scalar, StringValue, Vector
from m3_tpu.query.windows import NS
from m3_tpu.storage.database import Database
from m3_tpu.storage.options import DatabaseOptions
from m3_tpu.utils import trace
from m3_tpu.utils.instrument import default_registry


# ---- the oracle: the per-value renderer, as it was --------------------------

def _fmt_value(v: float) -> str:
    if np.isnan(v):
        return "NaN"
    if np.isposinf(v):
        return "+Inf"
    if np.isneginf(v):
        return "-Inf"
    return repr(float(v))


def _oracle_render_json(result, eval_ts, matrix: bool, engine, explain_doc):
    ts_sec = eval_ts.astype(np.float64) / NS
    if isinstance(result, Scalar):
        if matrix:
            data = {
                "resultType": "matrix",
                "result": [
                    {
                        "metric": {},
                        "values": [
                            [t, _fmt_value(v)]
                            for t, v in zip(ts_sec, result.values)
                            if not np.isnan(v)
                        ],
                    }
                ],
            }
        else:
            data = {
                "resultType": "scalar",
                "result": [ts_sec[0], _fmt_value(result.values[0])],
            }
    elif isinstance(result, Vector):
        if matrix:
            out = []
            for i, lb in enumerate(result.labels):
                values = [
                    [t, _fmt_value(v)]
                    for t, v in zip(ts_sec, result.values[i])
                    if not np.isnan(v)
                ]
                if values:
                    out.append(
                        {
                            "metric": {
                                k.decode(): v.decode() for k, v in lb.items()
                            },
                            "values": values,
                        }
                    )
            data = {"resultType": "matrix", "result": out}
        else:
            out = []
            for i, lb in enumerate(result.labels):
                v = result.values[i, 0]
                if not np.isnan(v):
                    out.append(
                        {
                            "metric": {
                                k.decode(): val.decode() for k, val in lb.items()
                            },
                            "value": [ts_sec[0], _fmt_value(v)],
                        }
                    )
            data = {"resultType": "vector", "result": out}
    else:
        data = {"resultType": "string", "result": [ts_sec[0], result.value]}
    doc = {"status": "success", "data": data}
    warns = getattr(engine, "last_warnings", None)
    if warns:
        doc["warnings"] = [str(w) for w in warns]
    stats = getattr(engine, "last_stats", None)
    if stats is not None:
        doc["stats"] = stats.to_dict()
    if explain_doc is not None:
        doc["explain"] = explain_doc
    return json.dumps(doc).encode()


# ---- seeded answers ---------------------------------------------------------

START_NS = 1_600_000_000_000_000_000
# values whose repr is the hard part: signed zero, the smallest subnormal,
# exponent forms, integral floats, the infinities
SPECIAL = [0.0, -0.0, 5e-324, -5e-324, 1e16, 1.5e-05, 3.0, -7.0, 1e22,
           123456789.0, 2.5e-308, 1.7976931348623157e308, 0.1, 1 / 3,
           np.inf, -np.inf]
UGLY_LABELS = ['say "hi"', "back\\slash", "tab\tnew\nline\x01\x1f",
               "café 日本 \U0001f600", "", "/", "<&>"]


def _grid(rng, n, odd=False):
    step = int(rng.choice([1, 10, 15, 60, 3600])) * NS
    start = START_NS + (int(rng.integers(1, NS)) if odd else 0)
    return start + step * np.arange(n, dtype=np.int64)


def _labels(rng, s, ugly=False):
    out = []
    for i in range(s):
        lb = {b"__name__": b"cpu_usage_user", b"hostname": b"host_%d" % i,
              b"region": b"us-east-%d" % int(rng.integers(1, 3))}
        if ugly:
            k = UGLY_LABELS[i % len(UGLY_LABELS)]
            lb[("l_" + k).encode()] = k.encode()
        out.append(lb)
    return out


def _values(rng, s, n, nan=0.0, all_nan_rows=0, special=False, dtype=None):
    v = rng.normal(50.0, 30.0, (s, n)) * 10.0 ** rng.integers(-6, 7, (s, n))
    if nan:
        v[rng.random((s, n)) < nan] = np.nan
    if all_nan_rows:
        v[rng.choice(s, all_nan_rows, replace=False)] = np.nan
    if special:
        at = rng.random((s, n)) < 0.5
        v[at] = rng.choice(np.array(SPECIAL), int(at.sum()))
    return v if dtype is None else v.astype(dtype)


class _Stats:
    def __init__(self, doc):
        self.doc = doc

    def to_dict(self):
        return self.doc


def _engine(envelope):
    if not envelope:
        return types.SimpleNamespace(last_warnings=None, last_stats=None)
    return types.SimpleNamespace(
        last_warnings=["partial result: 1 of 4 shards — timed out",
                       ValueError("limit \"x\" hit")],
        last_stats=_Stats({"series": 3, "stages_ms": {"render": 0.5,
                                                      "eval": 1.25},
                           "cache": {"hit": 2, "miss": None}, "rate": 0.1}))


CASES = {
    "long_rows": dict(s=5, n=720, nan=0.01),
    "wide": dict(s=2000, n=1),
    "one_step_grid": dict(s=3, n=1),
    "nan_rows": dict(s=40, n=60, nan=0.3, all_nan_rows=7),
    "all_nan": dict(s=6, n=12, nan=1.0),
    "specials": dict(s=30, n=25, nan=0.1, special=True),
    "odd_timestamps": dict(s=8, n=60, odd=True, special=True),
    "float32": dict(s=10, n=40, nan=0.05, dtype=np.float32),
    "empty": dict(s=0, n=12),
    "no_steps": dict(s=4, n=0),
    "ugly_labels": dict(s=21, n=8, ugly=True, nan=0.2),
    "no_labels": dict(s=3, n=8, bare=True),
    "scalar_matrix": dict(kind="scalar", n=50, nan=0.2, special=True),
    "scalar_matrix_all_nan": dict(kind="scalar", n=5, nan=1.0),
    "scalar_instant": dict(kind="scalar", n=1, matrix=False, special=True),
    "scalar_instant_nan": dict(kind="scalar", n=1, matrix=False, nan=1.0),
    "vector_instant": dict(s=300, n=1, matrix=False, nan=0.3, special=True,
                           ugly=True),
    "vector_instant_empty": dict(s=0, n=1, matrix=False),
    "string": dict(kind="string", n=1, matrix=False),
    "envelope": dict(s=7, n=30, nan=0.1, envelope=True, ugly=True),
    "envelope_instant": dict(s=7, n=1, matrix=False, envelope=True),
}


@pytest.mark.parametrize("seed", [0, 1, 2_718_281_828])
@pytest.mark.parametrize("case", sorted(CASES))
def test_render_is_byte_for_byte_the_per_value_renderer(case, seed):
    c = dict(CASES[case])
    rng = np.random.default_rng([seed, sorted(CASES).index(case)])
    kind = c.pop("kind", "vector")
    matrix = c.pop("matrix", True)
    envelope = c.pop("envelope", False)
    n = c.pop("n")
    eval_ts = _grid(rng, n, odd=c.pop("odd", False))
    if kind == "string":
        result = StringValue('a "quoted" \\ café\n')
    elif kind == "scalar":
        result = Scalar(_values(rng, 1, n, **c)[0])
    else:
        s, ugly, bare = c.pop("s"), c.pop("ugly", False), c.pop("bare", False)
        labels = [{} for _ in range(s)] if bare else _labels(rng, s, ugly)
        result = Vector(labels, _values(rng, s, n, **c))
    engine = _engine(envelope)
    explain = ({"plan": {"op": "max_over_time", "legs": [1, 2.5, None]},
                "note": "über"} if envelope else None)
    want = _oracle_render_json(result, eval_ts, matrix, engine, explain)
    got = CoordinatorAPI._render_json(types.SimpleNamespace(engine=None),
                                      result, eval_ts, matrix, engine,
                                      explain)
    assert got == want
    assert json.loads(got) == json.loads(want)


# ---- the query routes: one `render` stage a request -------------------------

def _render_observations(route):
    _c, _g, _t, hists = default_registry().snapshot()
    key = ("query.stage.seconds",
           (("route", route), ("stage", trace.STAGE_RENDER)))
    return hists[key][3] if key in hists else 0


@pytest.mark.parametrize("route", ["query_range", "query"])
def test_query_routes_render_once_a_request(tmp_path, route):
    db = Database(str(tmp_path / "db"), DatabaseOptions(n_shards=2))
    db.create_namespace("default")
    db.open(START_NS)
    try:
        api = CoordinatorAPI(db)
        t0 = START_NS / NS
        for host in ("a", "bé"):
            for i in range(6):
                status, *_ = api.handle(
                    "POST", "/api/v1/json/write", {}, json.dumps({
                        "metric": "cpu", "tags": {"host": host},
                        "timestamp": t0 + 60 * i,
                        "value": [1.5, -0.0, 1e16, 2.5e-05, 7.0, 3.25][i],
                    }).encode())
                assert status == 200
        if route == "query_range":
            q = {"query": ["cpu"], "start": [str(t0)],
                 "end": [str(t0 + 300)], "step": ["60"]}
            result, eval_ts = api.engine.query_range(
                "cpu", START_NS, START_NS + 300 * NS, 60 * NS)
        else:
            q = {"query": ["cpu"], "time": [str(t0 + 300)]}
            result, eval_ts = api.engine.query_instant(
                "cpu", START_NS + 300 * NS)
        want = json.loads(_oracle_render_json(
            result, eval_ts, route == "query_range", _engine(False), None))
        before = _render_observations(route)
        for _ in range(3):
            status, ctype, payload, _h = api.handle(
                "GET", "/api/v1/" + route, q, b"")
            assert (status, ctype) == (200, "application/json")
            got = json.loads(payload)
            assert got["status"] == "success"
            assert got["data"] == want["data"]
            assert len(got["data"]["result"]) == 2
        assert _render_observations(route) - before == 3
    finally:
        db.close()
