#!/usr/bin/env python3
"""harness/serve.py with the timed path broken underneath, for
test_harness_cpu.py: one value of every seventh query answer is altered
where it is rendered."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))


def plant() -> None:
    from m3_tpu.query import api

    calls = {"n": 0}
    render = api.CoordinatorAPI._render

    def faulty_render(self, result, eval_ts, *a, **k):
        calls["n"] += 1
        values = getattr(result, "values", None)
        if calls["n"] % 7 == 0 and values is not None and values.size:
            values = values.copy()
            values.reshape(-1)[0] *= 1.0 + 1e-6
            result.values = values
        return render(self, result, eval_ts, *a, **k)

    api.CoordinatorAPI._render = faulty_render


if __name__ == "__main__":
    plant()
    import serve

    serve.main()
