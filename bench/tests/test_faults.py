"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of a cell (cut to a CPU size, past the look
for a chip) with one fault planted in the program, and expects
``correct: false``: a solve or a step that returns its state unchanged,
half of the batch left out, an answer altered where it is produced. The
cells run on one chip, so no exchange between chips can be left out.
"""
import numpy as np
import pytest

import repro
from repro.serving.server import GraphServer
from small import CELLS, SHORTEST_PATH, run_small

ANALYTICS = ["plc-pagerank", "grid-pagerank", "plc-sssp-small"]


def window(cell: str) -> float:
    """Seconds a test window lasts: long enough for a solve to complete in
    it. An interpreted push solve takes about a second on a CPU."""
    return 3.0 if cell in SHORTEST_PATH else 1.0


def broken_solve(monkeypatch, fault):
    real = repro.solve

    def solve(algo, *a, **kw):
        res = real(algo, *a, **kw)
        x = np.array(res.x, copy=True)
        if fault == "unchanged":
            x = np.asarray(algo.x0, x.dtype).reshape(x.shape)  # never swept
        elif fault == "half":
            x[len(x) // 2:] = algo.x0.reshape(x.shape)[len(x) // 2:]
        elif fault == "altered":
            x[len(x) // 3] *= 1.001
        res.x = x
        return res

    monkeypatch.setattr(repro, "solve", solve)


def broken_server(monkeypatch, fault):
    real = GraphServer._resolve
    count = [0]

    def _resolve(self, fam, j, t, converged):
        q = fam.queries[j]
        real(self, fam, j, t, converged)
        count[0] += 1
        x = np.array(t.result, copy=True)
        if fault == "unchanged":
            x = q.x0[:, 0].copy()
        elif fault == "half" and count[0] % 2:
            x = q.x0[:, 0].copy()
        elif fault == "altered":
            x[int(np.argmax(x))] += 1e-4
        t.result = x

    monkeypatch.setattr(GraphServer, "_resolve", _resolve)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", ANALYTICS)
def test_analytics_fault_is_not_correct(monkeypatch, cell, fault):
    broken_solve(monkeypatch, fault)
    out = run_small(cell, seconds=window(cell))
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    broken_server(monkeypatch, fault)
    out = run_small("grid-ppr-serve", seconds=2.0, rate_qps=30.0)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", CELLS + list(SHORTEST_PATH))
def test_sound_run_is_correct(cell):
    out = run_small(cell, seconds=window(cell))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
