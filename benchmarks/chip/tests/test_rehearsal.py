"""A short window of each traffic mix through the harness, on the CPU at
small sizes: the records, the metric arithmetic and the comparison."""

import time

import pytest
from conftest import small_cell

import harness

CELLS = ["h2o-danube-1.8b.chat", "mamba2-370m.fleet"]


@pytest.fixture(scope="module", params=CELLS)
def run(request, counter):
    cell = small_cell(request.param)
    out = harness.run_cell(cell, seed=2**31 + 12345, seconds=3.0, traced=False, t_proc0=time.monotonic(), counter=counter)
    return cell, out


def test_result_line(run):
    cell, out = run
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["weights_mismatch"]["value"] == 0


def test_traced_host_metrics(counter):
    """Traced, a CPU run has no device plane: the device metrics are left
    out, and the host-side per-layer metrics are there."""
    cell = small_cell("mamba2-370m.fleet")
    out = harness.run_cell(cell, seed=2**32 + 99, seconds=2.0, traced=True, t_proc0=time.monotonic(), counter=counter)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"coldstart_s", "queue_s"}
    assert "busy_s" not in out["device"] and "breakdown" not in out
