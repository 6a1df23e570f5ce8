"""The per-layer metrics read from the program's own records
(``repro.tracing``): their arithmetic on synthetic records, the window
they read, None where the recorder dropped records, found nothing or is
not there, and every one of them in a traced CPU run of its cell."""

import sys
import time
from types import SimpleNamespace

import pytest
from conftest import CHIP, small_cell

import harness
from repro import tracing
from repro.tracing import Record

NAMES = ["stage_in_s", "du_read_s", "du_decode_s", "ckpt_save_s", "prefill_jit_ms"]
RUN = SimpleNamespace(t0=100.0, t_end=200.0, setup_s=50.0)


def metric(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py")


def rec(name, start, end, id_, parent=None, cu=None):
    return Record(name, start, end, id_, parent, cu, {})


#: two CUs in the window, one before it; two saves, one of an earlier run
#: in the same process; two prefills, one of which retraced
RECORDS = [
    rec("ckpt.save", 10.0, 20.0, 1),
    rec("ckpt.save", 60.0, 80.0, 2),
    rec("cu.stage_in", 90.0, 95.0, 3, cu="c0"),
    rec("du.load", 95.0, 99.0, 4, cu="c0"),
    rec("du.read", 95.0, 99.0, 5, 4, cu="c0"),
    rec("cu.stage_in", 101.0, 101.5, 10, cu="a"),
    rec("du.load", 102.0, 110.0, 11, cu="a"),
    rec("du.read", 102.0, 104.0, 12, 11, cu="a"),
    rec("du.read", 104.0, 106.0, 13, 11, cu="a"),
    rec("ckpt.decode", 106.0, 107.0, 14, 11, cu="a"),
    rec("cu.stage_in", 120.0, 121.0, 20, cu="b"),
    rec("du.load", 121.0, 131.0, 21, cu="b"),
    rec("leaf", 121.0, 131.0, 22, 21, cu="b"),  # a level between
    rec("du.read", 121.0, 127.0, 23, 22, cu="b"),
    rec("ckpt.decode", 127.0, 130.0, 24, 22, cu="b"),
    rec("engine.prefill", 150.0, 151.0, 30),
    rec("jit.trace", 150.0, 150.2, 31, 30),
    rec("jit.compile", 150.2, 150.5, 32, 30),
    rec("jit.cache_load", 150.3, 150.4, 33, 30),  # inside jit.compile
    rec("engine.prefill", 160.0, 160.1, 34),
    rec("jit.trace", 210.0, 211.0, 35, 36),  # after the window
    rec("engine.prefill", 210.0, 212.0, 36),
]
EXPECTED = {
    "stage_in_s": 0.75,
    "du_read_s": 5.0,
    "du_decode_s": 2.0,
    "ckpt_save_s": 20.0,
    "prefill_jit_ms": 250.0,
}


@pytest.fixture
def recorded(monkeypatch):
    def use(records, dropped=0):
        monkeypatch.setattr(tracing, "spans", lambda: list(records))
        monkeypatch.setattr(tracing, "counters", lambda: {"tracing.dropped": dropped})

    return use


@pytest.mark.parametrize("name", NAMES)
def test_metric_on_synthetic_records(name, recorded):
    recorded(RECORDS)
    assert metric(name).value(RUN) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NAMES)
def test_none_when_records_were_dropped(name, recorded):
    recorded(RECORDS, dropped=1)
    assert metric(name).value(RUN) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_when_nothing_is_found(name, recorded):
    recorded([r for r in RECORDS if not RUN.t0 - RUN.setup_s <= r.start <= RUN.t_end])
    assert metric(name).value(RUN) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_without_the_recorder(name, monkeypatch):
    """A checkout whose program has no ``repro.tracing``."""
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert metric(name).value(RUN) is None


@pytest.mark.parametrize(
    "cell,host_metrics",
    [
        ("mamba2-370m.fleet", {"coldstart_s", "queue_s", "stage_in_s", "du_read_s",
                               "du_decode_s", "ckpt_save_s"}),
        ("h2o-danube-1.8b.chat", {"ckpt_save_s", "prefill_jit_ms"}),
    ],
)
def test_traced_cpu_run_reports_them(cell, host_metrics, counter):
    """Traced, a CPU run has no device plane: the host-side per-layer
    metrics are there, the program's own among them."""
    out = harness.run_cell(small_cell(cell), seed=2**31 + 4242, seconds=2.0, traced=True,
                           t_proc0=time.monotonic(), counter=counter)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == host_metrics
    for name in host_metrics:
        assert out["metrics"][name]["value"] >= 0
    assert out["metrics"]["ckpt_save_s"]["value"] > 0
