"""trace.py: the interval arithmetic by hand, and the reduction of a small
trace recorded on a TPU v5e chip by ``record_trace.py``."""

from pathlib import Path

import record_trace

import trace as trace_mod

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_and_clip():
    merged = trace_mod._union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert merged == [(0, 3), (5, 9), (10, 11)]
    assert trace_mod._clip(merged, 2, 10) == [(2, 3), (5, 9)]


def test_gap_labels():
    spans = [(0, 100, "bench:window"), (0, 40, "bench:prefill"), (30, 60, "bench:sleep")]
    gaps = [(10, 20), (33, 37), (70, 80)]
    out = trace_mod._gap_labels(spans, gaps)
    assert out == {"prefill": 10e-9, "prefill+sleep": 4e-9, "other": 10e-9}


def test_recorded_trace():
    s = trace_mod.summarize(str(DATA))
    assert s.chips == 1
    secs, runs = s.executables["jit_serve_step"]
    assert runs == record_trace.STEPS
    assert 0 < s.busy_s < s.window_s
    # the step's module spans hold its ops and the short gaps between them
    assert s.busy_s * 0.9 < secs < s.busy_s * 1.1
    idle = dict(s.idle_gaps)
    assert idle["sleep"] >= record_trace.SLEEP_S
    if len(s.idle_gaps) < 10:  # every gap is listed: busy and idle fill the window
        assert abs(s.busy_s + sum(idle.values()) - s.window_s) < 1e-6
