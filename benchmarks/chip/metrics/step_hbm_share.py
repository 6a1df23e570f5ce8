"""The decode step's share of the HBM roofline: the mean over the window's
``jit_serve_step`` runs of the bytes one needs (``counts/<config>.py``, at
each run's ``(batch, live)``) over the bytes the chip could move in the
device time of one run in the trace (device seconds over runs, times
``hbm_bytes_per_s`` of ``peaks.json``).  The bytes a step needs are the
weights once, the live cache and the state, so the share stays at or
below 100% whatever the program copies besides."""

import statistics

UNIT, BETTER, LAYER, MOVES = "%", "higher", "model step", "tokens_per_s"
EXECUTABLE = "jit_serve_step"


def value(run):
    steps = run.steps.get(EXECUTABLE)
    if run.trace is None or EXECUTABLE not in run.trace.executables or not steps:
        return None
    secs, n = run.trace.executables[EXECUTABLE]
    need = statistics.fmean(run.counts.serve_step(run.sizes, b, live)[1] for b, live in steps)
    return 100.0 * need / (secs / n * run.peaks["hbm_bytes_per_s"])
