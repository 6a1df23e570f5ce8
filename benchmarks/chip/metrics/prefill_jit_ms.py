"""Mean over the window's ``engine.prefill`` spans of the host time JAX
spent under them tracing, lowering and compiling the step (``jit.trace``,
``jit.lower`` and ``jit.compile``; a persistent-cache load is part of
``jit.compile``).  A new engine's first prefill retraces its step."""

import statistics

import program_spans

UNIT, BETTER, LAYER, MOVES = "ms", "lower", "model step", "ttft_mean_s"
JIT = {"jit.trace", "jit.lower", "jit.compile"}


def value(run):
    recs = program_spans.records(run)
    totals = program_spans.seconds_under(recs or [], "engine.prefill", JIT)
    return 1e3 * statistics.fmean(totals.values()) if totals else None
