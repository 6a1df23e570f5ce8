"""Device time of one run of the ``serve_step`` executable: the device
durations of its events in the trace over their count."""

UNIT, BETTER, LAYER, MOVES = "ms", "lower", "model step", "tokens_per_s"
EXECUTABLE = "jit_serve_step"


def value(run):
    if run.trace is None or EXECUTABLE not in run.trace.executables:
        return None
    secs, n = run.trace.executables[EXECUTABLE]
    return 1e3 * secs / n
