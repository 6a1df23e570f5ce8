"""Mean over all requests of the window of the time from a request being
handed to the system to its first token on the host: for a chat turn,
from its tokens handed to ``engine.prefill``.  The mean, not the median:
where every conversation has two turns, half the turns open one and pay
the new engine's retrace, and a median sits on the edge between the two."""

import statistics

UNIT, BETTER = "s", "lower"


def value(run):
    waits = [r.t_first - r.t_submit for r in run.requests if r.ok and r.t_first is not None]
    return statistics.fmean(waits) if waits else None
