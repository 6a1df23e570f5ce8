"""Median over the window's requests of the time from submission to the
start of stage-in: ``CUTimings.stage_start - submitted``, on the host
clock."""

import statistics

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Session, scheduler and agent", "cu_p90_s"


def value(run):
    waits = [r.spans["queue"] for r in run.requests if r.ok and "queue" in r.spans]
    return statistics.median(waits) if waits else None
