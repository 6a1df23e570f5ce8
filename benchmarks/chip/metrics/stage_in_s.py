"""Median over the window's CUs of their stage-in: the program's
``cu.stage_in`` span, from the agent's claim of the CU to its inputs
being reachable from the pilot (a link, for a co-located Pilot-Data)."""

import statistics

import program_spans

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Pilot-Data data path", "cu_p90_s"


def value(run):
    recs = program_spans.records(run)
    per_cu = program_spans.seconds_by_cu(recs or [], "cu.stage_in")
    return statistics.median(per_cu.values()) if per_cu else None
