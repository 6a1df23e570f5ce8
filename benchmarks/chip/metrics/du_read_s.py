"""Median over the window's CUs of the time their weights' ``du.load``
spent reading the DU's files: the summed ``du.read`` spans under it (the
chunks of each file fetched from the Pilot-Data and joined)."""

import statistics

import program_spans

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Pilot-Data data path", "tokens_per_s"


def value(run):
    recs = program_spans.records(run)
    per_cu = program_spans.per_cu_under(recs or [], "du.load", "du.read")
    return statistics.median(per_cu.values()) if per_cu else None
