"""Median over the window's CUs of the time their weights' ``du.load``
spent decoding leaves: the summed ``ckpt.decode`` spans under it."""

import statistics

import program_spans

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Pilot-Data data path", "tokens_per_s"


def value(run):
    recs = program_spans.records(run)
    per_cu = program_spans.per_cu_under(recs or [], "du.load", "ckpt.decode")
    return statistics.median(per_cu.values()) if per_cu else None
