"""Median over the window's requests of the benchmark's own span around
the cold start in the CU body: ``params_from_input`` (the checkpoint DU
read and decoded through Pilot-Data) and the device put of the weights."""

import statistics

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Pilot-Data data path", "tokens_per_s"


def value(run):
    spans = [r.spans["coldstart"] for r in run.requests if r.ok and "coldstart" in r.spans]
    return statistics.median(spans) if spans else None
