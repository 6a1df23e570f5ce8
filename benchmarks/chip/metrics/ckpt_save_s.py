"""The set-up's save of the weights as a checkpoint DU: the program's
last ``ckpt.save`` span of the set-up (the leaves' copy to the host and
encoding, the DU's chunk manifest, its ingest into the Pilot-Data and its
seal)."""

import program_spans

UNIT, BETTER, LAYER, MOVES = "s", "lower", "Pilot-Data data path", "setup_s"


def value(run):
    saves = [r for r in program_spans.records(run, setup=True) or [] if r.name == "ckpt.save"]
    return saves[-1].end - saves[-1].start if saves else None
