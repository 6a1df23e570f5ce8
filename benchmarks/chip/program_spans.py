"""The program's own spans and counters (``repro.tracing``), as the
per-layer metrics read them.

A metric reads the records that start in the measured window, or in the
set-up before it.  It reads nothing, and returns None, where the program
has no recorder (a checkout older than it), where the recorder's ring
dropped records, or where no record of the name it asks for is there.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def records(run, setup: bool = False) -> Optional[List]:
    """The records that start in ``[run.t0, run.t_end]``, or with
    ``setup`` in the set-up ``[run.t0 - run.setup_s, run.t0)``; None where
    the program has no recorder or its ring dropped records."""
    try:
        import repro.tracing as tracing
    except ImportError:
        return None
    if tracing.counters().get("tracing.dropped", 0):
        return None
    if setup:
        lo, hi = run.t0 - run.setup_s, run.t0
        return [r for r in tracing.spans() if lo <= r.start < hi]
    return [r for r in tracing.spans() if run.t0 <= r.start <= run.t_end]


def seconds_by_cu(recs: List, name: str) -> Dict[str, float]:
    """Per CU, the summed seconds of its ``name`` records."""
    out: Dict[str, float] = {}
    for r in recs:
        if r.name == name:
            out[r.cu] = out.get(r.cu, 0.0) + (r.end - r.start)
    return out


def seconds_under(recs: List, root: str, leaves) -> Dict[int, float]:
    """For each ``root`` record, by id, the summed seconds of the records
    named in ``leaves`` below it, at any depth."""
    by_id = {r.id: r for r in recs}
    totals = {r.id: 0.0 for r in recs if r.name == root}
    for r in recs:
        if r.name not in leaves:
            continue
        up = r.parent
        while up is not None and up in by_id:
            if up in totals:
                totals[up] += r.end - r.start
                break
            up = by_id[up].parent
    return totals


def per_cu_under(recs: List, root: str, leaf: str) -> Dict[str, float]:
    """Per CU, the summed seconds of ``leaf`` records under its ``root``
    records."""
    roots = {r.id: r.cu for r in recs if r.name == root}
    out: Dict[str, float] = {}
    for rid, secs in seconds_under(recs, root, {leaf}).items():
        out[roots[rid]] = out.get(roots[rid], 0.0) + secs
    return out
