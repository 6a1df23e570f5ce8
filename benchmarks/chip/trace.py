"""Reduce a JAX profiler trace to device busy and idle time, per-executable
device time, the device operations that took longest, and the idle gaps by
what the benchmark's host spans were doing.

The benchmark marks its own host spans with ``jax.profiler.TraceAnnotation``
under names that start with ``bench:``; ``bench:window`` spans the measured
window.  Device planes are those named ``/device:TPU:<n>``.  On them, the
``XLA Modules`` line has one event per executable run (named like
``jit_serve_step(41)``) and the ``XLA Ops`` line one per operation.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple, Union

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Summary:
    window_s: float  # length of the traced window
    busy_s: float  # union of device op intervals in it, averaged over chips
    chips: int
    #: executable name → (device seconds, runs), summed over chips
    executables: Dict[str, Tuple[float, int]]
    device_ops: List[Tuple[str, float]]  # HLO ops, longest total device time first
    idle_gaps: List[Tuple[str, float]]  # idle seconds by host activity

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def start():
    """Start a profiler session and return it; ``session.stop()`` ends it
    and returns the trace as a serialized XSpace, for :func:`summarize`.

    The Python tracer is off and the host tracer at its first level: the
    benchmark's spans are host TraceMe events of that level, and tracing
    every Python call or runtime event would swell the trace and slow the
    host it measures.  The session is the one ``jax.profiler.start_trace``
    opens, held here so that its end skips ``stop_trace``'s export to
    TensorBoard's trace events, which takes minutes for a trace of some
    hundred MB and which nothing here reads."""
    import jax
    from jax._src.lib import _profiler

    jax.devices()  # the backend first, or the TPU tracer records nothing
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the spans; not the runtime's own events
    return _profiler.ProfilerSession(options)


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _gap_labels(spans, gaps) -> Dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) by the host spans open at each
    gap's midpoint, in one sweep."""
    bounds = sorted(
        [(s, 1, n) for s, e, n in spans if n != WINDOW_SPAN]
        + [(e, -1, n) for s, e, n in spans if n != WINDOW_SPAN]
    )
    open_: Dict[str, int] = {}
    out: Dict[str, float] = {}
    i = 0
    for s, e in gaps:
        mid = (s + e) // 2
        while i < len(bounds) and bounds[i][0] <= mid:
            _, step, name = bounds[i]
            open_[name] = open_.get(name, 0) + step
            i += 1
        names = sorted(n[len(SPAN_PREFIX):] for n, k in open_.items() if k > 0)
        label = "+".join(names) or "other"
        out[label] = out.get(label, 0.0) + (e - s) * 1e-9
    return out


def summarize(trace: Union[str, bytes], top: int = 10) -> Optional[Summary]:
    """The reduction of a trace, given as the path of an ``.xplane.pb`` or
    as a serialized XSpace, or None when it holds no device plane or no
    ``bench:window`` span."""
    from jax.profiler import ProfileData

    if isinstance(trace, bytes):
        data = ProfileData.from_serialized_xspace(trace)
    else:
        data = ProfileData.from_file(trace)
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns), ev.name))
    window = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not devices or len(window) != 1:
        return None
    lo, hi = window[0]
    # the profiler keeps a bounded number of device events, so the trace of
    # a long window can stop short of its end: the traced window ends at
    # the last device operation the trace holds, where that comes first
    last = max(
        (int(ev.start_ns) + int(ev.duration_ns) for plane in devices for line in plane.lines
         if line.name == "XLA Ops" for ev in line.events),
        default=hi,
    )
    hi = max(lo + 1, min(hi, last))

    executables: Dict[str, Tuple[float, int]] = {}
    ops: Dict[str, float] = {}
    busy_ns = 0
    gaps: Dict[str, float] = {}
    for plane in devices:
        busy = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e <= lo or s >= hi:
                        continue
                    name = _SUFFIX.sub("", ev.name)
                    t, n = executables.get(name, (0.0, 0))
                    executables[name] = (t + (e - s) * 1e-9, n + 1)
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e <= lo or s >= hi:
                        continue
                    busy.append((s, e))
                    name = ev.name.split(" = ", 1)[0]  # the HLO instruction's name
                    ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
        merged = _clip(_union(busy), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for label, secs in _gap_labels(spans, idle).items():
            gaps[label] = gaps.get(label, 0.0) + secs

    def longest(d):
        return sorted(d.items(), key=lambda kv: -kv[1])[:top]

    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9 / len(devices),
        chips=len(devices),
        executables=executables,
        device_ops=longest(ops),
        idle_gaps=longest(gaps),
    )
