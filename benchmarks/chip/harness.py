"""One run of one cell: set-up, warm-up, the measured window, the metrics
and the verdict on the comparison that decides ``correct``.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

- ``configs/<config>.json``: the published sizes, how they map onto the
  program's config, the types it is served in, batch and ``max_len``;
  ``configs/<reference>.py`` beside it is the plain float32 reference;
- ``counts/<config>.py``: the FLOPs and bytes one step needs;
- ``traffic/<mix>.json``: the parameters of the mix, and the ``driver``
  that reads them: ``drivers/<driver>.py``, which starts the pilots, warms
  up, drives the window, returns its records and compares what the timed
  path produced with the reference;
- ``metrics/<metric>.py``: one reduction each, from the window's generic
  records (:class:`Request`, steps) or its trace;
- ``limits/<cell>.json``: the limit of each number the comparison reads.

The program under test is imported from ``<checkout>/src``: its Session,
checkpoint DUs, agents and whatever entry points a driver calls.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import trace as trace_mod
import weights as weights_mod

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]
SRC = CHECKOUT / "src"
CACHE_DIR = CHECKOUT / ".jax_cache"
BENCHMARK = CHECKOUT / "BENCHMARK.json"
WAIT_S = 900  # longest wait for a CU, far past any run's limit


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    """The module at ``path``, known to Python as ``bench_<dir>_<stem>``."""
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def lookup(sizes: Dict, dotted: str):
    node = sizes
    for part in dotted.split("."):
        node = node[part]
    return node


# ----------------------------------------------------------------- the cell
@dataclasses.dataclass
class Metric:
    spec: Dict  # its entry in BENCHMARK.json
    module: object  # metrics/<name>.py

    @property
    def name(self) -> str:
        return self.spec["name"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    sizes: Dict  # published sizes, and those derived from them
    limits: Dict[str, float]
    reference: object
    counts: object
    driver: object  # drivers/<driver>.py
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def batch(self) -> int:
        return self.config["batch"]

    @property
    def max_len(self) -> int:
        return self.config["max_len"]


def read_cell(name: str, bench_path: Path = BENCHMARK) -> Cell:
    bench = _json(bench_path)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if len(found) != 1:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = found[0]
    config = _json(ROOT / "configs" / f"{w['config']}.json")

    def metrics(kind: str) -> List[Metric]:
        out = []
        for spec in bench[kind]:
            if name in spec.get("workloads", [name]):
                module = load_module(ROOT / "metrics" / f"{spec['name']}.py")
                for key in ("unit", "better", "moves", "layer"):
                    own = getattr(module, key.upper(), None)
                    if key in spec and own != spec[key]:
                        raise ValueError(f"metrics/{spec['name']}.py: {key} {own!r} != {spec[key]!r}")
                out.append(Metric(spec, module))
        return out

    traffic = _json(ROOT / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=traffic,
        sizes={**config["published"], **config.get("derived", {})},
        limits=_json(ROOT / "limits" / f"{name}.json"),
        reference=load_module(ROOT / "configs" / config["reference"]),
        counts=load_module(ROOT / "counts" / f"{w['config']}.py"),
        driver=load_module(ROOT / "drivers" / f"{traffic['driver']}.py"),
        end_to_end=metrics("end_to_end"),
        per_layer=metrics("per_layer"),
    )


def program_config(cell: Cell):
    """The program's config for the cell: its registry entry with every
    mapped published key set, nested groups by ``group.key``, and the
    types the configuration is served in (``program_dtypes``: weights,
    compute and KV cache) set, whatever the registry's defaults are."""
    from repro.configs import get_config

    cfg = get_config(cell.config["registry"])
    top: Dict = {}
    nested: Dict[str, Dict] = {}
    for published, field in cell.config["program"].items():
        value = lookup(cell.sizes, published)
        if "." in field:
            group, key = field.split(".")
            nested.setdefault(group, {})[key] = value
        else:
            top[field] = value
    for group, values in nested.items():
        top[group] = dataclasses.replace(getattr(cfg, group), **values)
    return dataclasses.replace(cfg, **top, **cell.config["program_dtypes"])


# ----------------------------------------------------------------- the run
class CompileCounter:
    """Compiles and persistent-cache hits, with their host times.  JAX
    reports a backend compile duration for a cache hit too, so the compiles
    in an interval are the durations less the hits."""

    def __init__(self):
        import jax.monitoring

        self.compiles: List[float] = []
        self.cache_hits: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.monotonic())

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.monotonic())

    @staticmethod
    def between(times, lo, hi) -> int:
        return sum(lo <= t <= hi for t in times)

    def in_window(self, lo, hi) -> int:
        return self.between(self.compiles, lo, hi) - self.between(self.cache_hits, lo, hi)


@dataclasses.dataclass
class Request:
    """One request of the user's work as a driver records it: a chat turn,
    a short serve CU.  Times are host monotonic seconds."""

    t_submit: float = 0.0  # handed to the system
    t_end: float = 0.0  # its whole result on the host
    units: int = 0  # tokens it completed
    t_first: Optional[float] = None  # its first token on the host, where seen
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)  # named host spans, s
    ok: bool = True


@dataclasses.dataclass
class Window:
    """What a driver returns from the measured window."""

    requests: List[Request]  # every request that started before the deadline
    steps: Dict[str, List[tuple]]  # per executable, the counts' arguments of each run
    t_end: float  # the last of them ends
    failed: int  # requests that never gave a result


@dataclasses.dataclass
class RunData:
    """What a metric reads."""

    setup_s: float
    t0: float  # the window opens
    t_end: float  # the last request that started before the deadline ends
    requests: List[Request]
    steps: Dict[str, List[tuple]]
    sizes: Dict
    counts: object
    peaks: Dict
    trace: Optional[trace_mod.Summary] = None


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


def peaks_for(kind: str) -> Dict:
    table = _json(ROOT / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """(correct, checks): every number compared beside its limit."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    traced: bool,
    t_proc0: float,
    counter: CompileCounter,
    peaks: Optional[Dict] = None,
    break_step: Optional[Callable] = None,
    program_overrides: Optional[Dict] = None,
    control: bool = False,
) -> Dict:
    """One run; returns the result line as a dict.

    For calibration and tests only, never in the benchmark's own runs:
    ``break_step`` wraps the program's model API to plant a fault in the
    timed path, ``program_overrides`` sets fields of the program's config,
    and ``control`` also judges the control on the same requests by the
    same limits (under ``control``, after ``checks``)."""
    import jax

    from repro.checkpoint import Checkpointer
    from repro.core import Session, make_tpu_fleet_topology
    from repro.models import build_model

    cfg = program_config(cell)
    if program_overrides:
        cfg = dataclasses.replace(cfg, **program_overrides)
    api = build_model(cfg)
    if break_step is not None:
        api = break_step(api)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    driver = cell.driver.Driver(cell, api, seed, annotate)

    topo, hosts = make_tpu_fleet_topology(pods=1, hosts_per_pod=cell.traffic["pilots"])
    with Session(topology=topo, scheduler_mode="async") as s:
        s.start_pilot_data(service_url="sharedfs://cluster:pod0/bench", affinity="cluster:pod0")
        t = time.monotonic()
        params = weights_mod.make_weights(shapes, seed)
        jax.block_until_ready(params)
        t_made = time.monotonic()
        du = Checkpointer(s, run_name=f"bench-{cell.name}-{seed}").save(0, params)
        del params
        t_saved = time.monotonic()
        log(f"setup: weights made on the device in {t_made - t:.3f} s, checkpoint DU of "
            f"{du.size} bytes saved in {t_saved - t_made:.3f} s")
        driver.start(s, du, hosts)
        log(f"setup: warm in {time.monotonic() - t_saved:.3f} s")

        session = trace_mod.start() if traced else None
        with annotate("window"):
            t0 = time.monotonic()
            win = driver.window(t0 + seconds)
        xspace = session.stop() if traced else None
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        summary = None
        if traced:
            t = time.monotonic()
            summary = trace_mod.summarize(xspace)
            log(f"trace: {len(xspace)} bytes reduced in {time.monotonic() - t:.3f} s")
            del xspace
        log(f"window: {len(win.requests)} requests in {win.t_end - t0:.3f} s; compiles in window: "
            f"{counter.in_window(t0, win.t_end)}, persistent-cache hits: "
            f"{counter.between(counter.cache_hits, t0, win.t_end)}")
    gc.collect()
    values, compared, ctrl = driver.compare(shapes, control)
    del driver
    run = RunData(
        setup_s=t0 - t_proc0,
        t0=t0,
        t_end=win.t_end,
        requests=win.requests,
        steps=win.steps,
        sizes=cell.sizes,
        counts=cell.counts,
        peaks=peaks or {},
        trace=summary,
    )
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        v = m.module.value(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.spec["unit"]}
    ok, checks = verdict(values, cell.limits)
    device = jax.devices()[0]
    result = {
        "correct": ok and win.failed == 0 and compared > 0,
        "attempted": len(win.requests),
        "failed": win.failed,
        "metrics": metrics,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(peak),
        },
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    result["checks"] = checks
    if control:
        c_ok, c_checks = verdict(ctrl, cell.limits)
        result["control"] = {"correct": c_ok, "checks": c_checks}
    return result


def print_result(result: Dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
