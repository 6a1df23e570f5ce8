"""Benchmark harness — one bench per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (one row per measurement), and with
``--json PATH`` additionally writes the same rows machine-readably so CI
can archive a perf trajectory artifact per run.

  bench_staging      — Fig. 7 (T_S per storage backend × size)
  bench_replication  — Fig. 8 (T_R group vs sequential, per-host inset)
  bench_placement    — Figs. 9–10 (five placement strategies, 8-task BWA)
                       + placement-plugin sync/async equivalence
  bench_scale        — Figs. 11–13 (1024 tasks × 1–3 machines ± replication)
                       + async-vs-sync pipelined staging comparison
  bench_dataflow     — Pilot-API v2 DAG: one-shot declarative submission
                       (sync + async) vs v1 submit-wait-submit
  bench_streaming    — chunk-streaming shuffle vs seal-gated pipeline
                       (prefix-released consumers) + exactly-once rollback
  bench_faults       — makespan-under-churn: kill k of n pilots
                       mid-workload; replication-factor healing + lineage
                       recomputation; monitor op-count O(changes) proof
  bench_tiering      — storage hierarchy: mem-tier caching + quota
                       eviction vs flat re-staging for a working set
                       larger than DRAM; eviction-correctness claim
  bench_mlstack      — ML stack on the runtime: one-shot training DAG vs
                       submit-wait, tier-cached serving fleet cold-start,
                       checkpoint-chain survival under pilot kill, and a
                       per-model-config cold-start scenario sweep
  bench_store        — coordination-store write throughput: sharded
                       (striped locks + queued dispatch + group-commit
                       WAL) vs legacy single-lock mode, 1 and N writers
  bench_multitenant  — QoS under tenant contention: light-tenant p99
                       uncontended vs quota-fair vs unquota'd flood, plus
                       the tenant-aware-eviction pinned-set claim
  bench_cost_model   — §6.1 calculus vs oracle + replication degree
  bench_roofline     — assignment §Roofline terms from dry-run artifacts
"""

import argparse
import json
import platform
import sys
import traceback
from typing import Dict, List


def _row_to_json(row: str) -> Dict[str, object]:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="shrink bench_scale")
    ap.add_argument("--only", default=None, help="run a single bench by name")
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="also write rows as a JSON artifact (for CI perf trajectories)",
    )
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_cost_model,
        bench_dataflow,
        bench_faults,
        bench_mlstack,
        bench_multitenant,
        bench_placement,
        bench_replication,
        bench_roofline,
        bench_scale,
        bench_staging,
        bench_store,
        bench_streaming,
        bench_tiering,
    )

    benches = {
        "staging": lambda: bench_staging.run(),
        "replication": lambda: bench_replication.run(),
        "placement": lambda: bench_placement.run(),
        "scale": lambda: bench_scale.run(n_tasks=128 if args.quick else 1024),
        "dataflow": lambda: bench_dataflow.run(),
        "streaming": lambda: bench_streaming.run(),
        "faults": lambda: bench_faults.run(quick=args.quick),
        "tiering": lambda: bench_tiering.run(),
        "mlstack": lambda: bench_mlstack.run(quick=args.quick),
        "store": lambda: bench_store.run(),
        "multitenant": lambda: bench_multitenant.run(quick=args.quick),
        "cost_model": lambda: bench_cost_model.run(),
        "roofline": lambda: bench_roofline.run(),
    }
    if args.only and args.only not in benches:
        print(
            f"unknown bench {args.only!r} (known: {', '.join(benches)})",
            file=sys.stderr,
        )
        sys.exit(2)
    print("name,us_per_call,derived")
    all_rows: List[str] = []
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        try:
            all_rows.extend(fn() or [])
        except Exception as exc:  # noqa: BLE001
            failed.append(name)
            row = f"{name}.ERROR,0.0,{type(exc).__name__}:{exc}"
            print(row)
            all_rows.append(row)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        payload = {
            "schema": "bench-rows/v1",
            "quick": args.quick,
            "only": args.only,
            "python": platform.python_version(),
            "rows": [_row_to_json(r) for r in all_rows],
            "failed": failed,
        }
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
