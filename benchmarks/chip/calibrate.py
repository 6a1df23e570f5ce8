"""Read the numbers a cell's limits are set from, on the chip, in one
process: for each seed, one run of the cell at its own size and load with
the program's numbers and the float8 control's on the same requests, each
judged by the cell's limits, and, with ``--program-variant``, a run of the
program with fields of its config changed (its own lower-precision path,
such as an int8 KV cache), judged the same way.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 [--program-variant kv_cache_dtype=int8]

Prints one JSON line per seed and run.  The benchmark's own runs never
run the control.
"""

import argparse
import json
import os
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-variant", action="append", default=[])
    args = ap.parse_args()
    variant = dict(kv.split("=", 1) for kv in args.program_variant)

    import harness

    cell = harness.read_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    with tempfile.TemporaryDirectory(prefix="bench-storage-") as storage:
        os.environ["REPRO_STORAGE_ROOT"] = storage
        sys.path.insert(0, str(harness.SRC))
        import jax

        jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if jax.devices()[0].platform != "tpu":
            harness.log("calibrate.py: the first device is not a TPU")
            return 1
        counter = harness.CompileCounter()
        for seed in args.seeds:
            runs = [("program", {}, True)] + ([("variant", variant, False)] if variant else [])
            for label, overrides, control in runs:
                out = harness.run_cell(
                    cell, seed, args.seconds, False, time.monotonic(), counter,
                    program_overrides=overrides, control=control,
                )
                line = {
                    "seed": seed,
                    "run": label,
                    "overrides": overrides,
                    "correct": out["correct"],
                    "checks": {k: v["value"] for k, v in out["checks"].items()},
                    "attempted": out["attempted"],
                    "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                }
                if control:
                    line["control"] = {
                        "correct": out["control"]["correct"],
                        "checks": {k: v["value"] for k, v in out["control"]["checks"].items()},
                    }
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
