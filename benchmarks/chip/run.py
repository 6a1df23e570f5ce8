"""Run one cell of the chip benchmark once, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It makes the cell's weights and traffic
from ``--seed``, sets the system up and warms it, measures for
``--seconds``, then compares what the timed path served with a plain
float32 reference.  Informational lines go to standard error, the numbers
compared with their limits last; the last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; ``checks`` last.

Exits 1 and prints no result when the first device is not a TPU or there
are fewer chips than the cell asks for, and 2 when the checkout holds no
program sources.  JAX's persistent compile cache is kept in
``<checkout>/.jax_cache``, so only the first run of a cell compiles.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def process_start() -> float:
    """The monotonic time at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return T_START


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    t_proc0 = process_start()

    import harness

    if not (harness.SRC / "repro").is_dir() or not harness.BENCHMARK.is_file():
        print(f"run.py: no program sources under {harness.SRC} or no {harness.BENCHMARK}", file=sys.stderr)
        return 2
    cell = harness.read_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    with tempfile.TemporaryDirectory(prefix="bench-storage-") as storage:
        # the file-backed Pilot-Data of the run lives here and goes with it
        os.environ["REPRO_STORAGE_ROOT"] = storage
        sys.path.insert(0, str(harness.SRC))
        import jax

        jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices = jax.devices()
        dev = devices[0]
        harness.log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
        if dev.platform != "tpu":
            harness.log("run.py: the first device is not a TPU")
            return 1
        if len(devices) < cell.chips:
            harness.log(f"run.py: {cell.name} needs {cell.chips} chips, found {len(devices)}")
            return 1
        peaks = harness.peaks_for(dev.device_kind)
        counter = harness.CompileCounter()
        result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), t_proc0, counter, peaks)
        harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
