"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/README.md``).  Earlier output lines
start with ``#``; the last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and ``checks``: each number compared beside
its limit), and the last lines of standard error repeat those numbers.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work.  JAX's compile cache is kept at
``<checkout>/.jax-cache``; the run's own files go to
``<checkout>/.bench-out/<cell>/``.

``--control 1`` puts the configuration's control (its plain reference in
the next lower precision) in the program's place on the timed path: its
``checks`` are the upper readings a limit is set from, and its
``correct`` has to come out false.  The benchmark's own runs leave it at 0.
"""
import time

T0 = time.perf_counter()    # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.chdir(ROOT)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # before JAX is imported: the cache lives in the checkout, whatever
    # the environment says, and every compile goes into it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax-cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

    from bench.harness import cell, device, runner

    resolved = cell.resolve(cell.benchmark(ROOT), args.workload)
    devices = device.require_tpu(resolved["chips"])
    out, run = runner.run_cell(resolved, seed=args.seed,
                               seconds=args.seconds, trace=bool(args.trace),
                               devices=devices, t0=T0,
                               control=bool(args.control))
    runner.emit(out, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
