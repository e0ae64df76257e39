"""Run one benchmark cell once and print its result line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX finds the TPU
chips the cell asks for; without them it exits non-zero and prints no
result.  The cell, its configuration, its traffic mix and its per-layer
metric readers are found by name from ``BENCHMARK.json`` (see
``bench/harness/spec.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, the
numbers that decide ``correct`` beside their limits.  The same numbers
are the last lines of standard error.
"""

import sys
import time

STARTED = time.perf_counter()

if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from harness import runner
    sys.exit(runner.main(sys.argv[1:], STARTED))
