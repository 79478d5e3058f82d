#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for; off a TPU it exits with code 3 and prints no result. JAX's
persistent compilation cache is kept in ``<checkout>/.jax_cache``. The
last line of standard output is the result as one JSON object; the last
lines of standard error are the numbers that decided ``correct``, each
with its limit.
"""

import time

T_PROC = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path.insert(0, str(ROOT / "bench"))
    from harness import runner
    sys.exit(runner.main(sys.argv[1:], T_PROC))
