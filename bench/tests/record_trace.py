"""Record a short traced run of a cell on the chip and keep its trace,
gzipped, with the kernel names of its programs: the real trace that
``test_trace.py`` reads.

    python3 bench/tests/record_trace.py <workload> <seconds> <seed> <dir>
"""

import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

from harness import runner  # noqa: E402


def main():
    name, seconds, seed, dest = (sys.argv[1], float(sys.argv[2]),
                                 int(sys.argv[3]), sys.argv[4])
    t = time.perf_counter()
    out = runner.run_cell(BENCH.parent, name, seed, seconds, True,
                          keep_trace=dest, t_proc=t)
    out.pop("_notes")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
