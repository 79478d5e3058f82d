"""Record a short traced run of a training cell on the chip and keep its
trace, gzipped, with the kernel names of its programs (as
``record_trace.py`` does) and the ``op_name`` of every instruction of its
train tick (``scopes.json``): the real trace that ``trim_scopes.py`` cuts
for ``test_scopes.py``. Prints the result line, and the run's notes on
standard error.

    python3 bench/tests/record_scopes.py <workload> <seconds> <seed> <dir>
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

from harness import runner, scopes  # noqa: E402


def main():
    name, seconds, seed, dest = (sys.argv[1], float(sys.argv[2]),
                                 int(sys.argv[3]), sys.argv[4])
    t = time.perf_counter()
    out = runner.run_cell(BENCH.parent, name, seed, seconds, True,
                          keep_trace=dest, t_proc=t)
    for note in out.pop("_notes"):
        print(f"bench: {note}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    _, model, mix = runner.cell_files(
        BENCH.parent, runner.load_benchmark(BENCH.parent), name)
    family = runner.load_family(BENCH.parent, model["family"])
    names = scopes.op_names(scopes.train_tick_hlo(family, model, mix, seed))
    with open(os.path.join(dest, "scopes.json"), "w") as f:
        json.dump(names, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
