"""Offered rate against backlog growth and tails for a serving cell's mix
run as an open loop, on the chip: the sweep that finds the rate the
program sustains. One process, one engine build per rate.

    python3 bench/tests/sweep.py <workload> <seconds> <seed> <rate>...

For each rate it prints the requests due and finished in the window, the
queue at the window's first and last step, and the median and 95th
percentile of time to first token and of the gap between tokens, and the
compiles inside the window.
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
from harness.common import nearest_rank  # noqa: E402
from harness.serve import serve_run  # noqa: E402


def main():
    name, seconds, seed = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
    import jax
    root = BENCH.parent
    bench = runner.load_benchmark(root)
    cell, model, mix = runner.cell_files(root, bench, name)
    family = runner.load_family(root, model["family"])
    for rate in [float(r) for r in sys.argv[4:]]:
        t = time.perf_counter()
        run = serve_run(cell, family, model,
                        dict(mix, loop="open", rate_per_s=rate), seed,
                        seconds, None, t, jax.devices()[0])
        q = [s.queue for s in run.steps]
        ttft, itl = run.samples["ttft_s"], run.samples["itl_s"]
        print(json.dumps({
            "rate": rate, "due": run.attempted,
            "queue_first": q[0] if q else None,
            "queue_last": q[-1] if q else None, "queue_max": max(q or [0]),
            "active_mean": sum(s.active for s in run.steps) / max(len(q), 1),
            "ttft_p50_ms": nearest_rank(ttft, 0.5) * 1e3,
            "ttft_p95_ms": nearest_rank(ttft, 0.95) * 1e3,
            "itl_p50_ms": nearest_rank(itl, 0.5) * 1e3,
            "itl_p95_ms": nearest_rank(itl, 0.95) * 1e3,
            "tokens_per_s": sum(s.tokens for s in run.steps) / run.window_s,
            "compiles_in_window": run.compiles_in_window,
            "notes": run.notes[-1]}), flush=True)


if __name__ == "__main__":
    main()
