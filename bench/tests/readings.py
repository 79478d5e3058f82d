"""Readings behind the limits of ``correct``, on the chip at the cells' own
sizes: the numbers compared for sound runs over many seeds, for the
control, and for the faults. One process per call, so that the programs
compile once; every reading goes through the harness's own run and its
decision of ``correct``.

    python3 bench/tests/readings.py <workload> <seconds> <what> <seed>...

``what``: ``program``; ``control`` (the reference with int8 weights put
in the program's place); or a training fault (``unchanged``,
``half_batch``, ``token``).
"""

import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

from harness import program, runner  # noqa: E402


def _train_fault(name):
    from repro.models import transformer as tf_lib
    from repro.train import engine
    if name == "unchanged":
        orig = engine.apply_updates

        def unchanged(params, grads, state, cfg):
            return params, state, orig(params, grads, state, cfg)[2]
        engine.apply_updates = unchanged
    elif name == "half_batch":
        orig = tf_lib.loss_fn

        def half(params, cfg, batch):
            import jax.numpy as jnp
            mask = jnp.ones_like(batch["labels"], jnp.float32)
            mask = mask.at[..., mask.shape[-1] // 2:].set(0.0)
            return orig(params, cfg, dict(batch, loss_mask=mask))
        tf_lib.loss_fn = half
    elif name == "token":
        def fault(eng):
            orig = eng.pipeline.batch_at

            def altered(i):
                b = orig(i)
                labels = b["labels"].copy()
                labels[:, 0] = (labels[:, 0] + 1) % 49152
                return dict(b, labels=labels)
            eng.pipeline.batch_at = altered
        return fault
    else:
        raise ValueError(f"unknown reading {name!r}")
    return None


def main():
    name, seconds, what = sys.argv[1], float(sys.argv[2]), sys.argv[3]
    seeds = [int(s) for s in sys.argv[4:]]
    root = BENCH.parent
    fault = None
    if what not in ("program", "control"):
        program.import_program()
        fault = _train_fault(what)
    for seed in seeds:
        t = time.perf_counter()
        out = runner.run_cell(root, name, seed, seconds, False, fault=fault,
                              control=what == "control", t_proc=t)
        print(json.dumps({"what": what, "seed": seed,
                          "correct": out["correct"],
                          "checks": {k: c["value"]
                                     for k, c in out["checks"].items()},
                          "metrics": {k: m["value"]
                                      for k, m in out["metrics"].items()},
                          "peak": out["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t,
                          "notes": out["_notes"]}), flush=True)


if __name__ == "__main__":
    main()
