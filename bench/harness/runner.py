"""One run of one cell: find the cell's files by name, check the device,
run the cell's module, decide ``correct``, reduce the trace, read the
metrics and print the result.

Names are looked up in ``BENCHMARK.json``: a workload names its
configuration (``bench/configs/<name>.json`` through the configuration's
``file``) and its traffic mix (``bench/traffic/<mix>.json``); the
configuration names its model family (``bench/families/<family>.py``,
whose module holds the architecture's weights, reference and counts);
every metric is read by ``bench/metrics/<metric>.py``, whose
``read(run)`` returns a number or ``None`` where it finds nothing to
read. The mix's ``kind`` picks the module (``harness/serve.py`` or
``harness/train.py``).
"""

from __future__ import annotations

import functools
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
from typing import Dict, Optional

from . import peaks as peaks_lib
from . import trace as trace_lib

BENCH = pathlib.Path(__file__).resolve().parents[1]


def load_benchmark(root: pathlib.Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(root: pathlib.Path, bench: dict, name: str):
    """(workload, configuration, mix) of the cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    model = load_json(root / conf["file"])
    if "family" not in model:
        raise ValueError(f"{conf['file']} names no model family: give it "
                         f"a \"family\" key, the name of a module under "
                         f"bench/families")
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return cell, model, mix


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path, name: str):
    """The module in the file ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: pathlib.Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    return _module(path, f"bench_metric_{name.replace('.', '_')}").read


def load_family(root: pathlib.Path, name: str):
    """The model family module ``bench/families/<name>.py``."""
    return _module(root / "bench" / "families" / f"{name}.py",
                   f"bench_family_{name}")


def metrics_for(bench: dict, cell: str, traced: bool):
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def run_cell(root: pathlib.Path, name: str, seed: int, seconds: float,
             traced: bool, require_tpu: bool = True,
             model: Optional[dict] = None, mix: Optional[dict] = None,
             fault=None, control: bool = False, keep_trace=None,
             t_proc: float = 0.0) -> Dict:
    """Run the cell and return the result object (``None`` where the
    device does not fit the cell). ``model``/``mix`` replace the cell's
    files, ``fault`` is handed the engine before its first step,
    ``control`` puts the reference computed one precision lower in the
    program's place when ``correct`` is decided, and ``keep_trace`` is a
    directory that keeps the trace and the kernel names: all four exist
    for the checks under ``bench/tests``."""
    import jax
    bench = load_benchmark(root)
    cell, model_f, mix_f = cell_files(root, bench, name)
    model = model or model_f
    mix = mix or mix_f
    family = load_family(root, model["family"])
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return None
    dev = devices[0]
    peaks = (peaks_lib.peaks_for(dev.device_kind)
             if dev.platform == "tpu" else {})
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        if mix["kind"] == "serve":
            from .serve import serve_check, serve_run
            run = serve_run(cell, family, model, mix, seed, seconds,
                            trace_dir, t_proc, dev, fault=fault)
            checks = serve_check(run, model, seed, control=control)
        else:
            from .train import train_check, train_run
            run = train_run(cell, family, model, mix, seed, seconds,
                            trace_dir, t_proc, dev, fault=fault)
            checks = train_check(run, model, seed, control=control)
        run.peaks = peaks
        breakdown = None
        if traced:
            from jax.profiler import ProfileData
            path = trace_lib.find_xplane(trace_dir)
            if keep_trace is not None:
                trace_lib.keep(path, run.kernels, keep_trace)
            run.trace = trace_lib.load(ProfileData.from_file(path))
            breakdown = {"device_ops": trace_lib.top_ops(run.trace,
                                                         run.kernels),
                         "idle_gaps": trace_lib.idle_gaps(run.trace)}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    limits = model["limits"]
    correct = all(math.isfinite(v) and v <= limits[k]
                  for k, v in checks.items())
    metrics = {}
    for m in metrics_for(bench, name, traced):
        v = metric_reader(root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": run.memory_peak}
    if traced:
        device["busy_s"] = trace_lib.busy_ns(run.trace) / 1e9
        device["window_s"] = run.trace.window_ns / 1e9
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in checks.items()}
    out["_notes"] = run.notes + [
        f"setup_s {run.setup_s}, window {run.window_s} s, compiles in the "
        f"window {run.compiles_in_window}"]
    return out


def main(argv, t_proc: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = BENCH.parent
    out = run_cell(root, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_proc=t_proc)
    if out is None:
        return 3
    for note in out.pop("_notes"):
        print(f"bench: {note}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0
