"""Cut a trace kept by ``record_trace.py`` down to a small text proto that
``test_trace.py`` reads: the device's ``XLA Modules`` and ``XLA Ops`` and
the host's main thread, the events that overlap ``<ms>`` milliseconds
centred on the first call of ``<at>``, a Pallas kernel, or on the end of
the first program run when ``<at>`` is ``module_end``; times in whole
nanoseconds,
operation names cut to their first 160 characters (the instruction name
leads), no stats. A host span
``bench.window`` is put over that interval. Beside it go the kernel names
of the kept instructions and the numbers the reduction must give, counted
here by plain sums over the kept events.

    python3 bench/tests/trim_trace.py <kept dir> <out stem> <at> <ms>

Writes ``<out stem>.pbtxt`` and ``<out stem>.json``. Needs the XSpace
proto that TensorFlow ships (``tensorflow.tsl.profiler.protobuf``).
"""

import gzip
import json
import sys

from google.protobuf import text_format
from tensorflow.tsl.profiler.protobuf import xplane_pb2

NAME_CHARS = 160


def _instr(name):
    return name.split(" ", 1)[0].lstrip("%")


def _abs(line, ev):
    s = line.timestamp_ns * 1000 + ev.offset_ps
    return s, s + ev.duration_ps


def trim(space, kernels, at, ms):
    dev = next(p for p in space.planes if p.name == "/device:TPU:0")
    host = next(p for p in space.planes if p.name == "/host:CPU")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    if at == "module_end":
        mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
        centre = min(_abs(mods, e)[1] for e in mods.events)
    else:
        want = {i for i, k in kernels.get("jit_tick", {}).items()
                if k == at}
        centre = min(_abs(ops, e)[0] for e in ops.events
                     if _instr(dev.event_metadata[e.metadata_id].name)
                     in want)
    half = int(ms * 1e9) // 2
    lo = (centre - half) // 1000 * 1000
    hi = lo + 2 * half // 1000 * 1000

    out = xplane_pb2.XSpace()
    for plane, keep in ((dev, ("XLA Modules", "XLA Ops")), (host, ("python",))):
        p = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if line.name not in keep and not line.name.startswith(keep):
                continue
            ln = p.lines.add(id=line.id, name=line.name,
                             timestamp_ns=lo // 1000)
            for e in line.events:
                s, t = _abs(line, e)
                if plane.event_metadata[e.metadata_id].name == "bench.window":
                    continue
                if t > lo and s < hi:
                    # whole nanoseconds, as the reduction reads them
                    s, t = s // 1000 * 1000, t // 1000 * 1000
                    ln.events.add(metadata_id=e.metadata_id,
                                  offset_ps=s - lo, duration_ps=t - s)
                    used.add(e.metadata_id)
            if line.name.startswith("python"):
                wid = max(plane.event_metadata) + 1
                ln.events.add(metadata_id=wid, offset_ps=0,
                              duration_ps=hi - lo)
                p.event_metadata[wid].id = wid
                p.event_metadata[wid].name = "bench.window"
        for i in used:
            p.event_metadata[i].id = i
            p.event_metadata[i].name = plane.event_metadata[i].name[:NAME_CHARS]
    return out, lo, hi


def expected(out, kernels, lo, hi):
    """The numbers the reduction must give, in nanoseconds, by plain sums
    over the kept events clipped to the window."""
    dev = out.planes[0]
    ops = [ln for ln in dev.lines if ln.name == "XLA Ops"][0]
    mods = [ln for ln in dev.lines if ln.name == "XLA Modules"][0]
    n = hi - lo
    cover = []
    kern = {}
    for e in ops.events:
        s, t = max(e.offset_ps, 0), min(e.offset_ps + e.duration_ps, n)
        cover.append((s, t))
        k = kernels.get("jit_tick", {}).get(
            _instr(dev.event_metadata[e.metadata_id].name))
        if k:
            tot, calls = kern.get(k, (0, 0))
            kern[k] = (tot + (t - s) // 1000, calls + 1)
    busy, end = 0, 0
    for s, t in sorted(cover):
        if t > end:
            busy += t - max(s, end)
            end = t
    tick = sum(min(e.offset_ps + e.duration_ps, n) - max(e.offset_ps, 0)
               for e in mods.events)
    return {"window_ns": n // 1000, "busy_ns": busy // 1000,
            "jit_tick_ns": tick // 1000,
            "kernels": {k: list(v) for k, v in sorted(kern.items())}}


def main():
    src, stem, at, ms = sys.argv[1], sys.argv[2], sys.argv[3], \
        float(sys.argv[4])
    space = xplane_pb2.XSpace()
    with gzip.open(f"{src}/trace.xplane.pb.gz", "rb") as f:
        space.ParseFromString(f.read())
    with open(f"{src}/kernels.json") as f:
        kernels = json.load(f)
    out, lo, hi = trim(space, kernels, at, ms)
    kept = {m: {i: k for i, k in ks.items()
                if any(_instr(v.name) == i
                       for v in out.planes[0].event_metadata.values())}
            for m, ks in kernels.items()}
    with open(f"{stem}.pbtxt", "w") as f:
        f.write(text_format.MessageToString(out))
    with open(f"{stem}.json", "w") as f:
        json.dump({"kernels": kept,
                   "expected": expected(out, kernels, lo, hi)}, f, indent=1)


if __name__ == "__main__":
    main()
