"""Cut a trace kept by ``record_scopes.py`` as ``trim_trace.py`` does, and
write beside it the ``op_name`` of each kept instruction of the train tick
and the numbers the phase split must give: each phase's self time and the
idle time by innermost program span, counted here by plain sums over the
kept events (an operation's self time is its time less that of the
operations whose smallest enclosing operation it is).

    python3 bench/tests/trim_scopes.py <kept dir> <out stem> <at> <ms>

``<at>`` is a Pallas kernel, whose first call the piece is centred on, or
``module_end``. Writes ``<out stem>.pbtxt`` and ``<out stem>.json``. Needs
the XSpace proto that TensorFlow ships.
"""

import gzip
import json
import pathlib
import re
import sys

from google.protobuf import text_format
from tensorflow.tsl.profiler.protobuf import xplane_pb2

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness.scopes import phase  # noqa: E402
from trim_trace import _instr, trim  # noqa: E402

PROGRAM_SPAN = re.compile(r"^(train|serve)(\.|$)")


def _clipped(line, n):
    for e in line.events:
        s, t = max(e.offset_ps, 0), min(e.offset_ps + e.duration_ps, n)
        if t > s:
            yield s // 1000, t // 1000, e.metadata_id


def expected(out, names, n):
    dev, host = out.planes[0], out.planes[1]
    meta = dev.event_metadata
    ops_line = [ln for ln in dev.lines if ln.name == "XLA Ops"][0]
    mods_line = [ln for ln in dev.lines if ln.name == "XLA Modules"][0]
    mods = [(s, t) for s, t, _ in _clipped(mods_line, n)]
    ops = sorted(((s, t, _instr(meta[i].name)) for s, t, i in
                  _clipped(ops_line, n)), key=lambda o: (o[0], -o[1]))
    self_ns = [t - s for s, t, _ in ops]
    for j, (s, t, _) in enumerate(ops):
        parents = [p for p in range(j) if ops[p][0] <= s and t <= ops[p][1]]
        if parents:
            p = min(parents, key=lambda p: (ops[p][1] - ops[p][0], -p))
            self_ns[p] -= t - s
    by_phase = {k: 0 for k in ("forward", "backward", "optimizer", "other")}
    for (s, t, instr), v in zip(ops, self_ns):
        if any(ms <= s <= mt for ms, mt in mods):
            by_phase[phase(names.get(instr, ""))] += v
    busy = sorted((s, t) for s, t, _ in ops)
    gaps, prev = [], 0
    for s, t in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    if prev < n // 1000:
        gaps.append((prev, n // 1000))
    spans = [(s, t, host.event_metadata[i].name)
             for ln in host.lines for s, t, i in _clipped(ln, n)
             if PROGRAM_SPAN.match(host.event_metadata[i].name)]
    idle = {}
    for s, t in gaps:
        mid = (s + t) // 2
        over = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        label = (min(over, key=lambda sp: sp[1] - sp[0])[2] if over
                 else "no host span")
        idle[label] = idle.get(label, 0) + t - s
    return {"phase_ns": by_phase,
            "idle_by_program_span_ns": idle if spans else {}}


def main():
    src, stem, at, ms = sys.argv[1], sys.argv[2], sys.argv[3], \
        float(sys.argv[4])
    space = xplane_pb2.XSpace()
    with gzip.open(f"{src}/trace.xplane.pb.gz", "rb") as f:
        space.ParseFromString(f.read())
    with open(f"{src}/kernels.json") as f:
        kernels = json.load(f)
    with open(f"{src}/scopes.json") as f:
        names = json.load(f)
    out, lo, hi = trim(space, kernels, at, ms)
    kept = {_instr(v.name) for v in out.planes[0].event_metadata.values()}
    names = {i: op for i, op in names.items() if i in kept}
    with open(f"{stem}.pbtxt", "w") as f:
        f.write(text_format.MessageToString(out))
    with open(f"{stem}.json", "w") as f:
        json.dump({"scopes": names,
                   "expected": expected(out, names, hi - lo)}, f, indent=1,
                  sort_keys=True)


if __name__ == "__main__":
    main()
