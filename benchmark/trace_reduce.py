"""From the profiler's trace to numbers: which planes are devices, the
union of the intervals in which an operation ran on each, each
operation's own time by name, and the idle gaps.

The reduction works on a plain structure, so that a test can hand it a
trace built by hand::

    [{"name": "/device:TPU:0",
      "lines": [{"name": "XLA Ops",
                 "events": [("fusion.1", start_ns, duration_ns), ...]}]},
     {"name": "/host:CPU", "lines": [...]}]

``read_xplane`` makes that structure from an ``.xplane.pb`` file with
``jax.profiler.ProfileData`` alone.

On a TPU plane the line ``XLA Ops`` holds one event per executed HLO
operation; an operation that holds others (a ``while`` and its body)
spans them, so busy time is a union and an operation's own time leaves
out what its children cover.  The harness marks each of its boundaries
with a ``bench_boundary`` annotation on the host, on the trace's clock:
an idle gap that holds one is the host's boundary work, any other lies
inside a dispatch.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
BOUNDARY = "bench_boundary"
Event = Tuple[str, int, int]


def read_xplane(path: str) -> List[Dict]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_ns(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Length of the union of ``(start, end)`` intervals, and the merged
    intervals in order."""
    merged: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged), [tuple(m) for m in merged]


def self_times(events: List[Event]) -> Dict[str, int]:
    """Each name's own nanoseconds: an event's duration less what the
    events nested in it cover."""
    out: Dict[str, int] = {}
    stack: List[List] = []     # [name, end, own]

    def close(upto: int):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + max(own, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(sys.maxsize)
    return out


def reduce(planes: List[Dict], window_s: Optional[float] = None) -> Dict:
    """Busy seconds averaged over the device planes, the operations'
    own seconds by name (summed over devices), the idle gaps of the
    first device with the boundary marks, and the window."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("no device plane in the trace: "
                         + ", ".join(p["name"] for p in planes))
    marks = sorted(start for p in planes for line in p["lines"] for name, start, _ in line["events"]
                   if name == BOUNDARY)
    busy, by_name, gaps, span, n_events = [], {}, [], None, 0
    for i, plane in enumerate(devices):
        ops = [ev for line in plane["lines"]
               if OPS_LINE is None or line["name"] == OPS_LINE
               for ev in line["events"] if ev[0] != BOUNDARY]
        total, merged = union_ns((s, s + d) for _, s, d in ops)
        busy.append(total / 1e9)
        for name, ns in self_times(ops).items():
            by_name[name] = by_name.get(name, 0.0) + ns / 1e9
        if i == 0 and merged:
            span = (merged[0][0], merged[-1][1])
            for (_, end), (start, _) in zip(merged, merged[1:]):
                held = any(end <= m <= start for m in marks)
                gaps.append((start - end, held))
        n_events += len(ops)
    if n_events == 0:
        raise ValueError("the trace holds no device operation")
    busy_s = sum(busy) / len(busy)
    if window_s is None:
        window_s = (span[1] - span[0]) / 1e9
    return {"busy_s": busy_s, "window_s": float(window_s),
            "devices": len(devices), "by_name": by_name,
            "gaps": sorted(gaps, reverse=True), "marks": len(marks),
            "events": n_events}


def reduce_dir(trace_dir: str, window_s: Optional[float] = None) -> Dict:
    return reduce(read_xplane(newest_xplane(trace_dir)), window_s)


def seconds_of(reduced: Dict, patterns: Iterable[str]) -> float:
    """Summed own seconds of the operations whose name holds one of
    ``patterns``."""
    pats = list(patterns)
    return sum(s for name, s in reduced["by_name"].items()
               if any(p in name for p in pats))


def op_kind(name: str) -> str:
    """An operation's kind, from the name the trace gives it (the HLO
    text): ``%multiply_reduce_fusion.147 = ...`` is
    ``multiply_reduce_fusion``; a Pallas kernel, which the trace names
    only ``closed_call.N`` with no kernel name, is ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in name:
        return "tpu_custom_call"
    head = name.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def by_kind(reduced: Dict) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, s in reduced["by_name"].items():
        kind = op_kind(name)
        out[kind] = out.get(kind, 0.0) + s
    return out


def breakdown(reduced: Dict, engine: str) -> Dict:
    """The line's ``breakdown``: the ten kinds of operation that took
    most of the device's time (own time, summed over their events) and
    the ten longest idle gaps, each gap named by whether a boundary of
    the harness falls inside it."""
    mark = "epoch_boundary" if engine == "epoch_scan" else "step_boundary"
    ops = sorted(by_kind(reduced).items(), key=lambda kv: -kv[1])[:10]
    gaps = [[mark if held else "inside_dispatch", ns / 1e9]
            for ns, held in reduced["gaps"][:10]]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}


def main(argv: List[str]) -> int:
    """``python -m benchmark.trace_reduce DIR``: what a trace holds, for
    a look by hand."""
    import json
    planes = read_xplane(newest_xplane(argv[1]))
    out = {"planes": [{"name": p["name"], "lines": [
        {"name": l["name"], "events": len(l["events"]),
         "first": [e[0] for e in l["events"][:5]]} for l in p["lines"]]}
        for p in planes]}
    try:
        red = reduce(planes)
        out["busy_s"], out["window_s"] = red["busy_s"], red["window_s"]
        out["marks"] = red["marks"]
        out["top"] = [[n[:160], t] for n, t in sorted(
            red["by_name"].items(), key=lambda kv: -kv[1])[:40]]
        out["kinds"] = sorted(by_kind(red).items(), key=lambda kv: -kv[1])
        out["custom_call_shapes"] = sorted(
            ((n.split(" custom-call(")[0][-120:], t)
             for n, t in red["by_name"].items()
             if op_kind(n) == "tpu_custom_call"), key=lambda kv: -kv[1])[:40]
        out["gaps"] = red["gaps"][:20]
    except ValueError as e:
        out["error"] = str(e)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
