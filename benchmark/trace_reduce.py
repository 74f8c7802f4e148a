"""From a profiler trace (`.xplane.pb`) to device busy time, device
time by operation, and idle gaps attributed to the harness's spans.

Busy time is the union of the intervals in which an operation ran on a
device (the `XLA Ops` line of each `/device:TPU:n` plane), inside the
harness's `bench.window` span; it is averaged over the devices that
ran anything.  An idle gap is a stretch of the window with no
operation on the device; it is charged to the innermost harness span
(a `bench.*` TraceAnnotation, on any host thread) that covers the gap's
midpoint, or to `host.other` when none does.

    python3 -m benchmark.trace_reduce <file.xplane.pb>
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")

# (plane, line, event name, start ns, duration ns)
Event = Tuple[str, str, str, float, float]


_KIND = re.compile(r"[\]\})] ([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")


def op_name(text: str) -> str:
    """A device operation's HLO text, e.g. `%tpu_custom_call.1 = (...)
    custom-call(s32[1,256]{...} %a, s32[33,256]{...} %b, ...), ...`,
    shortened to its kind and operand shapes, repeats counted:
    `custom-call(s32[1,256],24xs32[33,256])`.  Pallas kernels have no
    names of their own in the trace; their operands tell them apart."""
    m = _KIND.search(text)
    if not m:
        return text[:120]
    depth, end = 1, m.end()
    while end < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[end], 0)
        end += 1
    shapes = _SHAPE.findall(text[m.end() : end - 1])
    runs = []
    for shape in shapes:
        if runs and runs[-1][0] == shape:
            runs[-1][1] += 1
        else:
            runs.append([shape, 1])
    args = ",".join(f"{n}x{sh}" if n > 1 else sh for sh, n in runs)
    return f"{m.group(1)}({args})"


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(events: List[Event], top: int = 10) -> Optional[Dict]:
    """The trace's numbers inside the window span, or None when the
    trace holds no window span or no device operation in it."""
    window = [(s, s + d) for p, _l, n, s, d in events if n == WINDOW_SPAN and not _DEVICE.match(p)]
    if not window:
        return None
    lo, hi = window[0]
    ops_by_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    for plane, line, name, s, d in events:
        if _DEVICE.match(plane) and line == OP_LINE:
            clipped = _clip([(s, s + d)], lo, hi)
            if clipped:
                a, b = clipped[0]
                ops_by_dev[plane].append((a, b))
                op_time[op_name(name)] += (b - a) / 1e9
    if not ops_by_dev:
        return None
    busy = {dev: _union(iv) for dev, iv in ops_by_dev.items()}
    busy_s = sum(sum(b - a for a, b in iv) for iv in busy.values()) / len(busy) / 1e9
    spans = [
        (s, s + d, n)
        for p, _l, n, s, d in events
        if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN and not _DEVICE.match(p)
    ]
    gap_time: Dict[str, float] = defaultdict(float)
    gaps = 0
    # gaps of the first device that ran anything: every cell of this
    # benchmark drives its chips in lockstep or runs on one
    first = sorted(busy)[0]
    edge = lo
    for a, b in busy[first] + [(hi, hi)]:
        if a > edge:
            mid = (edge + a) / 2
            covering = [sp for sp in spans if sp[0] <= mid <= sp[1]]
            name = min(covering, key=lambda sp: sp[1] - sp[0])[2] if covering else "host.other"
            gap_time[name] += (a - edge) / 1e9
            gaps += 1
        edge = max(edge, b)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "devices": len(busy),
        "op_count": sum(len(iv) for iv in ops_by_dev.values()),
        "gap_count": gaps,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gap_time.items(), key=lambda kv: -kv[1])[:top],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    print(json.dumps(reduce(load(argv[0])), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
