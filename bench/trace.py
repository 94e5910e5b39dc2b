"""Profiler trace -> the events the per-layer readers need, and the
interval arithmetic they share.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
per TPU device, the events of its "XLA Ops" line (the HLO op's name, start,
duration, and whether it is a leaf: a while loop's event holds its body's
ops), plus the benchmark's own host annotations (``bench.*``).  The
result is plain JSON, so a recorded trace can be reduced again on a CPU
(``bench/testdata``).
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def mark_leaves(events: List) -> List:
    """``[name, start, dur]`` -> ``[name, start, dur, leaf]``: an op is a
    leaf unless another op of the line runs inside it (a while loop holds
    its body's ops)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    leaf = [1] * len(events)
    stack: List[int] = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1] + events[stack[-1]][2]:
            leaf[stack[-1]] = 0
        stack.append(i)
    return [list(ev[:3]) + [leaf[i]] for i, ev in enumerate(events)]


def extract(xplane_path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[m.group(1)] = mark_leaves(
                    [[short_name(ev.name), ev.start_ns, ev.duration_ns]
                     for ev in line.events])
            elif not m and plane.name.startswith("/host"):
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"devices": devices, "host": host}


def save(events: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of ``intervals`` outside ``cover`` (merged and sorted, as
    ``union`` returns it)."""
    ends = [d for _, d in cover]
    out = []
    for a, b in intervals:
        cur = a
        for c, d in cover[bisect.bisect_right(ends, a):]:
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def window(events: Dict, name: str = "bench.window"
           ) -> Optional[Interval]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == name]
    return max(spans, key=lambda x: x[1] - x[0]) if spans else None
