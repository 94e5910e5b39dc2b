"""What a traced run hands the per-layer readers (``bench/metrics``).

A reader is a file ``bench/metrics/<metric name>.py`` with a function
``read(ctx) -> float | None``.  It returns None where it finds nothing to
read; the harness then leaves the metric out of the result line.
"""

from __future__ import annotations

import importlib.util
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace

METRICS_DIR = Path(__file__).resolve().parent / "metrics"

# The ragged grouped-GEMM kernels of ``kernels/moe_gemm``: the trace names
# each custom call after the jitted function that launches it.
MOE_GEMM = re.compile(r"^ragged_(matmul|gate_up_silu|dw)_f32\b")


@dataclass
class Ctx:
    events: Dict  # trace.extract output
    steps: int  # steps inside the traced window
    cfg: Dict
    chips: int
    peak: Dict[str, float]
    tokens_per_step: int
    flops_per_token: float
    loads: List[np.ndarray] = field(default_factory=list)  # (L, E) per step
    host_fetches: int = 0
    coords: Dict[int, Tuple[int, int]] = field(default_factory=dict)
    pp: int = 1
    ep: int = 1

    # -- trace views ----------------------------------------------------------

    @property
    def window(self) -> Optional[trace.Interval]:
        return trace.window(self.events)

    @property
    def window_s(self) -> float:
        w = self.window
        return (w[1] - w[0]) * 1e-9 if w else 0.0

    def device_ids(self) -> List[str]:
        return sorted(self.events["devices"], key=int)

    def ops(self, dev: str):
        """``(name, start, duration, leaf)`` of the ops in the window."""
        w = self.window
        for name, s, d, leaf in self.events["devices"][dev]:
            if w is None or (s + d > w[0] and s < w[1]):
                yield name, s, d, leaf

    def busy(self, dev: str) -> List[trace.Interval]:
        w = self.window
        iv = trace.union([(s, s + d) for _, s, d, _ in self.ops(dev)])
        return trace.clip(iv, *w) if w else iv

    def kernel_ops(self, dev: str, pattern=MOE_GEMM):
        return [(n, s, d) for n, s, d, leaf in self.ops(dev)
                if leaf and pattern.search(n)]


def load_reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def breakdown(ctx: Ctx, top: int = 10) -> Dict:
    """Leaf device ops that took most time (mean over devices), and the longest
    idle gaps of the first device labelled by the host annotation they
    overlap (``bench.data``), else ``trainer loop``."""
    devs = ctx.device_ids()
    tot: Dict[str, float] = {}
    for dev in devs:
        for name, _, d, leaf in ctx.ops(dev):
            if leaf:
                tot[name] = tot.get(name, 0.0) + d * 1e-9 / len(devs)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    out = {"device_ops": [[n, s] for n, s in ops], "idle_gaps": []}
    w = ctx.window
    if not devs or w is None:
        return out
    data = [(s, s + d) for n, s, d in ctx.events["host"] if n == "bench.data"]
    gaps = sorted(trace.gaps(ctx.busy(devs[0]), *w),
                  key=lambda g: -(g[1] - g[0]))[:top]
    for a, b in gaps:
        label = ("bench.data" if any(s < b and e > a for s, e in data)
                 else "trainer loop")
        out["idle_gaps"].append([label, (b - a) * 1e-9])
    return out
