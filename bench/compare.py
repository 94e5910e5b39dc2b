"""The numbers that decide ``correct``, each held to its limit.

The program's first training steps (``training.check_steps`` of the
configuration, 2 or 3) are compared with the reference's
(``bench/reference.py``) from the same weights and batches:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: step 1's clipped gradient as the optimizer got it (the
  program's first Adam moment over ``1 - b1``), by the worst slice (one
  layer of a stacked leaf, or a whole other leaf): the gap between the two
  norms over the larger of the reference's norm of that slice and of the
  median slice;
- ``update_gap``: the same for the change of the weights over those
  steps, leaving out slices whose reference gradient is under a thousandth
  of the median slice's (they move by weight decay and round-off alone).

Limits live in ``bench/limits/<cell>.json`` with the readings they were set
from.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Optional, Set, Tuple

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
NUMBERS = ("loss_gap", "grad_gap", "update_gap")
QUIET_GRAD = 1e-3


def _slices(tree: Dict) -> Dict[Tuple[str, int], float]:
    out = {}
    for name, v in tree.items():
        for i, x in enumerate(np.atleast_1d(np.asarray(v, np.float64))):
            out[(name, i)] = float(x)
    return out


def worst(prog: Dict, ref: Dict, keep: Optional[Set] = None):
    p, r = _slices(prog), _slices(ref)
    keys = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in keys]))
    gaps = {k: abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in keys}
    key = max(gaps, key=lambda k: gaps[k] if math.isfinite(gaps[k])
              else math.inf)
    return gaps[key], f"{key[0]}[{key[1]}]"


def readings(prog: Dict, ref: Dict) -> Dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad_gap, grad_at = worst(prog["grad"], ref["grad"])
    g = _slices(ref["grad"])
    med = float(np.median(list(g.values())))
    keep = {k for k, v in g.items() if v >= QUIET_GRAD * med}
    update_gap, upd_at = worst(prog["delta"], ref["delta"], keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap, "worst": {"grad_gap": grad_at,
                                                "update_gap": upd_at},
            "left_out": sorted(f"{n}[{i}]" for n, i in set(g) - keep)}


def load_limits(workload: str) -> Dict[str, float]:
    path = LIMITS_DIR / f"{workload}.json"
    lim = json.loads(path.read_text())["limits"]
    return {k: float(lim[k]) for k in NUMBERS}


def judge(numbers: Dict, limits: Dict[str, float]) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
