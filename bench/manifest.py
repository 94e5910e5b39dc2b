"""``BENCHMARK.json`` and the files each entry names.

A cell's configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``, its limits ``bench/limits/<cell>.json``
and each per-layer metric's reader ``bench/metrics/<metric>.py``: a new
cell, mix or metric is new files plus new entries, with no code edited.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load(path: Path = MANIFEST) -> Dict:
    return json.loads(Path(path).read_text())


def cell(manifest: Dict, workload: str) -> Dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config(name: str) -> Dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> Dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def metrics_of(manifest: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]
