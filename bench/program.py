"""The benchmark's one door into the system under test (``src/repro``).

It builds a cell's training run through ``repro.launch.train.setup`` from
the configuration file's flags, exactly as the command line would, and maps
the benchmark's flat weights onto the program's parameter tree and back
(through the model family's file, ``bench/models``).  Nothing of the
program reaches the reference.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"


def import_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def setup(cfg: Dict):
    """``launch.train.setup`` for the cell: returns its run dict.  The
    program's own data stream is closed here; the benchmark feeds its own
    traffic through the program's ``Prefetcher``."""
    from repro.launch import train

    flags = ["--arch", cfg["program"]["arch"], *cfg["program"]["flags"],
             "--seed", "0"]
    run = train.setup(train.parse_args(flags))
    drain(run.pop("data"))
    return run


def prefetch(it):
    from repro.data import Prefetcher

    return Prefetcher(it)


def drain(prefetcher) -> None:
    """Stop a ``Prefetcher`` and let its thread end."""
    prefetcher.close()
    for _ in prefetcher:
        pass


def enable_compile_cache() -> str:
    from repro import compile_cache

    return compile_cache.enable()


def flat_from_params(cfg: Dict, params) -> Dict:
    """The program's parameter tree -> the flat names of the
    configuration's model family (``bench/weights.py``)."""
    from bench import weights

    return weights.family(cfg).to_flat(params)


def params_from_flat(cfg: Dict, flat: Dict, like) -> Dict:
    """The flat weights in the program's tree; leaves the flat layout does
    not hold are taken from ``like``."""
    from bench import weights

    return weights.family(cfg).from_flat(flat, like)


def mesh_coords(plan):
    """``{device id: (pipeline stage, EP rank)}`` of the plan's mesh."""
    import numpy as np

    names = plan.mesh.axis_names
    devs = np.asarray(plan.mesh.devices)
    out = {}
    for idx in np.ndindex(devs.shape):
        pos = dict(zip(names, idx))
        stage = pos.get(plan.pp_axis, 0) if plan.pp_axis else 0
        out[int(devs[idx].id)] = (int(stage), int(pos.get(plan.ep_axis, 0)))
    return out
