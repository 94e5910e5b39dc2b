"""Weights made from the seed, in a model family's flat layout.

A configuration's ``model_type`` names its family, a file
``bench/models/<model_type>.py`` that gives:

- ``shapes(cfg)``: ``{leaf name: shape}``, in an order that fixes each
  leaf's key; ``LAYER_LEAVES``: the leaves stacked over layers on their
  first axis;
- ``dims(cfg)``: at least ``L`` (layers), ``H`` (query heads) and ``hd``
  (head size), which ``bench/flops.py`` reads;
- ``active_matmul_params(cfg)``: the matmul parameters one token uses;
- ``loss_fn(p, tokens, labels, cfg, dot, dot_w)``: the plain float32
  loss, returning ``(loss, (ce, aux, z))``;
- ``to_flat(params)`` and ``from_flat(flat, like)``: the map between the
  program's parameter tree and the flat layout.

A new family is a new file there, with no code edited.  The same weights
feed the program (through ``from_flat``) and the reference, so the
reference takes nothing the program made.  Every leaf is float32.

Initialisation: a leaf named ``embed`` N(0, 0.02), leaves whose names end
in ``norm`` 0 (the norms multiply by 1 + scale), every other matrix
N(0, 1/fan_in) with the fan-in on the second-to-last axis.
"""

from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import Dict, Tuple

MODELS_DIR = Path(__file__).resolve().parent / "models"


@functools.lru_cache(maxsize=None)
def _load(model_type: str):
    path = MODELS_DIR / f"{model_type}.py"
    if not path.is_file():
        raise KeyError(f"no model family {model_type!r}: add "
                       f"bench/models/{model_type}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_model_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: Dict):
    """The module of the configuration's model family."""
    return _load(cfg["model_type"])


def shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    return family(cfg).shapes(cfg)


def make(cfg: Dict, key):
    """All leaves from ``key = jax.random.key(seed)``; call under
    ``jax.jit`` (the key as an argument, so one program serves every seed)
    to build them on the device."""
    import jax
    import jax.numpy as jnp

    out = {}
    for i, (name, shape) in enumerate(shapes(cfg).items()):
        if name.endswith("norm"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        scale = 0.02 if name == "embed" else 1.0 / math.sqrt(shape[-2])
        k = jax.random.fold_in(key, i)
        out[name] = jax.random.normal(k, shape, jnp.float32) * scale
    return out


def slice_norms(cfg: Dict, flat):
    """Per-slice L2 norms: one per layer of a layer leaf, one per other
    leaf.  Returns ``{name: (L,) or ()}`` float32 arrays."""
    import jax.numpy as jnp

    layer_leaves = family(cfg).LAYER_LEAVES
    out = {}
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        if name in layer_leaves:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x),
                                         axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out
