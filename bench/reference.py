"""Plain float32 reference: a model's loss and its first AdamW steps.

Independent of ``src/repro``: it reads the configuration file, takes the
loss of its model family and the weights from ``bench/weights.py`` (the
family's file under ``bench/models``) and the batches from
``bench/gen.py``, and computes in ``jax.numpy`` at ``precision=HIGHEST``:
no kernels, no capacity, no pipeline.  Its memory is bounded (see the
family's loss) so that one v5e holds it at the benchmark's sizes once the
program's state is freed.  The optimizer is AdamW with global-norm
clipping, linear warm-up and cosine decay.

A family whose loss takes state besides the weights (``deepseek_v3``: the
routers' selection bias, moved after every step from the step's expert
loads) gives ``initial_state(cfg)`` and ``after_step(state, aux, cfg)``,
where ``aux`` is the second value its ``loss_fn`` returns; the state is
passed to ``loss_fn`` after ``dot_w`` and moved between steps, as the
program moves it.

``mode="fp8"`` is the control: the same computation with the operands of
every weight matmul rounded to float8 (e4m3, per-tensor scale), the
precision below the configuration's bfloat16 compute.  ``fault`` plants
the faults the comparison must catch (see ``FAULTS``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import weights

# half_batch: the loss of the first half of each batch's rows alone.
FAULTS = ("half_batch",)


def _dots(mode: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=hi)

    if mode == "highest":
        return dot, dot
    if mode != "fp8":
        raise ValueError(f"unknown reference mode {mode!r}")

    def q8(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return x + lax.stop_gradient(r - x)  # straight-through rounding

    def dot_w(spec, a, b):
        return dot(spec, q8(a), q8(b))

    return dot, dot_w


def lr_at(opt: Dict, t):
    """Learning rate of optimizer step ``t`` (1-based)."""
    import jax.numpy as jnp

    t = t.astype(jnp.float32)
    warm = t / max(opt["warmup_steps"], 1)
    decay = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = jnp.clip((t - opt["warmup_steps"]) / decay, 0.0, 1.0)
    r = opt["min_lr_ratio"]
    cos = r + (1 - r) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * jnp.where(t < opt["warmup_steps"], warm, cos)


def train_step(p, m, v, step, tokens, labels, cfg, mode="highest",
               fault=None, state=None):
    """One AdamW step.  Returns ``(p, m, v, loss, clipped-gradient slice
    norms, state after the step)``; ``step`` counts the steps already
    taken, ``state`` is the family's loss state (None where it has
    none)."""
    import jax
    import jax.numpy as jnp

    opt = cfg["training"]["optimizer"]
    if fault == "half_batch":
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    dot, dot_w = _dots(mode)
    fam = weights.family(cfg)
    extra = () if state is None else (state,)
    (loss, aux), g = jax.value_and_grad(fam.loss_fn, has_aux=True)(
        p, tokens, labels, cfg, dot, dot_w, *extra)
    if state is not None:
        state = fam.after_step(state, aux, cfg)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    g = {name: x * jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
         for name, x in g.items()}
    t = step + 1
    lr = lr_at(opt, t)
    bc1 = 1.0 - opt["b1"] ** t.astype(jnp.float32)
    bc2 = 1.0 - opt["b2"] ** t.astype(jnp.float32)
    m = {kk: opt["b1"] * m[kk] + (1 - opt["b1"]) * g[kk] for kk in p}
    v = {kk: opt["b2"] * v[kk] + (1 - opt["b2"]) * jnp.square(g[kk])
         for kk in p}
    p = {kk: p[kk] - lr * (m[kk] / bc1 / (jnp.sqrt(v[kk] / bc2) + opt["eps"])
                           + opt["weight_decay"] * p[kk]) for kk in p}
    return p, m, v, loss, weights.slice_norms(cfg, g), state


def _shardings(cfg, devices):
    """Each state leaf split over the devices on its largest axis that
    divides; batches split by row.  None on one device."""
    if len(devices) <= 1:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("x",))
    nd = len(devices)

    def spec(shape):
        axes = sorted(range(len(shape)), key=lambda i: -shape[i])
        for ax in axes:
            if shape[ax] % nd == 0:
                return NamedSharding(mesh, P(*[("x" if i == ax else None)
                                              for i in range(len(shape))]))
        return NamedSharding(mesh, P())

    state = {name: spec(sh) for name, sh in weights.shapes(cfg).items()}
    return state, NamedSharding(mesh, P("x", None))


class Reference:
    """The reference's jitted programs for one configuration, mode, fault
    and set of devices; ``run`` takes one seed's steps."""

    def __init__(self, cfg: Dict, mode: str = "highest",
                 fault: Optional[str] = None, devices=None):
        import jax
        import jax.numpy as jnp

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.cfg = cfg
        self.st_sh, self.batch_sh = _shardings(
            cfg, devices or jax.devices()[:1])
        self._make = jax.jit(lambda kk: weights.make(cfg, kk),
                             out_shardings=self.st_sh)
        self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                              out_shardings=self.st_sh)
        fam = weights.family(cfg)
        self._state0 = (fam.initial_state(cfg) if hasattr(fam, "after_step")
                        else None)
        self._step = jax.jit(
            lambda p_, m_, v_, t, x, y, s_: train_step(
                p_, m_, v_, t, x, y, cfg, mode, fault, s_),
            donate_argnums=(0, 1, 2),
            out_shardings=(self.st_sh, self.st_sh, self.st_sh, None, None,
                           None),
        )

    def run(self, seed: int, batches: List[Dict[str, np.ndarray]]) -> Dict:
        """Take ``len(batches)`` steps from the seed's weights.

        Returns ``{"losses": [...], "grad": {name: norms of step 1's
        clipped gradient}, "delta": {name: norms of (weights after the last
        step - weights at the seed)}}`` as numpy values.
        """
        import jax
        import jax.numpy as jnp

        key = jax.random.key(seed)
        p = self._make(key)
        m, v = self._zeros(p), self._zeros(p)
        state = self._state0
        losses, grad = [], None
        for i, bt in enumerate(batches):
            tok, lab = (jax.device_put(bt[kk], self.batch_sh)
                        if self.batch_sh else bt[kk]
                        for kk in ("tokens", "labels"))
            p, m, v, loss, gn, state = self._step(p, m, v, jnp.int32(i), tok,
                                                  lab, state)
            losses.append(float(loss))
            if grad is None:
                grad = jax.tree.map(np.asarray, gn)
        del m, v
        return {"losses": losses, "grad": grad,
                "delta": delta_norms(self.cfg, p, key)}


def delta_norms(cfg: Dict, flat, key):
    """Slice norms of ``flat - weights.make(cfg, key)``, regenerating the
    seed's weights on the device."""
    import jax

    fn = jax.jit(lambda f, kk: weights.slice_norms(
        cfg, {n_: f[n_] - w for n_, w in weights.make(cfg, kk).items()}))
    return jax.tree.map(np.asarray, fn(flat, key))
