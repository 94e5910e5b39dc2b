"""Chip smoke test: train granite-moe-3b-a800m at its published widths on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # EP=4 and PP=2 x EP=2 on a 2x2 host

One chip: checks the ragged expert kernel against its jnp oracle, then takes
a few optimizer steps in each expert-dispatch mode (``capacity``, the config
default, and ``ragged``, the planner's choice) through the same entry
points as ``launch/train.py``: its ``parse_args`` and ``setup`` (planner
binding, ``LanguageModel``, ``training.init_state``, ``Trainer``), then
``Trainer.fit``.

Four chips: takes one step of the same model, seed and batch with expert
parallelism over all four chips and with a two-stage pipeline of two-way
expert parallelism, and compares each first-step loss with one device's.

Widths are published (d_model 1536, 24/8 heads of 64, 40 experts top-8 of
d_ff 512, vocab 49,155, seq 4096); only depth is cut, by whole periods.
Every number printed comes from the device named in the ``[device]`` line.
The last stdout line is ``{"ok": true, "device": {...}}``; any failure, or a
first device that is not a TPU, exits non-zero without printing it.  All
phases run in this one process, which is the only one that touches the chip.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "granite-moe-3b-a800m"
# Depth: 4 of the 32 layers (period 1).  On a v5e-described compile the
# 4-layer step holds 5.75 GB of fp32 params + Adam moments (aliased in and
# out) and 6.5 GB of temporaries in ragged mode; 5 layers would leave under
# 1 GB of the 16 GB for the runtime.
LAYERS = 4
BATCH, SEQ = 4, 4096
STEPS = 5
SEED = 0
# Initial loss: uniform random tokens over a fresh init sit at ln(vocab)
# plus the small aux/z losses and the init's logit spread.
LOSS0_BAND = 0.5
# Ragged-kernel check: fp32 operands under "highest" precision leave only
# summation-order differences (~1e-6 relative); bf16 passes (~4e-3) fail.
KERNEL_ROWS, KERNEL_OCCUPIED = 1024, 1000
KERNEL_RTOL = 1e-4
# Four chips vs one: the same fresh init and batch.  Compared is the
# language-model cross-entropy.  EP and PP change only reduction orders of
# bf16 activations (sequence shards, per-stage microbatches), which moves
# a ~11 nat loss by well under 1e-2 (EP=4 measured 1.2e-4 on a v5e); a
# wrong expert shard, a dropped stage or a double-counted microbatch moves
# it by far more.  The load-balancing aux loss is left out of the
# comparison: it is a product of per-batch routing fractions, and a
# pipeline takes it per microbatch, whose smaller token count raises it by
# design (+0.025 nats over 4 layers at 4,096-token microbatches on a v5e).
FOUR_CHIP_CE_ATOL = 1e-2


def _label(dev) -> str:
    return f"({dev.platform} {dev.device_kind})"


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check_ragged_kernel(dev) -> None:
    """Forward and grad of the ragged expert FFN on KERNEL_ROWS sorted rows
    at published widths vs ``ref.ragged_ffn``.  The oracle gathers a full
    expert weight per row, so it runs in row blocks that fit the chip."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.kernels.moe_gemm import ops, ref

    arch = get_arch(ARCH)
    E, d, f = arch.moe.num_experts, arch.d_model, arch.moe.d_ff
    T, bs = KERNEL_ROWS, 128
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    ids = jnp.sort(jax.random.randint(ks[0], (KERNEL_OCCUPIED,), 0, E))
    counts = jnp.zeros((E,), jnp.int32).at[ids].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    x = jax.random.normal(ks[1], (T, d), jnp.float32)
    wu = jax.random.normal(ks[2], (E, d, f), jnp.float32) / math.sqrt(d)
    wg = jax.random.normal(ks[3], (E, d, f), jnp.float32) / math.sqrt(d)
    wd = jax.random.normal(ks[4], (E, f, d), jnp.float32) / math.sqrt(f)
    ct = jax.random.normal(ks[5], (T, d), jnp.float32)

    def kernel(x, wu, wg, wd, offsets, ct):
        y, vjp = jax.vjp(
            lambda *a: ops.ragged_ffn(*a, offsets, "swiglu"), x, wu, wg, wd
        )
        return (y,) + vjp(ct)

    @jax.jit
    def oracle_block(xb, wu, wg, wd, off, ctb):
        y, vjp = jax.vjp(
            lambda *a: ref.ragged_ffn(*a, off, "swiglu"), xb, wu, wg, wd
        )
        return (y,) + vjp(ctb)

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(x, wu, wg, wd, offsets, ct).compile()
        t_compile = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError("ragged kernel check: no tpu_custom_call in HLO")
        got = jax.block_until_ready(compiled(x, wu, wg, wd, offsets, ct))
        ys, gx, gu, gg, gd = [], [], 0.0, 0.0, 0.0
        for a in range(0, T, bs):
            off = jnp.clip(offsets - a, 0, bs)
            y_b, gx_b, gu_b, gg_b, gd_b = oracle_block(
                x[a:a + bs], wu, wg, wd, off, ct[a:a + bs]
            )
            ys.append(y_b)
            gx.append(gx_b)
            gu, gg, gd = gu + gu_b, gg + gg_b, gd + gd_b
        want = (jnp.concatenate(ys), jnp.concatenate(gx), gu, gg, gd)
    errs = {
        name: _rel_err(g, w)
        for name, g, w in zip(("y", "dx", "dw_up", "dw_gate", "dw_down"),
                              got, want)
    }
    print(f"[kernel] ragged_ffn T={T} (occupied {KERNEL_OCCUPIED}) E={E} "
          f"d={d} f={f} fp32 @highest vs ref: "
          + " ".join(f"{k}={v:.2e}" for k, v in errs.items())
          + f" (tol {KERNEL_RTOL:.0e}); compile {t_compile:.1f}s {_label(dev)}")
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_RTOL}
    if bad:
        raise RuntimeError(f"ragged kernel disagrees with its oracle: {bad}")


def train_phase(name: str, argv, dev, steps: int, check_hlo: bool = False):
    """Train through ``launch/train.py``'s setup on one repeated batch and
    return a dict of ``losses``, the final ``state``, the ``plan`` and the
    last step's ``metrics``.

    The batch repeats because uniform random tokens cannot be learned below
    ln(vocab): across fresh batches a few steps' fall hides in batch noise,
    while on one batch gradient descent must lower the loss."""
    import numpy as np

    from repro.launch import train

    args = train.parse_args(
        ["--arch", ARCH, "--layers", str(LAYERS), "--batch", str(BATCH),
         "--seq", str(SEQ), "--steps", str(steps), "--seed", str(SEED)]
        + argv
    )
    print(f"[{name}] launch.train {' '.join(argv)}")
    run = train.setup(args)
    arch, plan, trainer = run["arch"], run["plan"], run["trainer"]
    trainer.cfg.log_every = 1
    batch = next(run["data"])
    run["data"].close()

    with plan.mesh:
        t0 = time.perf_counter()
        compiled = trainer.train_step.lower(run["state"], batch).compile()
        t_compile = time.perf_counter() - t0
        hlo = compiled.as_text()
        n_custom = hlo.count("tpu_custom_call")
        mem = compiled.memory_analysis()
        print(f"[{name}] compile {t_compile:.1f}s; step program: "
              f"{n_custom} tpu_custom_call, args "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temp "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB {_label(dev)}")
        if check_hlo and n_custom == 0:
            raise RuntimeError(f"{name}: ragged step has no tpu_custom_call")
        out = trainer.fit(run["state"], itertools.repeat(batch))

    losses = [loss for _, loss in trainer.losses]
    times = trainer.step_times
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{name}] layers kept {arch.num_layers}/32, tokens/step "
          f"{BATCH * SEQ}, losses {' '.join(f'{v:.4f}' for v in losses)}")
    warm = (f", mean step after warm-up {np.mean(times[1:]):.4f}s over "
            f"{len(times) - 1} steps" if len(times) > 1 else "")
    print(f"[{name}] step 0 (incl. compile-cache load) {times[0]:.3f}s{warm}, "
          f"process peak_bytes_in_use so far "
          f"{peak / 1e9 if peak else float('nan'):.2f} GB {_label(dev)}")
    if len(losses) != steps or not all(math.isfinite(v) for v in losses):
        raise RuntimeError(f"{name}: non-finite or missing losses {losses}")
    if out["anomalies"]:
        raise RuntimeError(f"{name}: skipped steps {out['anomalies']}")
    metrics = {k: float(v) for k, v in out["metrics"].items()
               if k in ("loss", "ce", "moe_aux_loss", "moe_z_loss")}
    return {"losses": losses, "state": out["state"], "plan": plan,
            "metrics": metrics}


def check_loss_curve(name: str, losses, vocab: int) -> None:
    ln_v = math.log(vocab)
    if abs(losses[0] - ln_v) > LOSS0_BAND:
        raise RuntimeError(
            f"{name}: first loss {losses[0]:.4f} not within {LOSS0_BAND} of "
            f"ln(vocab) = {ln_v:.4f}"
        )
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"{name}: loss did not fall: {losses}")


def check_expert_shards(name: str, state, plan) -> None:
    """Each device must hold its own share of every expert weight, not a
    copy of another device's."""
    import numpy as np

    w = state["params"]["blocks"][0]["ffn"]["w_up"]  # (reps, E, d, f)
    shards = w.addressable_shards
    idx = {str(s.index) for s in shards}
    e_share = {s.data.shape[1] for s in shards}
    devs = {s.device.id for s in shards}
    E = w.shape[1]
    if len(devs) != plan.num_devices or e_share != {E // plan.ep}:
        raise RuntimeError(
            f"{name}: w_up shards {[(s.device.id, s.data.shape) for s in shards]}"
            f" are not {E // plan.ep} experts per EP rank"
        )
    # Devices of one EP rank share a slice; distinct ranks must differ.
    by_index = {}
    for s in shards:
        by_index.setdefault(str(s.index), np.asarray(s.data))
    slices = list(by_index.values())
    if len(idx) < plan.ep or any(
        np.array_equal(a, b) for a, b in itertools.combinations(slices, 2)
    ):
        raise RuntimeError(f"{name}: expert shards repeat across EP ranks")
    print(f"[{name}] w_up: {len(idx)} distinct expert shards of "
          f"{E // plan.ep} experts over devices {sorted(devs)}")


def one_chip(dev) -> None:
    from repro.configs import get_arch

    check_ragged_kernel(dev)
    vocab = get_arch(ARCH).vocab_size
    for mode in ("capacity", "ragged"):
        # Only the losses are kept: the phase's final state is dropped
        # before the next phase builds its own; two would not fit one chip.
        losses = train_phase(
            f"train-{mode}", ["--dispatch", mode], dev, STEPS,
            check_hlo=mode == "ragged",
        )["losses"]
        check_loss_curve(f"train-{mode}", losses, vocab)


def _fmt(metrics) -> str:
    return " ".join(f"{k}={v:.5f}" for k, v in metrics.items())


def four_chips(dev) -> None:
    ref = train_phase("one-device", ["--mesh", "1,1"], dev, 1)["metrics"]
    print(f"[one-device] first step: {_fmt(ref)}")
    for name, argv in (("ep4", ["--mesh", "1,4"]),
                       ("pp2-ep2", ["--mesh", "2,1,2", "--pipeline"])):
        run = train_phase(name, argv, dev, 1)
        got = run["metrics"]
        diff = abs(got["ce"] - ref["ce"])
        print(f"[{name}] first step: {_fmt(got)}; |ce - one-device ce| "
              f"{diff:.2e} (tol {FOUR_CHIP_CE_ATOL:.0e})")
        if not diff <= FOUR_CHIP_CE_ATOL:
            raise RuntimeError(f"{name}: first-step ce off by {diff:.3e}")
        check_expert_shards(name, run["state"], run["plan"])
        del run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP=4 and PP=2 x EP=2 phases and the "
                         "one-device run they are compared with")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no {SRC / 'repro'}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chips else 1
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro import compile_cache

    print(f"[cache] {compile_cache.enable()}")
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(dev)
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
