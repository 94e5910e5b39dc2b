"""Expert-migration benchmark: closing the loop from skew to step time.

Three parts:

* **Controller simulation** — a synthetic skewed router (Zipf-weighted
  expert popularity whose hot expert drifts mid-run) drives the real
  controller stack (``core.migration``: LoadStats EMA -> plan_layer swaps
  + replica channels) in three modes: ``static`` (no rebalancing),
  ``swap_only`` (Algorithm 2), and ``replicated`` (swaps + hot-expert
  replica channels).  Emits the per-step imbalance trajectory and every
  rebalance event (swaps, replicas, wire bytes).
* **Model pricing** — each trajectory is priced step by step through
  ``core.resource_model.estimate`` on FRONTIER (Table IV constants), with
  each applied rebalance paying its full ``migration_time`` transfer
  quote.  The headline is ``modeled_recovery_frac``: the fraction of the
  skew-induced step-time loss (static vs always-balanced ideal) the
  rebalanced run recovers, net of transfer costs.
* **Measured step time** — a real (2, 4) host mesh (EP=4) trains a
  reduced MoE arch on the same low-entropy token stream, static vs
  rebalanced, and reports the measured mean step wall-clock (runs in a
  subprocess so the 8-device XLA flag applies regardless of the caller's
  environment).

Emits ``BENCH_migration.json``:

    PYTHONPATH=src python benchmarks/migration_bench.py [--out F]
    PYTHONPATH=src python benchmarks/migration_bench.py --smoke \
        --check-schema BENCH_migration.json    # CI schema-rot gate
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_OUT = ROOT / "BENCH_migration.json"

# Simulation shape: E experts over ep groups, L independent layers.
E, EP, LAYERS, R = 8, 4, 2, 2
TOKENS_PER_STEP = 4096
ZIPF_S = 1.4
MIGRATE_EVERY = 5
THRESHOLD = 1.05


def synth_loads(T: int, seed: int = 0):
    """(T, LAYERS, E) per-step token counts from a drifting Zipf router:
    expert popularity follows 1/rank^s and the rank order rotates mid-run
    (the regime where a one-shot placement goes stale)."""
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, E + 1) ** ZIPF_S
    order = rng.permutation(E)
    out = np.empty((T, LAYERS, E))
    for t in range(T):
        if t == T // 2:
            order = np.roll(order, E // 2)  # the hot experts move
        p = weights[np.argsort(order)]
        p = p / p.sum()
        for l in range(LAYERS):
            out[t, l] = rng.multinomial(TOKENS_PER_STEP, p)
    return out


def simulate(loads, mode: str):
    """Run the controller over a load trajectory.

    Returns (imbalance per step, active replica count per step, events).
    """
    from repro.core import migration as mig

    T = loads.shape[0]
    ls = mig.LoadStats(LAYERS, E)
    assign = np.tile(np.arange(E, dtype=np.int32), (LAYERS, 1))
    reps = (np.full((LAYERS, R), E, dtype=np.int32)
            if mode == "replicated" else None)
    imb_t, reps_t, events = [], [], []
    for t in range(T):
        ls.update(loads[t])
        imb = ls.imbalance(assign, EP, reps)
        if (mode != "static" and t % MIGRATE_EVERY == 0
                and imb > THRESHOLD):
            swaps = n_rep = 0
            for l in range(LAYERS):
                new_a, new_r, _, s = mig.plan_layer(
                    ls.ema[l], assign[l],
                    reps[l] if reps is not None else None, EP,
                )
                assign[l] = new_a
                swaps += s
                if new_r is not None:
                    reps[l] = new_r
                    n_rep += int((new_r < E).sum())
            imb_after = ls.imbalance(assign, EP, reps)
            events.append({
                "step": t,
                "imbalance_before": imb,
                "imbalance_after": imb_after,
                "swaps": swaps,
                "replicas": n_rep,
            })
            imb = imb_after
        imb_t.append(imb)
        reps_t.append(
            int((reps < E).sum(axis=1).max()) if reps is not None else 0
        )
    return imb_t, reps_t, events


def price(imb_t, reps_t, events) -> float:
    """Total modeled seconds for a trajectory on FRONTIER, each applied
    rebalance paying its full Table-IV transfer quote."""
    from repro.configs import get_arch
    from repro.core import resource_model as rm
    from repro.core.platform import FRONTIER

    m = rm.ModelShape.from_arch(get_arch("granite-moe-3b-a800m"))

    def t_step(imb, reps):
        t = rm.TrainSetup(b=256, s=4096, PP=2, EP=8, DP=8,
                          imbalance=max(imb, 1.0), replicas=reps)
        return rm.estimate(m, t, FRONTIER).t_step

    total = sum(t_step(i, r) for i, r in zip(imb_t, reps_t))
    if events:
        t = rm.TrainSetup(b=256, s=4096, PP=2, EP=8, DP=8)
        _, t_mig = rm.migration_time(m, t, FRONTIER)
        total += t_mig * len(events)
    return total


def measured_child(steps: int) -> None:
    """Subprocess body: real (2,4) mesh, static vs rebalanced trainer on
    the same skewed stream; prints one MEASURED json line."""
    import dataclasses

    import jax

    from repro import training
    from repro.configs import get_arch
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.runtime import Trainer, TrainerConfig
    from repro.sharding import host_mesh, make_plan

    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, capacity_factor=8.0,
                                aux_loss_coef=0.0, max_replicas=2)
    )
    mesh = host_mesh((2, 4), ("data", "model"))
    plan = make_plan(mesh, arch)
    lm = LanguageModel(arch, plan)
    opt = OptimizerConfig(lr=1e-3)

    def batch_at(s):
        rng = np.random.default_rng(s)
        toks = rng.integers(0, 4, size=(8, 32), dtype=np.int32)
        return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}

    def run(rebalance: bool):
        cfg = TrainerConfig(
            migrate_every=4 if rebalance else 10 ** 9,
            migrate_threshold=1.05, log_every=10 ** 9,
        )
        tr = Trainer(lm, opt, cfg, log_fn=lambda m: None)
        with plan.mesh:
            state = training.init_state(lm, jax.random.PRNGKey(0), opt)
            times = []
            for s in range(steps):
                t0 = time.perf_counter()
                state, met = tr.train_step(state, batch_at(s))
                loads = np.asarray(jax.device_get(met["expert_load"]))
                tr.load_stats.update(np.concatenate(
                    [loads[:, i, :] for i in range(loads.shape[1])]
                ))
                if rebalance:
                    state = tr._maybe_migrate(state, s + 1)
                times.append(time.perf_counter() - t0)
        # drop the compile step
        return float(np.mean(times[1:])), len(tr.migrations)

    static_s, _ = run(False)
    rebal_s, n_mig = run(True)
    print("MEASURED " + json.dumps({
        "steps": steps,
        "static_step_ms": static_s * 1e3,
        "rebalanced_step_ms": rebal_s * 1e3,
        "migrations_applied": n_mig,
    }))


def measure(steps: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # host devices; never the chip
    env["PYTHONPATH"] = f"{ROOT}/src"
    out = subprocess.run(
        [sys.executable, __file__, "--measure-child", str(steps)],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("MEASURED "):
            return json.loads(line[len("MEASURED "):])
    raise RuntimeError(
        f"measured child produced no MEASURED line:\n{out.stdout}\n{out.stderr}"
    )


def run(T: int, measure_steps: int) -> dict:
    from repro.core import migration as mig

    loads = synth_loads(T)
    modes = {}
    for mode in ("static", "swap_only", "replicated"):
        imb_t, reps_t, events = simulate(loads, mode)
        modes[mode] = {
            "imbalance": [round(i, 4) for i in imb_t],
            "final_imbalance": imb_t[-1],
            "mean_imbalance": float(np.mean(imb_t)),
            "events": events,
            "total_swaps": sum(e["swaps"] for e in events),
            "max_replicas_active": max(reps_t),
            "modeled_total_s": price(imb_t, reps_t, events),
        }

    ideal_total = price([1.0] * T, [0] * T, [])
    static_total = modes["static"]["modeled_total_s"]
    rebal_total = modes["replicated"]["modeled_total_s"]
    recovery = (static_total - rebal_total) / max(
        static_total - ideal_total, 1e-12
    )

    # The swap-only blind spot the tentpole closes: the dominant expert's
    # EMA share lower-bounds what swaps alone can reach; replica channels
    # must land below that floor.
    ls = mig.LoadStats(LAYERS, E)
    for t in range(T):
        ls.update(loads[t])
    floor = max(mig.swap_floor(ls.ema[l], EP) for l in range(LAYERS))

    return {
        "meta": {
            "T": T,
            "experts": E,
            "ep": EP,
            "layers": LAYERS,
            "replica_channels": R,
            "tokens_per_step": TOKENS_PER_STEP,
            "zipf_s": ZIPF_S,
            "migrate_every": MIGRATE_EVERY,
            "threshold": THRESHOLD,
        },
        "modes": modes,
        "modeled": {
            "ideal_total_s": ideal_total,
            "static_total_s": static_total,
            "swap_only_total_s": modes["swap_only"]["modeled_total_s"],
            "rebalanced_total_s": rebal_total,
            "recovery_frac": recovery,
            "swap_floor": floor,
        },
        "measured": measure(measure_steps),
        "summary": {
            "modeled_recovery_frac": recovery,
            "recovery_ge_half": bool(recovery >= 0.5),
            "replication_beats_swap_floor": bool(
                modes["replicated"]["final_imbalance"] < floor
                and modes["replicated"]["max_replicas_active"] > 0
            ),
            "rebalance_beats_static": bool(rebal_total < static_total),
        },
    }


def rows(smoke: bool = True):
    """benchmarks.run integration: (name, us_per_call, derived) rows."""
    rec = run(T=20 if smoke else 60, measure_steps=4 if smoke else 10)
    s = rec["summary"]
    out = []
    for mode, r in rec["modes"].items():
        out.append((
            f"migration_{mode}",
            r["modeled_total_s"] / rec["meta"]["T"] * 1e6,
            f"mean_imb={r['mean_imbalance']:.3f} swaps={r['total_swaps']} "
            f"replicas={r['max_replicas_active']}",
        ))
    out.append((
        "migration_recovery",
        0.0,
        f"recovery={s['modeled_recovery_frac']:.2f} "
        f"beats_floor={s['replication_beats_swap_floor']} "
        f"measured={rec['measured']['rebalanced_step_ms']:.0f}ms/step",
    ))
    return out


def schema(node):
    """Recursive key structure (dict keys; list element schema)."""
    if isinstance(node, dict):
        return {k: schema(v) for k, v in sorted(node.items())}
    if isinstance(node, list):
        return [schema(node[0])] if node else []
    return "leaf"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trajectory — schema/CI mode")
    ap.add_argument("--out", type=Path, default=None)
    ap.add_argument("--check-schema", type=Path, default=None,
                    help="compare the emitted JSON's key structure against "
                         "this committed file; exit 1 on drift")
    ap.add_argument("--measure-child", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.measure_child is not None:
        measured_child(args.measure_child)
        return

    if args.smoke:
        rec = run(T=20, measure_steps=4)
    else:
        rec = run(T=60, measure_steps=10)

    if args.check_schema:
        committed = json.loads(args.check_schema.read_text())
        if schema(committed) != schema(rec):
            print(f"SCHEMA DRIFT: {args.check_schema} no longer matches "
                  f"what this bench emits — regenerate and commit it.",
                  file=sys.stderr)
            sys.exit(1)
        print(f"schema ok: {args.check_schema}")
        return

    out = args.out or DEFAULT_OUT
    out.write_text(json.dumps(rec, indent=1) + "\n")
    s = rec["summary"]
    print(f"wrote {out}")
    print(f"modeled recovery {s['modeled_recovery_frac']:.2f} "
          f"(>=0.5: {s['recovery_ge_half']}); replication beats swap "
          f"floor: {s['replication_beats_swap_floor']}")


if __name__ == "__main__":
    main()
