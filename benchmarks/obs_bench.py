"""Observability benchmark: telemetry overhead + model-vs-measured drift.

Two halves:

* **Overhead** — wall-clocks the trainer hot-loop instrumentation pattern
  (one ``train.step`` span holding the skipped-flag fetch's counter and
  ``train.fetch`` span, exactly what ``runtime.trainer`` emits per step) around a
  warmed jitted train step, in alternating rounds with telemetry fully on
  (ring buffer + JSONL sink) and fully off (the ``_NULL_SPAN`` path).
  ``overhead_frac = enabled/disabled - 1`` is the acceptance number
  (scripts/ci.sh gates it at <= 2% of step time); per-event-type
  microcosts (span/instant/counter, enabled and disabled) localize any
  regression.
* **Drift** — one measured-vs-modeled ratio per resource-model phase:
  ``step`` (train.step spans vs ``Estimate.t_step``), ``ckpt``
  (``ckpt.save`` spans vs ``Estimate.t_ckpt``), ``a2a`` (the monolithic
  dispatch collective vs ``comm_model.flat_a2a_time`` on the same
  ``A2ACase``), and ``decode``/``prefill`` (engine spans vs
  ``ServeEstimate``).  Everything here runs on XLA:CPU while the model
  prices TPU v5e, so the absolute ratios are *structural* — the artifact
  is the coverage (every phase has a finite ratio) and the mechanism (the
  same ``DriftTracker`` path the launch scripts report through).

Emits ``BENCH_observability.json``:

    PYTHONPATH=src python benchmarks/obs_bench.py [--out F]
    PYTHONPATH=src python benchmarks/obs_bench.py --smoke \
        --check-schema BENCH_observability.json    # CI schema-rot gate
"""

from __future__ import annotations

import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
)

import argparse
import json
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_OUT = ROOT / "BENCH_observability.json"

# (timed rounds per mode, steps per round, micro-loop iters)
FULL = (4, 25, 20000)
SMOKE = (2, 6, 2000)

# a2a drift cell: (ep, rows-per-destination, d)
A2A_CELL = (4, 512, 128)
A2A_CELL_SMOKE = (2, 64, 32)


# ---------------------------------------------------------------------------
# Overhead: the trainer hot-loop instrumentation pattern, on vs off
# ---------------------------------------------------------------------------


def _train_env():
    import jax

    from repro import training
    from repro.configs import get_arch
    from repro.data import SyntheticTokens
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.sharding import single_device_plan

    arch = get_arch("smollm-360m").reduced()
    plan = single_device_plan(arch)
    lm = LanguageModel(arch, plan)
    opt = OptimizerConfig(lr=1e-3)
    state = training.init_state(lm, jax.random.PRNGKey(0), opt)
    step_fn = jax.jit(
        training.make_train_step(lm, opt), donate_argnums=(0,)
    )
    batch = next(iter(SyntheticTokens(arch.vocab_size, 2, 32)))
    return plan, arch, state, step_fn, batch


def _instrumented_round(step_fn, state, batch, n):
    """Run ``n`` steps with the exact per-step telemetry the Trainer hot
    loop emits: step span + skipped-flag fetch counter + fetch span.
    Whether anything is recorded depends on the installed global
    Telemetry — the timed code is identical in both modes."""
    import jax

    from repro import obs

    t0 = time.perf_counter()
    for i in range(n):
        with obs.span("train.step", step=i) as sp:
            state, metrics = step_fn(state, batch)
            obs.counter("train.host_fetches")
            with obs.span("train.fetch", what="skipped"):
                skipped = bool(jax.device_get(metrics.get("skipped", 0)))
            sp.set(skipped=skipped)
    return (time.perf_counter() - t0) / n, state


def measure_overhead(rounds, steps, tel_on, tel_off, ring):
    from repro import obs

    plan, arch, state, step_fn, batch = _train_env()
    with plan.mesh:
        # Warm outside any timing: first call compiles, second re-keys the
        # pjit cache for the step's own committed outputs.
        prev = obs.set_telemetry(tel_off)
        try:
            for _ in range(3):
                _, state = _instrumented_round(step_fn, state, batch, 1)
            dis, en = [], []
            # Alternate modes so drift in host load hits both equally.
            for _ in range(rounds):
                obs.set_telemetry(tel_off)
                t, state = _instrumented_round(step_fn, state, batch, steps)
                dis.append(t)
                obs.set_telemetry(tel_on)
                n_before = len(ring)
                t, state = _instrumented_round(step_fn, state, batch, steps)
                en.append(t)
                events_per_step = (len(ring) - n_before) / steps
        finally:
            obs.set_telemetry(prev)
    overhead = max(0.0, min(en) / max(min(dis), 1e-12) - 1.0)
    return {
        "disabled_s_per_step": min(dis),
        "enabled_s_per_step": min(en),
        "overhead_frac": overhead,
        "events_per_step": events_per_step,
        "round_means": {"disabled": dis, "enabled": en},
    }, (plan, arch, state)


def event_costs_us(iters, tel_on, tel_off):
    """Per-event microcosts in isolation (no jit work between events)."""
    from repro import obs

    def cost(tel, emit):
        prev = obs.set_telemetry(tel)
        try:
            t0 = time.perf_counter()
            for i in range(iters):
                emit(i)
            return (time.perf_counter() - t0) / iters * 1e6
        finally:
            obs.set_telemetry(prev)

    def span_once(i):
        with obs.span("micro.span", i=i):
            pass

    return {
        "span_enabled": cost(tel_on, span_once),
        "span_disabled": cost(tel_off, span_once),
        "instant_enabled": cost(
            tel_on, lambda i: obs.instant("micro.instant", i=i)
        ),
        "counter_enabled": cost(tel_on, lambda i: obs.counter("micro.ctr")),
    }


# ---------------------------------------------------------------------------
# Drift: one measured-vs-modeled ratio per phase
# ---------------------------------------------------------------------------


def _drift_ckpt(state):
    """Two saves of the live train state (first is the tracker's warmup)
    with the global telemetry on -> two ``ckpt.save`` spans in the ring."""
    import jax

    from repro.checkpoint import checkpointing as ck

    host = jax.device_get(state)
    with tempfile.TemporaryDirectory() as d:
        for step in (1, 2):
            ck.save_checkpoint(d, step, host)


def _drift_a2a(ep, rows, d, iters):
    """Two monolithic dispatch collectives (microbench emits one
    ``a2a.layer`` span per measurement) vs the TPU-v5e flat model priced
    on the identical A2ACase."""
    from repro.core import comm_model as cm
    from repro.core import microbench as mb
    from repro.core.platform import TPU_V5E

    for _ in range(2):
        mb.measure_a2a_overlap(
            ep, rows, d, d, part="a2a", iters=iters, warmup=1
        )
    case = cm.A2ACase(n_ranks=ep, row_bytes=rows * d * 4.0)
    return cm.flat_a2a_time(case, TPU_V5E)


def _drift_engine(n_requests, max_new):
    """Tiny serving run; the Engine's always-on telemetry ring carries the
    ``engine.prefill`` / ``engine.decode`` spans."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_arch
    from repro.models.model import LanguageModel, init_params
    from repro.serving import Engine, Request, ServeConfig
    from repro.sharding import single_device_plan

    arch = get_arch("granite-moe-3b-a800m").reduced()
    arch = arch.replace(
        moe=dataclasses.replace(arch.moe, dispatch="ragged")
    )
    plan = single_device_plan(arch)
    lm = LanguageModel(arch, plan)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            tokens=rng.integers(0, arch.vocab_size, size=int(l)),
            max_new_tokens=max_new,
        )
        for i, l in enumerate(rng.integers(4, 12, size=n_requests))
    ]
    with plan.mesh:
        params = init_params(arch, jax.random.PRNGKey(0))
        eng = Engine(
            lm, params,
            ServeConfig(max_seqs=2, block_size=4, num_blocks=64,
                        max_blocks_per_seq=16),
        )
        eng.run(reqs)
    return arch, eng


def measure_drift(smoke, train_ctx, ring):
    from repro import obs
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    plan, arch, state = train_ctx

    # Train-side modeled phases at this run's actual shape (b=2, s=32 from
    # _train_env's SyntheticTokens), priced on the target platform.
    setup = rm.TrainSetup(b=2, s=32, PP=1, EP=1, DP=1, zero="world")
    est = rm.estimate(rm.ModelShape.from_arch(arch), setup, TPU_V5E)

    ep, rows, d = A2A_CELL_SMOKE if smoke else A2A_CELL
    import jax

    ep = min(ep, len(jax.devices()))
    a2a_modeled = _drift_a2a(ep, rows, d, iters=2 if smoke else 5)
    _drift_ckpt(state)

    serve_arch, eng = _drift_engine(
        n_requests=2 if smoke else 4, max_new=4 if smoke else 6
    )
    ssetup = rm.ServeSetup(
        batch=2, context=16, prefill_len=8,
        dispatch=serve_arch.moe.dispatch,
    )
    se = rm.serve_estimate(
        rm.ModelShape.from_arch(serve_arch), ssetup, TPU_V5E
    )

    modeled = {
        "step": est.t_step,
        "ckpt": est.t_ckpt,
        "a2a": a2a_modeled,
        "decode": se.t_decode,
        "prefill": se.ttft,
    }
    tracker = obs.DriftTracker(modeled, warmup=1)
    tracker.observe_events(ring.events())
    tracker.observe_events(eng.trace_ring.events())
    report = tracker.report()

    phases = {}
    for name in sorted(modeled):
        r = report.get(name, {"modeled_s": modeled[name], "n": 0})
        phases[name] = {
            "modeled_s": r.get("modeled_s"),
            "mean_s": r.get("mean_s"),
            "n": r["n"],
            "ratio": r.get("ratio"),
        }
    return {
        "platform": TPU_V5E.name,
        "train_arch": "smollm-360m (reduced)",
        "serve_arch": "granite-moe-3b-a800m (reduced)",
        "a2a_cell": {"ep": ep, "rows": rows, "d": d},
        "phases": phases,
        "note": "host-CPU measurements vs TPU-v5e model: ratios are "
                "structural in this container; on the target platform the "
                "same path yields calibratable numbers",
    }


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

REQUIRED_PHASES = ("step", "a2a", "ckpt", "decode")
OVERHEAD_BUDGET = 0.02


def run(smoke: bool) -> dict:
    from repro import obs

    rounds, steps, micro = SMOKE if smoke else FULL
    ring = obs.RingBufferSink()
    with tempfile.TemporaryDirectory() as d:
        tel_on = obs.Telemetry(
            enabled=True,
            sinks=[ring, obs.JsonlSink(str(Path(d) / "metrics.jsonl"))],
        )
        tel_off = obs.Telemetry(enabled=False)
        overhead, train_ctx = measure_overhead(
            rounds, steps, tel_on, tel_off, ring
        )
        overhead["event_cost_us"] = event_costs_us(micro, tel_on, tel_off)

        # Drift spans (ckpt.save, a2a.layer) route through the same global
        # telemetry + ring the enabled rounds populated with train.step.
        prev = obs.set_telemetry(tel_on)
        try:
            drift = measure_drift(smoke, train_ctx, ring)
        finally:
            obs.set_telemetry(prev)
        tel_on.close()

    covered = [
        p for p in REQUIRED_PHASES
        if drift["phases"][p]["n"] > 0
        and drift["phases"][p]["ratio"] is not None
    ]
    return {
        "meta": {
            "smoke": smoke,
            "rounds_per_mode": rounds,
            "steps_per_round": steps,
            "micro_iters": micro,
            "overhead_budget_frac": OVERHEAD_BUDGET,
        },
        "overhead": overhead,
        "drift": drift,
        "summary": {
            "overhead_frac": overhead["overhead_frac"],
            "overhead_within_budget":
                overhead["overhead_frac"] <= OVERHEAD_BUDGET,
            "phases_covered": len(covered),
            "covered": covered,
            "all_required_ratios_finite": len(covered)
                == len(REQUIRED_PHASES),
        },
    }


def rows(smoke: bool = True):
    """benchmarks.run integration: (name, us_per_call, derived) rows."""
    rec = run(smoke)
    o, s = rec["overhead"], rec["summary"]
    out = [(
        "obs_overhead",
        (o["enabled_s_per_step"] - o["disabled_s_per_step"]) * 1e6,
        f"frac={o['overhead_frac']*100:.2f}% "
        f"events/step={o['events_per_step']:.0f} "
        f"span={o['event_cost_us']['span_enabled']:.1f}us",
    )]
    for name, r in rec["drift"]["phases"].items():
        if r["n"]:
            out.append((
                f"obs_drift_{name}",
                r["mean_s"] * 1e6,
                f"modeled={r['modeled_s']*1e6:.1f}us "
                f"ratio={r['ratio']:.1f} n={r['n']}",
            ))
    out.append((
        "obs_gate",
        0.0,
        f"within_budget={s['overhead_within_budget']} "
        f"phases={s['phases_covered']}/{len(REQUIRED_PHASES)}",
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
                    help="tiny sizes — schema/CI mode")
    ap.add_argument("--out", type=Path, default=None)
    ap.add_argument("--check-schema", type=Path, default=None,
                    help="compare the emitted JSON's key structure against "
                         "this committed file; exit 1 on drift")
    args = ap.parse_args()

    rec = run(smoke=args.smoke)

    if args.check_schema:
        import sys

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
    print(f"telemetry overhead {s['overhead_frac']*100:.2f}% of step time "
          f"(budget {OVERHEAD_BUDGET*100:.0f}%): "
          f"within={s['overhead_within_budget']}; "
          f"drift phases covered: {s['phases_covered']}"
          f"/{len(REQUIRED_PHASES)} {s['covered']}")


if __name__ == "__main__":
    main()
