"""End-to-end training driver.

Examples (CPU container — reduced configs; on TPU drop --reduced):

    PYTHONPATH=src python -m repro.launch.train \
        --arch granite-moe-3b-a800m --reduced --steps 50 --batch 8 --seq 128

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
    python -m repro.launch.train --arch granite-moe-3b-a800m --reduced \
        --mesh 2,2,2 --pipeline --steps 20 --batch 8 --seq 128

The driver: consults the planner for the configuration report, builds the
mesh+plan, initializes or restores state, and runs the fault-tolerant
Trainer (checkpointing, straggler monitor, expert migration).
:func:`setup` is that whole path up to the first step, so other entry
points (``chip_smoke.py``) train through exactly the same binding.
"""

from __future__ import annotations

import argparse


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep this many layers (whole periods of the "
                         "block pattern), e.g. to fit one chip; default: "
                         "the config's depth")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None,
                    help="comma mesh shape, e.g. 2,2,2 -> (pod,data,model)")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--schedule", default=None,
                    help="pipeline schedule (gpipe|1f1b|1f1b_overlap|"
                         "interleaved_1f1b|zb_h1); default: the planner's "
                         "choice, else 1f1b")
    ap.add_argument("--vstages", type=int, default=None,
                    help="virtual stages per pipeline stage (interleaved "
                         "schedules); default: the planner's choice, else 1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="checkpoint every N steps; default: the resource "
                         "model's Young-Daly optimal interval (clamped to "
                         "[1, steps/2]), else 50")
    ap.add_argument("--corpus", default=None, help="memmap token corpus path")
    ap.add_argument("--dispatch", default=None,
                    help="MoE expert dispatch (capacity|ragged); default: "
                         "the planner's ranked choice")
    ap.add_argument("--a2a", default=None, choices=["flat", "halo"],
                    help="EP all-to-all algorithm; default: the planner's "
                         "ranked choice")
    ap.add_argument("--a2a-chunks", type=int, default=None,
                    help="chunk depth of the double-buffered EP a2a "
                         "(1 = monolithic); default: the planner's choice")
    ap.add_argument("--migrate-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write telemetry events as JSONL here; a Chrome "
                         "trace_event view (openable in Perfetto, with "
                         "per-stage pipeline lanes when PP>1) lands next "
                         "to it as <path>.trace.json and a model-vs-"
                         "measured drift report prints at end of run")
    return ap.parse_args(argv)


def main(argv=None):
    from repro import compile_cache, obs

    args = parse_args(argv)
    compile_cache.enable()
    run = setup(args)
    with run["plan"].mesh:
        out = run["trainer"].fit(run["state"], run["data"])
    print(f"[done] step={out['last_step']} "
          f"loss={float(out['metrics']['loss']):.4f} "
          f"migrations={len(out['migrations'])} "
          f"stragglers={len(out['stragglers'])}")

    if run["ring"] is not None:
        _telemetry_reports(args, run["arch"], run["plan"], run["ring"])
        obs.get_telemetry().close()


def setup(args):
    """Everything before the first step: planner binding, mesh and plan,
    model, initial state, data stream and Trainer.

    Returns a dict with ``arch``, ``plan``, ``state``, ``data``,
    ``trainer`` and ``ring`` (the telemetry ring buffer, or None).
    """
    import jax
    import numpy as np

    from repro import obs, training
    from repro.configs import get_arch
    from repro.core import planner
    from repro.core.platform import TPU_V5E
    from repro.data import MemmapCorpus, Prefetcher, SyntheticTokens
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.runtime import Trainer, TrainerConfig
    from repro.sharding import host_mesh, make_plan, single_device_plan

    # Telemetry: --metrics-out turns the (otherwise zero-cost) spans across
    # trainer/pipeline/checkpointing on, teeing every event to a JSONL log
    # and an in-memory ring the end-of-run reports read back.
    ring = None
    if args.metrics_out:
        ring = obs.RingBufferSink()
        obs.configure(
            enabled=True, sinks=[ring, obs.JsonlSink(args.metrics_out)]
        )

    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    if args.layers:
        arch = arch.replace(num_layers=args.layers)
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")

    # Planner report (what this run would need at production scale).
    best = planner.best_strategy(
        get_arch(args.arch), TPU_V5E, 256, batch=256, seq=4096, zero="world"
    )
    if best is not None:
        print(f"[planner] production-strategy for {args.arch} @256xv5e:")
        print("          " + best.describe())

    # Checkpoint cadence: an explicit --ckpt-every wins, else default to
    # the resource model's Young-Daly optimal interval (sqrt(2*t_ckpt*MTBF)
    # priced from state bytes + platform write bandwidth), clamped to the
    # run length so short runs still checkpoint at least once.
    if args.ckpt_every is None:
        if best is not None:
            e = best.estimate
            hi = max(args.steps // 2, 1)
            args.ckpt_every = min(max(e.ckpt_every_steps, 1), hi)
            print(f"[planner] ckpt-every defaulted to {args.ckpt_every} "
                  f"steps (Young-Daly: t_ckpt={e.t_ckpt:.1f}s "
                  f"tau={e.ckpt_interval_s:.0f}s "
                  f"goodput={e.goodput_factor*100:.2f}%)")
        else:
            args.ckpt_every = 50

    # The schedule (and its vstage depth) binds planner -> plan -> executor:
    # an explicit flag wins, else inherit the planner's ranked choice.  An
    # explicit --schedule drops the planner's vstages (they belong to ITS
    # schedule), unless --vstages is also given.
    from repro.configs.base import DEFAULT_SCHEDULE

    if args.schedule:
        schedule = args.schedule
        vstages = args.vstages or 1
    else:
        schedule = best.schedule if best is not None else DEFAULT_SCHEDULE
        vstages = args.vstages or (best.vstages if best is not None else 1)
        if args.vstages is None and args.pipeline and args.mesh and vstages > 1:
            # The planner's V is sized for the production config; this run's
            # (possibly --reduced) layer stack over THIS mesh may not split
            # that deep.  Clamp to the largest feasible divisor — an explicit
            # --vstages is respected (and asserted) as given.
            pp = int(args.mesh.split(",")[0])
            reps = arch.reps
            rps = max(reps // pp, 1)
            want = vstages
            vstages = max(v for v in range(1, min(vstages, rps) + 1)
                          if rps % v == 0)
            if vstages != want:
                print(f"[planner] vstages {want} -> {vstages} (layer reps "
                      f"per stage: {rps})")
            if vstages == 1 and schedule == "interleaved_1f1b":
                schedule = DEFAULT_SCHEDULE

    # Same for the expert dispatch: flag wins, else the planner's choice
    # binds into MoECfg.dispatch (the MoE layer executes whatever the
    # config says — capacity buffers or the sort-based ragged path).
    if arch.moe is not None:
        import dataclasses

        # A share's slice of the experts runs the dropless path only.
        dispatch = args.dispatch or (
            best.dispatch if best is not None and arch.moe.ep_share == 1
            else arch.moe.dispatch
        )
        if dispatch != arch.moe.dispatch:
            arch = arch.replace(
                moe=dataclasses.replace(arch.moe, dispatch=dispatch)
            )
        print(f"[trainer] moe dispatch: {arch.moe.dispatch}")

    # And the a2a path: flag wins, else the planner's ranked
    # (algo, chunks); both bind into the MeshPlan the MoE layer reads.
    a2a_algo = args.a2a or (best.a2a_algo if best is not None else "flat")
    a2a_chunks = args.a2a_chunks or (
        best.a2a_chunks if best is not None else 1
    )
    if arch.moe is not None:
        print(f"[trainer] ep a2a: {a2a_algo} x{a2a_chunks} chunks")

    n_dev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        names = ("pod", "data", "model")[-len(shape):]
        mesh = host_mesh(shape, names)
        plan = make_plan(
            mesh, arch, pipeline_on_pod=args.pipeline, schedule=schedule,
            vstages=vstages if args.pipeline else 1,
            hierarchical_a2a=a2a_algo == "halo",
            a2a_chunks=a2a_chunks,
        )
    elif n_dev > 1:
        mesh = host_mesh((1, n_dev), ("data", "model"))
        plan = make_plan(mesh, arch, schedule=schedule,
                         hierarchical_a2a=a2a_algo == "halo",
                         a2a_chunks=a2a_chunks)
    else:
        plan = single_device_plan(arch)
    print(f"[mesh] devices={plan.num_devices} ep={plan.ep} tp={plan.tp} "
          f"pp={plan.pp} dp_axes={plan.dp_axes}"
          + (f" schedule={plan.schedule}" if plan.pp > 1 else "")
          + (f" vstages={plan.vstages}"
             if plan.pp > 1 and plan.vstages > 1 else ""))

    lm = LanguageModel(arch, plan)
    # Warm up for at most a tenth of the run: a run shorter than the
    # default 100-step warmup would otherwise never reach its learning rate.
    opt = OptimizerConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=min(100, args.steps // 10))
    with plan.mesh:
        state = training.init_state(lm, jax.random.PRNGKey(args.seed), opt)
        # Each device takes its share (its experts, its ZeRO slice) now,
        # not whatever the first step's compiler would pick.
        state = jax.device_put(state, training.state_shardings(lm))
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(state["params"])
        )
        print(f"[model] {args.arch}{' (reduced)' if args.reduced else ''}: "
              f"{arch.num_layers} layers, {n_params/1e6:.1f}M params")

    if args.corpus:
        data = MemmapCorpus(args.corpus, args.batch, args.seq)
    else:
        data = SyntheticTokens(arch.vocab_size, args.batch, args.seq)
    data = Prefetcher(iter(data))

    trainer = Trainer(
        lm, opt,
        TrainerConfig(
            total_steps=args.steps,
            checkpoint_dir=args.ckpt_dir,
            checkpoint_every=args.ckpt_every,
            migrate_every=args.migrate_every,
        ),
    )
    return {"arch": arch, "plan": plan, "state": state, "data": data,
            "trainer": trainer, "ring": ring}


def _telemetry_reports(args, arch, plan, ring):
    """End-of-run observability artifacts: the model-vs-measured drift
    report (this run's shape priced on TPU v5e — structural ratios when the
    run itself was host-lowered) and a Chrome trace_event file with
    per-stage schedule lanes when the run was pipelined."""
    from repro import obs
    from repro.core import resource_model as rm
    from repro.core import schedules as sched_lib
    from repro.core.platform import TPU_V5E

    events = ring.events()
    pp = max(plan.pp, 1)
    ep = max(plan.ep, 1)
    tp = max(plan.tp, 1)
    setup = rm.TrainSetup(
        b=args.batch,
        s=args.seq,
        PP=pp,
        EP=ep,
        DP=max(plan.num_devices // (pp * ep * tp), 1),
        zero="world",
        **(
            {"schedule": plan.schedule, "vstages": plan.vstages}
            if plan.pp > 1
            else {}
        ),
        **({"dispatch": arch.moe.dispatch} if arch.moe else {}),
    )
    est = rm.estimate(rm.ModelShape.from_arch(arch), setup, TPU_V5E)
    tracker = obs.DriftTracker(rm.modeled_phases(est))
    n = tracker.observe_events(events)
    print(tracker.format_report(
        f"drift {args.arch}: host-measured vs TPU-v5e model "
        f"(structural when run on CPU)"
    ))

    sched = None
    tick_s = 1e-3
    if plan.pp > 1:
        M = plan.microbatches or 2 * plan.pp
        sched = sched_lib.build(plan.schedule, plan.pp, M, plan.vstages)
        # Scale the lane ticks so the rendered pipeline spans the same
        # wall clock as a measured (post-compile) step.
        steps = [
            e["dur"] for e in events
            if e["kind"] == "span" and e["name"] == "train.step"
        ]
        if len(steps) > 1:
            tick_s = (sum(steps[1:]) / (len(steps) - 1)) / sched.num_ticks
    trace_path = args.metrics_out + ".trace.json"
    obs.write_chrome_trace(
        trace_path, events, schedule=sched, tick_s=tick_s,
        process_name=f"train {args.arch}",
    )
    print(f"[obs] {len(events)} events ({n} drift spans) -> "
          f"{args.metrics_out}; chrome trace: {trace_path}")


if __name__ == "__main__":
    main()
