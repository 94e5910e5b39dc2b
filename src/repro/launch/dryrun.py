import os
# Host-lowered by design: 512 virtual CPU devices, never an accelerator —
# this process and every child it starts stay off the chip.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) combination against the production meshes, with no device allocation
(ShapeDtypeStruct stand-ins), and extract the roofline inputs:

    compiled.memory_analysis()  — proves the cell fits per-chip HBM
    compiled.cost_analysis()    — per-device HLO FLOPs / bytes
    hlo_analysis                — loop-aware collective wire bytes

Usage:
    python -m repro.launch.dryrun --arch granite-moe-3b-a800m --shape train_4k
    python -m repro.launch.dryrun --arch grok-1-314b --shape train_4k --multi-pod --pipeline
    python -m repro.launch.dryrun --all --jobs 4        # every cell, both meshes

Results land in results/dryrun/<cell>.json (one file per cell) and are
consumed by repro.launch.roofline and EXPERIMENTS.md.

NOTE: the environment lines above must precede any jax import — jax locks
the platform and device count on first initialization.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

from repro import obs

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# v5e per-chip HBM; memory policy below keeps every cell under this.
HBM_BYTES = 16e9


def _cell_name(arch, shape, multi_pod, pipeline, tag=""):
    mesh = "pod2" if multi_pod else "pod1"
    pipe = "-pp" if pipeline else ""
    tag = f"-{tag}" if tag else ""
    return f"{arch}--{shape}--{mesh}{pipe}{tag}"


def _dispatch_model_record(arch, shape, chips: int, plan) -> dict:
    """Resource-model view of the cell's MoE dispatch: issued vs routed
    expert FLOPs, wasted fraction, drop rate and the expert activation
    bytes, for both dispatch modes (repro.core.resource_model)."""
    from repro.configs.base import DISPATCH_MODES
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    if arch.moe is None:
        return {}
    m = rm.ModelShape.from_arch(arch)
    PP = max(plan.pp, 1)
    EP = max(plan.ep, 1)
    DP = max(chips // (PP * EP), 1)  # tp folded into the replica count
    out = {}
    for mode in DISPATCH_MODES:
        t = rm.TrainSetup(
            b=shape.global_batch, s=shape.seq_len, PP=PP, EP=EP, DP=DP,
            dispatch=mode, zero="world",
        )
        est = rm.estimate(m, t, TPU_V5E)
        disp = rm.dispatch_costs(m, t)
        routed = 6.0 * m.L_moe * m.k * m.expert_params * t.b * t.s
        out[mode] = {
            "moe_flops_routed": routed,
            "moe_flops_issued": routed * disp.flops_factor,
            "wasted_flop_fraction": 1.0 - 1.0 / disp.flops_factor,
            "drop_rate": disp.drop_rate,
            "expert_act_bytes_per_layer": rm._expert_act_per_layer(
                m, t, t.b / t.DP, t.EP
            ),
            "dispatch_bytes_per_layer": disp.bytes_per_layer,
            "t_step_s": est.t_step,
            "t_dispatch_s": est.t_dispatch,
            "mem_stage0_bytes": est.mem_stage0,
        }
    out["selected"] = arch.moe.dispatch
    return out


def _a2a_model_record(arch, shape, chips: int, plan) -> dict:
    """Resource-model ranking of the EP a2a path for this cell: every
    ``a2a_algo x a2a_chunks`` combo the planner enumerates, priced at the
    cell's (PP, EP, DP), with the serial Eq-6 reference, the overlapped
    exposure, and the resulting step time — ranked best-first."""
    from repro.configs.base import A2A_ALGOS, A2A_CHUNK_CANDIDATES
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    if arch.moe is None or plan.ep <= 1:
        return {}
    m = rm.ModelShape.from_arch(arch)
    PP = max(plan.pp, 1)
    EP = max(plan.ep, 1)
    DP = max(chips // (PP * EP), 1)
    combos = []
    for algo in A2A_ALGOS:
        for K in A2A_CHUNK_CANDIDATES:
            t = rm.TrainSetup(
                b=shape.global_batch, s=shape.seq_len, PP=PP, EP=EP, DP=DP,
                dispatch=arch.moe.dispatch, zero="world",
                a2a_algo=algo, a2a_chunks=K,
            )
            est = rm.estimate(m, t, TPU_V5E)
            combos.append({
                "a2a_algo": algo,
                "a2a_chunks": K,
                "t_a2a_serial_s": est.t_a2a,
                "t_a2a_exposed_s": est.t_a2a_exposed,
                "a2a_overlap_saving_s": est.a2a_overlap_saving,
                "t_step_s": est.t_step,
                "mfu": est.mfu,
            })
    combos.sort(key=lambda c: c["t_step_s"])
    return {
        "combos": combos,
        "best": {k: combos[0][k] for k in ("a2a_algo", "a2a_chunks")},
        "selected": {
            "a2a_algo": "halo" if plan.hierarchical_a2a else "flat",
            "a2a_chunks": plan.a2a_chunks,
        },
    }


def _schedule_model_record(arch, shape, chips: int, plan) -> dict:
    """Exposed-comm pricing of the pipeline schedule for this cell: the
    cell's partition priced under the bound schedule AND its comm-lane /
    non-overlap twin, so the record shows what promoting the hand-offs to
    first-class comm ops buys (or costs) — serial p2p reference, the
    replayed exposure, the a2a bracket cap, and the comm-buffer bytes."""
    from repro.configs.base import SCHEDULES
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E
    from repro.core.schedules import OVERLAP_BASE

    if shape.kind != "train" or plan.pp <= 1:
        return {}
    m = rm.ModelShape.from_arch(arch)
    PP = plan.pp
    EP = max(plan.ep, 1)
    DP = max(chips // (PP * EP), 1)
    bound = plan.schedule
    twin = OVERLAP_BASE.get(bound)
    if twin is None:
        # the bound schedule is legacy: its overlap twin, if registered
        twin = next(
            (o for o, b in OVERLAP_BASE.items() if b == bound), None
        )
    names = [n for n in (bound, twin) if n in SCHEDULES]
    rows = []
    for name in names:
        t = rm.TrainSetup(
            b=shape.global_batch, s=shape.seq_len, PP=PP, EP=EP, DP=DP,
            dispatch=arch.moe.dispatch if arch.moe else "capacity",
            zero="world", schedule=name,
            vstages=plan.vstages if name == "interleaved_1f1b" else 1,
        )
        est = rm.estimate(m, t, TPU_V5E)
        rows.append({
            "schedule": name,
            "t_p2p_serial_s": est.t_p2p,
            "t_p2p_exposed_s": est.t_p2p_exposed,
            "p2p_overlap_saving_s": est.p2p_overlap_saving,
            "t_a2a_exposed_s": est.t_a2a_exposed,
            "comm_buf_bytes": est.comm_buf_bytes,
            "t_step_s": est.t_step,
            "mfu": est.mfu,
        })
    rows.sort(key=lambda r: r["t_step_s"])
    return {
        "bound": bound,
        "rows": rows,
        "best": rows[0]["schedule"] if rows else None,
    }


def _robustness_model_record(arch, shape, chips: int, plan) -> dict:
    """Young–Daly checkpoint pricing for this cell: state bytes, write
    time at the platform's sustained bandwidth, job MTBF, the optimal
    interval in seconds and steps, and the availability-adjusted goodput
    (repro.core.resource_model)."""
    from repro.core import resource_model as rm
    from repro.core.platform import TPU_V5E

    if shape.kind != "train":
        return {}
    m = rm.ModelShape.from_arch(arch)
    PP = max(plan.pp, 1)
    EP = max(plan.ep, 1)
    DP = max(chips // (PP * EP), 1)
    t = rm.TrainSetup(
        b=shape.global_batch, s=shape.seq_len, PP=PP, EP=EP, DP=DP,
        zero="world",
    )
    est = rm.estimate(m, t, TPU_V5E)
    return {
        "ckpt_bytes": rm.checkpoint_bytes(m),
        "t_ckpt_s": est.t_ckpt,
        "job_mtbf_s": rm.job_mtbf(TPU_V5E, t.P),
        "ckpt_interval_s": est.ckpt_interval_s,
        "ckpt_every_steps": est.ckpt_every_steps,
        "goodput_factor": est.goodput_factor,
        "mfu": est.mfu,
        "mfu_effective": est.mfu_effective,
    }


def choose_memory_policy(arch, shape, chips: int):
    """Planner-informed defaults so the full config fits 16 GB/chip."""
    params = arch.total_params()
    opt_dtype = "float32"
    if params * 12 / chips > 0.8 * HBM_BYTES:
        opt_dtype = "bfloat16"  # 8 B/param persistent state
    remat = "full" if shape.kind == "train" else "none"
    return opt_dtype, remat


def run_cell(
    arch_name: str,
    shape_name: str,
    multi_pod: bool,
    pipeline: bool = False,
    schedule: str = None,
    vstages: int = None,
    hierarchical_a2a: bool = False,
    a2a_chunks: int = None,
    compress_p2p: bool = False,
    remat: str = None,
    dispatch: str = None,
    tag: str = "",
    save: bool = True,
) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import training
    from repro.configs import SHAPES, get_arch, shape_applicable
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import LanguageModel
    from repro.optim import OptimizerConfig
    from repro.sharding import make_plan

    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    if dispatch and arch.moe is not None:
        arch = arch.replace(
            moe=dataclasses.replace(arch.moe, dispatch=dispatch)
        )
    cell = _cell_name(arch_name, shape_name, multi_pod, pipeline, tag)
    record = {
        "cell": cell,
        "arch": arch_name,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "pipeline": pipeline,
        "schedule": schedule,
        "vstages": vstages,
        "hierarchical_a2a": hierarchical_a2a,
        "a2a_chunks": a2a_chunks or 1,
        "compress_p2p": compress_p2p,
        "dispatch": arch.moe.dispatch if arch.moe else None,
    }

    ok, why = shape_applicable(arch, shape)
    if not ok:
        record.update(status="skipped", reason=why)
        if save:
            _save(record)
        return record

    try:
        t_start = time.time()
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        opt_dtype, auto_remat = choose_memory_policy(arch, shape, chips)
        from repro.configs.base import DEFAULT_SCHEDULE

        plan = make_plan(
            mesh,
            arch,
            pipeline_on_pod=pipeline,
            schedule=schedule or DEFAULT_SCHEDULE,
            vstages=vstages or 1,
            remat=remat or auto_remat,
            optimizer_dtype=opt_dtype,
            hierarchical_a2a=hierarchical_a2a,
            a2a_chunks=a2a_chunks or 1,
        )
        plan.compress_p2p = compress_p2p
        if pipeline:
            # XLA bug b/433785288 workaround (see MeshPlan.embed_grad).
            plan.embed_grad = False
            record["embed_grad_frozen"] = True
        lm = LanguageModel(arch, plan)
        ns = lambda tree: jax.tree.map(
            lambda s: NamedSharding(plan.mesh, s),
            tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        record.update(
            chips=chips,
            ep=plan.ep,
            tp=plan.tp,
            pp=plan.pp,
            schedule=plan.schedule if plan.pp > 1 else None,
            vstages=plan.vstages if plan.pp > 1 else None,
            optimizer_dtype=opt_dtype,
            remat=plan.remat,
        )
        # Dispatch-aware analytical FLOPs/memory for this cell (both modes,
        # so the padding-tax / sort-overhead tradeoff is visible next to
        # the compiled HLO numbers).
        record["dispatch_model"] = _dispatch_model_record(
            arch, shape, chips, plan
        )
        # Ranked a2a_algo x a2a_chunks enumeration for this cell (the
        # planner's knob, priced by the overlap-aware resource model).
        record["a2a_model"] = _a2a_model_record(arch, shape, chips, plan)
        # Exposed-comm pricing of the bound schedule vs its overlap twin.
        record["schedule_model"] = _schedule_model_record(
            arch, shape, chips, plan
        )
        # Young–Daly checkpoint pricing (interval + goodput) for the cell.
        record["robustness_model"] = _robustness_model_record(
            arch, shape, chips, plan
        )

        with plan.mesh:
            if shape.kind == "train":
                step = training.make_train_step(lm, OptimizerConfig())
                state = training.abstract_state(lm)
                batch = training.batch_struct(arch, shape)
                in_sh = (ns(training.state_specs(lm)), ns(training.batch_specs(lm, shape)))
                out_sh = (ns(training.state_specs(lm)), None)
                jitted = jax.jit(
                    step, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=(0,),
                )
                lowered = jitted.lower(state, batch)
            elif shape.kind == "prefill":
                step = training.make_prefill_step(lm)
                params = __import__(
                    "repro.models.model", fromlist=["abstract_params"]
                ).abstract_params(arch, jnp.float32)
                batch = training.batch_struct(arch, shape)
                from repro.models import model as model_lib

                in_sh = (
                    ns(model_lib.param_specs(arch, plan)),
                    ns(training.batch_specs(lm, shape)),
                )
                jitted = jax.jit(step, in_shardings=in_sh)
                lowered = jitted.lower(params, batch)
            else:  # decode
                from repro.models import model as model_lib

                step = training.make_decode_step(lm)
                params = model_lib.abstract_params(arch, jnp.float32)
                cache = lm.abstract_cache(shape.global_batch, shape.seq_len)
                batch = training.batch_struct(arch, shape)
                cache_sh = ns(lm.cache_specs(shape.global_batch, shape.seq_len))
                in_sh = (
                    ns(model_lib.param_specs(arch, plan)),
                    cache_sh,
                    ns(training.batch_specs(lm, shape)),
                    None,
                )
                out_sh = (None, cache_sh)
                jitted = jax.jit(
                    step, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(
                    params, cache, batch, jax.ShapeDtypeStruct((), jnp.int32)
                )
            t_lower = time.time()
            obs.get_telemetry().record_span(
                "dryrun.lower", t_lower - t_start, cell=cell, kind=shape.kind
            )
            with obs.span("dryrun.compile", cell=cell, kind=shape.kind):
                compiled = lowered.compile()
            t_compile = time.time()

        ma = compiled.memory_analysis()
        print(ma)
        ca = compiled.cost_analysis() or {}
        # cost_analysis visits while-loop bodies once; analyze_hlo multiplies
        # by trip counts (see hlo_analysis docstring) — it is the authoritative
        # number for the roofline.
        cost = hlo_analysis.analyze_hlo(compiled.as_text(), chips)
        print({"hlo_flops": cost.flops, "hlo_bytes": cost.bytes_accessed,
               "wire_bytes": cost.total_wire_bytes})

        # On this single-host CPU backend, memory_analysis reports module-
        # level sizes; per-device = module / chips for arguments (weights,
        # caches are sharded), while temps are already per-partition-shaped.
        arg_b = ma.argument_size_in_bytes
        record.update(
            status="ok",
            lower_seconds=t_lower - t_start,
            compile_seconds=t_compile - t_lower,
            memory_analysis={
                "argument_bytes": arg_b,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
                "peak_bytes_per_device": (
                    arg_b
                    + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes
                    - ma.alias_size_in_bytes
                ),
            },
            cost_analysis={
                "flops": cost.flops,
                "bytes_accessed": cost.bytes_accessed,
                "bytes_large": cost.bytes_large,
                "raw_flops_once": ca.get("flops", 0.0),
                "raw_bytes_once": ca.get("bytes accessed", 0.0),
            },
            collectives=cost.collective_summary(),
        )
    except Exception as e:  # noqa: BLE001
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    if save:
        _save(record)
    return record


def _save(record: dict):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    with open(RESULTS_DIR / f"{record['cell']}.json", "w") as f:
        json.dump(record, f, indent=1)


def all_cells(pipeline_moe: bool = True):
    """The full dry-run matrix."""
    from repro.configs import ASSIGNED, SHAPES

    cells = []
    for arch in ASSIGNED:
        for shape in SHAPES:
            cells.append((arch, shape, False, False))
            cells.append((arch, shape, True, False))
    if pipeline_moe:
        # Piper's paper-faithful config: PP over the pod axis for the MoE
        # and hybrid architectures (train shapes).
        for arch in ("granite-moe-3b-a800m", "grok-1-314b",
                     "jamba-1.5-large-398b"):
            cells.append((arch, "train_4k", True, True))
    return cells


def _run_all(jobs: int, force: bool):
    cells = all_cells()
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    procs = []
    pending = []
    for arch, shape, mp, pp in cells:
        cell = _cell_name(arch, shape, mp, pp)
        out = RESULTS_DIR / f"{cell}.json"
        if out.exists() and not force:
            continue
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape,
        ]
        if mp:
            cmd.append("--multi-pod")
        if pp:
            cmd.append("--pipeline")
        pending.append((cell, cmd))

    running = []
    results = {}
    while pending or running:
        while pending and len(running) < jobs:
            cell, cmd = pending.pop(0)
            print(f"[dryrun] launch {cell}")
            p = subprocess.Popen(
                cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env={**os.environ, "PYTHONPATH": "src",
                     "JAX_PLATFORMS": "cpu"},
            )
            running.append((cell, p, time.time()))
        done = [r for r in running if r[1].poll() is not None]
        for cell, p, t0 in done:
            running.remove((cell, p, t0))
            print(f"[dryrun] {cell}: rc={p.returncode} ({time.time()-t0:.0f}s)")
        time.sleep(2)
    # summary
    n_ok = n_skip = n_err = 0
    for f in sorted(RESULTS_DIR.glob("*.json")):
        rec = json.loads(f.read_text())
        s = rec.get("status")
        n_ok += s == "ok"
        n_skip += s == "skipped"
        n_err += s == "error"
        if s == "error":
            print(f"[dryrun] ERROR {rec['cell']}: {rec.get('error')}")
    print(f"[dryrun] ok={n_ok} skipped={n_skip} error={n_err}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="Piper: pipeline stages over the pod axis")
    ap.add_argument("--schedule", default=None,
                    help="pipeline schedule (gpipe|1f1b|1f1b_overlap|"
                         "interleaved_1f1b|zb_h1)")
    ap.add_argument("--vstages", type=int, default=None,
                    help="virtual stages per stage (interleaved_1f1b)")
    ap.add_argument("--hierarchical-a2a", action="store_true")
    ap.add_argument("--a2a-chunks", type=int, default=None,
                    help="chunk depth of the double-buffered EP a2a "
                         "(1 = monolithic)")
    ap.add_argument("--compress-p2p", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--dispatch", default=None,
                    help="MoE expert dispatch (capacity|ragged); default: "
                         "the arch config's mode")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--metrics-out", default=None,
                    help="write lower/compile telemetry spans as JSONL "
                         "(in-process cells only; --all fans out to "
                         "subprocesses)")
    args = ap.parse_args()

    if args.metrics_out:
        obs.configure(
            enabled=True, sinks=[obs.JsonlSink(args.metrics_out)]
        )

    if args.all:
        _run_all(args.jobs, args.force)
        return
    rec = run_cell(
        args.arch,
        args.shape,
        args.multi_pod,
        pipeline=args.pipeline,
        schedule=args.schedule,
        vstages=args.vstages,
        hierarchical_a2a=args.hierarchical_a2a,
        a2a_chunks=args.a2a_chunks,
        compress_p2p=args.compress_p2p,
        remat=args.remat,
        dispatch=args.dispatch,
        tag=args.tag,
    )
    status = rec.get("status")
    obs.get_telemetry().close()
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=1)[:2000])
    if status == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
