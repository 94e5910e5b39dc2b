"""One run of one cell: set-up, the measured (or traced) window, then the
check against the reference.

Set-up builds the cell's run through ``launch.train.setup``, puts the
weights made from the seed on the plan's shardings (one jitted call), and
drives ``Trainer.fit`` through the first steps on the cell's own
traffic, fed through the program's ``Prefetcher``: that compiles (or loads
from the persistent cache) the one step program the window uses, and gives
the readings the check compares.  The window then calls ``Trainer.fit``
again on the same trainer, state and feed, and ends it through the
trainer's own SIGTERM handler at the first step to end after ``seconds``.
A traced run traces a few steps instead and reduces the trace.
"""

from __future__ import annotations

import gc
import os
import shutil
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import (compare, flops, gen, manifest, peaks, program, reduce,
                   reference, trace, weights)



def check_steps(cfg: Dict) -> int:
    """The first steps the check compares (2 or 3, the configuration's
    ``training.check_steps``); they also warm the window up."""
    return int(cfg["training"].get("check_steps", 3))

TRACE_STEPS = 3
TRACE_DIR = manifest.ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The devices JAX sees are not the chips the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


class Feed:
    """The trainer's batch iterator: the program's ``Prefetcher`` over the
    cell's traffic, each ``next`` marked ``bench.data`` in a trace."""

    def __init__(self, stream):
        self._p = program.prefetch(iter(stream))

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.data"):
            return next(self._p)

    def close(self):
        program.drain(self._p)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) while open."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def _readings_grad(cfg: Dict, state) -> Dict:
    """Step 1's clipped gradient per slice, from the first Adam moment."""
    import jax

    b1 = cfg["training"]["optimizer"]["b1"]
    fn = jax.jit(lambda m: jax.tree.map(
        lambda x: x / (1.0 - b1),
        weights.slice_norms(cfg, program.flat_from_params(cfg, m))))
    return jax.tree.map(np.asarray, fn(state["m"]))


def _peak(stats: Dict) -> int:
    """A device's peak: the allocator's peak of live buffers plus its peak
    reservation.  On a TPU a program's temporaries are reserved apart
    (``bytes_reserved``) and never show in ``peak_bytes_in_use``."""
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def _fullest(devices) -> Dict:
    """``memory_stats()`` of the device with the highest peak ({} where
    the backend keeps none)."""
    stats = [st for st in (d.memory_stats() or {} for d in devices)
             if "peak_bytes_in_use" in st]
    return max(stats, key=_peak) if stats else {}


def template(state):
    """``state`` with its float leaves replaced by their shapes, shardings
    and dtypes (the integer tables are kept): drop the state after taking
    it, and ``fresh_state`` builds the new one in the memory it held."""
    import jax

    return jax.tree.map(
        lambda x: x if x.dtype.kind in "iu" else jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding), state)


def fresh_state(cfg: Dict, state, key):
    """The seed's weights on the plan's shardings (one jitted call), with
    zero Adam moments and step: a state like the program's initial one.
    ``state`` may be a ``template``."""
    import jax
    import jax.numpy as jnp

    like = state["params"]
    make = jax.jit(
        lambda k: {
            "params": program.params_from_flat(cfg, weights.make(cfg, k),
                                               like),
            "m": jax.tree.map(jnp.zeros_like, state["m"]),
            "v": jax.tree.map(jnp.zeros_like, state["v"]),
            "step": jnp.zeros_like(state["step"]),
        },
        out_shardings=jax.tree.map(lambda x: x.sharding, state))
    return make(key)


def first_steps(trainer, state, feed, cfg: Dict, key):
    """Drive ``Trainer.fit`` through the first ``check_steps`` steps and
    take the program's readings: the losses, step 1's gradient and the
    weights' change.  Returns ``(state, readings, seconds spent reading)``.
    """
    tc = trainer.cfg
    log_every = tc.log_every
    check_s = 0.0
    tc.log_every = 1
    prog: Dict = {}
    steps = check_steps(cfg)
    for upto in (1, steps):
        tc.total_steps = upto
        state = trainer.fit(state, feed)["state"]
        t_c = time.perf_counter()
        if upto == 1:
            prog["grad"] = _readings_grad(cfg, state)
        else:
            prog["delta"] = reference.delta_norms(
                cfg, program.flat_from_params(cfg, state["params"]), key)
        check_s += time.perf_counter() - t_c
    prog["losses"] = [loss for _, loss in trainer.losses[:steps]]
    tc.log_every = log_every
    return state, prog, check_s


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t0: float, require_chip: bool = True, cfg: Optional[Dict] = None,
             mix: Optional[Dict] = None, limits: Optional[Dict] = None,
             patch: Optional[Callable[[Dict], None]] = None,
             dump_events: Optional[str] = None) -> Dict:
    """Run ``workload`` once and return the result line's object.

    ``cfg``, ``mix`` and ``limits`` default to the files of the
    workload's entry in ``BENCHMARK.json``;
    ``require_chip=False`` and ``patch`` (called with the program's run
    dict before the first step) serve the CPU tests.
    """
    man = manifest.load()
    cell = manifest.cell(man, workload)
    cfg = cfg or manifest.config(cell["config"])
    mix = mix or manifest.traffic(cell["traffic"])
    limits = limits or compare.load_limits(workload)
    program.import_path()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) != cell["chips"]):
        raise NoChip(f"{workload} needs {cell['chips']} TPU chip(s); JAX "
                     f"sees {len(devices)} {dev.platform} device(s)")
    pk = peaks.peak(dev.device_kind) if require_chip else None
    log(f"[bench] {workload} seed={seed} seconds={seconds} trace={int(traced)}"
        f" device={dev.platform} kind={dev.device_kind} count={len(devices)}"
        f" cache={program.enable_compile_cache()}")
    tr = cfg["training"]
    batch, seq = tr["batch"], tr["seq"]
    t_imports = time.perf_counter() - t0

    run = program.setup(cfg)
    trainer, plan = run["trainer"], run["plan"]
    like = template(run.pop("state"))  # the program's own init is dropped
    used = list(plan.mesh.devices.flat)
    step_fn = trainer.train_step  # the compiled step, whatever ``patch`` does
    if patch is not None:
        patch(run)
    t_setup = time.perf_counter() - t0

    key = jax.random.key(seed)
    state = jax.block_until_ready(fresh_state(cfg, like, key))
    t_weights = time.perf_counter() - t0

    stream = gen.TokenStream(mix, cfg["vocab_size"], batch, seq, seed)
    feed = Feed(stream)
    tc = trainer.cfg
    total = tc.total_steps
    state, prog, check_s = first_steps(trainer, state, feed, cfg, key)
    setup_failed = len(trainer.anomalies)
    setup_s = time.perf_counter() - t0 - check_s
    log(f"[setup] imports {t_imports:.3f}s, launch.train.setup (planner, "
        f"eager init, placement) {t_setup - t_imports:.3f}s, weights "
        f"{t_weights - t_setup:.3f}s, first {check_steps(cfg)} steps "
        f"(compile or cache load included) {setup_s - t_weights:.3f}s; "
        f"setup_s {setup_s:.4f} (check readings {check_s:.3f}s left out)")

    steps0, fetch0 = len(trainer.step_times), trainer.host_fetches
    anom0 = len(trainer.anomalies)
    loads: List[np.ndarray] = []
    result_metrics: Dict = {}
    device_info: Dict = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}
    breakdown = None
    with CompileCounter() as compiles:
        if traced:
            stats = trainer.load_stats
            if stats is not None:
                update = stats.update

                def record(x):
                    loads.append(np.asarray(x, np.float64).reshape(
                        stats.ema.shape))
                    update(x)

                stats.update = record
            tdir = TRACE_DIR / workload
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(str(tdir))
            t_w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                tc.total_steps = steps0 + TRACE_STEPS
                out = trainer.fit(state, feed)
                jax.block_until_ready(out["state"])
            t_w1 = time.perf_counter()
            jax.profiler.stop_trace()
        else:
            tc.total_steps = total
            timer = threading.Timer(seconds, os.kill,
                                    (os.getpid(), signal.SIGTERM))
            t_w0 = time.perf_counter()
            timer.start()
            try:
                out = trainer.fit(state, feed)
                jax.block_until_ready(out["state"])
            finally:
                timer.cancel()
            t_w1 = time.perf_counter()
    steps = len(trainer.step_times) - steps0
    failed = len(trainer.anomalies) - anom0
    window_s = t_w1 - t_w0
    log(f"[window] {steps} steps ({failed} skipped) in {window_s:.4f}s; "
        f"compilations inside the window: {compiles.n}; step times (s, host "
        f"clock to the skip flag): " + " ".join(
            f"{t:.4f}" for t in trainer.step_times[steps0:]))
    fullest = _fullest(used)
    device_info["memory_peak_bytes"] = _peak(fullest) if fullest else None
    log(f"[memory] fullest chip: peak {device_info['memory_peak_bytes']} "
        f"bytes (peak_bytes_in_use + peak_bytes_reserved); memory_stats "
        f"{fullest}")
    t_m = time.perf_counter()
    mem = step_fn.lower(out["state"],
                        stream.batch_at(0)).compile().memory_analysis()
    if mem is not None:
        device_info["step_program_bytes"] = (mem.argument_size_in_bytes
                                             + mem.temp_size_in_bytes)
        log(f"[memory] step program memory_analysis: arguments "
            f"{mem.argument_size_in_bytes} + temporaries "
            f"{mem.temp_size_in_bytes} = "
            f"{device_info['step_program_bytes']} bytes per chip (read in "
            f"{time.perf_counter() - t_m:.2f}s after the window)")
    flops_tok = flops.model_flops_per_token(cfg, seq)
    if not traced and pk is not None:
        tps = (steps - failed) * batch * seq / window_s
        result_metrics = {
            "tokens_per_s": {"value": tps, "unit": "tokens/s"},
            "mfu": {"value": 100.0 * flops_tok * tps
                    / (len(used) * pk["bf16_flops"]), "unit": "%"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    if traced:
        xplane = trace.find_xplane(str(tdir))
        events = trace.extract(xplane)
        if dump_events:
            trace.save({**events, "steps": steps,
                        "host_fetches": trainer.host_fetches - fetch0,
                        "loads": [x.tolist() for x in loads]}, dump_events)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = reduce.Ctx(
            events=events, steps=steps, cfg=cfg, chips=len(used),
            peak=pk or {}, tokens_per_step=batch * seq,
            flops_per_token=flops_tok, loads=loads,
            host_fetches=trainer.host_fetches - fetch0,
            coords=program.mesh_coords(plan), pp=plan.pp, ep=plan.ep)
        device_info["window_s"] = ctx.window_s
        devs = ctx.device_ids()
        if devs and ctx.window:
            device_info["busy_s"] = sum(
                trace.length(ctx.busy(d)) for d in devs) * 1e-9 / len(devs)
        for m in manifest.metrics_of(man, workload, "per_layer"):
            value = reduce.load_reader(m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        breakdown = reduce.breakdown(ctx)

    # Free the program's state before the reference runs on the chips.
    feed.close()
    del out, state, run, trainer
    gc.collect()
    t_r = time.perf_counter()
    batches = [stream.batch_at(i) for i in range(check_steps(cfg))]
    ref = reference.Reference(cfg, devices=used).run(seed, batches)
    numbers = compare.readings(prog, ref)
    log(f"[check] reference {time.perf_counter() - t_r:.2f}s; losses program "
        f"{prog['losses']} reference {ref['losses']}; worst slices "
        f"{numbers['worst']}; left out of update_gap {numbers['left_out']}")
    correct = compare.judge(numbers, limits) and setup_failed == 0
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in compare.NUMBERS}
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": result_metrics, "device": device_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def check_lines(result: Dict) -> List[str]:
    return [f"check {k}: {v['value']:.6g} (limit {v['limit']:.6g})"
            for k, v in result["checks"].items()]
