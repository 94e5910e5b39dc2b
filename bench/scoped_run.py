"""A traced run of one cell that reads the program's own names: the scope of
every device op and the program's host spans (``bench/scopes.py``).  It
also measures what the telemetry costs when it is on.

    python bench/scoped_run.py --workload <name> --seed <n>
        [--windows 2] [--window-steps 8] [--dump <path.json.gz>]

The persistent compile cache is off for the run: its key leaves the op
metadata out, so a step compiled before the program had its scopes would
load with the old names.  After set-up (as ``bench/harness.py`` does it,
without the check) it runs
``--windows`` pairs of untraced windows of ``--window-steps`` steps each,
telemetry off then on with no sinks, then one traced window of
``harness.TRACE_STEPS`` steps with telemetry on.  It prints the coverage
checks, one line per scope (ms per step in the forward, backward,
recomputed and undifferentiated passes), the readers of ``scopes.READERS``
and of the cell's ``BENCHMARK.json`` entries, the labelled breakdown, and
as its last line a JSON object of all of them.  ``--dump`` writes the
traced window's events with the spans, the op_names of the ops seen, the
steps, fetches and loads (the form of ``bench/testdata``).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import (flops, gen, harness, manifest, peaks, program,  # noqa: E402
                   reduce, scopes, trace)

log = harness.log


def _window(trainer, state, feed, steps: int):
    """``steps`` more steps through ``Trainer.fit``: (state, seconds, the
    host's step times)."""
    import jax

    k0 = len(trainer.step_times)
    trainer.cfg.total_steps = k0 + steps
    t0 = time.perf_counter()
    state = trainer.fit(state, feed)["state"]
    jax.block_until_ready(state)
    return state, time.perf_counter() - t0, trainer.step_times[k0:]


def run(workload: str, seed: int, **kw) -> Dict:
    """One scoped run, with the persistent compile cache off (see above);
    the keywords are ``_run``'s."""
    program.import_path()
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return _run(workload, seed, **kw)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _run(workload: str, seed: int, *, windows: int = 2, window_steps: int = 8,
         dump: Optional[str] = None, cfg: Optional[Dict] = None,
         require_chip: bool = True, trace_dir: Optional[Path] = None
         ) -> Dict:
    """``cfg``, ``require_chip=False`` and ``trace_dir`` (kept, not
    removed) serve the CPU tests."""
    man = manifest.load()
    cell = manifest.cell(man, workload)
    cfg = cfg or manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    import jax

    from repro import obs

    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) != cell["chips"]):
        raise harness.NoChip(f"{workload} needs {cell['chips']} TPU chip(s)")
    pk = peaks.peak(dev.device_kind) if require_chip else {}
    log(f"[scoped] {workload} seed={seed} device={dev.device_kind} "
        f"count={len(devices)}; persistent compile cache off")
    tr = cfg["training"]
    tokens = tr["batch"] * tr["seq"]
    run_ = program.setup(cfg)
    trainer, plan = run_["trainer"], run_["plan"]
    like = harness.template(run_.pop("state"))
    used = list(plan.mesh.devices.flat)
    state = harness.fresh_state(cfg, like, jax.random.key(seed))
    stream = gen.TokenStream(mix, cfg["vocab_size"], tr["batch"], tr["seq"],
                             seed)
    feed = harness.Feed(stream)
    state, warm_s, _ = _window(trainer, state, feed, 2)
    log(f"[scoped] set-up {time.perf_counter() - T0:.2f}s (first two steps "
        f"{warm_s:.2f}s)")

    prev = obs.get_telemetry()
    rates: Dict[str, List[float]] = {"off": [], "on": []}
    untraced: List[float] = []
    try:
        for _ in range(windows):
            for mode in ("off", "on"):
                obs.configure(enabled=mode == "on")
                state, secs, times = _window(trainer, state, feed,
                                             window_steps)
                rates[mode].append(window_steps * tokens / secs)
                untraced += times
        loads: List[np.ndarray] = []
        stats = trainer.load_stats
        if stats is not None:
            update = stats.update

            def record(x):
                loads.append(np.asarray(x, np.float64).reshape(
                    stats.ema.shape))
                update(x)

            stats.update = record
        tdir = trace_dir or harness.TRACE_DIR / f"{workload}.scoped"
        shutil.rmtree(tdir, ignore_errors=True)
        obs.configure(enabled=True)
        fetch0 = trainer.host_fetches
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _, traced = _window(trainer, state, feed,
                                       harness.TRACE_STEPS)
        jax.profiler.stop_trace()
        fetches = trainer.host_fetches - fetch0
        if stats is not None:
            stats.update = update
    finally:
        obs.set_telemetry(prev)
    feed.close()

    t_c = time.perf_counter()
    names = scopes.op_names(trainer.train_step.lower(
        state, stream.batch_at(0)).compile().as_text())
    log(f"[scoped] op_name map: {len(names)} instructions "
        f"({time.perf_counter() - t_c:.2f}s)")
    xplane = trace.find_xplane(str(tdir))
    events = trace.extract(xplane)
    spans = scopes.extract_spans(xplane)
    if trace_dir is None:
        shutil.rmtree(tdir, ignore_errors=True)
    steps = harness.TRACE_STEPS
    ctx = reduce.Ctx(
        events=events, steps=steps, cfg=cfg, chips=len(used), peak=pk,
        tokens_per_step=tokens,
        flops_per_token=flops.model_flops_per_token(cfg, tr["seq"]),
        loads=loads, host_fetches=fetches,
        coords=program.mesh_coords(plan), pp=plan.pp, ep=plan.ep)

    times = scopes.scope_times(ctx, names)
    cov = scopes.coverage(times)
    busy = scopes.busy_s_per_step(ctx)
    by_pass = {p: sum(v for (_, q), v in times.items() if q == p)
               for p in scopes.PASSES}
    log(f"[scoped] leaf-op time that found an HLO instruction: "
        f"{100 * cov['matched']:.3f}% (>= 99); that found a scope: "
        f"{100 * cov['scoped']:.3f}% (>= 95)")
    log(f"[scoped] leaf ops {1e3 * sum(times.values()):.3f} ms/step against "
        f"busy {1e3 * busy:.3f} ms/step; by pass (ms/step): " + ", ".join(
            f"{p} {1e3 * v:.3f}" for p, v in by_pass.items()))
    for line in scopes.table(times):
        log(f"[scoped] {line}")
    metrics: Dict[str, float] = {}
    for name, fn in scopes.READERS.items():
        value = fn(ctx, names)
        if value is not None:
            metrics[name] = value
    for m in manifest.metrics_of(man, workload, "per_layer"):
        value = reduce.load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = value
    for k, v in metrics.items():
        log(f"[scoped] {k} = {v!r}")
    bd = scopes.breakdown(ctx, names, spans)
    log(f"[scoped] breakdown {json.dumps(bd)}")
    step_s = {"untraced_median": statistics.median(untraced) if untraced
              else None, "traced": traced}
    log(f"[scoped] tokens/s telemetry off {rates['off']}, on {rates['on']}; "
        f"step s untraced median {step_s['untraced_median']}, traced "
        f"{traced}")
    if dump:
        seen = {n for d in events["devices"].values() for n, *_ in d}
        trace.save({**events, "spans": spans,
                    "scopes": {n: names[n] for n in sorted(seen & set(names))},
                    "steps": steps, "host_fetches": fetches,
                    "loads": [x.tolist() for x in loads]}, dump)
    return {"metrics": metrics, "coverage": cov,
            "scope_ms": {f"{s}.{p}": 1e3 * v for (s, p), v in times.items()},
            "busy_ms": 1e3 * busy, "breakdown": bd, "spans": spans,
            "op_names": names,
            "tokens_per_s": rates, "step_s": step_s,
            "device": {"kind": dev.device_kind, "count": len(devices)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--window-steps", type=int, default=8)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, windows=args.windows,
                  window_steps=args.window_steps, dump=args.dump)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for bulky in ("spans", "op_names"):
        out.pop(bulky)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
