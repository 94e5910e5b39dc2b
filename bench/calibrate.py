"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--faults half_batch] \\
        [--fault-seeds 1,2,3] [--out calib.jsonl]

One process, on the cell's chips: the program is set up and compiled once,
then for each seed it takes the cell's first checked steps from that seed's
weights and traffic (as ``bench/run.py`` does before its window), the
reference takes the same steps, and the three compared numbers are
printed.  The control (the reference at float8, ``reference.py``) and the
planted faults are read against the same reference on their seeds.  No
benchmark run calls this; it is how the limits were found.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import compare, gen, harness, manifest, program, reference

    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    cfg = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    program.import_path()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        print(f"calibrate: needs {cell['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    program.enable_compile_cache()
    tr = cfg["training"]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    run = program.setup(cfg)
    trainer, plan = run["trainer"], run["plan"]
    used = list(plan.mesh.devices.flat)
    like = harness.template(run.pop("state"))
    del run
    refs = {}

    def ref_of(mode="highest", fault=None):
        if (mode, fault) not in refs:
            refs[(mode, fault)] = reference.Reference(cfg, mode, fault, used)
        return refs[(mode, fault)]

    def batches(seed):
        s = gen.TokenStream(mix, cfg["vocab_size"], tr["batch"], tr["seq"],
                            seed)
        return [s.batch_at(i) for i in range(harness.check_steps(cfg))]

    faults = [f for f in args.faults.split(",") if f]
    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | (set(args.fault_seeds) if faults else set()))
    for seed in seeds:
        t = time.perf_counter()
        key = jax.random.key(seed)
        ref = ref_of().run(seed, batches(seed))
        rec = {"seed": seed, "ref_losses": ref["losses"],
               "ref_s": time.perf_counter() - t}
        if seed in args.seeds:
            state = harness.fresh_state(cfg, like, key)
            for lst in (trainer.losses, trainer.step_times,
                        trainer.anomalies):
                lst.clear()
            trainer._stop = False
            feed = harness.Feed(gen.TokenStream(
                mix, cfg["vocab_size"], tr["batch"], tr["seq"], seed))
            state, prog, _ = harness.first_steps(trainer, state, feed, cfg,
                                                 key)
            feed.close()
            del state
            gc.collect()
            rec["program"] = compare.readings(prog, ref)
            rec["program_losses"] = prog["losses"]
            rec["program_skipped"] = len(trainer.anomalies)
        if seed in args.control_seeds:
            rec["control"] = compare.readings(
                ref_of("fp8").run(seed, batches(seed)), ref)
        if seed in args.fault_seeds:
            for f in faults:
                rec[f] = compare.readings(
                    ref_of(fault=f).run(seed, batches(seed)), ref)
        rec["seconds"] = time.perf_counter() - t
        emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
