"""Run one benchmark cell once and print its result as the last stdout line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Exits 2, printing no result, where JAX finds no TPU or another number of
chips than the cell asks for.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default=None,
                    help="also write the traced run's extracted device "
                         "events here (gzip JSON)")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t0=T0,
                                  dump_events=args.dump_events)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
