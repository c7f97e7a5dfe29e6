"""The readings a cell's correctness limits are set from, in one process.

    python3 bench/readings.py --workload web-google.rank --seconds 1 \
        --seeds 11 12 13 --control-seeds 11 12 13 --out chiprun_out/r.jsonl

For each of ``--seeds`` it makes one run of the cell as configured (the
lower readings: what sound runs of the program read); for each of
``--control-seeds`` one run with the program's own float32 path switched
on (the control: the upper readings).  A short ``--seconds`` window still
drives the timed path at the cell's sizes: one whole solve, or one whole
micro-batch at the mix's load.  The runs share one process, so the
programs compile once.  Each run is one JSON line on standard output and
in ``--out``; the benchmark's own runs never run the control.
"""
import argparse
import json
import sys

import harness

# the program's own lower-precision path, one step below float64
CONTROL_DTYPE = "float32"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    runs = ([(s, None) for s in args.seeds]
            + [(s, CONTROL_DTYPE) for s in args.control_seeds])
    out = open(args.out, "a") if args.out else None
    try:
        for seed, dtype in runs:
            try:
                result, checks = harness.run(args.workload, seed,
                                             args.seconds, False, dtype=dtype)
            except harness.SetupError as e:
                print(e, file=sys.stderr)
                return 2
            line = json.dumps(dict(
                workload=args.workload, seed=seed,
                dtype=dtype or "as configured", correct=result["correct"],
                failed=result["failed"], attempted=result["attempted"],
                checks={k: v["value"] for k, v in checks.items()},
                window=result["window"], phases_s=result["phases_s"]))
            print(line, flush=True)
            if out:
                print(line, file=out, flush=True)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
