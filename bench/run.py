"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload web-google.rank --seed 7 --seconds 30 --trace 0

Each run is its own process: it builds the cell's graph from ``--seed``,
prepares the engine, warms up every shape the window uses, measures for
``--seconds`` (with the profiler on under ``--trace 1``), checks what the
window produced against the float64 reference, and prints one JSON object
as its last line of standard output.  Each compared number and its limit
are the last lines of standard error.

It refuses to run anywhere but on a TPU, and exits 2 without a result when
it finds none, too few chips, or no program beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def _plain(x):
    """JSON-safe copy: a number that is not finite becomes null."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = harness.run(args.workload, args.seed, args.seconds,
                                     bool(args.trace), t_start=T_START)
    except harness.SetupError as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(_plain(result)), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
