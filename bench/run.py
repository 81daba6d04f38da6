#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each compared number
beside its limit); the numbers compared are also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks for,
it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    import jax
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    del jax
    try:
        result = harness.run_cell(spec, cell, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
