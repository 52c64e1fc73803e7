"""Run one benchmark cell on the local TPU chips.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Set-up
(``setup_s``) runs from process start to the window; the window measures
for ``--seconds`` and closes at the first completion after it; then the
outputs of the timed path are compared with the plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result as one JSON
object; the numbers compared, each beside its limit, close standard
error and the result's ``compared`` key.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402  (records the process start)
from harness import BenchError, log  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
        cache = harness.enable_compile_cache()
        devs = harness.require_devices(cell.chips)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log(f"bench: {cell.name} on {len(devs)} x {devs[0].device_kind}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, compile cache "
        f"{cache}")
    driver = harness.load_module(harness.driver_file(cell.driver))
    outcome = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         devs)
    line = harness.result_line(cell, outcome, devs, bool(args.trace))
    for c in outcome.checks:
        log(f"compared {c.name}: {c.value!r} (limit {c.limit!r})"
            f"{'' if c.ok else '  FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
