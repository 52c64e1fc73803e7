"""The readings that the limits of a training cell are set from.

    python bench/readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --fault-seeds 7,8,9

Runs on the cell's chips at the cell's own size, in one process, no
window.  A training cell: for every ``--seeds`` seed, the program's
check steps (the production step from the seed, as a benchmark run
drives them) against the float32 reference (the lower readings); for
every ``--control-seeds`` seed, the reference at float8 in the
program's place (the control); for every ``--fault-seeds`` seed, the
reference with half of each batch left out, the mean taken over the
rest (a planted fault).  A planning cell: for every ``--control-seeds``
seed, the reference with its byte counts in int32 in the program's
place, on what a run with that seed compares.  One JSON line per reading
on standard output.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import seeded  # noqa: E402

NO_LIMITS = {"grad_diff": math.inf, "grad_norm_gap": math.inf,
             "update_norm_gap": math.inf}


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def emit(drv, kind: str, seed: int, prog: dict, ref: dict) -> None:
    checks = drv.compare(prog, ref, NO_LIMITS)
    print(json.dumps({"kind": kind, "seed": seed,
                      "loss_gap": drv.loss_gap(prog, ref),
                      **{c.name: c.value for c in checks}}), flush=True)


def train_readings(cell, devs, args) -> None:
    drv = harness.load_module(harness.driver_file("train"))
    ref_mod = harness.load_module(cell.config["reference"])
    t = cell.traffic
    steps, rows = int(t["check_steps"]), int(t["rows_per_block"])

    exact: dict = {}

    def reference(seed, **kw):
        if not kw and seed in exact:
            return exact[seed]
        batches = [seeded.token_batch(seed, i, int(t["global_batch"]),
                                      int(t["seq_len"]),
                                      int(cell.config["vocab_size"]))
                   for i in range(steps)]
        out = ref_mod.run(cell.config, t["optimizer"], seed, batches,
                          rows_per_block=rows, device=devs[0], **kw)
        if not kw:
            exact[seed] = out
        return out

    for seed in args.seeds:
        job = drv.Job(cell, seed, devs)
        prog = drv.program_readings(job, steps)
        job.free()
        del job
        emit(drv, "program", seed, prog, reference(seed))
        exact.pop(seed)
    for seed in args.control_seeds:
        emit(drv, "control", seed, reference(seed, lowp=True),
             reference(seed))
    half = int(t["global_batch"]) // 2
    for seed in args.fault_seeds:
        emit(drv, "half_batch", seed, reference(seed, keep_rows=half),
             reference(seed))


def sweep_readings(cell, args) -> None:
    """The control of a sweep cell: the reference in int32 in the
    program's place, on the cells a run with that seed samples."""
    import numpy as np
    from reference.plan_ref import Planner
    drv = harness.load_module(harness.driver_file("plan_sweep"))
    g = cell.traffic["grid"]
    exact = Planner(cell.config_name, cell.config)
    narrow = Planner(cell.config_name, cell.config, int32=True)
    cells = drv.reference_cells(exact, g)
    for seed in args.control_seeds:
        idx = np.sort(np.random.default_rng(seed).choice(
            len(cells), size=int(cell.traffic["sample_cells"]),
            replace=False))
        bad = sum(drv.expected(narrow, g, cells[int(i)])
                  != drv.expected(exact, g, cells[int(i)]) for i in idx)
        print(json.dumps({"kind": "control", "seed": seed,
                          "mismatched_cells": bad, "sampled": len(idx)}),
              flush=True)


def query_readings(cell, args) -> None:
    """The control of a query cell: the reference in int32 answering the
    queries a run with that seed checks."""
    import numpy as np
    from reference.plan_ref import Planner
    drv = harness.load_module(harness.driver_file("plan_query"))
    qs = drv.queries(cell.traffic)
    exact = Planner(cell.config_name, cell.config)
    narrow = Planner(cell.config_name, cell.config, int32=True)
    for seed in args.control_seeds:
        picked = np.random.default_rng(seed).choice(
            len(qs), size=int(cell.traffic["reference_queries"]),
            replace=False)
        bad = 0
        for k in sorted(int(i) for i in picked):
            rq = drv.reference_query(qs[k])
            ask = "min_chips" if qs[k]["kind_of"] == "min_chips" \
                else "frontier"
            a, b = getattr(narrow, ask)(rq), getattr(exact, ask)(rq)
            bad += a != b
            print(json.dumps({"kind": "control", "seed": seed,
                              "query": qs[k]["name"], "control": a,
                              "reference": b}), flush=True)
        print(json.dumps({"kind": "control", "seed": seed,
                          "mismatched_answers": bad}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    devs = harness.require_devices(cell.chips)
    if cell.driver == "train":
        train_readings(cell, devs, args)
    elif cell.driver == "plan_sweep":
        sweep_readings(cell, args)
    else:
        query_readings(cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
