"""Driver ``plan_sweep``: the autopilot's re-pricing loop.

One warm ``SweepEngine`` sweeps the traffic file's grid with the jitted
columnar engine (``engine="jax"``) back to back, one caller, closed
loop.  Set-up builds the grid and the engine and runs ``warm_sweeps``
sweeps (table folds and compiles; the compiles come from the persistent
cache after the cell's first run).  Every sweep of the window reads
back its result columns; the window keeps, of every sweep, the columns
of ``sample_cells`` cells drawn from the seed.  Once it has closed, the
plain reference (``reference/plan_ref``) enumerates the same grid on its
own and evaluates each sampled cell: its knobs (chip count, mesh,
optimizer, remat, schedule, microbatches, accumulation, batch, length)
and its verdict (peak, budget, fits, liveness slack) must equal the
sweep's, in every sweep.
"""

from __future__ import annotations

import itertools

import numpy as np

import harness
from harness import Check, Outcome, log

def program_grid(arch: str, g: dict):
    from repro.core import sweep as SW
    tup = lambda k: tuple(g[k])
    return SW.SweepGrid(
        arch=arch, chips=tup("chips"), chip=g["chip"],
        mesh_axes=tup("mesh_axes"), max_axis=dict(g["max_axis"]),
        optimizers=tup("optimizers"), remats=tup("remats"),
        schedules=tup("schedules"), microbatches=tup("microbatches"),
        grad_accums=tup("grad_accums"),
        global_batches=tup("global_batches"), seq_lens=tup("seq_lens"),
        kind=g["kind"], backend=g["backend"], assembly=g["assembly"])


def reference_cells(planner, g: dict) -> list:
    """The grid's cells in the order the sweep documents: chip count,
    mesh, optimizer, remat, schedule, microbatches (slowest to fastest),
    then accumulation, global batch (skipping batches the accumulation
    does not divide) and length."""
    from reference.plan_ref.specs import enumerate_meshes
    meshes = [m for n in g["chips"] for m in enumerate_meshes(
        n, tuple(g["mesh_axes"]), g["max_axis"])]
    out = []
    for mesh, opt, remat, sched, mb in itertools.product(
            meshes, g["optimizers"], g["remats"], g["schedules"],
            g["microbatches"]):
        for acc, gb, seq in itertools.product(
                g["grad_accums"], g["global_batches"], g["seq_lens"]):
            if gb % acc == 0:
                out.append((mesh, opt or planner.cfg.optimizer, remat,
                            sched, mb, acc, gb, seq))
    return out


def expected(planner, g: dict, cell: tuple) -> dict:
    mesh, opt, remat, sched, mb, acc, gb, seq = cell
    v = planner.cell(mesh, kind=g["kind"], global_batch=gb, seq_len=seq,
                     chip=g["chip"], backend=g["backend"], grad_accum=acc,
                     remat=remat, optimizer=opt, microbatches=mb,
                     schedule=sched, assembly=g["assembly"])
    return {"n_chips": v.n_chips, "mesh": tuple(sorted(mesh.items())),
            "optimizer": opt, "remat": remat, "schedule": sched,
            "microbatches": mb, "grad_accum": acc, "global_batch": gb,
            "seq_len": seq, "peak_bytes": v.peak_bytes,
            "budget_bytes": v.budget_bytes, "fits": v.fits,
            "overlap_slack_bytes": v.overlap_slack_bytes}


def take(c, idx: np.ndarray) -> tuple:
    """The sampled cells' columns of one sweep, and its code tables."""
    slack = c.overlap_slack_bytes
    cols = (c.n_chips[idx], c.mesh_c[idx], c.opt_c[idx], c.remat_c[idx],
            c.sched_c[idx], c.microbatches[idx], c.grad_accum[idx],
            c.global_batch[idx], c.seq_len[idx], c.peak_bytes[idx],
            c.budget_bytes[idx], c.fits[idx],
            np.zeros(len(idx), np.int64) if slack is None else slack[idx])
    return cols, (c.meshes, c.opt_names, c.remat_names, c.sched_names)


def answers(cols: tuple, tables: tuple, j: int) -> dict:
    (nc, mesh, opt, remat, sched, mb, acc, gb, seq, peak, budget, fits,
     slack) = (x[j] for x in cols)
    meshes, opts, remats, scheds = tables
    return {"n_chips": int(nc),
            "mesh": tuple(sorted(meshes[int(mesh)].items())),
            "optimizer": opts[int(opt)], "remat": remats[int(remat)],
            "schedule": scheds[int(sched)], "microbatches": int(mb),
            "grad_accum": int(acc), "global_batch": int(gb),
            "seq_len": int(seq), "peak_bytes": int(peak),
            "budget_bytes": int(budget), "fits": bool(fits),
            "overlap_slack_bytes": int(slack)}


class Kept:
    """The sampled columns of every sweep, each distinct set once with
    the number of sweeps that returned it."""

    def __init__(self):
        self.distinct: list = []          # [cols, tables, sweeps]

    def add(self, cols: tuple, tables: tuple) -> None:
        for entry in self.distinct:
            if entry[1] == tables and all(
                    np.array_equal(a, b) for a, b in zip(entry[0], cols)):
                entry[2] += 1
                return
        self.distinct.append([cols, tables, 1])

    def mismatches(self, idx: np.ndarray, want: list) -> int:
        """Sampled (sweep, cell) pairs whose knobs or verdict differ from
        the reference's."""
        bad = 0
        for cols, tables, sweeps in self.distinct:
            for j in range(len(idx)):
                got = answers(cols, tables, j)
                if got != want[j]:
                    if not bad:
                        log(f"mismatch at cell {int(idx[j])}: sweep {got}, "
                            f"reference {want[j]}")
                    bad += sweeps
        return bad


def run(cell, seed: int, seconds: float, trace: bool, devs) -> Outcome:
    import jax
    from repro.core import sweep as SW
    from reference.plan_ref import Planner

    t = cell.traffic
    g = t["grid"]
    grid = program_grid(cell.config["program"]["arch"], g)
    engine = SW.SweepEngine()
    for _ in range(int(t["warm_sweeps"])):
        first = engine.sweep(grid, engine="jax")
    n = len(first)
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(n, size=min(int(t["sample_cells"]), n),
                             replace=False))
    kept, failed = Kept(), 0

    def one(i):
        nonlocal failed
        with jax.profiler.TraceAnnotation("sweep"):
            res = engine.sweep(grid, engine="jax")
        if len(res) != n:
            failed += 1
        kept.add(*take(res.columns, idx))
        return len(res)

    w = harness.measure(cell.name, seed, seconds, trace, devs, one)
    log(f"window: {w.calls} sweeps of {n} cells, {w.work} cells in "
        f"{w.elapsed} s, compiles {w.compiles}")

    planner = Planner(cell.config_name, cell.config)
    ref_cells = reference_cells(planner, g)
    if len(ref_cells) == n:
        want = [expected(planner, g, ref_cells[int(i)]) for i in idx]
        bad = kept.mismatches(idx, want)
    else:
        log(f"the reference enumerates {len(ref_cells)} cells, the sweep {n}")
        bad = w.calls * len(idx)
    log(f"reference: {len(idx)} sampled cells x {w.calls} sweeps "
        f"({len(kept.distinct)} distinct), {bad} mismatching")
    checks = [Check("mismatched_cells", bad, 0),
              Check("window_compiles", w.compiles, 0)]
    return Outcome(attempted=w.calls, failed=failed, checks=checks,
                   end_to_end={"setup_s": w.setup_s,
                               "sweep_cells_per_s": w.work / w.elapsed},
                   trace=w.trace, device=w.device)
