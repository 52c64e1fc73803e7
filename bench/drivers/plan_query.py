"""Driver ``plan_query``: a planning service answering distinct questions.

The traffic file's fixed set of queries (``plan_min_chips`` and
``plan_frontier`` over the configuration's architecture) is cycled, each
cycle in an order shuffled from the seed, by one caller in a closed
loop.  Every query runs on a fresh ``SweepEngine`` with the jitted
engine (``compute_engine="jax"``) and the pruned search, and is timed
from its call to its answer on the host.  Set-up asks every query once
(table shapes compiled, from the persistent cache after the cell's
first run).

Once the window has closed, the plain reference (``reference/plan_ref``)
answers ``reference_queries`` of the queries, drawn from the seed, by
enumerating every legal plan in order; each answer the window gave to
those queries must match: for ``min_chips`` the chip count and the
smallest fitting peak, and the reference's own verdict on the plan
returned; for ``frontier`` the whole (chips, largest batch) list.
"""

from __future__ import annotations

import time

import numpy as np

import harness
from harness import Check, Outcome, log


def queries(traffic: dict) -> list:
    return [{**traffic["common"], **q} for q in traffic["queries"]]


def order(n: int, seed: int, cycles: int) -> list:
    """Query indices, each cycle of all ``n`` shuffled from the seed."""
    rng = np.random.default_rng(seed)
    return [int(i) for _ in range(cycles) for i in rng.permutation(n)]


def ask(arch: str, q: dict, stats=None):
    """One query through the planner's public entry point."""
    from repro.configs import ShapeConfig
    from repro.core import planner
    from repro.core import sweep as SW
    shape = ShapeConfig(q["name"], q["seq_len"], q["global_batch"],
                        q["kind"])
    kw = dict(chips=tuple(q["chips"]), chip=q["chip"],
              backend=q["backend"], allow_pp=q["allow_pp"],
              max_pp=q["max_pp"], allow_ep=q["allow_ep"],
              max_ep=q["max_ep"], allow_cp=q["allow_cp"],
              max_cp=q["max_cp"], microbatches=tuple(q["microbatches"]),
              schedules=tuple(q["schedules"]), engine=SW.SweepEngine(),
              stats=stats, compute_engine="jax")
    if q["kind_of"] == "min_chips":
        return planner.plan_min_chips(arch, shape, **kw)
    return planner.plan_frontier(arch, shape, **kw)


def batches_down(gb: int) -> list:
    """The planner's default frontier batches: gb, gb/2, ..., 1."""
    out = []
    while gb >= 1:
        out.append(gb)
        gb //= 2
    return out


def reference_query(q: dict) -> dict:
    axes, max_axis = ("data", "model"), {}
    for allow, axis, cap in (("allow_ep", "expert", "max_ep"),
                             ("allow_cp", "context", "max_cp"),
                             ("allow_pp", "pipe", "max_pp")):
        if q[allow]:
            axes += (axis,)
            max_axis[axis] = q[cap]
    return dict(q, axes=axes, max_axis=max_axis,
                global_batches=batches_down(q["global_batch"]))


def judge(planner, q: dict, want, got) -> bool:
    """Whether one answer (None when the query failed) says what the
    reference says."""
    if got is None or want is None:
        return got is None and want is None and q["kind_of"] == "min_chips"
    if q["kind_of"] == "frontier":
        return [tuple(map(int, p)) for p in got] == want
    if (got.n_chips, got.peak_bytes) != want or not got.fits:
        return False
    v = planner.cell(dict(got.mesh_shape), kind=q["kind"],
                     global_batch=got.global_batch, seq_len=got.seq_len,
                     chip=got.chip, backend=q["backend"],
                     grad_accum=got.grad_accum, remat=got.remat,
                     optimizer=got.optimizer,
                     microbatches=got.microbatches, schedule=got.schedule,
                     assembly=q["assembly"])
    return v.fits and v.peak_bytes == got.peak_bytes


def run(cell, seed: int, seconds: float, trace: bool, devs) -> Outcome:
    import jax
    from repro.core import search as SR
    from reference.plan_ref import Planner

    arch = cell.config["program"]["arch"]
    qs = queries(cell.traffic)
    for q in qs:
        ask(arch, q)
    plan = order(len(qs), seed, cycles=100000 // len(qs))
    answers, latencies, cells, failed = [], [], [], 0

    def one(i):
        nonlocal failed
        q = qs[plan[i]]
        stats = SR.SearchStats()
        with jax.profiler.TraceAnnotation(f"query:{q['kind_of']}"):
            t0 = time.perf_counter()
            try:
                got = ask(arch, q, stats)
            except Exception as e:                   # an answer that fails
                log(f"query {q['name']} failed: {e!r}")
                got, failed = None, failed + 1
            latencies.append(time.perf_counter() - t0)
        answers.append((plan[i], got))
        cells.append(stats.cells_evaluated + stats.probes)
        return 1

    w = harness.measure(cell.name, seed, seconds, trace, devs, one)
    lat_ms = np.asarray(latencies) * 1000.0
    log(f"window: {w.calls} queries in {w.elapsed} s, compiles "
        f"{w.compiles}; latency ms median {np.median(lat_ms)}, p90 "
        f"{np.percentile(lat_ms, 90)}, max {lat_ms.max()}")

    planner = Planner(cell.config_name, cell.config)
    rng = np.random.default_rng(seed)
    picked = sorted(int(i) for i in rng.choice(
        len(qs), size=int(cell.traffic["reference_queries"]),
        replace=False))
    bad = 0
    for k in picked:
        q = qs[k]
        rq = reference_query(q)
        want = planner.min_chips(rq) if q["kind_of"] == "min_chips" \
            else planner.frontier(rq)
        for j, got in answers:
            if j == k and not judge(planner, q, want, got):
                if not bad:
                    log(f"query {q['name']}: answer {got}, reference "
                        f"{want}")
                bad += 1
        log(f"reference: {q['name']} -> {want}")
    checks = [Check("mismatched_answers", bad, 0),
              Check("window_compiles", w.compiles, 0)]
    return Outcome(attempted=w.calls, failed=failed, checks=checks,
                   end_to_end={"setup_s": w.setup_s,
                               "query_ms_p90": float(np.percentile(lat_ms,
                                                                   90))},
                   facts={"query_cells": cells}, trace=w.trace,
                   device=w.device)
