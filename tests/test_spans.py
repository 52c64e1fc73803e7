"""The planning engine's host spans (``repro.spans``): under
``jax.profiler.trace`` a jax-engine sweep and a ``plan_min_chips`` query
leave every ``plan.*`` span in the trace, each nested in the parent
docs/tracing.md gives it, one ``plan.sweep`` per ``engine.sweep`` call;
the results are the same with and without the profiler; and without jax
a span is a no-op."""

import glob
import os
import sys

import pytest

np = pytest.importorskip("numpy")
jax = pytest.importorskip("jax")

from repro import spans  # noqa: E402
from repro.configs import ShapeConfig  # noqa: E402
from repro.core import planner as PL  # noqa: E402
from repro.core import sweep as SW  # noqa: E402

ARCH = "deepseek-v2-lite-16b"
COLUMNS = ("peak_bytes", "fits", "budget_bytes", "n_chips",
           "global_batch", "overlap_slack_bytes")


def grid():
    return SW.SweepGrid(
        arch=ARCH, chips=(8, 16), chip="v5e", global_batches=(8,),
        seq_lens=(2048,), kind="train", assembly="liveness",
        mesh_axes=("data", "model", "expert", "pipe"),
        max_axis={"expert": 2, "pipe": 2})


def traced(tmp_path, fn):
    """``fn()`` under the profiler; returns its result and the trace's
    ``plan.*`` spans as (name, start, end, thread line)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    found = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("plan."):
                    s = int(ev.start_ns)
                    found.append((ev.name, s, s + int(ev.duration_ns),
                                  (plane.name, line.name)))
    return out, found


def parents(found) -> dict:
    """span name -> the set of names of its parents (the innermost
    enclosing span on the same thread line, None at the top)."""
    out: dict = {}
    for n, s, e, line in found:
        encl = [f for f in found if f[3] == line and f[1] <= s
                and e <= f[2] and f[2] - f[1] > e - s]
        p = min(encl, key=lambda f: f[2] - f[1])[0] if encl else None
        out.setdefault(n, set()).add(p)
    return out


def test_sweep_spans_nest_and_leave_results_alone(tmp_path):
    ref = SW.SweepEngine().sweep(grid(), engine="jax")
    got, found = traced(tmp_path, lambda: SW.SweepEngine().sweep(
        grid(), engine="jax"))
    for name in COLUMNS:
        assert np.array_equal(getattr(ref.columns, name),
                              getattr(got.columns, name)), name
    tree = parents(found)
    assert tree["plan.sweep"] == {None}
    assert tree["plan.columns"] == {"plan.sweep"}
    assert tree["plan.tables"] == {"plan.sweep"}
    assert tree["plan.fold"] == {"plan.tables"}
    assert tree["plan.compose"] == {"plan.sweep"}
    assert tree["plan.to_host"] == {"plan.compose"}
    assert tree["plan.finalize"] == {"plan.sweep"}
    assert "plan.arch" in tree
    n = lambda name: sum(f[0] == name for f in found)
    assert n("plan.sweep") == 1
    # one pipe-degree group per pipe size: a knob table, a table build
    # and a composition each
    assert n("plan.compose") == n("plan.to_host") == n("plan.tables") == 2
    assert n("plan.columns") == 3


def test_query_spans_count_the_sweeps(tmp_path):
    shape = ShapeConfig("q", 2048, 16, "train")
    engine, calls = SW.SweepEngine(), []
    sweep = engine.sweep

    def counted(*a, **k):
        calls.append(1)
        return sweep(*a, **k)

    engine.sweep = counted
    got, found = traced(tmp_path, lambda: PL.plan_min_chips(
        ARCH, shape, chips=(8, 16, 32), engine=engine,
        compute_engine="jax", allow_ep=True, max_ep=2, max_pp=2))
    ref = PL.plan_min_chips(ARCH, shape, chips=(8, 16, 32),
                            compute_engine="jax", allow_ep=True, max_ep=2,
                            max_pp=2)
    assert (got.n_chips, got.peak_bytes) == (ref.n_chips, ref.peak_bytes)
    tree = parents(found)
    assert tree["plan.grid"] == {None}
    assert tree["plan.search"] == {None}
    assert tree["plan.sweep"] == {"plan.search"}
    assert tree["plan.fold"] == {"plan.tables"}
    assert sum(f[0] == "plan.sweep" for f in found) == len(calls) >= 2


def test_numpy_engine_spans(tmp_path):
    _, found = traced(tmp_path, lambda: SW.SweepEngine().sweep(grid()))
    tree = parents(found)
    assert tree["plan.tables"] == {"plan.sweep"}
    assert tree["plan.finalize"] == {"plan.sweep"}
    assert "plan.compose" not in tree


def test_span_without_jax(monkeypatch):
    spans._annotation.cache_clear()
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    try:
        with spans.span("plan.x") as s:
            assert s == "plan.x"          # contextlib.nullcontext(name)
    finally:
        spans._annotation.cache_clear()
