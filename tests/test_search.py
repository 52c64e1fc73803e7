"""Branch-and-bound planner (core/search.py): pruned searches must
return answers IDENTICAL to exhaustive enumeration — same cell, same
tie-break — on every query shape tier-1 exercises, and the bounds they
prune with must be sound on full sweeps.

Deterministic twin of tests/test_monotone_property.py (which fuzzes the
same invariants under hypothesis in CI); everything here runs without
optional dependencies.
"""

import pytest

np = pytest.importorskip("numpy")

from repro.configs import ShapeConfig  # noqa: E402
from repro.core import planner as PL  # noqa: E402
from repro.core import search as SR  # noqa: E402
from repro.core import sweep as SW  # noqa: E402
from repro.core.spec import FULL_TRAIN  # noqa: E402


@pytest.fixture(scope="module")
def eng():
    return SW.SweepEngine()


# ---------------------------------------------------------------------------
# statics floor soundness
# ---------------------------------------------------------------------------


FLOOR_GRIDS = [
    dict(arch="llama3.2-3b", kind="train",
         optimizers=("adamw", "adafactor", "adamw8bit"),
         offload_optimizer=(False, True)),
    dict(arch="llama3.1-8b", kind="train"),
    dict(arch="deepseek-v2-lite-16b", kind="train"),
    dict(arch="llava15-7b", kind="train"),
    dict(arch="llama3.2-3b", kind="decode"),
]


@pytest.mark.parametrize("kw", FLOOR_GRIDS,
                         ids=[f"{g['arch']}-{g['kind']}"
                              for g in FLOOR_GRIDS])
def test_floor_never_exceeds_any_peak(eng, kw):
    """floor // n_chips <= peak for EVERY cell of a full sweep — the
    invariant min_chips_search/frontier_search prune with."""
    grid = SW.SweepGrid(chips=(8, 16), chip="v5e",
                        global_batches=(8, 16), seq_lens=(2048,),
                        microbatches=(1, 2), **kw)
    floor = SR._floor_for(grid)
    assert floor > 0
    res = eng.sweep(grid)
    assert len(res) > 0
    bound = floor // res.columns.n_chips
    assert int((res.columns.peak_bytes < bound).sum()) == 0


def test_floor_grows_with_train_statics():
    """The train floor strictly dominates params-only (grads + opt
    states are counted), serve kinds fall back to params, and the
    offload-capable grid drops the optimizer share."""
    params_only = SR.static_floor_bytes("llama3.1-8b", FULL_TRAIN,
                                        kind="decode")
    no_opt = SR.static_floor_bytes("llama3.1-8b", FULL_TRAIN,
                                   kind="train", include_opt=False)
    full = SR.static_floor_bytes("llama3.1-8b", FULL_TRAIN, kind="train")
    assert params_only < no_opt < full
    # adafactor keeps no fp32 master/moments per element -> smaller floor
    ada = SR.static_floor_bytes("llama3.1-8b", FULL_TRAIN, kind="train",
                                optimizer="adafactor")
    assert ada < full


def test_floor_disabled_under_profile():
    from repro.calibrate.profile import CalibrationProfile

    prof = CalibrationProfile(
        coefficients={"static": 0.5, "act_saved": 1.0,
                      "act_transient": 1.0, "overhead": 1.0},
        chip_constant_bytes={})
    grid = SW.SweepGrid(arch="llama3.1-8b", chips=(8,), chip="v5e",
                        global_batches=(8,), seq_lens=(2048,),
                        profile=prof)
    assert SR._floor_for(grid) == 0


# ---------------------------------------------------------------------------
# min-chips / frontier: pruned == exhaustive (oracle-checked)
# ---------------------------------------------------------------------------


MIN_CHIPS_QUERIES = [
    ("llama3.2-3b", ShapeConfig("q", 2048, 16, "train"),
     (4, 8, 16), {}),
    ("llama3.1-8b", ShapeConfig("q", 4096, 16, "train"),
     (8, 16, 32), {}),
    ("deepseek-v2-lite-16b", ShapeConfig("q", 2048, 16, "train"),
     (8, 16, 32), {"allow_ep": True, "max_ep": 4}),
    ("qwen3-32b", ShapeConfig("q", 4096, 32, "train"),
     (8, 16, 32), {"allow_cp": True, "max_cp": 4}),
    ("llama3.2-3b", ShapeConfig("q", 2048, 64, "decode"),
     (4, 8), {"allow_pp": False}),
    # statics floor above every budget: both sides must agree on None
    ("llama3.1-8b", ShapeConfig("q", 2048, 8, "train"),
     (4,), {}),
]


@pytest.mark.parametrize("arch,shape,chips,kw", MIN_CHIPS_QUERIES,
                         ids=[q[0] + "-" + q[1].kind
                              for q in MIN_CHIPS_QUERIES])
def test_min_chips_pruned_equals_exhaustive(eng, arch, shape, chips, kw):
    st = SR.SearchStats()
    got = PL.plan_min_chips(arch, shape, chips=chips, engine=eng,
                            stats=st, **kw)
    ref = PL.plan_min_chips(arch, shape, chips=chips, engine=eng,
                            search="exhaustive", **kw)
    SR._assert_same_cell(got, ref, "min_chips")  # raises on divergence
    # accounting: evaluated + pruned covers exactly the knob domain
    grid = PL._search_grid(arch, shape, chips, "v5e", FULL_TRAIN, "tpu",
                           PL.HEADROOM, kw.get("allow_pp", True), 8,
                           kw.get("allow_ep", False),
                           kw.get("max_ep", 8),
                           kw.get("allow_cp", False),
                           kw.get("max_cp", 8),
                           (1, 4, 8), ("1f1b", "gpipe"), None)
    if grid is not None:
        assert st.total_cells == grid.size()
        assert st.cells_evaluated < grid.size()  # something was pruned


def test_min_chips_search_oracle_mode(eng):
    """oracle=True runs the exhaustive reduction inline and asserts —
    the cross-check the bench and CI lean on."""
    shape = ShapeConfig("q", 2048, 16, "train")
    grid = PL._search_grid("llama3.2-3b", shape, (4, 8, 16), "v5e",
                           FULL_TRAIN, "tpu", PL.HEADROOM, True, 8,
                           False, 8, False, 8, (1, 4, 8),
                           ("1f1b", "gpipe"), None)
    got = SR.min_chips_search(grid, engine=eng, oracle=True)
    assert got is not None and got.fits


FRONTIER_QUERIES = [
    ("llama3.2-3b", ShapeConfig("q", 2048, 64, "train"), (4, 8, 16), {}),
    ("llava15-7b", ShapeConfig("q", 2048, 128, "train"), (8, 16, 32), {}),
    ("deepseek-v2-lite-16b", ShapeConfig("q", 2048, 32, "train"),
     (16, 32), {"allow_ep": True, "max_ep": 4}),
]


@pytest.mark.parametrize("arch,shape,chips,kw", FRONTIER_QUERIES,
                         ids=[q[0] for q in FRONTIER_QUERIES])
def test_frontier_pruned_equals_exhaustive(eng, arch, shape, chips, kw):
    st = SR.SearchStats()
    got = PL.plan_frontier(arch, shape, chips=chips, engine=eng,
                           stats=st, **kw)
    ref = PL.plan_frontier(arch, shape, chips=chips, engine=eng,
                           search="exhaustive", **kw)
    assert got == ref
    assert st.cells_evaluated + st.cells_pruned == st.total_cells


def test_unknown_search_rejected(eng):
    shape = ShapeConfig("q", 2048, 16, "train")
    with pytest.raises(ValueError, match="search"):
        PL.plan_min_chips("llama3.2-3b", shape, chips=(4,), engine=eng,
                          search="greedy")
    with pytest.raises(ValueError, match="search"):
        PL.plan_frontier("llama3.2-3b", shape, chips=(4,), engine=eng,
                         search="greedy")


def test_pruned_equals_exhaustive_under_profile(eng):
    """Calibrated grids disable the floor (0) but must stay exact."""
    from repro.calibrate.profile import CalibrationProfile

    prof = CalibrationProfile(
        coefficients={"static": 0.8, "act_saved": 1.1,
                      "act_transient": 1.0, "overhead": 1.0},
        chip_constant_bytes={"*": 512 * 1024 ** 2})
    shape = ShapeConfig("q", 2048, 16, "train")
    got = PL.plan_min_chips("llama3.2-3b", shape, chips=(4, 8, 16),
                            engine=eng, profile=prof)
    ref = PL.plan_min_chips("llama3.2-3b", shape, chips=(4, 8, 16),
                            engine=eng, profile=prof,
                            search="exhaustive")
    SR._assert_same_cell(got, ref, "min_chips[profile]")


# ---------------------------------------------------------------------------
# aligned-ladder concurrency search
# ---------------------------------------------------------------------------


def test_batch_align():
    assert SR.batch_align({"data": 2, "model": 2, "pipe": 4}) == 4
    assert SR.batch_align({"pipe": 8}) == 1
    assert SR.batch_align({}) == 1
    assert SR.batch_align({"data": 4, "model": 2, "expert": 2}) == 16


CONC_QUERIES = [
    ("llama3.2-3b", 2048, {"data": 1, "model": 4}, "decode", 512),
    ("llama3.2-3b", 2048, {"data": 2, "model": 2}, "decode", 512),
    ("smollm-360m", 1024, {"data": 4, "model": 1}, "decode", 512),
    ("smollm-360m", 512, {"data": 2, "model": 1}, "prefill", 256),
]


@pytest.mark.parametrize("arch,seq,mesh,kind,cap", CONC_QUERIES,
                         ids=[f"{q[0]}-{q[3]}-d{q[2]['data']}"
                              for q in CONC_QUERIES])
def test_max_concurrency_equals_linear_scan(eng, arch, seq, mesh, kind,
                                            cap):
    """The galloping aligned-ladder search vs a full linear scan —
    including data>1 meshes, where peak(gb) is NOT monotone in raw gb
    and a naive binary search over integers would be unsound."""
    budget = int(PL.chip_hbm("v5e") * PL.HEADROOM)

    def peak(gb):
        return eng.report(arch, ShapeConfig("c", seq, gb, kind),
                          dict(mesh), budget_bytes=budget,
                          chip="v5e").peak_bytes

    brute = 0
    for gb in range(1, cap + 1):
        if peak(gb) <= budget:
            brute = gb
    st = SR.SearchStats()
    rep = PL.plan_max_concurrency(arch, seq, mesh_shape=mesh, kind=kind,
                                  cap=cap, engine=eng, stats=st)
    assert rep.max_concurrency == brute
    assert st.probes < cap // 4  # actually pruned, not a hidden scan
    if brute:
        assert rep.peak_bytes == peak(brute) <= budget


def test_max_concurrency_nothing_fits(eng):
    """Even one sequence OOMs on a single v5e for an 8B decode."""
    rep = PL.plan_max_concurrency("llama3.1-8b", 8192,
                                  mesh_shape={"data": 1, "model": 1},
                                  cap=64, engine=eng)
    assert rep.max_concurrency == 0
    assert rep.peak_bytes > rep.budget_bytes


def test_peak_not_monotone_off_ladder(eng):
    """The counterexample motivating the aligned ladder: on a
    batch-sharded mesh there exist gb < gb' with peak(gb) > peak(gb')
    — so monotone_max must NOT binary-search raw integers."""
    budget = int(PL.chip_hbm("v5e") * PL.HEADROOM)
    mesh = {"data": 4, "model": 1}

    def peak(gb):
        return eng.report("smollm-360m", ShapeConfig("c", 1024, gb,
                                                     "decode"),
                          mesh, budget_bytes=budget,
                          chip="v5e").peak_bytes

    vals = [peak(gb) for gb in range(1, 33)]
    assert any(vals[i] > vals[j] for i in range(len(vals))
               for j in range(i + 1, len(vals))), \
        "expected a non-monotone pair on a data-sharded mesh"
    # ...but along the aligned ladder (multiples of 4) it IS monotone
    ladder = vals[3::4]
    assert all(a <= b for a, b in zip(ladder, ladder[1:]))


def test_monotone_max_synthetic_ladders():
    """monotone_max against predicates with known exact answers."""
    for align in (1, 3, 4, 7):
        for true_max in (0, 1, 5, 63, 64, 100):
            def fits(gb, m=true_max):
                return gb <= m
            st = SR.SearchStats()
            got = SR.monotone_max(fits, cap=100, align=align, stats=st)
            assert got == true_max, (align, true_max)
            assert st.probes <= 40


def test_search_stats_merge():
    a = SR.SearchStats(cells_evaluated=3, cells_pruned=7, probes=2)
    b = SR.SearchStats(cells_evaluated=1, cells_pruned=9, probes=3)
    a.merge(b)
    assert (a.cells_evaluated, a.cells_pruned, a.probes) == (4, 16, 5)
    assert a.total_cells == 20
    assert a.reduction == 20 / 9
    assert SR.SearchStats().reduction == float("inf")


# ---------------------------------------------------------------------------
# liveness assembly soundness
# ---------------------------------------------------------------------------


def test_liveness_peak_le_legacy_and_floor_sound(eng):
    """The two invariants that let the branch-and-bound search run
    unchanged under assembly="liveness": every liveness peak is bounded
    above by the legacy peak (sub-sum argument) and below by the
    statics floor (the first event prefix already holds the persistent
    base)."""
    import dataclasses

    live = SW.SweepGrid(arch="llava15-7b", chips=(8, 16), chip="v5e",
                        global_batches=(8, 16), seq_lens=(2048,),
                        microbatches=(1, 2), kind="train",
                        assembly="liveness")
    legacy = dataclasses.replace(live, assembly="legacy")
    r_live = eng.sweep(live)
    r_leg = eng.sweep(legacy)
    assert len(r_live) == len(r_leg) > 0
    lp = r_live.columns.peak_bytes
    gp = r_leg.columns.peak_bytes
    assert (lp <= gp).all()
    assert (lp < gp).any()          # the tighter peak actually bites
    slack = r_live.columns.overlap_slack_bytes
    assert (slack >= 0).all()
    # winning stage's legacy total (live + slack) never exceeds the
    # legacy grid peak (the legacy max is over the same stages)
    assert (lp + slack <= gp).all()
    floor = SR._floor_for(live)
    assert floor > 0
    assert int((lp < floor // r_live.columns.n_chips).sum()) == 0


def test_min_chips_and_frontier_liveness_oracle(eng):
    """Pruned searches vs inline exhaustive oracle, liveness assembly."""
    import dataclasses

    shape = ShapeConfig("q", 2048, 16, "train")
    grid = PL._search_grid("llama3.2-3b", shape, (4, 8, 16), "v5e",
                           FULL_TRAIN, "tpu", PL.HEADROOM, True, 8,
                           False, 8, False, 8, (1, 4, 8),
                           ("1f1b", "gpipe"), None)
    grid = dataclasses.replace(grid, assembly="liveness")
    got = SR.min_chips_search(grid, engine=eng, oracle=True)
    assert got is not None and got.fits
    assert SR.frontier_search(grid, engine=eng, oracle=True)


def test_max_concurrency_liveness_ladder(eng):
    """The aligned batch ladder stays exact under the liveness peak
    (max of gb-aligned-monotone prefixes is monotone): galloping search
    vs a full linear scan on a batch-sharded mesh."""
    budget = int(PL.chip_hbm("v5e") * PL.HEADROOM)
    mesh = {"data": 2, "model": 2}

    def peak(gb):
        return eng.report("llama3.2-3b", ShapeConfig("c", 2048, gb,
                                                     "decode"),
                          dict(mesh), budget_bytes=budget, chip="v5e",
                          assembly="liveness").peak_bytes

    cap = 256
    brute = 0
    for gb in range(1, cap + 1):
        if peak(gb) <= budget:
            brute = gb
    st = SR.SearchStats()
    got = SR.max_concurrency_search(peak, budget, cap, mesh_shape=mesh,
                                    stats=st)
    assert got == brute
    assert st.probes < cap // 4
