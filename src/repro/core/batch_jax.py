"""JAX-lowered columnar engine: the jitted twin of
:func:`repro.core.batch.sweep_columnar`.

The numpy engine stays the byte-exact reference; this module re-expresses
its per-cell composition as a handful of table gathers so the O(cells)
work runs inside one jitted ``lax.scan`` over pipeline stages:

* the per-stage component tables come from the SAME host
  :func:`repro.core.batch._stage_tables` the numpy engine uses (one
  source of truth for every TermSpec / shard-factor evaluation), then
  get **folded** into compound gather tables — the saved-activation
  table absorbs the schedule stash multiplier on its knob axis, the
  static group absorbs the optimizer-update transient, the calibration
  profile's per-term-group ``rint`` scaling is applied in table space.
  Folding is exact: every fold either pre-applies an elementwise op
  that commutes with the gather (``rint(c*x)``, ``x*stash[t2]``) or
  merges tables indexed by the same code tuple (integer addition), so
  each cell's folded value is bit-equal to the numpy engine's
  gather-then-combine value;
* the composition domain drops from ``n_cells`` to
  ``n_meshes x inner`` knob tuples: the chip axis never enters the
  stage max (the calibration chip offset is a per-stage constant, so
  adding it after the max — and outside the strictly-greater peak-stage
  provenance compare — is exact), and the per-chip HBM budget is
  applied by the shared result finalizer;
* one jitted ``lax.scan`` walks the stacked per-stage tables with a
  donated carry of running ``(best, pool, draft, hit, offload)``
  buffers, reproducing the numpy loop's strictly-greater peak-stage
  provenance update; everything is int64 under
  ``jax.enable_x64(True)`` (jax's default int32 canonicalization
  would overflow byte counts);
* folded tables are cached on the engine keyed by everything that
  determines their values (arch, policy, meshes, knob axes, profile
  hash), so re-pricing sweeps — the autopilot / planner search hot
  path — skip straight to the jitted composition.

Byte-identity to the numpy engine (and therefore to per-cell
``planner.check``) is asserted on mixed train/serve/offload grids in
tests/test_batch_jax.py and on the 9,544-cell parity set + the
124,416-cell large grid by ``benchmarks/sweep_throughput.py --verify
--engine jax``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from repro.core import batch as B
from repro.core import planner as PL
from repro.core import sweep as SW
from repro.mesh_ctx import PIPE_AXIS
from repro.spans import span

I64 = np.int64


# ---------------------------------------------------------------------------
# jitted stage-scan composition
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _compose_fn():
    """Build the jitted composition once (import jax lazily so the numpy
    engine never pays for it)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from repro.core import liveness as LV

    def compose(carry0, tabs, idx, has_profile: bool, serve: bool,
                off: bool, assembly: str, kind: str):
        c_aff, c_b, c_ctr, c_ho, t2 = idx

        def step(carry, xs):
            best, bp, bd, bh, bo, bs = carry
            if assembly == "liveness":
                # gather the liveness component columns (the folded
                # tables already hold profile-scaled telescoped deltas
                # in calibrated mode — see _fold_stage), then unroll the
                # cell-independent event program at trace time: a
                # running sum over its delta rows whose max IS the
                # segmented cummax of core.batch.liveness_peak_batch.
                # The chip offset rides every prefix uniformly (base is
                # persistent from event 0), so the driver adding it
                # after the stage max stays exact.
                ot = jnp.take(xs["ctr"], c_ctr, axis=1) if has_profile \
                    else jnp.take(xs["otr"], c_ho, axis=1)
                comps = {
                    "base": jnp.take(xs["aff"], c_aff, axis=1),
                    "inputs": jnp.take(xs["inp"], t2, axis=1),
                    "cache": jnp.take(xs["cch"], t2, axis=1),
                    "loss": jnp.take(xs["lss"], t2, axis=1),
                    "saved": jnp.take(xs["b"], c_b, axis=1),
                    "boundary": jnp.take(xs["bd"], t2, axis=1),
                    "transient": jnp.take(xs["tr"], t2, axis=1),
                    "embed": xs["emb"],
                    "opt_transient": ot,
                    "out_copy": xs["ocp"][:, None],
                }
                if serve:
                    comps["pool"] = jnp.take(xs["pool"], t2, axis=1)
                    comps["draft"] = jnp.take(xs["drf"], t2, axis=1)
                # legacy peak = plain sum of every component (the event
                # deltas partition it), needed for the slack provenance
                speak = functools.reduce(jnp.add, comps.values())
                run = None
                peakl = None
                for row in LV.compile_program(kind).delta_matrix():
                    for ci, coef in enumerate(row):
                        name = LV.COMPONENTS[ci]
                        if coef and name in comps:
                            term = coef * comps[name]
                            run = term if run is None else run + term
                    peakl = run if peakl is None \
                        else jnp.maximum(peakl, run)
                upd = peakl > best
                best = jnp.where(upd, peakl, best)
                bs = jnp.where(upd, speak - peakl, bs)
                if serve:
                    bp = jnp.where(upd, comps["pool"], bp)
                    bd = jnp.where(upd, comps["draft"], bd)
                    bh = jnp.where(upd,
                                   jnp.take(xs["hit"], t2, axis=1), bh)
                if off:
                    bo = jnp.where(upd,
                                   jnp.take(xs["ho"], c_ho, axis=1), bo)
                return (best, bp, bd, bh, bo, bs), None
            speak = (jnp.take(xs["aff"], c_aff, axis=1)
                     + jnp.take(xs["b"], c_b, axis=1)
                     + jnp.take(xs["base"], t2, axis=1))
            if has_profile:
                speak = speak + jnp.take(xs["ctr"], c_ctr, axis=1)
            if serve:
                p = jnp.take(xs["pool"], t2, axis=1)
                d = jnp.take(xs["drf"], t2, axis=1)
                h = jnp.take(xs["hit"], t2, axis=1)
                speak = speak + p + d
                upd = speak > best
                best = jnp.where(upd, speak, best)
                bp = jnp.where(upd, p, bp)
                bd = jnp.where(upd, d, bd)
                bh = jnp.where(upd, h, bh)
            elif off:
                hop = jnp.take(xs["ho"], c_ho, axis=1)
                upd = speak > best
                best = jnp.where(upd, speak, best)
                bo = jnp.where(upd, hop, bo)
            else:
                best = jnp.maximum(best, speak)
            return (best, bp, bd, bh, bo, bs), None

        return lax.scan(step, carry0, tabs)[0]

    return jax.jit(compose, static_argnames=("has_profile", "serve",
                                             "off", "assembly", "kind"),
                   donate_argnums=(0,))


# ---------------------------------------------------------------------------
# table folding (host, exact int64 / profile-rint arithmetic)
# ---------------------------------------------------------------------------


def _fold_stage(tabs: "B._StageTables", profile, env, pp: int,
                stage: int, liveness: bool = False) -> dict:
    """Fold one stage's component tables into compound gather tables.

    Returns 2-D ``(n_lm, K)`` arrays whose flattened trailing codes the
    composition gathers with:

    * ``aff``  — static group (+ optimizer transient when unscaled),
      code ``(opt*n_off + off)*2 + cls``;
    * ``b``    — saved activations with the schedule stash folded per
      (schedule-class, remat), code ``(gpipe*n_r + remat)*T + t2``;
    * ``base`` — transient+overhead terms indexed by ``t2`` alone;
    * ``ctr``  — profile mode only: the act_transient rint group
      (transient+boundary+embed+opt_trans), code ``(opt*n_off+off)*T+t2``;
    * ``pool/drf/hit`` (serve) and ``ho`` (offload provenance).
    """
    eff_m = env["_eff_m"]
    # schedule stash per knob tuple: 1F1B stage s stashes min(pp-s, m),
    # GPipe stashes all m — folded onto the saved table's T axis
    stash = np.stack([np.maximum(np.minimum(pp - stage, eff_m), 1),
                      np.maximum(eff_m, 1)])              # (2, T)
    n_lm = tabs.transient.shape[0]
    n_r = tabs.saved.shape[0]
    T = tabs.transient.shape[1]
    sv = tabs.saved[None, :, :, :] * stash[:, None, None, :]
    out: dict = {}
    if liveness:
        # liveness assembly: keep the event-program components separate
        # instead of folding them into aff/base sums.  ``aff`` becomes
        # the persistent base (static group MINUS the out-copy, which is
        # live only in the optimizer-update window); in calibrated mode
        # tr/bd/emb/ctr hold the TELESCOPED act_transient deltas
        # (cumulative scaled prefixes in liveness.TRANSIENT_ORDER), so
        # their sum telescopes back to the legacy rint group exactly.
        if profile is None:
            aff = tabs.static_sum - tabs.outcopy[:, None, None, None]
            out["b"] = sv
            out["ocp"] = tabs.outcopy
            out["emb"] = np.asarray(tabs.embed, I64)
            out["tr"], out["bd"] = tabs.transient, tabs.boundary
            out["lss"], out["inp"] = tabs.loss, tabs.inputs
            out["cch"] = tabs.cache
            out["otr"] = np.ascontiguousarray(
                tabs.opt_trans).reshape(n_lm, -1)
        else:
            aff = tabs.static_scaled \
                - tabs.outcopy_scaled[:, None, None, None]
            out["b"] = profile.scale_batch(sv, "act_saved")
            out["ocp"] = tabs.outcopy_scaled
            e = np.asarray(tabs.embed, I64)
            p1 = profile.scale_batch(e, "act_transient")
            p2 = profile.scale_batch(e + tabs.boundary, "act_transient")
            p3 = profile.scale_batch(e + tabs.boundary + tabs.transient,
                                     "act_transient")
            ctr = profile.scale_batch(
                (tabs.transient + tabs.boundary + e)[:, None, None, :]
                + tabs.opt_trans[:, :, :, None], "act_transient")
            out["emb"] = p1
            out["bd"] = p2 - p1
            out["tr"] = p3 - p2
            out["ctr"] = np.ascontiguousarray(
                ctr - p3[:, None, None, :]).reshape(n_lm, -1)
            out["lss"] = profile.scale_batch(tabs.loss, "overhead")
            out["inp"] = profile.scale_batch(tabs.inputs, "overhead")
            out["cch"] = profile.scale_batch(tabs.cache, "overhead")
        out["aff"] = np.ascontiguousarray(aff).reshape(n_lm, -1)
        out["b"] = np.ascontiguousarray(
            out["b"].transpose(2, 0, 1, 3)).reshape(n_lm, 2 * n_r * T)
        if tabs.pool is not None:
            pool, hit = tabs.pool, tabs.pool_saved
            drf = tabs.draft if tabs.draft is not None \
                else np.zeros_like(pool)
            if profile is not None:
                pool = profile.scale_batch(pool, "overhead")
                hit = profile.scale_batch(hit, "overhead")
                drf = profile.scale_batch(drf, "static")
            out["pool"], out["hit"], out["drf"] = pool, hit, drf
        if tabs.host_opt is not None:
            out["ho"] = np.ascontiguousarray(
                tabs.host_opt).reshape(n_lm, -1)
        return out
    if profile is None:
        aff = tabs.static_sum + tabs.opt_trans[:, :, :, None]
        b = sv
        base = (tabs.transient + tabs.loss + tabs.inputs + tabs.cache
                + tabs.boundary + tabs.embed)
    else:
        aff = tabs.static_scaled
        b = profile.scale_batch(sv, "act_saved")
        out["ctr"] = profile.scale_batch(
            (tabs.transient + tabs.boundary + tabs.embed
             )[:, None, None, :]
            + tabs.opt_trans[:, :, :, None],
            "act_transient").reshape(n_lm, -1)
        base = (profile.scale_batch(tabs.loss, "overhead")
                + profile.scale_batch(tabs.inputs, "overhead")
                + profile.scale_batch(tabs.cache, "overhead"))
    out["aff"] = np.ascontiguousarray(aff).reshape(n_lm, -1)
    # (2, n_r, n_lm, T) -> (n_lm, 2*n_r*T) with (gpipe, remat) leading
    out["b"] = np.ascontiguousarray(
        b.transpose(2, 0, 1, 3)).reshape(n_lm, 2 * n_r * T)
    out["base"] = np.ascontiguousarray(base, dtype=I64)
    if tabs.pool is not None:
        pool, hit = tabs.pool, tabs.pool_saved
        drf = tabs.draft if tabs.draft is not None \
            else np.zeros_like(pool)
        if profile is not None:
            pool = profile.scale_batch(pool, "overhead")
            hit = profile.scale_batch(hit, "overhead")
            drf = profile.scale_batch(drf, "static")
        out["pool"], out["hit"], out["drf"] = pool, hit, drf
    if tabs.host_opt is not None:
        out["ho"] = np.ascontiguousarray(tabs.host_opt).reshape(n_lm, -1)
    return out


def _mesh_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def _group_tables(engine, grid, cols, cfg, model, rows, rules, rep_ctx,
                  arch, env, profile, opt_res, remat_eval, mesh_ids,
                  pp: int, jobs: int, drafts) -> dict:
    """Folded + stage-stacked tables for one (arch, pipeline-degree)
    group, cached on the engine by everything that determines their
    values so repeated sweeps skip straight to the jitted composition."""
    from repro.calibrate.profile import profile_hash_of
    key = ("jax_tables", arch, grid.policy, cols.kind, cols.backend, pp,
           tuple(_mesh_key(cols.meshes[i]) for i in mesh_ids),
           opt_res, remat_eval, cols.offs, cols.serves, cols.pairs,
           cols.seqs, cols.mbs, profile_hash_of(profile),
           grid.assembly)
    cache = engine.__dict__.setdefault("_jax_table_cache", {})
    hit = cache.get(key)
    if hit is not None:
        return hit
    with span("plan.tables"):
        plan = engine._stage_plan(arch, grid.policy, pp)
        folded = []
        for s, srows in enumerate(plan.stages):
            tabs = B._stage_tables_jobs(
                cfg, model, list(srows), rules, rep_ctx, cols, env,
                profile, opt_res, remat_eval, mesh_ids, s, pp, jobs,
                drafts)
            with span("plan.fold"):
                folded.append(_fold_stage(
                    tabs, profile, env, pp, s,
                    liveness=grid.assembly == "liveness"))
        stacked = {k: np.stack([f[k] for f in folded])
                   for k in folded[0]}
    cache[key] = stacked
    return stacked


# ---------------------------------------------------------------------------
# the jax sweep driver
# ---------------------------------------------------------------------------


def sweep_columnar_jax(engine, grid, jobs: int = 1) -> "SW.SweepResults":
    """Drop-in twin of :func:`repro.core.batch.sweep_columnar` running
    the per-cell composition under jax; byte-identical results."""
    import jax

    t0 = time.perf_counter()
    grid.check_parallel()
    grid.check_serve()
    grid.check_offload()
    grid.check_assembly()
    live_mode = grid.assembly == "liveness"
    with span("plan.columns"):
        cols = B.build_columns(grid)
    if cols.n == 0:
        return SW.SweepResults(grid=grid, results=[],
                               elapsed_s=time.perf_counter() - t0)
    profile = grid.profile
    n = cols.n
    n_pairs, n_seq = len(cols.pairs), len(cols.seqs)
    n_chip, n_mesh = len(cols.chips), len(cols.meshes)
    n_arch = len(cols.arches)
    n_off = len(cols.offs)
    block = n // n_arch
    inner = block // (n_chip * n_mesh)
    # inner-axis code columns: the first `inner` cells cycle every axis
    # right of the mesh axis once, and those codes repeat verbatim for
    # every (arch, chip, mesh) prefix — so the composition runs on the
    # (mesh, inner) domain and the result broadcasts over the chip axis
    o_i = cols.opt_c[:inner]
    f_i = cols.off_c[:inner]
    rm_i = cols.remat_c[:inner]
    mb_i = cols.mb_c[:inner]
    sv_i = cols.srv_c[:inner]
    pr_i = cols.pair_c[:inner]
    sq_i = cols.seq_c[:inner]
    accum_i = cols.accum[:inner]
    is_gpipe_sched = np.array([s == "gpipe" for s in cols.scheds], bool)
    gp_i = is_gpipe_sched[cols.sched_c[:inner]].astype(I64)
    t2_full_i = (mb_i * n_pairs + pr_i) * n_seq + sq_i
    t2_flat_i = pr_i * n_seq + sq_i
    t2_srv_i = (sv_i * n_pairs + pr_i) * n_seq + sq_i
    pp_of = np.array([int(m.get(PIPE_AXIS, 1)) for m in cols.meshes], I64)
    drafts = B._draft_states(engine, cols)
    off_grp = cols.kind == "train" and any(cols.offs)

    peak = np.zeros(n, I64)
    pool_arr = np.zeros(n, I64)
    draft_arr = np.zeros(n, I64)
    hit_arr = np.zeros(n, I64)
    off_arr = np.zeros(n, I64)
    slack_arr = np.zeros(n, I64) if live_mode else None
    opt_names: list = []
    remat_names: list = []
    opt_tbl: dict = {}
    remat_tbl: dict = {}
    res_opt_c = np.zeros(n, I64)
    res_remat_c = np.zeros(n, I64)
    compose = _compose_fn()
    from repro.launch.mesh import arch_rules
    for ai, arch in enumerate(cols.arches):
        sl = slice(ai * block, (ai + 1) * block)
        cfg, model, rows = engine._arch_state(arch, grid.policy)
        rules = arch_rules(cfg, cols.kind)
        opt_res = tuple(o or cfg.optimizer for o in cols.opts)
        remat_res = tuple(r or cfg.remat for r in cols.remats)
        remat_eval = tuple(dict.fromkeys(remat_res))
        remat_idx = np.array([remat_eval.index(r) for r in remat_res],
                             I64)
        r_i = remat_idx[rm_i]
        n_r = len(remat_eval)
        rep_ctx = PL.make_context(
            cfg, dict(cols.meshes[0]), kind=cols.kind,
            global_batch=int(cols.gb[sl][0]), seq_len=int(cols.seq[sl][0]),
            backend=cols.backend)
        view = lambda a: a[sl].reshape(n_chip, n_mesh, inner)
        peak_v = view(peak)
        pool_v, draft_v, hit_v, off_v = (view(pool_arr), view(draft_arr),
                                         view(hit_arr), view(off_arr))
        slack_v = view(slack_arr) if live_mode else None
        for pp in sorted(set(pp_of.tolist())):
            mesh_ids = np.flatnonzero(pp_of == pp)
            with span("plan.columns"):
                env = B._knob_env(cfg, cols, pp)
            serve_grp = env["_serve_expanded"]
            t2 = (t2_full_i if env["_expanded"]
                  else t2_srv_i if serve_grp else t2_flat_i)
            T = len(env["mb"])
            cls_i = ((accum_i > 1) | (env["_eff_m"][t2] > 1)).astype(I64)
            tabs = _group_tables(engine, grid, cols, cfg, model, rows,
                                 rules, rep_ctx, arch, env, profile,
                                 opt_res, remat_eval, mesh_ids, pp, jobs,
                                 drafts)
            n_lm = len(mesh_ids)
            c_aff = (o_i * n_off + f_i) * 2 + cls_i
            c_b = (gp_i * n_r + r_i) * T + t2
            c_ctr = (o_i * n_off + f_i) * T + t2 if profile is not None \
                else np.zeros(0, I64)
            c_ho = o_i * n_off + f_i \
                if off_grp or (live_mode and profile is None) \
                else np.zeros(0, I64)
            carry0 = tuple(np.zeros((n_lm, inner), I64)
                           for _ in range(6))
            with jax.enable_x64(True), span("plan.compose"):
                best, bp, bd, bh, bo, bs = compose(
                    carry0, tabs, (c_aff, c_b, c_ctr, c_ho, t2),
                    has_profile=profile is not None,
                    serve=bool(serve_grp), off=bool(off_grp),
                    assembly=grid.assembly, kind=cols.kind)
                with span("plan.to_host"):
                    best = np.asarray(best)
                    peak_v[:, mesh_ids, :] = best
                    if serve_grp:
                        pool_v[:, mesh_ids, :] = np.asarray(bp)
                        draft_v[:, mesh_ids, :] = np.asarray(bd)
                        hit_v[:, mesh_ids, :] = np.asarray(bh)
                    if off_grp:
                        off_v[:, mesh_ids, :] = np.asarray(bo)
                    if live_mode:
                        slack_v[:, mesh_ids, :] = np.asarray(bs)
        if profile is not None:
            # per-chip calibration offset: stage-constant, so adding it
            # after the stage max (and outside the strictly-greater
            # provenance compare, which it shifts uniformly) is exact
            chip_off = np.array([profile.chip_offset(c)
                                 for c in cols.chips], I64)
            peak_v += chip_off[:, None, None]
        per_opt = np.array([B._intern(opt_tbl, opt_names, o)
                            for o in opt_res], I64)
        res_opt_c[sl] = per_opt[cols.opt_c[sl]]
        per_remat = np.array([B._intern(remat_tbl, remat_names, r)
                              for r in remat_res], I64)
        res_remat_c[sl] = per_remat[cols.remat_c[sl]]
    with span("plan.finalize"):
        return B._finalize_results(grid, cols, t0, peak, pool_arr,
                                   draft_arr, hit_arr, off_arr, opt_names,
                                   remat_names, res_opt_c, res_remat_c,
                                   slack_arr)
