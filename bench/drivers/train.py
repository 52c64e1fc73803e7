"""Driver ``train``: the launcher's production training step.

Set-up, in one process: the guard (``plan_job``) plans the job for the
devices it is given, ``train_program`` builds the jitted, state-donating
step with the production shardings, the step is compiled (from the
persistent cache after a cell's first run), and the state is made on
the device in one jitted call from the seed.  That one state and step
then run the check steps (the first ``check_steps``), whose losses, first
gradient and parameter change the reference follows once the window has
closed, and go on into the window.  Every step gets its own seeded rows,
put on the device as the step needs them; its metrics are read on the
host after it, as ``ResilientTrainer.run`` does.  No checkpoint is saved.

Traffic keys: ``seq_len``, ``global_batch``, ``data`` (devices on the
data axis; the model axis takes the rest), ``check_steps``,
``optimizer`` (the settings the reference uses; the program must run
the same), ``rows_per_block`` (the reference's row blocks) and the
limits of the three compared numbers.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import harness
import seeded
from harness import BenchError, Check, Outcome, log

#: published config.json key -> the program's ArchConfig field
WIDTHS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "head_dim": "head_dim", "tie_word_embeddings": "tie_embeddings",
          "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta"}


def program_config(cell):
    """The program's ArchConfig for the configuration file: the named
    architecture with the file's ``overrides``, registered under the
    configuration's name; it must carry the file's published sizes."""
    from repro.configs import get_config, register_config
    spec = cell.config["program"]
    cfg = dataclasses.replace(get_config(spec["arch"]),
                              **spec.get("overrides", {}))
    if cfg.name != cell.config_name:
        cfg = dataclasses.replace(cfg, name=cell.config_name)
        register_config(cfg)
    for key, field in WIDTHS.items():
        if key not in cell.config:
            continue
        want, got = cell.config[key], getattr(cfg, field)
        if field == "head_dim":
            got = cfg.resolved_head_dim
        if want != got:
            raise BenchError(f"the program's {field}={got!r} departs from "
                             f"{key}={want!r} of {cell.config_name}")
    return cfg


def check_optimizer(cfg, opt: dict):
    from repro.train import OptimizerConfig
    prog = OptimizerConfig(name=cfg.optimizer,
                           master_fp32=cfg.optimizer != "adafactor")
    for k, v in opt.items():
        if getattr(prog, k) != v:
            raise BenchError(f"the program's optimizer {k}="
                             f"{getattr(prog, k)!r} departs from {v!r}")
    return prog


def memory_analysis_total(compiled) -> tuple:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return total, ma


def ape(pred: float, ref: float) -> float:
    return abs(pred - ref) / ref * 100.0


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """The largest |norm_prog - norm_ref| over leaves, each against the
    larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        return math.inf, "leaves differ"
    med = float(np.median([ref[k] for k in keys]))
    worst, which = 0.0, ""
    for k in keys:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(gap) or gap > worst:
            worst, which = gap if math.isfinite(gap) else math.inf, k
    return worst, which


def worst_leaf_diff(prog: dict, ref: dict) -> tuple:
    """The largest norm of (program - reference) over leaves, each against
    the larger of that leaf's reference norm and the median leaf's."""
    if set(prog) != set(ref):
        return math.inf, "leaves differ"
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    worst, which = 0.0, ""
    for k, r in ref.items():
        d = float(np.linalg.norm(prog[k].astype(np.float64) - r)) \
            / max(norms[k], med, 1e-30)
        if not math.isfinite(d) or d > worst:
            worst, which = d if math.isfinite(d) else math.inf, k
    return worst, which


def loss_gap(prog: dict, ref: dict) -> float:
    """The largest relative gap between the steps' losses."""
    if len(prog["loss"]) != len(ref["loss"]) \
            or not all(math.isfinite(v) for v in prog["loss"]):
        return math.inf
    return max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers the cell compares, each with its limit.

    ``grad_diff`` (the first gradient's relative error, worst leaf) is
    the one a lower precision moves at first order; the gaps between
    norms move at second order and catch lost or doubled work instead.
    The loss gap is logged and not compared: neither the control nor a
    planted fault reads far enough above sound runs to set a limit.
    """
    grad_gap, g_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    diff, d_leaf = worst_leaf_diff(prog["grads"], ref["grads"])
    med = float(np.median(list(ref["grad_norms"].values())))
    moving = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    change_gap, c_leaf = worst_leaf_gap(prog["change_norms"],
                                        ref["change_norms"], moving)
    log(f"compare: loss_gap {loss_gap(prog, ref)!r} (not compared); "
        f"worst leaves: "
        f"gradient norm {g_leaf}, gradient difference {d_leaf}, change "
        f"{c_leaf}; leaves left out of the change "
        f"{sorted(set(ref['grad_norms']) - moving)}")
    return [Check("grad_diff", diff, limits["grad_diff"]),
            Check("grad_norm_gap", grad_gap, limits["grad_norm_gap"]),
            Check("update_norm_gap", change_gap, limits["update_norm_gap"])]


class Job:
    """The production step and its state, built once from the seed."""

    def __init__(self, cell, seed: int, devs):
        import jax
        import jax.numpy as jnp
        from repro.configs import ShapeConfig
        from repro.core import planner
        from repro.core.spec import FULL_TRAIN
        from repro.launch import mesh as M
        from repro.launch.train import plan_job, train_program
        from repro.mesh_ctx import mesh_context
        from repro.models import build_model, param as PM
        from repro.train import TrainState
        from repro.train.optimizer import init_opt_state

        t = cell.traffic
        self.seed = seed
        self.cfg = program_config(cell)
        self.opt_cfg = check_optimizer(self.cfg, t["optimizer"])
        self.seq, self.batch = int(t["seq_len"]), int(t["global_batch"])
        self.shape = ShapeConfig(f"train_s{self.seq}_b{self.batch}",
                                 self.seq, self.batch, "train")
        self.mesh_shape, self.chip, self.report = plan_job(
            self.cfg.name, self.shape, len(devs), devs[0].device_kind,
            t.get("data"))
        log(f"guard: {self.chip} x {self.mesh_shape}: {self.report}")
        if not self.report.fits:
            raise BenchError(f"the guard refuses the job: {self.report}")
        log(f"setup: {harness.setup_seconds():.1f} s guard done")
        self.liveness = planner.check(
            self.cfg.name, self.shape, self.mesh_shape, chip=self.chip,
            grad_accum=self.report.grad_accum, remat=self.report.remat,
            assembly="liveness")

        model = build_model(self.cfg)
        mesh = M.make_smoke_mesh(self.mesh_shape["data"],
                                 self.mesh_shape["model"], devs)
        with mesh_context(mesh, M.arch_rules(self.cfg)):
            init, step, self.bsh = train_program(
                model, self.shape, mesh, remat=self.report.remat,
                grad_accum=self.report.grad_accum)
            shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
            state_sh = jax.tree.map(lambda s: s.sharding, shapes)
            self.param_specs = model.param_specs()
            mask = PM.trainable_mask(model.spec, FULL_TRAIN)
            opt_cfg = self.opt_cfg

            def make_state(words):
                params = seeded.make_params(words, self.param_specs)
                trainable, _ = PM.partition_params(params, mask)
                return TrainState(params=params,
                                  opt=init_opt_state(trainable, opt_cfg),
                                  step=jnp.zeros((), jnp.int32))

            lowered = step.lower(shapes, model.batch_spec(self.shape))
            log(f"setup: {harness.setup_seconds():.1f} s step traced")
            self.compiled = lowered.compile()
            log(f"setup: {harness.setup_seconds():.1f} s step compiled")
            self.words = seeded.seed_words(seed)
            self.state = jax.jit(make_state, out_shardings=state_sh)(
                self.words)
            jax.block_until_ready(self.state)
            log(f"setup: {harness.setup_seconds():.1f} s state made")
        self.tokens_per_step = self.seq * self.batch
        self.vocab = self.cfg.vocab

    def host_batch(self, index: int) -> dict:
        return seeded.token_batch(self.seed, index, self.batch, self.seq,
                                  self.vocab)

    def step(self, index: int) -> float:
        """One step on rows ``index``; returns its loss, read on the host."""
        import jax
        with jax.profiler.TraceAnnotation("batch"):
            b = jax.device_put(self.host_batch(index), self.bsh)
        with jax.profiler.TraceAnnotation("step"):
            self.state, metrics = self.compiled(self.state, b)
        with jax.profiler.TraceAnnotation("metrics_to_host"):
            return float(np.asarray(metrics["loss"]))

    def free(self) -> None:
        self.state = self.compiled = None


def _flat(tree) -> dict:
    import jax
    return dict(zip(seeded.paths(tree), jax.tree.leaves(tree)))


def _norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def program_readings(job: Job, steps: int) -> dict:
    """Drive the first ``steps`` steps and read what the reference
    follows: each loss, the first gradient as Adam's first moment holds
    it after step 1 (m = (1 - b1) g), and the change of the float32
    master weights over all the steps."""
    import jax
    scale = 1.0 / (1.0 - job.opt_cfg.b1)

    @jax.jit
    def grads(opt):
        return {k[:-2]: x * scale for k, x in _flat(opt).items()
                if k.endswith("/m")}

    @jax.jit
    def change(opt, words):
        init = _flat(seeded.make_params(words, job.param_specs))
        return {k[:-7]: _norm(x - init[k[:-7]].astype(x.dtype))
                for k, x in _flat(opt).items() if k.endswith("/master")}

    losses, g = [], None
    for i in range(steps):
        losses.append(job.step(i))
        if i == 0:
            g = jax.device_get(grads(job.state.opt))
    moved = {k: float(v)
             for k, v in change(job.state.opt, job.words).items()}
    return {"loss": losses, "grads": g,
            "grad_norms": {k: float(np.linalg.norm(v)) for k, v in g.items()},
            "change_norms": moved}


def run(cell, seed: int, seconds: float, trace: bool, devs) -> Outcome:
    import jax
    t = cell.traffic
    job = Job(cell, seed, devs)
    total, ma = memory_analysis_total(job.compiled)
    pred = job.report.peak_bytes
    log(f"memory: compiled step memory_analysis total {total} B: "
        f"arguments {ma.argument_size_in_bytes}, outputs "
        f"{ma.output_size_in_bytes}, temporaries {ma.temp_size_in_bytes}, "
        f"aliased {ma.alias_size_in_bytes}")
    log(f"memory: guard (legacy) peak {pred} B, APE {ape(pred, total)}%; "
        f"liveness peak {job.liveness.peak_bytes} B, APE "
        f"{ape(job.liveness.peak_bytes, total)}%")

    steps = int(t["check_steps"])
    prog = program_readings(job, steps)
    log(f"setup: {harness.setup_seconds():.1f} s check steps done")
    log(f"program: losses {prog['loss']}")

    losses = []

    def one(i):
        losses.append(job.step(steps + i))
        return job.tokens_per_step

    w = harness.measure(cell.name, seed, seconds, trace, devs, one)
    failed = sum(not math.isfinite(v) for v in prog["loss"] + losses)
    log(f"window: {w.calls} steps, {w.work} tokens in {w.elapsed} s, "
        f"compiles {w.compiles}, losses {losses[0]} .. {losses[-1]}")
    log(f"memory: peak_bytes_in_use {w.device['memory_peak_bytes']} B "
        f"(leaves out program temporaries on the TPU)")
    job.free()

    ref_mod = harness.load_module(cell.config["reference"])
    ref = ref_mod.run(cell.config, t["optimizer"], seed,
                      [job.host_batch(i) for i in range(steps)],
                      rows_per_block=int(t["rows_per_block"]),
                      device=devs[0])
    log(f"reference: losses {ref['loss']}")
    checks = compare(prog, ref, t["limits"])
    checks.append(Check("window_compiles", w.compiles, 0))

    pm = job.report.prediction
    rate = w.work / w.elapsed
    facts = dict(
        tokens_per_s=rate, chips=len(devs),
        device_kind=devs[0].device_kind, config=cell.config, seq=job.seq,
        argument_bytes=ma.argument_size_in_bytes,
        predicted_argument_bytes=(pm.param_bytes + pm.opt_bytes
                                  + pm.input_bytes) if pm else None)
    return Outcome(attempted=w.calls + steps, failed=failed, checks=checks,
                   end_to_end={"setup_s": w.setup_s,
                               "train_tokens_per_s": rate,
                               "mem_pred_ape": ape(pred, total)},
                   facts=facts, trace=w.trace, device=w.device)
